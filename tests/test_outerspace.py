"""OuterSPACE SpMSpM (paper Figs. 3 and 5) through ``simulate()`` on
the vector engine, on seeded R-MAT matrices:

  * Z equals scipy's X^T X and the Python oracle's, every Einsum runs
    native (no fallback, no downgrade), and under the interpreted
    Pallas kernels the ``lookup_keys`` seam runs on the device path
    with no host delegation;
  * the generator's ``gen:partition`` and ``gen:swizzle`` spans nest
    in ``gen:transform``, their leaf counters equal the leaves each
    step moved, tracing changes no statistic, and with no tracer the
    new sites allocate nothing in ``repro.obs``.
"""
import numpy as np
import pytest
import scipy.sparse as sp

from repro.accelerators import simulate
from repro.core.csf import CSF
from repro.core.vectorized import VectorBackend
from repro.obs import active_tracer, metrics, trace_session

#: (rows, nonzeros, seed) of the matrices X; Z = X^T X
SIZES = [(48, 200, 1), (96, 700, 2), (128, 1100, 3)]


def rmat(n, nnz, seed, abc=(0.57, 0.19, 0.19)):
    """``nnz`` distinct R-MAT positions of an n x n matrix (n a power
    of two or not: positions beyond n are redrawn), values uniform in
    [0.1, 1.1); sorted COO (row, col, val)."""
    rng = np.random.default_rng(seed)
    a, b, c = abc
    bits = max(1, int(np.ceil(np.log2(n))))
    keys = np.zeros(0, np.int64)
    while len(keys) < nnz:
        m = 2 * nnz
        i = np.zeros(m, np.int64)
        j = np.zeros(m, np.int64)
        for bit in range(bits):
            u = rng.random(m)
            i |= (u >= a + b).astype(np.int64) << bit
            j |= (((u >= a) & (u < a + b)) | (u >= a + b + c)
                  ).astype(np.int64) << bit
        ok = (i < n) & (j < n)
        keys = np.unique(np.concatenate([keys, i[ok] * n + j[ok]]))
    keys = np.sort(rng.permutation(keys)[:nnz])
    return keys // n, keys % n, rng.random(nnz) + 0.1


def workload(n, nnz, seed):
    rows, cols, vals = rmat(n, nnz, seed)
    pts = np.stack([rows, cols], axis=1)
    a = CSF.from_coo("A", ["K", "M"], pts, vals, {"K": n, "M": n})
    b = CSF.from_coo("B", ["K", "N"], pts, vals, {"K": n, "N": n})
    x = sp.csr_matrix((vals, (rows, cols)), shape=(n, n))
    return ({"A": a.to_ftensor(), "B": b.to_ftensor()},
            {"m": n, "k": n, "n": n}, x)


def leaves(z):
    """Z[M, N] as {(m, n): value}."""
    assert list(z.ranks) == ["M", "N"]
    return {tuple(p): v for p, v in z.iter_leaves()}


def run(inputs, shapes, kernels):
    return simulate("outerspace", dict(inputs), shapes,
                    backend=VectorBackend(kernel_backend=kernels))


def counters(prefix):
    return {k: v for k, v in metrics().snapshot()["counters"].items()
            if k.startswith(prefix)}


@pytest.mark.parametrize("kernels", ["numpy", "pallas-interpret"])
@pytest.mark.parametrize("n,nnz,seed", SIZES)
def test_outerspace_matches_scipy_and_oracle_natively(n, nnz, seed,
                                                      kernels):
    inputs, shapes, x = workload(n, nnz, seed)
    before = counters("kernel.")
    res = run(inputs, shapes, kernels)
    after = counters("kernel.")
    assert res.fallback_reasons == {}
    assert res.downgrade_events == {}
    got = leaves(res["Z"])
    want = (x.T @ x).tocoo()
    assert set(got) == set(zip(want.row.tolist(), want.col.tolist()))
    for m, nn, v in zip(want.row, want.col, want.data):
        assert got[(m, nn)] == pytest.approx(v, rel=1e-12)
    oracle = simulate("outerspace", dict(inputs), shapes,
                      backend="python")
    assert leaves(oracle["Z"]) == got
    if kernels == "pallas-interpret":
        def delta(key):
            return after.get(key, 0.0) - before.get(key, 0.0)
        assert delta("kernel.device_call/lookup_keys") > 0
        assert delta("kernel.host_delegation/lookup_keys") == 0


def test_transform_spans_nest_and_count_leaves():
    """A's nonzeros go through one group reorder (a copy: A is stored
    [K, M]), one flatten and two occupancy splits; T's leaves (one per
    multiply) through two occupancy splits of M and the merge swizzle
    into [M2, M1, M0, N, K]."""
    inputs, shapes, x = workload(*SIZES[1])
    nnz = x.nnz
    mul = int((np.diff(x.indptr).astype(np.int64) ** 2).sum())
    metrics().reset()
    with trace_session() as tr:
        res = run(inputs, shapes, "numpy")
    assert res.fallback_reasons == {}
    gen = tr.spans("gen")
    transforms = [e for e in gen if e["name"] == "gen:transform"]
    parts = [e for e in gen if e["name"] == "gen:partition"]
    swz = [e for e in gen if e["name"] == "gen:swizzle"]
    assert len(transforms) == 2
    assert len(parts) == 5 and len(swz) == 2
    win = [(e["ts"], e["ts"] + e["dur"]) for e in transforms]
    for e in parts + swz:
        assert e["args"]["parent"] == "gen:transform", e
        assert any(lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1.0
                   for lo, hi in win)
    c = counters("gen.")
    assert c == {"gen.partition_leaves": 3 * nnz + 2 * mul,
                 "gen.swizzle_leaves": nnz + mul}


def test_tracing_changes_no_statistic():
    inputs, shapes, _ = workload(*SIZES[0])
    plain = run(inputs, shapes, "numpy")
    with trace_session():
        traced = run(inputs, shapes, "numpy")
    assert leaves(traced["Z"]) == leaves(plain["Z"])
    a, b = plain.report, traced.report
    assert (a.seconds, a.dram_read_bytes, a.dram_write_bytes,
            a.energy_pj) == (b.seconds, b.dram_read_bytes,
                             b.dram_write_bytes, b.energy_pj)
    assert a.action_counts == b.action_counts


def test_disabled_transform_sites_allocate_nothing():
    """With no tracer the partition and swizzle sites take the shared
    null span and count nothing: no allocation in ``repro.obs``."""
    import sys
    import tracemalloc

    metrics_mod = sys.modules["repro.obs.metrics"]
    spans_mod = sys.modules["repro.obs.spans"]

    assert active_tracer() is None
    inputs, shapes, _ = workload(*SIZES[0])
    run(inputs, shapes, "numpy")                      # warm caches
    metrics().reset()
    tracemalloc.start()
    try:
        run(inputs, shapes, "numpy")
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, spans_mod.__file__),
         tracemalloc.Filter(True, metrics_mod.__file__)]
    ).statistics("filename")
    assert sum(s.size for s in stats) == 0, stats
    assert counters("gen.") == {}

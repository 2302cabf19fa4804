"""Production-scale DSE: batched evaluation parity, result caching,
process-pool sharding, the sweep service, and search."""
import math
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.dse import (DesignPoint, DesignSpace, PointResult, ResultCache,
                       EvolutionarySearch, HalvingSearch, SweepEngine,
                       SweepService, ServiceClosed, pareto_front,
                       result_key, workload_hash)


def _workload(rng, n=48, d=0.15):
    a = rng.random((n, n)) * (rng.random((n, n)) < d)
    b = rng.random((n, n)) * (rng.random((n, n)) < d)
    return {"A": a, "B": b}, {"m": n, "k": n, "n": n}


def _space(values=(0.002, 0.01, 0.05, 0.25, 1.0, 3.0)):
    return DesignSpace("gamma", axes={"fibercache_mb": list(values)})


def _objectives(results):
    return [(r.label, r.seconds, r.energy_pj, r.dram_bytes)
            for r in results]


# ---------------------------------------------------------------------- #
# batched evaluation parity
# ---------------------------------------------------------------------- #
def test_batched_sweep_bitwise_identical_to_per_point(rng):
    """The tentpole invariant: grouped probe+replay evaluation returns
    the SAME bits as evaluating every point through the full backend."""
    inputs, shapes = _workload(rng)
    pts = _space().grid()
    batched = SweepEngine(inputs, shapes, backend="analytic").sweep(pts)
    scalar = SweepEngine(inputs, shapes, backend="analytic",
                         batch=False).sweep(pts)
    assert all(r.ok for r in batched + scalar)
    assert _objectives(batched) == _objectives(scalar)


def test_batched_sweep_amortizes_probe(rng):
    inputs, shapes = _workload(rng)
    pts = _space().grid()
    eng = SweepEngine(inputs, shapes, backend="analytic")
    results = eng.sweep(pts)
    assert all(r.ok for r in results)
    # one probe through the backend, every other point replayed
    assert eng.plan_cache_hits == len(pts) - 1


def test_batched_stat_misses_matches_scalar_bitwise():
    from repro.core.density import batched_stat_misses, stat_misses
    rng = np.random.default_rng(3)
    for _ in range(50):
        unique = float(rng.integers(0, 1000))
        n = unique + float(rng.integers(0, 1000))
        nbytes = float(rng.integers(1, 1 << 22))
        caps = np.array([float(rng.integers(1, 1 << 22))
                         for _ in range(8)])
        vec = batched_stat_misses(n, unique, nbytes, caps)
        for j, cap in enumerate(caps):
            assert vec[j] == stat_misses(n, unique, nbytes, float(cap))


def test_batched_group_key_separates_mappings(rng):
    """Points with different mapping params must not share a group's
    recorded stream (different plans -> different events)."""
    inputs, shapes = _workload(rng, n=24)
    pts = [DesignPoint.make("extensor",
                            params={"K0": k0, "K1": 256, "M1": 256,
                                    "M0": 64, "N1": 256, "N0": 64})
           for k0 in (32, 64)]
    batched = SweepEngine(inputs, shapes, backend="analytic").sweep(pts)
    scalar = SweepEngine(inputs, shapes, backend="analytic",
                         batch=False).sweep(pts)
    assert _objectives(batched) == _objectives(scalar)


# ---------------------------------------------------------------------- #
# result cache
# ---------------------------------------------------------------------- #
def test_result_cache_serves_repeat_sweeps(rng):
    inputs, shapes = _workload(rng)
    pts = _space().grid()
    cache = ResultCache()
    eng = SweepEngine(inputs, shapes, backend="analytic",
                      result_cache=cache)
    first = eng.sweep(pts)
    evaluated = eng.points_evaluated
    second = eng.sweep(pts)
    assert eng.points_evaluated == evaluated       # no backend work
    assert all(r.cached and r.status == "cached" for r in second)
    assert _objectives(first) == _objectives(second)
    assert eng.last_coverage["cached"] == len(pts)
    assert cache.stats()["hits"] == len(pts)
    assert f"{len(pts)} cached" in SweepEngine.summarize(second)


def test_result_cache_persistence_round_trip(rng, tmp_path):
    inputs, shapes = _workload(rng)
    pts = _space().grid()
    cache = ResultCache(directory=tmp_path / "rc")
    eng = SweepEngine(inputs, shapes, backend="analytic",
                      result_cache=cache)
    first = eng.sweep(pts)
    # sweep() flushed on exit; a second flush has nothing new
    assert not cache.flush()
    # a fresh process-equivalent: new cache object, same directory
    cache2 = ResultCache(directory=tmp_path / "rc")
    assert len(cache2) == len(pts)
    eng2 = SweepEngine(inputs, shapes, backend="analytic",
                       result_cache=cache2)
    again = eng2.sweep(pts)
    assert all(r.cached for r in again)
    assert _objectives(first) == _objectives(again)
    assert eng2.points_evaluated == 0


def test_result_cache_lru_eviction():
    c = ResultCache(capacity=2)
    c.put("a", 1, 1, 1)
    c.put("b", 2, 2, 2)
    assert c.get("a")["seconds"] == 1      # refresh a
    c.put("c", 3, 3, 3)                    # evicts b
    assert c.get("b") is None
    assert c.get("a") is not None and c.get("c") is not None


def test_result_cache_keys_are_content_addressed(rng):
    inputs, shapes = _workload(rng, n=16)
    wl = workload_hash(inputs, shapes)
    assert wl == workload_hash(dict(inputs), dict(shapes))
    inputs2 = {k: v.copy() for k, v in inputs.items()}
    inputs2["A"][0, 0] += 1.0
    assert wl != workload_hash(inputs2, shapes)
    p1 = DesignPoint.make("gamma", {"fibercache_mb": 1.0})
    p2 = DesignPoint.make("gamma", {"fibercache_mb": 1.0})
    p3 = DesignPoint.make("gamma", {"fibercache_mb": 2.0})
    k1 = result_key(wl, "sig", p1, "analytic", "calibrated")
    assert k1 == result_key(wl, "sig", p2, "analytic", "calibrated")
    assert k1 != result_key(wl, "sig", p3, "analytic", "calibrated")
    assert k1 != result_key(wl, "sig", p1, "python", "calibrated")


def test_result_cache_never_caches_failures(rng):
    inputs, shapes = _workload(rng, n=16)
    cache = ResultCache()
    eng = SweepEngine(inputs, shapes, result_cache=cache)
    res = eng.evaluate(DesignPoint.make("no-such-design"))
    assert not res.ok
    assert len(cache) == 0


# ---------------------------------------------------------------------- #
# process-pool sharded sweeps
# ---------------------------------------------------------------------- #
def test_process_sweep_bitwise_identical_to_serial(rng):
    inputs, shapes = _workload(rng)
    pts = _space().grid()
    serial = SweepEngine(inputs, shapes, backend="analytic").sweep(pts)
    sharded = SweepEngine(inputs, shapes, backend="analytic",
                          executor="process", max_workers=2).sweep(pts)
    assert all(r.ok for r in sharded), [r.error for r in sharded]
    assert _objectives(serial) == _objectives(sharded)


def test_process_sweep_on_tpu_host_spawns_workers_off_the_chip(
        rng, monkeypatch):
    """On a TPU host the parent holds the chip: sweep workers start
    with spawn and see JAX_PLATFORMS=cpu and the numpy kernels, and
    the sharded sweep still matches the serial one bit for bit."""
    import os

    import jax
    from repro.dse import engine
    inputs, shapes = _workload(rng, n=16)
    pts = _space((0.01, 1.0)).grid()
    serial = SweepEngine(inputs, shapes, backend="analytic").sweep(pts)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the workers' settings come from the pool, not from this process
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    sharded = SweepEngine(inputs, shapes, backend="analytic",
                          executor="process", max_workers=2).sweep(pts)
    assert _objectives(serial) == _objectives(sharded)
    with engine._worker_pool(1, (inputs, shapes, {"backend": "analytic"},
                                 None)) as pool:
        assert pool._mp_context.get_start_method() == "spawn"
        seen = [pool.submit(os.getenv, k).result(timeout=120)
                for k in ("JAX_PLATFORMS", "REPRO_KERNEL_BACKEND")]
    assert seen == ["cpu", "numpy"]


def test_process_sweep_worker_crash_checkpoint_resume(rng, tmp_path):
    """PR-8 contract across the worker boundary: a worker killed by an
    injected crash loses only its in-flight chunk; the parent persists
    completed points and a resumed sweep is bit-identical."""
    from repro.testing.faults import (FaultInjector, FaultSpec,
                                      SimulatedCrash, clear_injector,
                                      install_injector)
    inputs, shapes = _workload(rng)
    pts = _space().grid()
    truth = SweepEngine(inputs, shapes, backend="analytic").sweep(pts)
    truth_front = _objectives(pareto_front(truth))

    ckpt = tmp_path / "sweep"
    install_injector(FaultInjector(
        [FaultSpec(kind="crash", point=pts[3].label, at=1)]))
    try:
        eng1 = SweepEngine(inputs, shapes, backend="analytic",
                           executor="process", max_workers=2)
        with pytest.raises(SimulatedCrash):
            eng1.sweep(pts, checkpoint_dir=str(ckpt),
                       checkpoint_every=1)
    finally:
        clear_injector()
    assert (ckpt / "LATEST").exists()

    eng2 = SweepEngine(inputs, shapes, backend="analytic",
                       executor="process", max_workers=2)
    results = eng2.sweep(pts, checkpoint_dir=str(ckpt), resume=True)
    assert len(results) == len(pts)
    restored = [r for r in results if r.restored]
    assert restored and len(restored) < len(pts)
    cov = eng2.last_coverage
    assert cov["total"] == len(pts)
    assert cov["skipped"] == len(restored)
    assert cov["ok"] == len(pts)
    assert cov["evaluated"] == len(pts) - len(restored)
    assert _objectives(pareto_front(results)) == truth_front


def test_host_shard_partitions_exactly():
    from repro.launch.mesh import host_shard
    items = list(range(10))
    shards = [host_shard(items, process_index=i, process_count=3)
              for i in range(3)]
    assert [len(s) for s in shards] == [4, 3, 3]
    assert sum(shards, []) == items                # contiguous cover
    assert host_shard(items, process_index=0, process_count=1) == items
    with pytest.raises(ValueError):
        host_shard(items, process_index=3, process_count=3)


# ---------------------------------------------------------------------- #
# space.random properties
# ---------------------------------------------------------------------- #
def test_space_random_stable_across_processes():
    code = (
        "from repro.dse import DesignSpace\n"
        "s = DesignSpace('gamma', axes={\n"
        "    'fibercache_mb': [0.1 * i for i in range(1, 11)],\n"
        "    'merge_radix': [2, 4, 8, 16, 32, 64]})\n"
        "print([p.label for p in s.random(5, seed=7)])\n")
    outs = {
        subprocess.run([sys.executable, "-c", code], check=True,
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src",
                            "PYTHONHASHSEED": str(seed)}).stdout
        for seed in (0, 1)}
    assert len(outs) == 1                          # hash-seed invariant
    space = DesignSpace("gamma", axes={
        "fibercache_mb": [0.1 * i for i in range(1, 11)],
        "merge_radix": [2, 4, 8, 16, 32, 64]})
    assert str([p.label for p in space.random(5, seed=7)]) == \
        outs.pop().strip()


def test_space_random_collision_free_subset_of_grid():
    space = DesignSpace("gamma", axes={
        "fibercache_mb": [0.1 * i for i in range(1, 9)],
        "merge_radix": [2, 4, 8, 16]})
    grid_labels = {p.label for p in space.grid()}
    assert len(grid_labels) == space.size
    for n in (1, 5, 17, space.size):
        pts = space.random(n, seed=3)
        labels = [p.label for p in pts]
        assert len(labels) == len(set(labels)) == n
        assert set(labels) <= grid_labels
    # n beyond the space clamps instead of hanging
    assert len(space.random(10 * space.size, seed=0)) == space.size
    assert space.random(0) == []


# ---------------------------------------------------------------------- #
# pareto edge cases
# ---------------------------------------------------------------------- #
def _res(label, s, e=0.0, d=0.0, ok=True):
    if ok:
        return PointResult(point=DesignPoint.make(label), seconds=s,
                           energy_pj=e, dram_bytes=d)
    return PointResult(point=DesignPoint.make(label), error="boom")


def test_pareto_excludes_failed_results():
    rs = [_res("a", 1.0), _res("b", 0.0, ok=False), _res("c", 2.0)]
    front = pareto_front(rs, objectives=("seconds",))
    assert [r.label for r in front] == ["a"]


def test_pareto_all_failed_is_empty():
    rs = [_res("a", 0.0, ok=False), _res("b", 0.0, ok=False)]
    assert pareto_front(rs) == []


def test_pareto_ties_keep_first_duplicate_labels_tolerated():
    rs = [_res("a", 1.0, 2.0, 3.0), _res("a", 1.0, 2.0, 3.0),
          _res("b", 1.0, 2.0, 3.0)]
    front = pareto_front(rs)
    assert len(front) == 1 and front[0] is rs[0]


# ---------------------------------------------------------------------- #
# sweep service
# ---------------------------------------------------------------------- #
def test_service_round_trip_and_coalescing(rng):
    inputs, shapes = _workload(rng)
    pts = _space().grid()
    cache = ResultCache()
    eng = SweepEngine(inputs, shapes, backend="analytic",
                      result_cache=cache)
    with SweepService(eng, max_batch=32, batch_window_s=0.01) as svc:
        futs = [svc.submit(p) for p in pts]
        dups = [svc.submit(pts[0]) for _ in range(3)]
        res = [f.result(timeout=60) for f in futs]
        dup_res = [f.result(timeout=60) for f in dups]
        # repeats served from the result cache
        res2 = [svc.what_if(p, timeout=60) for p in pts]
        stats = svc.stats()
    assert all(r.ok for r in res + dup_res + res2)
    assert _objectives(res) == _objectives(res2)
    assert all(r.seconds == res[0].seconds for r in dup_res)
    assert stats["requests"] == 2 * len(pts) + 3
    assert stats["batches"] >= 1
    assert all(r.cached for r in res2)


def test_service_concurrent_clients_agree(rng):
    inputs, shapes = _workload(rng)
    pts = _space().grid()
    eng = SweepEngine(inputs, shapes, backend="analytic",
                      result_cache=ResultCache())
    seen = {}
    lock = threading.Lock()

    def client(cid, svc):
        import random as _random
        r = _random.Random(cid)
        for _ in range(8):
            res = svc.what_if(r.choice(pts), timeout=60)
            assert res.ok, res.error
            with lock:
                seen.setdefault(res.label, set()).add(
                    (res.seconds, res.energy_pj, res.dram_bytes))

    with SweepService(eng, max_batch=16) as svc:
        threads = [threading.Thread(target=client, args=(i, svc))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    # every client observed identical objectives per configuration
    assert seen and all(len(v) == 1 for v in seen.values())


def test_service_rejects_when_stopped(rng):
    inputs, shapes = _workload(rng, n=16)
    eng = SweepEngine(inputs, shapes, backend="analytic")
    svc = SweepService(eng)
    with pytest.raises(ServiceClosed):
        svc.submit(DesignPoint.make("gamma"))
    svc.start()
    svc.stop()
    with pytest.raises(ServiceClosed):
        svc.submit(DesignPoint.make("gamma"))


def test_service_point_failure_is_structured_not_fatal(rng):
    inputs, shapes = _workload(rng, n=16)
    eng = SweepEngine(inputs, shapes, backend="analytic")
    with SweepService(eng) as svc:
        bad = svc.what_if(DesignPoint.make("no-such-design"), timeout=60)
        good = svc.what_if(DesignPoint.make("gamma"), timeout=60)
    assert not bad.ok and "no-such-design" in bad.error
    assert good.ok


# ---------------------------------------------------------------------- #
# search
# ---------------------------------------------------------------------- #
def test_evolutionary_search_finds_grid_optimum(rng):
    inputs, shapes = _workload(rng)
    space = _space()
    eng = SweepEngine(inputs, shapes, backend="analytic",
                      result_cache=ResultCache())
    grid = eng.sweep(space.grid())
    best_traffic = min(r.dram_bytes for r in grid if r.ok)
    search = EvolutionarySearch(space, eng, population=4, generations=5,
                                elite=1, seed=0, objective="dram_bytes")
    out = search.run()
    assert out.best is not None and out.best_value == best_traffic
    assert out.evaluations == 4 * 5
    # monotone non-increasing incumbent trajectory
    inc = [min(out.trajectory[:i + 1]) for i in range(len(out.trajectory))]
    assert inc == sorted(inc, reverse=True)
    # cache exploited across generations: far fewer backend evals than
    # queries
    assert eng.points_evaluated < out.evaluations + len(grid)


def test_halving_search_promotes_across_fidelities(rng):
    inputs, shapes = _workload(rng)
    space = _space()
    lo = SweepEngine(inputs, shapes, backend="analytic", mode="uniform")
    hi = SweepEngine(inputs, shapes, backend="analytic")
    out = HalvingSearch(space, [lo, hi], n=6, eta=3, seed=0,
                        objective="dram_bytes").run()
    assert out.best is not None and out.best.ok
    assert math.isfinite(out.best_value)
    assert len(out.trajectory) == 2
    # rung sizes: 6 on the cheap engine, 2 promoted to the exact one
    assert out.evaluations == 6 + 2


def test_search_steers_around_failures(rng):
    inputs, shapes = _workload(rng, n=16)
    space = DesignSpace("no-such-design",
                        axes={"fibercache_mb": [0.1, 1.0]})
    eng = SweepEngine(inputs, shapes, backend="analytic")
    out = EvolutionarySearch(space, eng, population=2, generations=2,
                             elite=1, seed=0).run()
    assert out.best is None and out.best_value == math.inf

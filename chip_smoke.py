#!/usr/bin/env python3
"""Smoke run of the simulator's main path on one TPU chip.

Drives the vector engine through the entry points users call --
``simulate()`` and ``CascadeSimulator.run_iterative`` -- under the numpy
oracle and under each device kernel backend (``pallas-tpu``,
``jax-jit``), at deployment scale:

* phase A, ``gamma``: Gamma SpMSpM (Z = A^T B, A = B) on a matrix of
  wiki-Vote's published shape (8,297 x 8,297, 103,689 nonzeros; the
  paper's Table 4), nonzeros at uniform positions drawn from ``--seed``;
* phase B, ``bfs``: the Sec.-8 design (Ours-VCP) running BFS under the
  min-plus semiring for 64 iterations on the 131,044-vertex
  grid-plus-shortcuts graph of ``benchmarks/fig13_vcp.py``.

Every device backend must reproduce the oracle bit for bit (output
tensors, action counts, modeled seconds, traffic, energy), with no
Einsum falling back to the interpreter and no downgrade in the kernel
chain, and must launch at least one seam program on the chip in each
phase.  One JSON line per phase and backend reports wall seconds, the
compiles (and their seconds), and per seam the device calls and the
host delegations.  The last line of standard output is
``{"ok": true, "device": {...}}``; a failed check exits 1 before it.
Without a TPU the script exits 1 and prints no result.

    python3 chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from benchmarks.workloads import sparse_grid_graph  # noqa: E402
from repro.accelerators import graphicionado, simulate  # noqa: E402
from repro.core.csf import CSF  # noqa: E402
from repro.core.einsum import Semiring  # noqa: E402
from repro.core.generator import CascadeSimulator  # noqa: E402
from repro.core.vectorized import VectorBackend  # noqa: E402
from repro.obs.metrics import metrics  # noqa: E402

GAMMA_N, GAMMA_NNZ = 8297, 103689        # wiki-Vote, paper Table 4
BFS_SIDE, BFS_ITERS = 362, 64            # 362^2 = 131,044 vertices
#: the oracle first: every device backend is compared with it
BACKENDS = ("numpy", "pallas-tpu", "jax-jit")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


# ---------------------------------------------------------------------- #
# the two phases (a CPU test drives them at a tiny size)
# ---------------------------------------------------------------------- #
def gamma_workload(n: int = GAMMA_N, nnz: int = GAMMA_NNZ, seed: int = 0):
    """Inputs and shapes of phase A: an n x n matrix with ``nnz``
    nonzeros at uniform positions, built columnar (never dense)."""
    rng = np.random.default_rng(seed)
    flat = rng.choice(n * n, size=nnz, replace=False)
    pts = np.stack([flat // n, flat % n], axis=1).astype(np.int64)
    vals = rng.random(nnz) + 0.1
    a = CSF.from_coo("A", ["K", "M"], pts, vals, {"K": n, "M": n})
    b = CSF.from_coo("B", ["K", "N"], pts, vals, {"K": n, "N": n})
    return ({"A": a.to_ftensor(), "B": b.to_ftensor()},
            {"m": n, "k": n, "n": n})


def gamma_phase(workload, kernel_backend: str):
    """Phase A through ``simulate()``; returns (SimResult, iterations)."""
    inputs, shapes = workload
    res = simulate("gamma", inputs, shapes,
                   backend=VectorBackend(kernel_backend=kernel_backend))
    return res, 1


def bfs_workload(side: int = BFS_SIDE, seed: int = 0):
    """Graph of phase B: a side x side grid plus v/16 random shortcuts,
    as ``benchmarks/fig13_vcp.py`` builds it."""
    v = side * side
    return sparse_grid_graph(side, extra=v // 16, seed=seed), v


def bfs_phase(workload, kernel_backend: str, max_iters: int = BFS_ITERS):
    """Phase B through ``run_iterative``; returns (SimResult,
    iterations)."""
    g, v = workload
    a0 = np.zeros(v)
    a0[0] = 1.0
    p0 = a0.copy()                   # properties stored as distance+1
    sim = CascadeSimulator(graphicionado.improved_spec(weighted=False),
                           semiring=Semiring.min_plus(),
                           backend=VectorBackend(
                               kernel_backend=kernel_backend))
    return sim.run_iterative(
        {"G": g, "A0": a0, "P0": p0}, carry={"A0": "A1", "P0": "P1"},
        done_when_empty="A1", max_iters=max_iters,
        var_shapes={"d": v, "s": v})


def fingerprint(res, iters: int) -> dict:
    """Every simulated statistic of a run, in a form compared bit for
    bit: the tensors' CSF arrays as bytes, the report's floats by
    ``repr`` (exact for doubles)."""
    out = {"iterations": iters}
    for name, ft in sorted(res.tensors.items()):
        c = CSF.from_ftensor(ft)
        out[f"tensor:{name}"] = (
            tuple(c.ranks), [x.tobytes() for x in c.coords],
            [None if s is None else s.tobytes() for s in c.segments],
            c.values.tobytes())
    r = res.report
    out["action_counts"] = repr(sorted(r.action_counts.items()))
    out["modeled_seconds"] = repr(r.seconds)
    out["traffic"] = repr((r.dram_read_bytes, r.dram_write_bytes,
                           sorted(r.dram_bytes_per_einsum.items())))
    out["energy"] = repr((r.energy_pj,
                          sorted(r.energy_breakdown_pj.items())))
    return out


def native_failures(res) -> list:
    """Fallbacks to the interpreter and kernel-chain downgrades: on the
    chip each is a failure, not a recovery."""
    bad = [f"fallback {e}: {r}" for e, r in res.fallback_reasons.items()]
    for e, evs in res.downgrade_events.items():
        bad += [f"downgrade {e}: {ev}" for ev in evs]
    return bad


# ---------------------------------------------------------------------- #
# the chip run
# ---------------------------------------------------------------------- #
class _CompileLog:
    """Compiles seen through ``jax.monitoring``: backend compiles (a
    persistent-cache hit is one too, cheap) and their seconds."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return self.count, self.seconds, self.cache_hits


def _counter_deltas(before: dict, after: dict, prefix: str) -> dict:
    return {k[len(prefix):]: int(after[k] - before.get(k, 0))
            for k in sorted(after)
            if k.startswith(prefix) and after[k] != before.get(k, 0)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of both workloads (default 0)")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device}); nothing run",
              file=sys.stderr)
        return 1
    print(json.dumps({"step": 0, "device": device}), flush=True)

    log = _CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)

    phases = (("gamma", gamma_workload(seed=args.seed), gamma_phase),
              ("bfs", bfs_workload(seed=args.seed), bfs_phase))
    failures = []
    for phase, workload, run in phases:
        oracle = None
        for backend in BACKENDS:
            c0 = metrics().snapshot()["counters"]
            n0, s0, h0 = log.mark()
            t0 = time.perf_counter()
            res, iters = run(workload, backend)
            wall = time.perf_counter() - t0
            c1 = metrics().snapshot()["counters"]
            n1, s1, h1 = log.mark()
            calls = _counter_deltas(c0, c1, "kernel.device_call/")
            row = {"phase": phase, "backend": backend,
                   "wall_s": wall, "iterations": iters,
                   "compiles": n1 - n0, "compile_s": s1 - s0,
                   "cache_hits": h1 - h0, "device_calls": calls,
                   "host_delegations": _counter_deltas(
                       c0, c1, "kernel.host_delegation/")}
            bad = native_failures(res)
            fp = fingerprint(res, iters)
            if oracle is None:
                oracle = fp
            else:
                bad += [f"differs from numpy: {k}" for k in oracle
                        if fp.get(k) != oracle[k]]
                if not calls:
                    bad.append("no seam program ran on the chip")
            row["ok"] = not bad
            print(json.dumps(row), flush=True)
            failures += [f"{phase}/{backend}: {b}" for b in bad]
    print(json.dumps({"compiles": log.count, "compile_s": log.seconds,
                      "cache_hits": log.cache_hits}), flush=True)
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Pallas kernel micro-bench: interpret-mode correctness latency vs the
jnp reference (CPU container; TPU wall-clock is out of scope -- the
roofline table carries the performance story).

``backend`` additionally drives a small SpMSpM loop nest through the
selected execution backend (python | vector), so the offset-keyed
co-iteration primitives (intersect_keys / union_keys) are exercised on
their real call path.

``seam_rates`` measures the four dispatch seams of the kernel-backend
registry (intersect / union-k / lookup / segmented-reduce) in keys per
second per backend; ``--record`` merges them into BENCH_backend.json
under ``kernel_rates``."""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.backends import KERNEL_BACKENDS, resolve_kernel_backend

BENCH_JSON = Path(__file__).resolve().parent.parent / "BENCH_backend.json"


def _t(fn, *args, reps=3) -> Tuple[float, object]:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6, out


def run(backend: str = "vector") -> List[Tuple[str, float, float]]:
    rows = []
    rng = np.random.default_rng(0)

    # flash attention
    q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    us, got = _t(lambda a, b, c: ops.flash_attention(a, b, c), q, k, v)
    err = float(jnp.max(jnp.abs(got - ref.attention_ref(q, k, v))))
    rows.append(("kernels/flash_attention/interpret", us, err))
    us_ref, _ = _t(ref.attention_ref, q, k, v)
    rows.append(("kernels/flash_attention/jnp_ref", us_ref, 0.0))

    # block-sparse matmul
    a = rng.standard_normal((256, 256)).astype(np.float32)
    mask = rng.random((4, 4)) < 0.4
    a = a * np.kron(mask, np.ones((64, 64), np.float32))
    b = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    tiles, rws, cls = ops.compact_tiles(a, 64, 64)
    us, got = _t(lambda t_, r_, c_, b_: ops.block_sparse_matmul(
        t_, r_, c_, b_, m=256, bn=64), tiles, rws, cls, b)
    err = float(jnp.max(jnp.abs(
        got - ref.block_sparse_matmul_ref(jnp.asarray(a), b))))
    rows.append(("kernels/block_sparse_matmul/interpret", us, err))

    # ssd chunk
    x = jnp.asarray(rng.standard_normal((1, 2, 128, 4, 64)), jnp.float32)
    aa = -jnp.abs(jnp.asarray(rng.standard_normal((1, 4, 2, 128)),
                              jnp.float32)) * 0.1
    bb = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    us, got = _t(ops.ssd_chunk, x, aa, bb, cc)
    err = float(jnp.max(jnp.abs(got - ref.ssd_chunk_ref(x, aa, bb, cc))))
    rows.append(("kernels/ssd_chunk/interpret", us, err))

    # sorted-coordinate intersection (ExTensor skip-ahead -> TPU)
    ac = ops.pad_sorted(np.sort(rng.choice(100000, 2000,
                                           replace=False)).astype(np.int32))
    bc = ops.pad_sorted(np.sort(rng.choice(100000, 4000,
                                           replace=False)).astype(np.int32))
    us, got = _t(ops.intersect_sorted, jnp.asarray(ac), jnp.asarray(bc))
    err = float(jnp.max(jnp.abs(
        got - ref.intersect_sorted_ref(ac, bc))))
    rows.append(("kernels/intersect_sorted/interpret", us, err))

    # 2-way stable merge ranks (the union kernel) vs numpy merge
    # (both rows pad to the same 4096-key bucket)
    am = np.sort(rng.choice(50000, 2500, replace=False)).astype(np.int32)
    bm = np.sort(rng.choice(50000, 3500, replace=False)).astype(np.int32)
    stacked = np.stack([ops.pad_sorted(am), ops.pad_sorted(bm)])
    us, ranks = _t(ops.multi_merge_ranks, jnp.asarray(stacked))
    ranks = np.asarray(ranks)
    merged = np.empty(len(am) + len(bm), np.int64)
    merged[ranks[0, :len(am)]] = am
    merged[ranks[1, :len(bm)]] = bm
    err = float(np.max(np.abs(merged - np.sort(np.concatenate([am, bm])))))
    rows.append(("kernels/multi_merge_ranks/interpret", us, err))

    # execution-backend co-iteration micro-bench (real call path of the
    # intersect/union primitives)
    from repro.core.generator import CascadeSimulator
    from repro.core.trace import CollectingInstr
    from repro.accelerators.zoo import rowwise_spmspm
    n = 256
    a = rng.random((n, n)) * (rng.random((n, n)) < 0.05)
    b = rng.random((n, n)) * (rng.random((n, n)) < 0.05)
    ci = CollectingInstr()
    sim = CascadeSimulator(rowwise_spmspm(), model=False, extra_instr=ci,
                           backend=backend)
    t0 = time.time()
    sim.run({"A": a, "B": b}, {"m": n, "k": n, "n": n})
    dt = time.time() - t0
    muls = int(ci.compute_counts[("Z", "mul")])
    rows.append((f"kernels/spmspm_coiter/{backend}", dt * 1e6,
                 round(muls / max(dt, 1e-9), 1)))
    return rows


# ---------------------------------------------------------------------- #
# dispatch-seam microbenchmarks (kernel-backend registry)
# ---------------------------------------------------------------------- #
def _seam_inputs(n: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    dom = 8 * n
    a = np.sort(rng.choice(dom, size=n, replace=False)).astype(np.int64)
    b = np.sort(rng.choice(dom, size=n, replace=False)).astype(np.int64)
    c = np.sort(rng.choice(dom, size=n // 2, replace=False)).astype(
        np.int64)
    probes = rng.integers(0, dom, size=n).astype(np.int64)
    vals = rng.random(n) + 0.1
    gids = np.sort(rng.integers(0, max(n // 8, 1), size=n)).astype(
        np.int64)
    gids = np.cumsum(np.diff(gids, prepend=gids[0:1]) > 0).astype(np.int64)
    starts = np.flatnonzero(np.diff(gids, prepend=-1) > 0)
    return a, b, c, probes, vals, starts, gids


def seam_rates(kernel_backend: str = "numpy", n: int = 1 << 20,
               reps: int = 3) -> Dict[str, float]:
    """Keys per second through each registry dispatch seam (best of
    ``reps``), on sorted unique key arrays of ``n`` elements."""
    from repro.core.einsum import Semiring

    kb = resolve_kernel_backend(kernel_backend)
    a, b, c, probes, vals, starts, gids = _seam_inputs(n)
    sr = Semiring.arithmetic()
    seams = {
        "intersect": (lambda: kb.intersect_keys(a, b), n),
        "union_k": (lambda: kb.union_k_keys([a, b, c]), n * 5 // 2),
        "lookup": (lambda: kb.lookup_keys(a, probes), n),
        "segmented_reduce": (
            lambda: kb.segmented_reduce(vals, starts, sr, group_ids=gids),
            n),
    }
    out: Dict[str, float] = {}
    for name, (fn, keys) in seams.items():
        fn()                                  # warm (jit compile etc.)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        out[name] = round(keys / best, 1)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", action="store_true",
                    help=f"merge kernel_rates into {BENCH_JSON.name}")
    ap.add_argument("--kernel-backends", default="numpy,jax-jit",
                    help="comma-separated registry backends to measure")
    ap.add_argument("--n", type=int, default=1 << 20)
    args = ap.parse_args()
    names = [s for s in args.kernel_backends.split(",") if s]
    bad = [s for s in names if s not in KERNEL_BACKENDS]
    if bad:
        ap.error(f"unknown kernel backends {bad}; choose from "
                 f"{KERNEL_BACKENDS}")
    rates = {name: seam_rates(name, n=args.n) for name in names}
    summary = {"metric": "keys per second", "n_keys": args.n,
               "backends": rates}
    print(json.dumps(summary, indent=2))
    if args.record:
        doc = {}
        if BENCH_JSON.exists():
            doc = json.loads(BENCH_JSON.read_text())
        doc["kernel_rates"] = summary
        BENCH_JSON.write_text(json.dumps(doc, indent=2) + "\n")
        print(f"wrote {BENCH_JSON}")


if __name__ == "__main__":
    main()

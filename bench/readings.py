#!/usr/bin/env python3
"""Readings that a cell's limits are set from: for each seed, the
worst value over the jobs of every compared number, for the control
(the reference in the program's place, one step worse: float32 SpMSpM,
a BFS stopped one iteration short) and, with ``--program-jobs N``,
for the program's first N jobs of that seed.  The benchmark's own
runs do not run this.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \
        [--program-jobs N]

One JSON line per seed and side.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness import registry  # noqa: E402


def worst(cell, pairs):
    out = {k: 0.0 for k in cell.config["limits"]}
    for got, ref in pairs:
        for k, v in cell.reference.compare(got, ref).items():
            out[k] = max(out[k], v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--program-jobs", type=int, default=0)
    args = ap.parse_args(argv)
    cell = registry.find_cell(args.workload)
    ref = cell.reference
    cfg = cell.config
    for seed in (int(s) for s in args.seeds.split(",")):
        _, jobs = cell.driver.prepare(seed, cfg, cell.traffic)
        sides = {"control": [(ref.control(j, cfg), ref.expected(j, cfg))
                             for j in jobs[:max(args.program_jobs, 3)]]}
        if args.program_jobs:
            sides["program"] = [
                (cell.driver.answer(j, cell.driver.run(j, cfg), cfg),
                 ref.expected(j, cfg))
                for j in jobs[:args.program_jobs]]
        for side, pairs in sides.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": side, "jobs": len(pairs),
                              "worst": worst(cell, pairs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

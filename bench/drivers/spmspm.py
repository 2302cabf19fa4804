"""SpMSpM jobs through ``repro.accelerators.simulate``: one job is one
whole simulation of ``Z = A^T B`` (B = A, as in the paper's Fig. 10)
on the configuration's design.

The matrix of job ``j`` is drawn from ``(seed, j)``; the warm-up jobs
use streams no window job uses.  A is handed to the program in the
rank order the configuration's ``a_order`` states (the order the
spec stores A in).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import numpy as np

from harness import gen
from harness.checks import model_stats, native_failures

#: job index of the first warm-up job's matrix (window jobs count
#: from 0)
WARMUP_JOB = 2**31


@dataclass
class Job:
    index: int
    #: the matrix X as sorted COO (row k, column m), float64 values
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    n: int
    inputs: Dict[str, Any]


def _job(seed: int, index: int, cfg: Dict) -> Job:
    from repro.core.csf import CSF

    n = cfg["rows"]
    rows, cols, vals = gen.rmat_matrix(seed, index, n, cfg["nonzeros"],
                                       cfg["rmat_abc"])
    # A[k, m] = B[k, n] = X[k, m]
    a_pts = {"M": cols, "K": rows}
    a_order = cfg["a_order"]
    a = CSF.from_coo("A", a_order,
                     np.stack([a_pts[r] for r in a_order], axis=1),
                     vals, {"K": n, "M": n})
    b = CSF.from_coo("B", ["K", "N"], np.stack([rows, cols], axis=1),
                     vals, {"K": n, "N": n})
    return Job(index, rows, cols, vals, n,
               {"A": a.to_ftensor(), "B": b.to_ftensor()})


def prepare(seed: int, cfg: Dict, traffic: Dict):
    """(warm-up jobs, window jobs): every job's inputs, made here."""
    jobs = [_job(seed, j, cfg) for j in range(traffic["job_cap"])]
    warm = [_job(seed, WARMUP_JOB + j, cfg)
            for j in range(traffic["warmup_jobs"])]
    return warm, jobs


def run(job: Job, cfg: Dict):
    """One whole simulation through the entry point users call."""
    from repro.accelerators import simulate
    from repro.core.vectorized import VectorBackend

    n = job.n
    return simulate(cfg["design"], job.inputs, {"m": n, "k": n, "n": n},
                    backend=VectorBackend(
                        kernel_backend=cfg["kernel_backend"]))


def answer(job: Job, res, cfg: Dict) -> Dict[str, Any]:
    """What the program answered for this job, read from its output
    tensor Z[M, N] leaf by leaf, and its model statistics."""
    z = res.tensors["Z"]
    if list(z.ranks) != ["M", "N"]:
        raise ValueError(f"Z is stored as {z.ranks}, expected [M, N]")
    m, nn, v = [], [], []
    for (zm, zn), val in z.iter_leaves():
        m.append(zm)
        nn.append(zn)
        v.append(val)
    return {"z": (np.asarray(m, np.int64), np.asarray(nn, np.int64),
                  np.asarray(v, np.float64)),
            "stats": model_stats(res.report),
            "native_failures": native_failures(res)}

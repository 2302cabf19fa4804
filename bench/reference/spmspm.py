"""Plain reference of an SpMSpM job on Gamma, ``Z = X^T X``: scipy's
sparse product, and the performance model's statistics
(``perfmodel.py``) from the counts of Gamma's dataflow, from the job's
COO arrays alone.  Nothing here imports the program.

Gamma (paper Fig. 8a) stores A[k, m] = X[k, m] as rows ``m`` (its
``[M, K]`` order), cuts M into rounds of ``pes`` nonempty rows and each
row's K into chunks of ``radix`` nonzeros, and for each nonzero
A[m, k] fetches row k of B = X.  With ``a_m`` the nonzeros of A's row
m, ``r_k`` those of X's row k, and per chunk ``c`` of a row the set
``N_c`` of columns its fetched rows reach:

* T = take(A, B): A's rounds, rows, chunks and nonzeros are read once;
  B's row heads once per nonzero of A; every fetched element
  (sum_k r_k**2, the job's multiplies) is read from B and written to T;
* the merger swizzles each row's a_m fetched rows (sum of their r_k
  elements) into N order;
* Z = T * A: for each chunk, each column of N_c steps through the
  chunk's coordinates of A (and of T): sum_c |N_c| * |c| steps; every
  fetched element is multiplied and written into Z, and all but the
  first into each output are adds (multiplies - nnz(Z)).
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np
import scipy.sparse as sp

from reference.perfmodel import expand, replay, stat_gaps


def ops(job) -> int:
    """Simulated multiplies of the job: sum_k nnz(X[k, :])**2."""
    r = np.bincount(job.rows, minlength=job.n).astype(np.int64)
    return int((r * r).sum())


def _product(job, dtype):
    x = sp.csr_matrix((job.vals.astype(dtype), (job.rows, job.cols)),
                      shape=(job.n, job.n))
    return x, (x.T @ x).tocoo()


def events(job, spec: Dict) -> List[List[tuple]]:
    """Gamma's aggregate events of the job (one iteration), for the
    configuration's ``model`` section ``spec``."""
    pes, radix = spec["rows_per_round"], spec["merge_radix"]
    x = sp.csr_matrix((np.ones(len(job.rows)), (job.rows, job.cols)),
                      shape=(job.n, job.n))
    r = np.diff(x.indptr).astype(np.int64)
    a = x.T.tocsr()                      # A as rows m, columns k
    a.sort_indices()
    a_m = np.diff(a.indptr)
    rows_ne = int(np.count_nonzero(a_m))
    rounds = -(-rows_ne // pes)
    nnz = int(a.nnz)
    mul = ops(job)
    # chunk of each nonzero of A: rows cut into runs of ``radix``
    pos = np.arange(nnz) - np.repeat(a.indptr[:-1], a_m)
    chunk = np.repeat(np.cumsum(-(-a_m // radix)) - -(-a_m // radix),
                      a_m) + pos // radix
    chunks = int(np.sum(-(-a_m // radix)))
    # (chunk, n) of every fetched element, and its distinct pairs
    k = a.indices
    fetched_chunk = np.repeat(chunk, r[k])
    fetched_n = x.indices[expand(x.indptr[k], r[k])]
    pairs = np.unique(fetched_chunk * job.n + fetched_n)
    n_per_chunk = np.bincount(pairs // job.n, minlength=chunks)
    chunk_size = np.bincount(chunk, minlength=chunks)
    steps = int(np.dot(n_per_chunk, chunk_size))
    n_chunk_cols = int(len(pairs))
    _, z = _product(job, np.float64)
    adds = mul - int(z.nnz)

    upper = {"M1": rounds, "M0": rows_ne, "K1": chunks}
    t: Dict[tuple, int] = {}
    zz: Dict[tuple, int] = {}
    for rank, n in upper.items():
        for ev in (t, zz):
            ev[("iterate", rank)] = n
            ev[("touch", "A", rank, "coord", "r")] = n
        zz[("touch", "T", rank, "coord", "r")] = n
        zz[("isect_step", rank, "A")] = n
    t.update({("iterate", "K0"): nnz, ("iterate", "N"): mul,
              ("isect_step", "K0", "A"): nnz,
              ("touch", "A", "K0", "coord", "r"): nnz,
              ("touch", "A", "K0", "payload", "r"): nnz,
              ("touch", "B", "K0", "coord", "r"): nnz,
              ("touch", "B", "N", "coord", "r"): mul,
              ("touch", "B", "N", "payload", "r"): mul,
              ("touch", "T", "N", "payload", "w"): mul})
    zz.update({("iterate", "N"): n_chunk_cols, ("iterate", "K0"): mul,
               ("isect_step", "K0", "A"): steps,
               ("touch", "A", "K0", "coord", "r"): steps,
               ("touch", "A", "K0", "payload", "r"): mul,
               ("touch", "T", "K0", "coord", "r"): steps,
               ("touch", "T", "K0", "payload", "r"): mul,
               ("touch", "T", "N", "coord", "r"): n_chunk_cols,
               ("touch", "Z", "N", "payload", "r"): adds,
               ("touch", "Z", "N", "payload", "w"): mul,
               ("compute", "mul"): mul, ("compute", "add"): adds})
    merged = np.add.reduceat(r[k], a.indptr[:-1][a_m > 0]) \
        if nnz else np.zeros(0, np.int64)
    merges = [("merge", "Z", int(e), int(lists))
              for e, lists in zip(merged, a_m[a_m > 0])]
    return [[("einsum", "T", t)] + merges + [("einsum", "Z", zz)]]


def expected(job, cfg: Dict, dtype=np.float64) -> Dict[str, Any]:
    """Z and the model's statistics, computed in ``dtype`` (float64 as
    configured; float32 is the control)."""
    _, z = _product(job, dtype)
    order = np.lexsort((z.col, z.row))
    stats = replay(cfg["model"], events(job, cfg["model"]))
    if dtype != np.float64:
        stats = {k: float(dtype(v)) for k, v in stats.items()}
    return {"z": (z.row[order].astype(np.int64),
                  z.col[order].astype(np.int64),
                  z.data[order].astype(np.float64)),
            "stats": stats, "native_failures": []}


def control(job, cfg: Dict) -> Dict[str, Any]:
    """The reference computed in float32, one precision below the
    configured float64."""
    return expected(job, cfg, np.float32)


def compare(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """Numbers compared for one job (each against its limit):

    * ``z_pattern_diff``: output positions present on one side only;
    * ``z_rel_gap``: the widest |z - z_ref| / |z_ref| over the shared
      positions (values are positive, so no cancellation);
    * ``count_gap``, ``model_rel_gap``: the model's statistics
      (``perfmodel.stat_gaps``);
    * ``native_failures``: fallbacks and kernel-chain downgrades."""
    gm, gn, gv = got["z"]
    rm, rn, rv = ref["z"]
    n = int(max(gm.max(initial=0), rm.max(initial=0),
                gn.max(initial=0), rn.max(initial=0))) + 1
    gk, rk = gm * n + gn, rm * n + rn
    shared, gi, ri = np.intersect1d(gk, rk, assume_unique=True,
                                    return_indices=True)
    pattern = len(gk) + len(rk) - 2 * len(shared)
    gap = float(np.max(np.abs(gv[gi] - rv[ri]) / np.abs(rv[ri]),
                       initial=0.0))
    out = {"z_pattern_diff": float(pattern), "z_rel_gap": gap}
    out.update(stat_gaps(got["stats"], ref["stats"]))
    out["native_failures"] = float(len(got["native_failures"]))
    return out

"""Plain reference of a BFS job on Ours-VCP: a level-synchronous BFS
over the edge list, capped at the job's iterations, and the
performance model's statistics (``perfmodel.py``) from the counts of
the design's Einsums.  Nothing here imports the program.

Iteration ``i`` expands the ``F`` vertices of level ``i - 1`` (the
root first) over their ``E`` out-edges, which reach ``R`` distinct
destinations: ``V`` of them already have a distance, ``N`` are new.
The design (paper Sec. 8) runs, per iteration:

* SO = take(G, A0): the frontier's ``F`` entries and edge heads, its
  ``E`` edges read from G and written to SO;
* R = SO * A0 (min-plus): ``E`` multiplies, ``E - R`` reductions;
* MP = take(R, P0): the ``R`` destinations led through P0, ``V`` hits;
* NP = R + MP, M = NP - MP: ``R`` outputs, ``V`` of them combined;
* P0 = take(M, NP), A1 = take(M, NP): the ``N`` new vertices update
  the properties and form the next frontier.

The design stops after an iteration with no new vertex, or at the cap:

* ``iterations``: min(cap, deepest level + 1);
* simulated operations: the edges traversed, sum_i E_i (Graph500's
  count).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from harness.gen import bfs_depths
from reference.perfmodel import replay, stat_gaps


def _levels(job, cap: int):
    """Distances, iterations, and per iteration (F, E, R, V, N)."""
    g = job.graph
    depth = bfs_depths(g, job.root, cap)
    iters = min(cap, int(depth.max()) + 1)
    src_level = depth[g.src]
    rows = []
    for level in range(iters):
        hit = src_level == level
        dst = np.unique(g.dst[hit])
        d = depth[dst]
        known = int(np.count_nonzero((d >= 0) & (d <= level)))
        rows.append((int(np.count_nonzero(depth == level)),
                     int(hit.sum()), len(dst), known, len(dst) - known))
    return depth, iters, rows


def ops(job) -> int:
    """Edges traversed by the job's BFS."""
    return sum(e for _, e, _, _, _ in _levels(job, job.cap)[2])


def _iteration(f: int, e: int, r: int, v: int, n: int) -> List[tuple]:
    """Ours-VCP's aggregate events of one iteration."""
    def take(follower: str) -> Dict[tuple, int]:
        return {("iterate", "D"): n, ("isect_step", "D", "M"): n,
                ("touch", "M", "D", "coord", "r"): n,
                ("touch", "M", "D", "payload", "r"): n,
                ("touch", "NP", "D", "coord", "r"): n,
                ("touch", "NP", "D", "payload", "r"): n,
                ("touch", follower, "D", "payload", "w"): n}

    frontier = {("touch", "A0", "S", "coord", "r"): f,
                ("touch", "A0", "S", "payload", "r"): f,
                ("iterate", "S"): f, ("iterate", "D"): e}
    so = {**frontier,
          ("isect_step", "S", "A0"): f,
          ("touch", "G", "S", "coord", "r"): f,
          ("touch", "G", "D", "coord", "r"): e,
          ("touch", "G", "D", "payload", "r"): e,
          ("touch", "SO", "D", "payload", "w"): e}
    rr = {**frontier,
          ("isect_step", "S", "SO"): f,
          ("touch", "SO", "S", "coord", "r"): f,
          ("touch", "SO", "D", "coord", "r"): e,
          ("touch", "SO", "D", "payload", "r"): e,
          ("touch", "R", "D", "payload", "r"): e - r,
          ("touch", "R", "D", "payload", "w"): e,
          ("compute", "mul"): e, ("compute", "add"): e - r}
    mp = {("iterate", "D"): v, ("isect_step", "D", "R"): r,
          ("touch", "R", "D", "coord", "r"): r,
          ("touch", "R", "D", "payload", "r"): v,
          ("touch", "P0", "D", "coord", "r"): r,
          ("touch", "P0", "D", "payload", "r"): v,
          ("touch", "MP", "D", "payload", "w"): v}
    known = {("touch", "MP", "D", "coord", "r"): v,
             ("touch", "MP", "D", "payload", "r"): v}
    np_ = {**known,
           ("iterate", "D"): r, ("compute", "add"): v,
           ("touch", "R", "D", "coord", "r"): r,
           ("touch", "R", "D", "payload", "r"): r,
           ("touch", "NP", "D", "payload", "w"): r}
    m = {**known,
         ("iterate", "D"): r, ("compute", "add"): r,
         ("touch", "NP", "D", "coord", "r"): r,
         ("touch", "NP", "D", "payload", "r"): r,
         ("touch", "M", "D", "payload", "w"): n}
    return [("einsum", "SO", so), ("einsum", "R", rr),
            ("einsum", "MP", mp), ("einsum", "NP", np_),
            ("einsum", "M", m), ("einsum", "P0", take("P0")),
            ("einsum", "A1", take("A1"))]


def events(job, spec: Dict, cap: Optional[int] = None
           ) -> List[List[tuple]]:
    """The design's aggregate events of the job, iteration by
    iteration."""
    rows = _levels(job, job.cap if cap is None else cap)[2]
    return [_iteration(*row) for row in rows]


def expected(job, cfg: Dict, cap: Optional[int] = None
             ) -> Dict[str, Any]:
    """Distances, iterations and model statistics of the BFS capped
    at ``cap`` iterations (by default the job's cap)."""
    cap = job.cap if cap is None else cap
    depth, iters, _ = _levels(job, cap)
    return {"dist": depth, "iterations": iters,
            "stats": replay(cfg["model"], events(job, cfg["model"], cap)),
            "native_failures": []}


def control(job, cfg: Dict) -> Dict[str, Any]:
    """The reference stopped one iteration short: it breaks the
    guarantee that the BFS runs until no vertex changes, or the cap."""
    return expected(job, cfg, expected(job, cfg)["iterations"] - 1)


def compare(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """Numbers compared for one job (each against its limit):

    * ``dist_diff``: vertices whose hop distance differs (reached on
      one side only included);
    * ``iter_gap``: |iterations - iterations_ref|;
    * ``count_gap``, ``model_rel_gap``: the model's statistics
      (``perfmodel.stat_gaps``);
    * ``native_failures``: fallbacks and kernel-chain downgrades."""
    out = {"dist_diff": float(np.count_nonzero(got["dist"] != ref["dist"])),
           "iter_gap": float(abs(got["iterations"] - ref["iterations"]))}
    out.update(stat_gaps(got["stats"], ref["stats"]))
    out["native_failures"] = float(len(got["native_failures"]))
    return out

"""BFS jobs on a vertex-centric design through
``CascadeSimulator.run_iterative``: one job is one whole BFS, under
the min-plus semiring, from one root, until no vertex changes or the
configuration's iteration cap.

All jobs share the configuration's graph, made once from its
``graph_seed``; roots are drawn from the run's seed by the traffic
mix's rule (as Graph500 draws its 64 search keys).  Properties are stored as
distance + 1, as the design's module states.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import numpy as np

from harness import gen
from harness.checks import model_stats, native_failures


@dataclass
class Job:
    index: int
    root: int
    graph: gen.Graph
    g_tensor: Any
    cap: int


def prepare(seed: int, cfg: Dict, traffic: Dict):
    """(warm-up jobs, window jobs): one graph, one root per job."""
    from repro.core.csf import CSF

    g = gen.make_graph(cfg)
    pts = np.stack([g.src, g.dst], axis=1)
    g_tensor = CSF.from_coo("G", ["S", "D"], pts, np.ones(len(g.src)),
                            {"S": g.v, "D": g.v}).to_ftensor()
    n_warm = traffic["warmup_jobs"]
    roots = gen.pick_roots(seed, g, traffic, n_warm + traffic["job_cap"])
    jobs = [Job(i - n_warm, r, g, g_tensor, cfg["max_iters"])
            for i, r in enumerate(roots)]
    return jobs[:n_warm], jobs[n_warm:]


def run(job: Job, cfg: Dict):
    """One whole BFS through the entry point users call; returns
    (SimResult, iterations)."""
    from repro.accelerators import REGISTRY
    from repro.core.einsum import Semiring
    from repro.core.generator import CascadeSimulator
    from repro.core.vectorized import VectorBackend

    v = job.graph.v
    a0 = np.zeros(v)
    a0[job.root] = 1.0
    sim = CascadeSimulator(
        REGISTRY[cfg["design"]](weighted=False),
        semiring=Semiring.min_plus(),
        backend=VectorBackend(kernel_backend=cfg["kernel_backend"]))
    return sim.run_iterative(
        {"G": job.g_tensor, "A0": a0, "P0": a0.copy()},
        carry={"A0": "A1", "P0": "P1"}, done_when_empty="A1",
        max_iters=job.cap, var_shapes={"d": v, "s": v})


def answer(job: Job, out, cfg: Dict) -> Dict[str, Any]:
    """Hop distances read from the final properties P1[D] leaf by leaf
    (-1 where unreached), the iterations run and the model
    statistics."""
    res, iters = out
    p = res.tensors["P1"]
    dist = np.full(job.graph.v, -1, np.int64)
    for (d,), val in p.iter_leaves():
        dist[d] = int(val) - 1
    return {"dist": dist, "iterations": iters,
            "stats": model_stats(res.report),
            "native_failures": native_failures(res)}


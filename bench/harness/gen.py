"""The benchmark's one traffic generator: sparse matrices and graphs
drawn from a seed, and the jobs a traffic mix makes of them.

* ``kronecker_edges``: the Graph500 Kronecker (R-MAT) edge generator,
  as the Graph500 specification's reference code draws it (quadrant
  probabilities A, B, C per bit level, then a random relabelling of
  the vertices and a shuffle of the edge list).
* ``rmat_matrix``: an n x n matrix whose nonzeros are Kronecker edges,
  drawn until a stated number are distinct, with values uniform in
  [0.1, 1.1).
* ``graph500_graph``: a Kronecker graph made undirected and
  deduplicated, without self loops (the Graph500 BFS input).

Every array is made columnar with numpy from a ``numpy.random``
generator seeded by ``rng(seed, *stream)``; nothing here imports the
program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

#: Graph500 initiator probabilities (graph500.org specification)
GRAPH500_ABC = (0.57, 0.19, 0.19)


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Generator for ``seed`` and a stream path; any integer seed."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


@dataclass
class Graph:
    """A directed graph as an edge list (src -> dst), sorted by
    (src, dst), with ``v`` vertices."""
    v: int
    src: np.ndarray
    dst: np.ndarray

    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.v)


def kronecker_edges(r: np.random.Generator, scale: int, m: int,
                    abc: Sequence[float] = GRAPH500_ABC):
    """``m`` directed Kronecker edges on 2**scale vertices."""
    a, b, c = abc
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = r.random(m) > ab
        jj = r.random(m) > np.where(ii, c_norm, a_norm)
        i |= ii.astype(np.int64) << bit
        j |= jj.astype(np.int64) << bit
    perm = r.permutation(1 << scale)
    i, j = perm[i], perm[j]
    order = r.permutation(m)
    return i[order], j[order]


def rmat_matrix(seed: int, job: int, n: int, nnz: int,
                abc: Sequence[float] = GRAPH500_ABC):
    """(rows, cols, vals) of an n x n matrix (n a power of two) with
    exactly ``nnz`` distinct nonzeros, sorted row-major."""
    r = rng(seed, 1, job)
    scale = n.bit_length() - 1
    if n != 1 << scale:
        raise ValueError(f"R-MAT needs a power-of-two size, not {n}")
    keys = np.zeros(0, np.int64)
    while len(keys) < nnz:
        i, j = kronecker_edges(r, scale, 2 * nnz, abc)
        new = i * n + j
        cat = np.concatenate([keys, new])
        _, first = np.unique(cat, return_index=True)
        keys = cat[np.sort(first)]           # distinct, in draw order
    keys = np.sort(keys[:nnz])
    vals = r.random(nnz) + 0.1
    return keys // n, keys % n, vals


def _dedup(v: int, src: np.ndarray, dst: np.ndarray) -> Graph:
    keys = np.unique(src * v + dst)
    return Graph(v, keys // v, keys % v)


def graph500_graph(seed: int, scale: int, edgefactor: int,
                   abc: Sequence[float] = GRAPH500_ABC) -> Graph:
    """Undirected Kronecker graph: both directions of every edge, no
    self loops, no duplicates."""
    i, j = kronecker_edges(rng(seed, 2), scale, edgefactor << scale, abc)
    keep = i != j
    i, j = i[keep], j[keep]
    return _dedup(1 << scale, np.concatenate([i, j]),
                  np.concatenate([j, i]))


def make_graph(cfg: Dict) -> Graph:
    """The graph a configuration names under ``graph``, drawn from its
    ``graph_seed``: a deployment's graph is one fixed dataset, and a
    run's seed draws the search keys."""
    kind = cfg["graph"]
    if kind == "graph500":
        return graph500_graph(cfg["graph_seed"], cfg["scale"],
                              cfg["edgefactor"])
    raise ValueError(f"unknown graph generator {kind!r}")


def bfs_depths(g: Graph, root: int, max_levels: int = -1) -> np.ndarray:
    """Hop distance of every vertex from ``root`` (-1 where unreached
    or beyond ``max_levels`` levels, when that is not negative): a
    level-synchronous BFS over the edge list."""
    starts = np.searchsorted(g.src, np.arange(g.v + 1))
    depth = np.full(g.v, -1, np.int64)
    depth[root] = 0
    frontier = np.array([root], np.int64)
    level = 0
    while len(frontier) and level != max_levels:
        level += 1
        lo = starts[frontier]
        cnt = starts[frontier + 1] - lo
        total = int(cnt.sum())
        idx = (np.arange(total) - np.repeat(np.cumsum(cnt) - cnt, cnt)
               + np.repeat(lo, cnt))
        nxt = np.unique(g.dst[idx])
        nxt = nxt[depth[nxt] < 0]
        depth[nxt] = level
        frontier = nxt
    return depth


def pick_roots(seed: int, g: Graph, traffic: Dict, count: int
               ) -> List[int]:
    """``count`` distinct roots of a BFS traffic mix, drawn from the
    seed.  ``root_rule`` ``nonzero_degree`` is Graph500's: any vertex
    with an edge, in a seeded random order."""
    if traffic["root_rule"] != "nonzero_degree":
        raise ValueError(f"unknown root rule {traffic['root_rule']!r}")
    r = rng(seed, 4)
    cands = r.permutation(g.v)
    roots = cands[g.out_degree()[cands] > 0][:count]
    if len(roots) < count:
        raise ValueError(f"only {len(roots)} vertices have an edge")
    return [int(x) for x in roots]

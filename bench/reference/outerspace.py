"""Plain reference of an SpMSpM job on OuterSPACE, ``Z = X^T X``:
scipy's sparse product (``spmspm.py``), and the performance model's
statistics from the counts of OuterSPACE's outer-product dataflow,
from the job's COO arrays alone.  Nothing here imports the program.

OuterSPACE (paper Figs. 3 and 5) stores A[k, m] = X[k, m] as columns
(its ``[K, M]`` order, CSC) and runs two phases, each an Einsum on a
topology of its own:

* multiply, T[k, m, n] = A[k, m] * B[k, n]: the nonzeros of A, (K, M)
  flattened in K-major order, are cut into batches of ``256`` and each
  batch into groups of ``16`` (``partitions.multiply``).  For each
  nonzero A[k, m] the PE fetches row k of B = X, multiplies and writes
  the r_k products into T's linked list of row m.  With ``r_k`` the
  nonzeros of X's row k: sum_k r_k**2 multiplies (the job's ops).
* merge, Z[m, n] = T[k, m, n]: the nonempty rows m of T (``[M, K, N]``
  as stored) are cut into batches of ``128`` and groups of ``8``; each
  row's a_m lists (one per nonzero of column m of X) are sorted into N
  order, the merger's work, and reduced over K: multiplies - nnz(Z)
  adds.

Rules that ``perfmodel.py`` does not state, stated here
(``OuterSpaceModel``):

* Each Einsum runs on its own topology (``topology``), with its own
  components (``components[topology]``); a buffer level is one per
  (component, tensor, kind) across the cascade, sized by the topology
  that first binds it, width x depth x instances (the per-PT L0s are
  pooled into one level).  ``cache`` and ``buffet`` levels follow the
  same residency rule under the aggregate touches the vector engine
  makes (the line width does not enter; see below).
* A Sequencer takes one step per iterated coordinate, at every rank
  of its Einsum, over its instances (1 here); it counts no action and
  costs no energy, but it is a component of its block's time.
* A buffer bound with ``evict-on`` rank R (``evict_on``) is emptied,
  its dirty lines written back, at each advance of R and at the end
  of its Einsum.  An Einsum's events arrive in the order of their
  keys, so the advance comes before the touches.
* The merger is the one in the topology of the Einsum its work is
  sent to (``SortNet``: radix 2, one output; ``inputs`` does not
  enter); a compute unit's cycles are its operations over its
  instances.  The design has no intersection unit and reports no
  intersection count.
* Space ranks (KM1, KM0; M1, M0) spread nothing: every unit's cycles
  are its aggregate count over its instances.

Where the statement departs from the published OuterSPACE, because
the program's model does so:

* aggregate counts instead of per-PE load: the 256 multipliers and 128
  adders are loaded evenly, whatever the R-MAT skew does to a PT;
* residency is kept per (tensor, rank, kind), not per line or list: a
  bound level fills each key once per epoch, so the merge phase reads
  6 x 4 B of T from DRAM, not T; the multiply phase writes T as its
  4-B payloads alone, no coordinates and no list pointers;
* T's Fig.-5c format moves no byte: the merge phase touches T at M2,
  M1, M0 (partition ranks, which no format lists, so 32-bit
  compressed), K and N; M's 64-bit uncompressed heads and N's 64-bit
  fiber headers would count only in an eager subtree fill, which
  aggregate touches never make;
* Table 5's L1 (4 kB per 4 PTs) is not in the spec, and the
  sequencer of the multiply phase is its bottleneck (one step per
  iterated coordinate on one instance).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np
import scipy.sparse as sp

from reference.perfmodel import Events, Model, _Level
# the runner reads ``ops`` and ``compare`` from the cell's reference
from reference.spmspm import _product, compare, ops  # noqa: F401


def _groups(n: int, batch: int, group: int) -> tuple:
    """(batches, groups) when ``n`` elements are cut into batches of
    ``batch`` and each batch into groups of ``group``."""
    batches = -(-n // batch)
    full, last = divmod(n, batch)
    return batches, full * -(-batch // group) + -(-last // group)


class OuterSpaceModel(Model):
    """``perfmodel.Model`` with a topology per Einsum, a sequencer,
    ``evict-on`` buffers and no intersection unit."""

    def __init__(self, spec: Dict):
        self.spec = spec
        self.levels = {}
        self.evict = {e: [] for e in spec["einsums"]}
        for e in spec["einsums"]:
            comps = self._comps(e)
            for tensor, comp in spec["bound"].get(e, {}).items():
                for kind in ("coord", "payload"):
                    lvl = self.levels.setdefault((comp, tensor, kind),
                                                 _Level(comps[comp]))
                    rank = spec.get("evict_on", {}).get(e, {}).get(tensor)
                    if rank is not None:
                        self.evict[e].append((rank, lvl))
        self.dram_r = self.dram_w = 0.0
        self.dram_by_einsum = {e: 0.0 for e in spec["einsums"]}
        self.units = {e: {} for e in spec["einsums"]}
        self.seq_steps = {e: 0 for e in spec["einsums"]}
        self.merge_elems = {e: 0 for e in spec["einsums"]}
        self.merge_cycles = {e: 0.0 for e in spec["einsums"]}
        self.finalized = False

    def _comps(self, e: str) -> Dict[str, Dict]:
        return self.spec["components"][self.spec["topology"][e]]

    def _of_class(self, e: str, klass: str):
        return next(((n, c) for n, c in self._comps(e).items()
                     if c["class"] == klass), (None, None))

    def _evict(self, lvl: _Level) -> None:
        for size, dirty in lvl.resident.values():
            if dirty:
                self._drain(lvl, size)
        lvl.resident.clear()
        lvl.resident_bytes = 0.0

    def merge(self, e: str, elements: int, lists: int) -> None:
        _, m = self._of_class(e, "Merger")
        if m is None:
            return
        self.merge_elems[e] += elements
        if lists > 1:
            passes = max(1, math.ceil(math.log(max(lists, 2), m["radix"])))
            self.merge_cycles[e] += elements * passes / m["outputs"]

    def einsum(self, e: str, events: Events) -> None:
        mark = self.dram_r + self.dram_w
        for key in sorted(events, key=repr):
            n = int(events[key])
            if n <= 0:
                continue
            if key[0] == "touch":
                self._touch(e, *key[1:], n)
            elif key[0] == "compute":
                ops_ = self.spec["compute"].get(e, {})
                unit = ops_.get(key[1]) or ops_.get("mul") or ops_.get("add")
                if unit is not None:
                    self.units[e][unit] = self.units[e].get(unit, 0) + n
            elif key[0] == "iterate":
                self.seq_steps[e] += n
            elif key[0] == "advance":
                for rank, lvl in self.evict[e]:
                    if rank == key[1]:
                        self._evict(lvl)
            else:
                raise ValueError(f"unknown event {key}")
        for _, lvl in self.evict[e]:
            self._evict(lvl)
        self.dram_by_einsum[e] += self.dram_r + self.dram_w - mark

    def _component_seconds(self, e: str, hz: float) -> Dict[str, float]:
        comps = self._comps(e)
        out: Dict[str, float] = {}
        for tensor, comp in self.spec["bound"].get(e, {}).items():
            for kind in ("coord", "payload"):
                lvl = self.levels[(comp, tensor, kind)]
                out[comp] = out.get(comp, 0.0) + lvl.seconds(hz)
        for unit in self.spec["compute"].get(e, {}).values():
            out[unit] = out.get(unit, 0.0) + self.units[e].get(unit, 0) \
                / comps[unit]["instances"] / hz
        name, seq = self._of_class(e, "Sequencer")
        if seq is not None:
            out[name] = self.seq_steps[e] / seq["instances"] / hz
        name, m = self._of_class(e, "Merger")
        if m is not None:
            out[name] = self.merge_cycles[e] / m["instances"] / hz
        return out

    def report(self) -> Dict[str, float]:
        """Modeled seconds, DRAM bytes, energy and action counts."""
        hz = self.spec["clock_ghz"] * 1e9
        dram = self.spec["dram"]
        seconds = 0.0
        for block in self.spec["blocks"]:
            comp: Dict[str, float] = {}
            dbytes = 0.0
            for e in block:
                for c, s in self._component_seconds(e, hz).items():
                    comp[c] = comp.get(c, 0.0) + s
                dbytes += self.dram_by_einsum[e]
            comp[dram["name"]] = dbytes / (dram["gbs"] * 1e9)
            seconds += max(comp.values())

        acts: Dict[str, float] = {"sram_read": 0.0, "sram_write": 0.0,
                                  "sram_fill_bytes": 0.0,
                                  "sram_drain_bytes": 0.0}
        sram = 0.0
        pj = self.spec["energy_pj"]
        for lvl in self.levels.values():
            acts["sram_read"] += lvl.reads
            acts["sram_write"] += lvl.writes
            acts["sram_fill_bytes"] += lvl.fill_bytes
            acts["sram_drain_bytes"] += lvl.drain_bytes
            small = lvl.comp["width"] * lvl.comp["depth"] <= \
                self.spec["small_buffer_bytes"]
            sram += (lvl.access_bytes + lvl.fill_bytes + lvl.drain_bytes) \
                * pj["sram_small_per_byte" if small else
                     "sram_large_per_byte"]
        for e in self.spec["einsums"]:
            for op, unit in self.spec["compute"].get(e, {}).items():
                acts[op] = acts.get(op, 0.0) + self.units[e].get(unit, 0)
        acts["merge_elem"] = float(sum(self.merge_elems.values()))
        acts["dram_bytes"] = self.dram_r + self.dram_w
        energy = (acts["dram_bytes"] * pj["dram_per_byte"]
                  + sram * len(self.spec["einsums"])
                  + acts.get("mul", 0.0) * pj["mul"]
                  + acts.get("add", 0.0) * pj["add"]
                  + acts["merge_elem"] * pj["merge_elem"])
        out = {"seconds": seconds, "dram_read_bytes": self.dram_r,
               "dram_write_bytes": self.dram_w, "energy_pj": energy}
        out.update({f"count {k}": v for k, v in acts.items()})
        return out


def replay(spec: Dict, iterations: List[List[tuple]]) -> Dict[str, float]:
    """``perfmodel.replay`` on an ``OuterSpaceModel``."""
    m = OuterSpaceModel(spec)
    for it in iterations:
        for entry in it:
            if entry[0] == "merge":
                m.merge(*entry[1:])
            else:
                m.einsum(entry[1], entry[2])
        m.evaluate()
    return m.report()


def events(job, spec: Dict) -> List[List[tuple]]:
    """OuterSPACE's aggregate events of the job (one iteration), for
    the configuration's ``model`` section ``spec``."""
    (mb, mg), (zb, zg) = (spec["partitions"]["multiply"],
                          spec["partitions"]["merge"])
    x = sp.csr_matrix((np.ones(len(job.rows)), (job.rows, job.cols)),
                      shape=(job.n, job.n))
    r = np.diff(x.indptr).astype(np.int64)
    nnz = int(x.nnz)
    mul = ops(job)
    km2, km1 = _groups(nnz, mb, mg)
    t: Dict[tuple, int] = {}
    for rank, n in (("KM2", km2), ("KM1", km1), ("KM0", nnz),
                    ("N", mul)):
        t[("iterate", rank)] = t[("advance", rank)] = n
    t.update({("touch", "A", "KM2", "coord", "r"): km2,
              ("touch", "A", "KM1", "coord", "r"): km1,
              ("touch", "A", "KM0", "coord", "r"): nnz,
              ("touch", "A", "KM0", "payload", "r"): nnz,
              ("touch", "B", "K", "coord", "r"): nnz,
              ("touch", "B", "N", "coord", "r"): mul,
              ("touch", "B", "N", "payload", "r"): mul,
              ("touch", "T", "N", "payload", "w"): mul,
              ("compute", "mul"): mul})

    # T as stored, [M, K, N]: row m holds a_m lists (the k of column m
    # of X), of r_k elements each
    a = x.T.tocsr()
    a.sort_indices()
    a_m = np.diff(a.indptr)
    rows_ne = int(np.count_nonzero(a_m))
    m2, m1 = _groups(rows_ne, zb, zg)
    _, z = _product(job, np.float64)
    z_nnz = int(z.nnz)
    adds = mul - z_nnz
    zz: Dict[tuple, int] = {}
    for rank, n in (("M2", m2), ("M1", m1), ("M0", rows_ne),
                    ("N", z_nnz), ("K", mul)):
        zz[("iterate", rank)] = zz[("advance", rank)] = n
    zz.update({("touch", "T", "M2", "coord", "r"): m2,
               ("touch", "T", "M1", "coord", "r"): m1,
               ("touch", "T", "M0", "coord", "r"): rows_ne,
               ("touch", "T", "N", "coord", "r"): z_nnz,
               ("touch", "T", "K", "coord", "r"): mul,
               ("touch", "T", "K", "payload", "r"): mul,
               ("touch", "Z", "N", "payload", "r"): adds,
               ("touch", "Z", "N", "payload", "w"): mul,
               ("compute", "add"): adds})
    merged = np.add.reduceat(r[a.indices], a.indptr[:-1][a_m > 0]) \
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

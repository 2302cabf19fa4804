"""Plain statement of the simulator's performance model (TeAAL Sec. 4),
for the aggregate event streams that a whole simulation produces: one
count per (Einsum, event) and iteration.  Nothing here imports the
program; the design's hardware, bindings and formats come from the
configuration's ``model`` section, and the counts from the design's
reference (``spmspm.events``, ``bfs.events``).

Rules, as the model states them:

* A touch of ``n`` elements of a tensor moves ``n`` times the bytes its
  format gives the rank and kind (coordinate or payload; an unlisted
  rank is 32-bit compressed).  Where the Einsum binds the tensor to a
  buffer, the touch is ``n`` accesses of that buffer, which holds one
  line per (tensor, rank, kind): the first access after the line left
  fills it (and reads its bytes from DRAM when it is a read), a write
  makes it dirty.  The first evaluation writes back every dirty line
  and empties the buffers; later iterations keep their lines.  A
  fused intermediate moves nothing; any other touch streams to DRAM.
* Each Einsum has its own compute units (an unbound operation runs
  on the ``mul`` unit, else the ``add`` unit), intersection unit and
  merger; a unit's cycles are its work over its instances.  A merge of
  ``e`` elements from ``l > 1`` sorted lists takes ``e`` times
  ceil(log_radix l) passes.  Leader-follower intersection costs the
  leader's steps.
* A fusion block lasts as long as its busiest component (DRAM bytes
  over bandwidth among them); the job lasts the sum of its blocks.
* Energy: DRAM bytes, buffer bytes (accesses, fills and drains; small
  buffers are cheaper), multiplies, adds, intersection steps and merged
  elements, each at its per-action cost.  The model sums the buffer
  term once per Einsum of the design.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: one Einsum's events of one iteration: {event key: count}, keys as
#: ("touch", tensor, rank, kind, rw), ("compute", op),
#: ("isect_step", rank, tensor), ("merge", elements, lists) ...
Events = Dict[Tuple, int]


class _Level:
    def __init__(self, comp: Dict):
        self.comp = comp
        self.capacity = comp["width"] * comp["depth"] * comp["instances"]
        self.resident: Dict[Tuple, List] = {}
        self.resident_bytes = 0.0
        self.reads = self.writes = self.fills = self.drains = 0
        self.access_bytes = self.fill_bytes = self.drain_bytes = 0.0

    def seconds(self, clock_hz: float) -> float:
        if self.comp.get("gbs"):
            return self.access_bytes / (self.comp["gbs"] * 1e9)
        return (self.reads + self.writes) / self.comp["instances"] / clock_hz


class Model:
    """The model's state over a whole simulation, fed einsum by einsum
    and iteration by iteration."""

    def __init__(self, spec: Dict):
        self.spec = spec
        self.comps = spec["components"]
        self.levels: Dict[Tuple[str, str, str], _Level] = {}
        for e in spec["einsums"]:
            for tensor, comp in spec["bound"].get(e, {}).items():
                for kind in ("coord", "payload"):
                    self.levels.setdefault((comp, tensor, kind),
                                           _Level(self.comps[comp]))
        self.dram_r = self.dram_w = 0.0
        self.dram_by_einsum = {e: 0.0 for e in spec["einsums"]}
        self.units = {e: {} for e in spec["einsums"]}
        self.steps = {e: {} for e in spec["einsums"]}
        self.merge_elems = {e: 0 for e in spec["einsums"]}
        self.merge_cycles = {e: 0.0 for e in spec["einsums"]}
        self.finalized = False

    # ---------------------------------------------------------------- #
    def _bytes(self, tensor: str, rank: str, kind: str) -> float:
        c, p = self.spec["bytes"].get(tensor, {}).get(rank, (4.0, 4.0))
        return c if kind == "coord" else p

    def _touch(self, e: str, tensor: str, rank: str, kind: str, rw: str,
               n: int) -> None:
        nbytes = self._bytes(tensor, rank, kind)
        comp = self.spec["bound"].get(e, {}).get(tensor)
        if comp is None:
            if tensor in self.spec["stream"] or not nbytes:
                return
            if rw == "r":
                self.dram_r += nbytes * n
            else:
                self.dram_w += nbytes * n
            return
        lvl = self.levels[(comp, tensor, kind)]
        lvl.access_bytes += nbytes * n
        if rw == "r":
            lvl.reads += n
        else:
            lvl.writes += n
        key = (tensor, rank, kind)
        got = lvl.resident.pop(key, None)
        if got is not None:
            lvl.resident[key] = [got[0], got[1] or rw == "w"]
            return
        lvl.fills += 1
        lvl.fill_bytes += nbytes
        if rw == "r":
            self.dram_r += nbytes
        lvl.resident[key] = [nbytes, rw == "w"]
        lvl.resident_bytes += nbytes
        while lvl.resident_bytes > lvl.capacity and len(lvl.resident) > 1:
            old = next(iter(lvl.resident))
            size, dirty = lvl.resident.pop(old)
            lvl.resident_bytes -= size
            if dirty:
                self._drain(lvl, size)

    def _drain(self, lvl: _Level, size: float) -> None:
        lvl.drains += 1
        lvl.drain_bytes += size
        self.dram_w += size

    def merge(self, e: str, elements: int, lists: int) -> None:
        m = self.spec["merger"]
        self.merge_elems[e] += elements
        if lists > 1:
            passes = max(1, math.ceil(math.log(max(lists, 2), m["radix"])))
            self.merge_cycles[e] += elements * passes / m["outputs"]

    def einsum(self, e: str, events: Events) -> None:
        """One Einsum's aggregate events of one iteration."""
        mark = self.dram_r + self.dram_w
        for key in sorted(events, key=repr):
            n = int(events[key])
            if n <= 0:
                continue
            if key[0] == "touch":
                self._touch(e, *key[1:], n)
            elif key[0] == "compute":
                ops = self.spec["compute"].get(e, {})
                unit = ops.get(key[1]) or ops.get("mul") or ops.get("add")
                if unit is not None:
                    self.units[e][unit] = self.units[e].get(unit, 0) + n
            elif key[0] == "isect_step":
                t = key[2]
                self.steps[e][t] = self.steps[e].get(t, 0) + n
            elif key[0] not in ("iterate", "isect_match", "advance"):
                raise ValueError(f"unknown event {key}")
        self.dram_by_einsum[e] += self.dram_r + self.dram_w - mark

    def evaluate(self) -> None:
        """End of one iteration's cascade: the first one writes back."""
        if self.finalized:
            return
        self.finalized = True
        mark = self.dram_r + self.dram_w
        for lvl in self.levels.values():
            for size, dirty in lvl.resident.values():
                if dirty:
                    self._drain(lvl, size)
            lvl.resident.clear()
            lvl.resident_bytes = 0.0
        last = self.spec["einsums"][-1]
        self.dram_by_einsum[last] += self.dram_r + self.dram_w - mark

    # ---------------------------------------------------------------- #
    def _component_seconds(self, e: str, hz: float) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for tensor, comp in self.spec["bound"].get(e, {}).items():
            for kind in ("coord", "payload"):
                lvl = self.levels[(comp, tensor, kind)]
                out[comp] = out.get(comp, 0.0) + lvl.seconds(hz)
        for unit, total in self.units[e].items():
            out[unit] = out.get(unit, 0.0) + \
                total / self.comps[unit]["instances"] / hz
        isect = self.spec["isect"]
        steps = self.steps[e]
        lead = steps.get(isect["leader"], 0) or sum(steps.values()) / 2
        out[isect["name"]] = lead / isect["instances"] / hz
        m = self.spec["merger"]
        if m.get("name"):
            out[m["name"]] = self.merge_cycles[e] / m["instances"] / hz
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

        acts: Dict[str, float] = {}
        for lvl in self.levels.values():
            for k, v in (("sram_read", lvl.reads),
                         ("sram_write", lvl.writes),
                         ("sram_fill_bytes", lvl.fill_bytes),
                         ("sram_drain_bytes", lvl.drain_bytes)):
                acts[k] = acts.get(k, 0.0) + v
        for e in self.spec["einsums"]:
            for op, unit in self.spec["compute"].get(e, {}).items():
                acts[op] = acts.get(op, 0.0) + self.units[e].get(unit, 0)
            acts["isect_step"] = acts.get("isect_step", 0.0) + \
                sum(self.steps[e].values())
            if self.spec["merger"].get("name"):
                acts["merge_elem"] = acts.get("merge_elem", 0.0) + \
                    self.merge_elems[e]
        acts["dram_bytes"] = self.dram_r + self.dram_w

        pj = self.spec["energy_pj"]
        sram = 0.0
        for lvl in self.levels.values():
            small = lvl.comp["width"] * lvl.comp["depth"] <= \
                self.spec["small_buffer_bytes"]
            per = pj["sram_small_per_byte" if small else
                     "sram_large_per_byte"]
            sram += (lvl.access_bytes + lvl.fill_bytes
                     + lvl.drain_bytes) * per
        energy = (acts["dram_bytes"] * pj["dram_per_byte"]
                  + sram * len(self.spec["einsums"])
                  + acts.get("mul", 0.0) * pj["mul"]
                  + acts.get("add", 0.0) * pj["add"]
                  + acts.get("isect_step", 0.0) * pj["isect_step"]
                  + acts.get("merge_elem", 0.0) * pj["merge_elem"])
        out = {"seconds": seconds, "dram_read_bytes": self.dram_r,
               "dram_write_bytes": self.dram_w, "energy_pj": energy}
        out.update({f"count {k}": v for k, v in acts.items()})
        return out


def replay(spec: Dict, iterations: Sequence[List[Tuple]]
           ) -> Dict[str, float]:
    """The model's statistics of a whole simulation.  Each iteration is
    a list of ("merge", einsum, elements, lists) and ("einsum", name,
    events) entries in the order the design runs them."""
    m = Model(spec)
    for it in iterations:
        for entry in it:
            if entry[0] == "merge":
                m.merge(*entry[1:])
            else:
                m.einsum(entry[1], entry[2])
        m.evaluate()
    return m.report()


def expand(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """The indices ``starts[i] + j`` for ``j < counts[i]``, run after
    run."""
    counts = np.asarray(counts, np.int64)
    total = int(counts.sum())
    return (np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts)
            + np.repeat(np.asarray(starts, np.int64), counts))


def stat_gaps(got: Dict[str, float], ref: Dict[str, float]
              ) -> Dict[str, float]:
    """``count_gap``: summed |count - count_ref| over the action counts
    (a count on one side only counts whole); ``model_rel_gap``: the
    widest relative gap of modeled seconds, DRAM bytes read and
    written, and energy."""
    counts = sum(abs(got.get(k, 0.0) - ref.get(k, 0.0))
                 for k in set(got) | set(ref) if k.startswith("count "))
    rel = max(abs(got.get(k, 0.0) - v) / max(abs(v), 1e-300)
              for k, v in ref.items() if not k.startswith("count "))
    return {"count_gap": float(counts), "model_rel_gap": float(rel)}

"""Reduce a JAX profiler trace (``.xplane.pb``) to what the benchmark
reports: the device's busy seconds (the union of the intervals in
which an operation ran on it), the operations that took most device
time, and the longest idle gaps, each labelled with what the host was
doing then.

Device planes are those named ``/device:<platform>:<n>``; their
operations are the events of the line ``XLA Ops``, named by
``op_name``.  Host events come
from the ``/host:CPU`` plane; a gap's label is the innermost host
event that covers the gap's midpoint, among the benchmark's own
annotations (``job ...``) and the runtime's named events.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]            # (start_ns, end_ns)

#: the line of a device plane that holds one event per device op
OPS_LINE = "XLA Ops"


@dataclass
class TraceSummary:
    #: busy seconds averaged over the device planes found
    busy_s: float
    n_devices: int
    #: [name, seconds] of the ops that took most device time
    device_ops: List[List] = field(default_factory=list)
    #: [label, seconds] of the longest idle gaps
    idle_gaps: List[List] = field(default_factory=list)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The idle intervals of [lo, hi] outside the merged ``busy``."""
    out, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def label(t: float, host: Sequence[Tuple[str, float, float]]) -> str:
    """The shortest host event covering time ``t``, or ``host``."""
    best, best_len = "host", float("inf")
    for name, s, e in host:
        if s <= t <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def summarize(device: Dict[str, List[Tuple[str, float, float]]],
              host: List[Tuple[str, float, float]],
              window: Interval, top: int = 10) -> TraceSummary:
    """Busy seconds, top ops and longest labelled gaps inside
    ``window`` (start_ns, end_ns), from events given as (name,
    start_ns, end_ns); ``device`` maps a device plane to its op
    events.  Only planes that ran an op inside the window count as
    devices used; gaps are those of the first of them."""
    per_op: Dict[str, float] = {}
    merged: Dict[str, List[Interval]] = {}
    for plane, evs in device.items():
        kept = [(n, max(s, window[0]), min(e, window[1]))
                for n, s, e in evs if e > window[0] and s < window[1]]
        if not kept:
            continue
        for name, s, e in kept:
            per_op[name] = per_op.get(name, 0.0) + (e - s)
        merged[plane] = union([(s, e) for _, s, e in kept])
    if not merged:
        return TraceSummary(0.0, 0)
    total = sum(e - s for iv in merged.values() for s, e in iv)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    first = merged[sorted(merged)[0]]
    idle = sorted(gaps(first, *window), key=lambda g: g[0] - g[1])[:top]
    return TraceSummary(
        busy_s=total / len(merged) / 1e9, n_devices=len(merged),
        device_ops=[[n, ns / 1e9] for n, ns in ops],
        idle_gaps=[[label((s + e) / 2, host), (e - s) / 1e9]
                   for s, e in idle])


def job_window(host: Sequence[Tuple[str, float, float]],
               prefix: str = "job ") -> Interval:
    """From the start of the first host annotation named ``prefix...``
    to the end of the last."""
    jobs = [(s, e) for name, s, e in host if name.startswith(prefix)]
    if not jobs:
        raise ValueError(f"no {prefix!r} annotation in the trace")
    return min(s for s, _ in jobs), max(e for _, e in jobs)


def op_name(hlo: str) -> str:
    """An op's name and first result shape, from the trace's HLO text
    (``%intersect_sorted.1 = (s32[64,128]{1,0...}, ...) custom-call(...)``
    becomes ``intersect_sorted.1 s32[64,128]``)."""
    lhs, eq, rhs = hlo.partition(" = ")
    if not eq:
        return hlo[:120]
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rhs)
    return lhs.lstrip("%") + (" " + shape.group(0) if shape else "")


def read(trace_dir: Path):
    """(device events by plane, host events) of the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (op_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events if ev.duration_ns > 0]
    return device, host

"""What the readers of the program's own layers share: the window's
``repro.obs`` spans summed by name, and the profiler trace of a
``--trace 1`` run, on whose ``/host:CPU`` plane the program's spans
sit beside the device's operations, on the same clock (``repro.obs``
enters a ``jax.profiler.TraceAnnotation`` of each span's name)."""
from __future__ import annotations

import functools
from typing import Optional, Sequence

from harness import runner, trace


def span_seconds(w, *names: str) -> Optional[float]:
    """Summed seconds of the window's spans named one of ``names`` (a
    name ending in ``:`` matches every span it begins); None on a run
    without spans, or where no such span ran."""
    if w.spans is None:
        return None
    total, found = 0.0, False
    for ev in w.spans:
        if ev.get("ph") != "X":
            continue
        n = ev["name"]
        if any(n == x or (x[-1] == ":" and n.startswith(x))
               for x in names):
            total += ev["dur"] / 1e6
            found = True
    return total if found else None


def share(w, *names: str) -> Optional[float]:
    """``span_seconds`` over the window's timed seconds."""
    s = span_seconds(w, *names)
    return None if s is None else s / w.timed_s


@functools.cache
def profile():
    """(device events by plane, host events) of the newest profiler
    trace under the runner's trace directory, read once a process;
    None where the run left none."""
    try:
        return trace.read(runner.TRACE_DIR)
    except FileNotFoundError:
        return None


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted, merged interval
    lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def device_seconds_inside(host_names: Sequence[str]) -> Optional[float]:
    """Seconds a device was busy inside host events named one of
    ``host_names``, averaged over the device planes that ran an op;
    None where the trace, such an event or a device op is missing."""
    prof = profile()
    if prof is None:
        return None
    device, host = prof
    inside = trace.union([(s, e) for n, s, e in host if n in host_names])
    planes = [trace.union([(s, e) for _, s, e in evs])
              for evs in device.values() if evs]
    if not inside or not planes:
        return None
    busy = sum(_overlap(p, inside) for p in planes) / len(planes) / 1e9
    return busy or None


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind

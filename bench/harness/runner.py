"""One run of one cell: set-up, the measured window, the comparison
with the plain reference, and the metrics.

Set-up makes every job's inputs from the seed and runs the traffic
mix's ``warmup_jobs`` jobs, drawn like the window's, so that the
programs the window launches are built (or read from the persistent
compile cache in the checkout) before it.
The window then runs whole jobs back to back, one architect in a
closed loop, until the jobs' own seconds reach ``seconds``.  After
each job, outside its timed interval, the program's answer is read
out and the job's result is dropped.  Once the window has closed and
the memory peaks are read, the plain reference recomputes every job
and each compared number is held against its limit.

With ``traced`` the window runs under the program's ``repro.obs``
tracer and the JAX profiler (python tracer off), each job inside a
``jax.profiler.TraceAnnotation`` named ``job <n>``.
"""
from __future__ import annotations

import resource
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from harness import registry, trace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_DIR = registry.ROOT / ".bench" / "trace"


@dataclass
class Window:
    """What the measured window left for the metric readers."""
    cell: str
    setup_s: float
    #: the jobs' own seconds, summed: the window's timed seconds
    timed_s: float
    job_seconds: List[float]
    #: simulated operations of the window's jobs (the reference counts)
    ops: int
    #: the process's peak resident set once the window has closed, and
    #: its resident set once JAX had found the chip (the runtime's own)
    peak_rss_bytes: int
    base_rss_bytes: int
    #: ``repro.obs`` counters: increase over the window
    counters: Dict[str, float]
    #: programs built in the window (a compile or a cache read), and
    #: those of them read from the persistent compile cache
    compiles: int
    cache_hits: int = 0
    #: ``repro.obs`` span events of the window (traced runs only)
    spans: Optional[List[Dict[str, Any]]] = None
    trace: Optional[trace.TraceSummary] = None
    #: the traced window's length on the profiler's clock
    trace_window_s: Optional[float] = None


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, Dict[str, Any]]
    device: Dict[str, Any]
    checks: Dict[str, Dict[str, float]]
    breakdown: Optional[Dict[str, list]] = None
    window: Optional[Window] = field(default=None, repr=False)

    def line(self) -> Dict[str, Any]:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        w = self.window
        if w is not None:
            out["window"] = {"job_seconds": w.job_seconds,
                             "ops": w.ops, "compiles": w.compiles,
                             "cache_hits": w.cache_hits}
        out["checks"] = self.checks
        return out


class CompileLog:
    """Programs built, as ``jax.monitoring`` reports them."""

    def __init__(self):
        self.count = 0
        self.hits = 0

    def on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1


def _counters() -> Dict[str, float]:
    from repro.obs.metrics import metrics
    return dict(metrics().snapshot()["counters"])


def resident_bytes() -> int:
    """The process's resident set now (``VmRSS``)."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("no VmRSS in /proc/self/status")


def _memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def measure(cell: registry.Cell, jobs, seconds: float, traced: bool
            ) -> Tuple[List[Tuple[Any, Any, float]], Dict[str, Any]]:
    """Run whole jobs until their seconds reach ``seconds``; returns
    [(job, answer, seconds)] and what the window observed."""
    import jax
    from repro.obs.spans import Tracer, set_tracer

    cfg = cell.config
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    tracer = Tracer() if traced else None
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        set_tracer(tracer)
    c0 = _counters()
    done: List[Tuple[Any, Any, float]] = []
    timed = 0.0
    try:
        for job in jobs:
            if timed >= seconds:
                break
            with jax.profiler.TraceAnnotation(f"job {job.index}"):
                t0 = time.perf_counter()
                out = cell.driver.run(job, cfg)
                dt = time.perf_counter() - t0
            timed += dt
            done.append((job, cell.driver.answer(job, out, cfg), dt))
            del out
    finally:
        if traced:
            set_tracer(None)
            jax.profiler.stop_trace()
    if timed < seconds:
        raise RuntimeError(
            f"the traffic's {len(jobs)} jobs ran out after {timed:.1f} s "
            f"of a {seconds} s window: raise its job_cap")
    c1 = _counters()
    seen = {"counters": {k: c1[k] - c0.get(k, 0.0) for k in c1
                         if c1[k] != c0.get(k, 0.0)},
            "compiles": log.count, "cache_hits": log.hits,
            "spans": tracer.events if traced else None}
    return done, seen


def run_cell(cell: registry.Cell, seed: int, seconds: float,
             traced: bool, device: Dict[str, Any], t_start: float,
             base_rss: int) -> Result:
    """Set-up, window and comparison of one run; ``t_start`` is the
    host clock (``time.perf_counter``) at the process's start and
    ``base_rss`` the resident set once JAX had found the chip."""
    cfg, traffic = cell.config, cell.traffic
    phases = {"init": time.perf_counter() - t_start}
    t = time.perf_counter()
    warm, jobs = cell.driver.prepare(seed, cfg, traffic)
    phases["inputs"] = time.perf_counter() - t
    for job in warm:
        t = time.perf_counter()
        cell.driver.run(job, cfg)
        phases[f"warmup {job.index}"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    print("setup phases: " + ", ".join(f"{k} {v:.3f} s"
                                       for k, v in phases.items()),
          file=sys.stderr)

    done, seen = measure(cell, jobs, seconds, traced)
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    device = dict(device,
                  memory_peak_bytes=_memory_peak(device["count"]))

    limits: Dict[str, float] = cfg["limits"]
    worst: Dict[str, float] = {k: 0.0 for k in limits}
    failed = 0
    for job, got, _ in done:
        nums = cell.reference.compare(got,
                                      cell.reference.expected(job, cfg))
        if set(nums) != set(limits):
            raise KeyError(f"compared {sorted(nums)}, limits name "
                           f"{sorted(limits)}")
        failed += any(nums[k] > limits[k] for k in limits)
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
    checks = {k: {"value": worst[k], "limit": limits[k]} for k in limits}

    win = Window(
        cell=cell.name, setup_s=setup_s,
        timed_s=sum(dt for _, _, dt in done),
        job_seconds=[dt for _, _, dt in done],
        ops=sum(cell.reference.ops(job) for job, _, _ in done),
        peak_rss_bytes=rss, base_rss_bytes=base_rss,
        counters=seen["counters"],
        compiles=seen["compiles"], cache_hits=seen["cache_hits"],
        spans=seen["spans"])
    breakdown = None
    if traced:
        dev_events, host = trace.read(TRACE_DIR)
        lo, hi = trace.job_window(host)
        win.trace = trace.summarize(dev_events, host, (lo, hi))
        win.trace_window_s = (hi - lo) / 1e9
        device.update(busy_s=win.trace.busy_s,
                      window_s=win.trace_window_s)
        breakdown = {"device_ops": win.trace.device_ops,
                     "idle_gaps": win.trace.idle_gaps}

    wanted = cell.per_layer if traced else cell.end_to_end
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in wanted:
        value = registry.metric_reader(m["name"]).read(win)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return Result(correct=failed == 0 and bool(done),
                  attempted=len(done), failed=failed, metrics=metrics,
                  device=device, checks=checks, breakdown=breakdown,
                  window=win)

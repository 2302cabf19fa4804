"""The telemetry layer, end to end: hierarchical spans, the metrics
registry, Perfetto export, and the instrumented seams.

Contract under test (DESIGN.md "Telemetry contract"):

  * spans nest ``cascade -> gen / model / einsum -> vec / stage ->
    seam -> device`` across ``execute_batch``, with each span's parent
    recorded in ``args["parent"]`` and its simulation in
    ``args["sim"]``;
  * every span is also a host event of the JAX profiler's trace, on
    the device's clock;
  * stage spans are real intervals that never overlap and sum to
    ``stage_seconds``;
  * the disabled path is free -- ``maybe_span`` returns the shared
    ``NULL_SPAN`` and neither a guarded seam call nor a whole
    simulation allocates **anything** in ``obs/spans.py`` (asserted
    with ``tracemalloc``);
  * the Chrome-trace export round-trips through ``json.loads`` with
    valid ``ph``/``ts``/``dur`` fields and Perfetto-required instant
    markers;
  * injected faults (``REPRO_FAULTS`` syntax) surface as ``downgrade``
    instant events, and every ``DowngradeEvent`` carries a monotonic
    ``ts_us`` plus the active Einsum tag;
  * ``stage_seconds`` ride ``SimResult``/``Report`` as per-request
    deltas (benchmarks no longer reach into the backend);
  * ``TeeInstr``/``CollectingInstr`` aggregate (n-weighted) and
    per-element emission produce identical totals, with the ``unique``
    hint passed through the tee verbatim.
"""
import json
import time

import numpy as np
import pytest

from _hyp import given, settings, st  # hypothesis, or seeded fallback
import chip_smoke
from repro.accelerators import gamma
from repro.core.generator import CascadeSimulator
from repro.core.trace import CollectingInstr, Instrumentation, TeeInstr
from repro.core.vectorized import VectorBackend
from repro.kernels import backends as kbk
from repro.obs import (NULL_SPAN, MetricsRegistry, Tracer, active_tracer,
                       chrome_trace, maybe_span, metrics, summarize_trace,
                       to_jsonl, trace_session, write_trace)
from repro.testing.faults import (FaultInjector, clear_injector,
                                  install_injector, parse_faults)


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    """No tracer, no injector, no demotions, fresh metrics."""
    monkeypatch.delenv("REPRO_FAULTS", raising=False)
    monkeypatch.delenv("REPRO_GUARDS", raising=False)
    clear_injector()
    kbk.reset_guard_state()
    metrics().reset()
    yield
    clear_injector()
    kbk.reset_guard_state()
    metrics().reset()
    assert active_tracer() is None, "a test leaked an installed tracer"


def _spmm(rng, n=24, d=0.25):
    a = rng.random((n, n)) * (rng.random((n, n)) < d)
    b = rng.random((n, n)) * (rng.random((n, n)) < d)
    return {"A": a, "B": b}, {"m": n, "k": n, "n": n}


def _vector_sim(spec=None, model=False, **kw):
    vb = VectorBackend(kernel_backend=kbk.GuardedKernels(
        "numpy", sleep=lambda s: None))
    return CascadeSimulator(spec if spec is not None else gamma.spec(),
                            model=model, backend=vb, **kw), vb


# ---------------------------------------------------------------------- #
# tracer / span primitives
# ---------------------------------------------------------------------- #
def test_span_nesting_records_parent():
    tr = Tracer()
    with tr.span("outer", "a"):
        with tr.span("inner", "b"):
            pass
    inner = next(e for e in tr.spans() if e["name"] == "inner")
    outer = next(e for e in tr.spans() if e["name"] == "outer")
    assert inner["args"]["parent"] == "outer"
    assert "args" not in outer or "parent" not in outer.get("args", {})
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_span_error_annotation_and_set():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom", "t") as sp:
            sp.set("k", 3)
            raise ValueError("x")
    ev = tr.spans()[0]
    assert ev["args"]["error"] == "ValueError"
    assert ev["args"]["k"] == 3


def test_trace_session_installs_and_restores():
    assert active_tracer() is None
    with trace_session() as tr:
        assert active_tracer() is tr
        with trace_session() as tr2:
            assert active_tracer() is tr2
        assert active_tracer() is tr
    assert active_tracer() is None


def test_maybe_span_disabled_is_null_singleton():
    assert active_tracer() is None
    s1 = maybe_span("einsum:x", "einsum")
    s2 = maybe_span("seam:y", "seam", {"a": 1})
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with s1 as s:               # context protocol is a no-op
        s.set("k", "v")


def test_disabled_seam_path_allocates_nothing_in_spans():
    """The committed ``vector_rate`` rides on this: with no tracer
    installed, a guarded seam call must not allocate a single object
    in ``obs/spans.py`` (one cached-global read + ``None`` check)."""
    import tracemalloc

    import repro.obs.spans as spans_mod
    assert active_tracer() is None
    gk = kbk.GuardedKernels("numpy", sleep=lambda s: None)
    a = np.array([1, 3, 5, 7, 9], dtype=np.int64)
    b = np.array([3, 7, 11], dtype=np.int64)
    gk.intersect_keys(a, b)     # warm resolution + caches
    tracemalloc.start()
    try:
        for _ in range(64):
            gk.intersect_keys(a, b)
            maybe_span("seam:intersect_keys", "seam")
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, spans_mod.__file__)]
    ).statistics("filename")
    assert sum(s.size for s in stats) == 0, stats


def test_disabled_span_sites_allocate_nothing(rng, monkeypatch):
    """Every span site of a whole simulation -- the generator's
    ``gen:`` / ``model:`` spans, the engine's ``vec:`` spans, the
    stage clock and the device launch -- stays on the disabled path
    without a tracer: nothing is allocated in ``obs/spans.py``, no
    stage clock is built and no compile log is consulted."""
    import tracemalloc

    import repro.core.vectorized as vec_mod
    import repro.obs.spans as spans_mod

    def refused(*a, **k):
        raise AssertionError("built on the disabled path")

    monkeypatch.setattr(vec_mod, "_StageClock", refused)
    monkeypatch.setattr(kbk, "_compile_log", refused)
    workload = chip_smoke.gamma_workload(n=48, nnz=300, seed=1)
    chip_smoke.gamma_phase(workload, "jax-jit")       # warm caches
    tracemalloc.start()
    try:
        chip_smoke.gamma_phase(workload, "jax-jit")
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, spans_mod.__file__)]
    ).statistics("filename")
    assert sum(s.size for s in stats) == 0, stats


# ---------------------------------------------------------------------- #
# metrics registry
# ---------------------------------------------------------------------- #
def test_metrics_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)
    reg.gauge("g").set(7.0)
    h = reg.histogram("h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 7.0
    hs = snap["histograms"]["h"]
    assert hs["count"] == 3
    assert hs["buckets"] == [0.1, 1.0, "+Inf"]
    assert hs["counts"] == [1, 1, 1]
    assert hs["sum"] == pytest.approx(5.55)
    table = reg.summary_table()
    assert "c" in table and "h" in table
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_metrics_registry_same_instrument_identity():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("y") is reg.histogram("y")


# ---------------------------------------------------------------------- #
# spans across the execution layer
# ---------------------------------------------------------------------- #
def test_spans_nest_across_execute_batch(rng):
    """Gamma's two-Einsum cascade through the vector backend: one
    cascade span, one einsum span per Einsum parented to it, stage
    spans parented to their einsum and lying inside it, seam spans
    inside a stage."""
    inputs, shapes = _spmm(rng)
    sim, _ = _vector_sim()
    with trace_session() as tr:
        res = sim.run(dict(inputs), shapes)
    assert not res.fallback_reasons
    cascades = tr.spans("cascade")
    assert len(cascades) == 1
    cname = cascades[0]["name"]
    einsums = tr.spans("einsum")
    assert {e["name"] for e in einsums} == {"einsum:T", "einsum:Z"}
    for e in einsums:
        assert e["args"]["parent"] == cname
        assert e["args"]["path"] == "vector"
    stages = tr.spans("stage")
    assert stages
    for e in stages:
        assert e["args"]["parent"] == "einsum:" + e["args"]["einsum"], e
        assert "synthetic" not in e["args"]
    seams = tr.spans("seam")
    assert seams, "guarded seam calls must produce spans"
    assert {e["args"]["parent"] for e in seams} <= {
        e["name"] for e in stages}
    # every stage span is a real interval inside its einsum's window
    win = {e["name"]: (e["ts"], e["ts"] + e["dur"]) for e in einsums}
    for e in stages:
        lo, hi = win[e["args"]["parent"]]
        assert e["ts"] >= lo and e["ts"] + e["dur"] <= hi + 1.0
    # one simulation: every span carries the cascade's id
    assert {e["args"]["sim"] for e in tr.spans()} == {
        cascades[0]["args"]["sim"]}


def test_seam_spans_carry_backend_and_histogram(rng):
    """Each seam span names the backend that served it and the keys
    the call consumed and returned; the keys add up on the
    ``kernel.seam_keys/<seam>`` counters, and no seam-latency
    histogram is kept (the span's duration is the latency)."""
    inputs, shapes = _spmm(rng)
    sim, _ = _vector_sim()
    with trace_session() as tr:
        sim.run(dict(inputs), shapes)
    seams = tr.spans("seam")
    assert all(e["args"]["backend"] == "numpy" for e in seams)
    assert all(e["args"]["keys"] > 0 for e in seams)
    snap = metrics().snapshot()
    assert not snap["histograms"], snap["histograms"]
    keys = {k[len("kernel.seam_keys/"):]: v
            for k, v in snap["counters"].items()
            if k.startswith("kernel.seam_keys/")}
    assert keys
    for seam, total in keys.items():
        assert total == sum(e["args"]["keys"] for e in seams
                            if e["name"] == "seam:" + seam)


def test_seam_keys_count_what_each_call_consumed_and_returned():
    gk = kbk.GuardedKernels("numpy", sleep=lambda s: None)
    a = np.array([1, 3, 5, 7, 9], dtype=np.int64)
    b = np.array([3, 7, 11], dtype=np.int64)
    probes = np.array([7, 2, 9, 9], dtype=np.int64)
    with trace_session():
        gk.intersect_keys(a, b)
        gk.lookup_keys(a, probes)
    c = metrics().snapshot()["counters"]
    assert c["kernel.seam_keys/intersect_keys"] == 5 + 3 + 5
    assert c["kernel.seam_keys/lookup_keys"] == 5 + 4 + 4
    gk.intersect_keys(a, b)          # untraced: not counted
    assert metrics().snapshot()["counters"][
        "kernel.seam_keys/intersect_keys"] == 13


def _seam_keys(phase, workload, backend):
    metrics().reset()
    with trace_session():
        phase(workload, backend)
    return {k: v for k, v in metrics().snapshot()["counters"].items()
            if k.startswith("kernel.seam_keys/")}


@pytest.mark.parametrize("phase", ["gamma", "bfs"])
def test_seam_keys_identical_under_numpy_and_pallas(phase):
    """The key counters measure the work of the seam, not of the kernel
    that serves it: the same inputs count the same keys under the numpy
    lowering and the interpreted Pallas kernel."""
    if phase == "gamma":
        workload = chip_smoke.gamma_workload(n=48, nnz=300, seed=2)
        run = chip_smoke.gamma_phase
    else:
        workload = chip_smoke.bfs_workload(side=8, seed=2)
        run = lambda w, kb: chip_smoke.bfs_phase(w, kb, max_iters=4)  # noqa
    oracle = _seam_keys(run, workload, "numpy")
    assert oracle.get("kernel.seam_keys/intersect_keys", 0) > 0
    assert _seam_keys(run, workload, "pallas-interpret") == oracle


def test_stage_seconds_on_simresult_and_report(rng):
    inputs, shapes = _spmm(rng)
    sim, vb = _vector_sim(model=True)
    with trace_session() as tr:
        res = sim.run(dict(inputs), shapes)
    assert set(res.stage_seconds) == {"T", "Z"}
    for per in res.stage_seconds.values():
        assert per and all(v > 0 for v in per.values())
    # the report aggregate is the per-Einsum sum (execute() resets the
    # profile counters per request, so each dict is that Einsum alone)
    agg = {}
    for per in res.stage_seconds.values():
        for k, v in per.items():
            agg[k] = agg.get(k, 0.0) + v
    assert res.report.stage_seconds == pytest.approx(agg)
    # the backend's own counters hold the last-executed request (Z)
    assert vb.stage_seconds == pytest.approx(res.stage_seconds["Z"])
    # the stage spans carry the same seconds; no counter repeats them
    for einsum, per in res.stage_seconds.items():
        spans = {}
        for e in tr.spans("stage"):
            if e["args"]["einsum"] == einsum:
                stage = e["name"][len("stage:"):]
                spans[stage] = spans.get(stage, 0.0) + e["dur"] / 1e6
        assert spans == pytest.approx(per, rel=1e-2)
    assert not any(k.startswith("vector.stage_seconds/")
                   for k in metrics().snapshot()["counters"])


@pytest.mark.parametrize("phase", ["gamma", "bfs"])
def test_stage_spans_never_overlap_and_sum_to_stage_seconds(phase):
    """Nested stages (finalize around reduce and whole levels,
    materialize around pair-merge and lookup) are charged exclusively:
    the stage spans of one Einsum never overlap, and their durations
    sum to ``stage_seconds`` within 1%."""
    if phase == "gamma":
        workload = chip_smoke.gamma_workload(n=64, nnz=500, seed=4)
        with trace_session() as tr:
            res, _ = chip_smoke.gamma_phase(workload, "numpy")
        runs = [(tr.spans(), res.stage_seconds)]
    else:
        # one iteration: the result's stage_seconds are its cascade's
        workload = chip_smoke.bfs_workload(side=10, seed=4)
        with trace_session() as tr:
            res, _ = chip_smoke.bfs_phase(workload, "numpy", max_iters=1)
        runs = [(tr.spans(), res.stage_seconds)]
    for spans, stage_seconds in runs:
        assert stage_seconds
        for einsum, per in stage_seconds.items():
            mine = sorted((e for e in spans if e["cat"] == "stage"
                           and e["args"]["einsum"] == einsum),
                          key=lambda e: e["ts"])
            for x, y in zip(mine, mine[1:]):
                assert x["ts"] + x["dur"] <= y["ts"] + 1e-3, (x, y)
            total = sum(e["dur"] for e in mine) / 1e6
            assert total == pytest.approx(sum(per.values()), rel=1e-2)


#: each new span and the parent it must have
NEW_SPANS = {
    "gen:transform": "cascade:", "gen:restore": "cascade:",
    "model:evaluate": "cascade:", "vec:lower": "einsum:",
    "vec:to_csf": "einsum:", "vec:to_ftensor": "einsum:",
    "device:intersect_keys": "seam:intersect_keys",
}


@pytest.mark.parametrize("phase", ["gamma", "bfs"])
def test_every_new_span_appears_with_its_parent(phase):
    """One Gamma ``simulate()`` and one Ours-VCP ``run_iterative`` on
    the interpreted Pallas kernels carry every new span, each under
    the parent its layer puts it in; ``model:intake`` appears under
    the cascade (merger events) or an einsum (the engine's events), and
    ``device:`` spans name their padded bucket and whether a program
    was built."""
    if phase == "gamma":
        workload = chip_smoke.gamma_workload(n=48, nnz=300, seed=5)
        with trace_session() as tr:
            chip_smoke.gamma_phase(workload, "pallas-interpret")
    else:
        workload = chip_smoke.bfs_workload(side=8, seed=5)
        with trace_session() as tr:
            chip_smoke.bfs_phase(workload, "pallas-interpret",
                                 max_iters=3)
    spans = tr.spans()
    for name, parent in NEW_SPANS.items():
        mine = [e for e in spans if e["name"] == name]
        assert mine, name
        assert all(e["args"]["parent"].startswith(parent) for e in mine), \
            (name, {e["args"]["parent"] for e in mine})
    intake = {e["args"]["parent"].split(":")[0] for e in spans
              if e["name"] == "model:intake"}
    assert "einsum" in intake
    if phase == "gamma":       # Gamma merges its intermediate T
        assert "cascade" in intake
    for e in spans:
        if e["name"].startswith("device:"):
            assert e["args"]["bucket"] and all(
                isinstance(n, int) for shape in e["args"]["bucket"]
                for n in shape)
            assert isinstance(e["args"]["compiled"], bool)
    # the generator and model spans never overlap one another
    top = sorted((e for e in spans
                  if e["name"].split(":")[0] in ("gen", "model")
                  and e["args"]["parent"].startswith("cascade:")),
                 key=lambda e: e["ts"])
    for x, y in zip(top, top[1:]):
        assert x["ts"] + x["dur"] <= y["ts"] + 1e-3, (x, y)


@pytest.mark.parametrize("phase", ["gamma", "bfs"])
def test_statistics_bit_identical_with_and_without_tracer(phase):
    """Tracing observes; it never changes a simulated statistic."""
    if phase == "gamma":
        workload = chip_smoke.gamma_workload(n=64, nnz=500, seed=6)
        run = chip_smoke.gamma_phase
    else:
        workload = chip_smoke.bfs_workload(side=10, seed=6)
        run = lambda w, kb: chip_smoke.bfs_phase(w, kb, max_iters=5)  # noqa
    plain = chip_smoke.fingerprint(*run(workload, "numpy"))
    with trace_session():
        traced = chip_smoke.fingerprint(*run(workload, "numpy"))
    assert traced == plain


def test_span_is_a_profiler_host_event_inside_the_job(tmp_path):
    """With a tracer installed, a span is also a host event of the
    same name in the JAX profiler's trace, on its clock, nested inside
    the benchmark's ``job`` annotation."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace_session() as tr:
            with jax.profiler.TraceAnnotation("job 0"):
                with tr.span("cascade:probe", "cascade"):
                    with tr.span("gen:transform", "gen"):
                        time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    data = ProfileData.from_file(str(files[-1]))
    host = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    host[ev.name] = (ev.start_ns,
                                     ev.start_ns + ev.duration_ns)
    job, casc, gen_ = (host["job 0"], host["cascade:probe"],
                       host["gen:transform"])
    assert job[0] <= casc[0] <= gen_[0]
    assert gen_[1] <= casc[1] <= job[1]
    assert gen_[1] - gen_[0] >= 2e6
    sims = {e["args"]["sim"] for e in tr.spans()}
    assert len(sims) == 1


def test_each_cascade_span_starts_a_simulation():
    with trace_session() as tr:
        for _ in range(2):
            with tr.span("cascade:x", "cascade"):
                with tr.span("einsum:Z", "einsum"):
                    pass
        with tr.span("loose", "t"):
            pass
    by = {}
    for e in tr.spans():
        by.setdefault(e["name"], []).append(e.get("args", {}).get("sim"))
    assert by["cascade:x"] == by["einsum:Z"] and len(set(by["cascade:x"])) == 2
    assert by["loose"] == [None]


def test_stage_seconds_absent_when_disabled(rng):
    inputs, shapes = _spmm(rng)
    sim, vb = _vector_sim(model=True)
    assert active_tracer() is None
    res = sim.run(dict(inputs), shapes)
    assert res.stage_seconds == {}
    assert res.report.stage_seconds == {}
    assert vb.profile is False


# ---------------------------------------------------------------------- #
# export round-trip
# ---------------------------------------------------------------------- #
def _traced_run(rng):
    inputs, shapes = _spmm(rng)
    sim, _ = _vector_sim()
    with trace_session() as tr:
        tr.instant("downgrade:x", "downgrade", {"seam": "s"})
        sim.run(dict(inputs), shapes)
    return tr


def test_chrome_trace_round_trips_json(rng):
    tr = _traced_run(rng)
    doc = json.loads(json.dumps(chrome_trace(tr)))
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["args"]["name"] == "repro"
    assert doc["displayTimeUnit"] == "ms"
    assert "metrics" in doc["otherData"]
    phs = {e["ph"] for e in evs}
    assert phs <= {"M", "X", "i"}
    last_ts = -1.0
    for e in evs[1:]:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert e["ts"] >= last_ts      # exporter time-orders events
        last_ts = e["ts"]
        assert e["pid"] and e["name"] and e["cat"]
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        elif e["ph"] == "i":
            assert e["s"] == "t"       # Perfetto requires a scope


def test_write_trace_formats(tmp_path, rng):
    tr = _traced_run(rng)
    pj = write_trace(tmp_path / "t.json", tr)
    doc = json.loads(pj.read_text())
    assert doc["traceEvents"]
    pl = write_trace(tmp_path / "t.jsonl", tr)
    lines = [json.loads(ln) for ln in pl.read_text().splitlines()]
    assert lines[-1]["kind"] == "metrics"
    assert all("ph" in ln for ln in lines[:-1])
    assert len(lines) - 1 == len(tr.events)
    text = summarize_trace(tr)
    assert "einsum:" in text and "downgrade:x" in text


# ---------------------------------------------------------------------- #
# chaos leg: injected faults in the trace
# ---------------------------------------------------------------------- #
def test_injected_faults_appear_as_instant_events(rng):
    """A REPRO_FAULTS-syntax spec fires mid-run; the resulting
    downgrade must surface as a trace instant carrying the event's
    fields, and the recorded DowngradeEvent must be stamped with a
    timestamp and the active Einsum."""
    install_injector(FaultInjector(parse_faults(
        "kind=raise,seam=intersect_keys,backend=numpy,at=1")))
    inputs, shapes = _spmm(rng)
    sim, vb = _vector_sim()
    with trace_session() as tr:
        res = sim.run(dict(inputs), shapes)
    assert res.downgrade_events, "the fault must be recorded"
    insts = tr.instants("downgrade")
    assert insts, "every recorded downgrade emits a trace instant"
    evs = [e for per in res.downgrade_events.values() for e in per]
    by_name = {}
    for i in insts:
        by_name.setdefault(i["name"], []).append(i)
    for ev in evs:
        assert "downgrade:" + ev.action in by_name
    args = insts[0]["args"]
    assert args["seam"] == "intersect_keys"
    assert args["backend"] == "numpy"
    assert args["ts_us"] > 0 and args["einsum"]
    snap = metrics().snapshot()
    assert sum(v for k, v in snap["counters"].items()
               if k.startswith("kernel.downgrade/")) >= len(evs)


def test_downgrade_events_timestamped_and_monotonic(rng):
    """Satellite (c): ``ts_us`` is stamped at record time (tracer or
    not) and orders events monotonically; the Einsum tag names the
    Einsum that was executing."""
    install_injector(FaultInjector(parse_faults(
        "kind=raise,seam=intersect_keys,backend=numpy,every=1")))
    inputs, shapes = _spmm(rng)
    sim, _ = _vector_sim()
    assert active_tracer() is None   # stamping must not need a tracer
    res = sim.run(dict(inputs), shapes)
    evs = [e for per in res.downgrade_events.values() for e in per]
    assert evs
    assert all(e.ts_us > 0 for e in evs)
    assert [e.ts_us for e in evs] == sorted(e.ts_us for e in evs)
    for einsum, per in res.downgrade_events.items():
        assert all(e.einsum == einsum for e in per), (einsum, per)
    d = evs[0].as_dict()
    assert d["ts_us"] == evs[0].ts_us and d["einsum"] == evs[0].einsum


# ---------------------------------------------------------------------- #
# DSE sweep telemetry
# ---------------------------------------------------------------------- #
def test_dse_sweep_point_spans_and_tallies(rng):
    from repro.dse import DesignSpace, SweepEngine
    inputs, shapes = _spmm(rng, n=32, d=0.15)
    points = DesignSpace(
        "gamma", axes={"fibercache_mb": [0.01, 1.0]}).grid()
    eng = SweepEngine(inputs, shapes, backend="analytic")
    with trace_session() as tr:
        results = eng.sweep(points)
    assert all(r.ok for r in results)
    spans = tr.spans("dse")
    assert len(spans) == len(points)
    assert {s["args"]["status"] for s in spans} == {"ok"}
    snap = metrics().snapshot()
    assert snap["counters"]["dse.point/ok"] == len(points)
    assert snap["counters"]["dse.point_attempts"] == len(points)
    cache = {k: v for k, v in snap["counters"].items()
             if k.startswith("dse.plan_cache/")}
    assert sum(cache.values()) == len(points)
    assert cache.get("dse.plan_cache/miss", 0) >= 1


# ---------------------------------------------------------------------- #
# TeeInstr / CollectingInstr parity (satellite b)
# ---------------------------------------------------------------------- #
class _RecordingSink(Instrumentation):
    """Captures raw call args -- CollectingInstr drops ``unique``, so
    pass-through can only be asserted on a sink that keeps it."""

    def __init__(self):
        self.touches = []
        self.computes = []

    def touch(self, einsum, tensor, rank, path, kind, rw, n=1,
              unique=None):
        self.touches.append((einsum, tensor, rank, kind, rw, n, unique))

    def compute(self, einsum, op, n=1):
        self.computes.append((einsum, op, n))


COUNTERS = ("touch_counts", "iter_counts", "compute_counts",
            "isect_steps", "isect_matches", "advances")


@settings(max_examples=20)
@given(n_events=st.integers(min_value=1, max_value=12),
       seed=st.integers(min_value=0, max_value=2**16))
def test_tee_aggregate_matches_per_element(n_events, seed):
    """n-weighted aggregate emission and element-by-element emission
    drive identical collected totals through a tee, and the ``unique``
    hint reaches every sink verbatim."""
    r = np.random.default_rng(seed)
    tensors = ("A", "B", "Z")
    ranks = ("m", "k", "n")
    events = []
    for _ in range(n_events):
        n = int(r.integers(1, 9))
        events.append((
            tensors[r.integers(0, 3)], ranks[r.integers(0, 3)],
            ("coord", "payload")[r.integers(0, 2)],
            ("read", "write")[r.integers(0, 2)], n,
            None if r.integers(0, 2) else int(r.integers(0, n + 1)),
            ("mul", "add")[r.integers(0, 2)],
        ))
    agg_c, agg_r = CollectingInstr(), _RecordingSink()
    ele_c, ele_r = CollectingInstr(), _RecordingSink()
    agg, ele = TeeInstr(agg_c, agg_r), TeeInstr(ele_c, ele_r)
    for tensor, rank, kind, rw, n, unique, op in events:
        agg.touch("Z", tensor, rank, (), kind, rw, n=n, unique=unique)
        agg.compute("Z", op, n=n)
        agg.iterate("Z", rank, n=n)
        agg.advance("Z", rank, n=n)
        agg.isect_step("Z", rank, tensor, n=n)
        agg.isect_match("Z", rank, n=n)
        for _ in range(n):
            ele.touch("Z", tensor, rank, (), kind, rw)
            ele.compute("Z", op)
            ele.iterate("Z", rank)
            ele.advance("Z", rank)
            ele.isect_step("Z", rank, tensor)
            ele.isect_match("Z", rank)
    for name in COUNTERS:
        assert getattr(agg_c, name) == getattr(ele_c, name), name
    # unique pass-through: the tee forwards the kwarg untouched
    assert [t[-1] for t in agg_r.touches] == [e[5] for e in events]
    assert [t[5] for t in agg_r.touches] == [e[4] for e in events]
    # per-element emission cannot carry an aggregate hint
    assert all(t[-1] is None for t in ele_r.touches)


# ---------------------------------------------------------------------- #
# bench_compare gate logic
# ---------------------------------------------------------------------- #
def test_bench_compare_gate_semantics():
    from benchmarks.bench_compare import Gate
    g = Gate()
    g.rate("fast-enough", 100.0, 80.0, 0.25)     # 80 >= 75: ok
    g.rate("faster", 100.0, 500.0, 0.25)         # one-sided: ok
    g.rate("too-slow", 100.0, 74.0, 0.25)        # 74 < 75: regression
    g.exact("same", 5, 5)
    g.exact("drifted", 5, 6)
    g.skip("leg", "missing")
    assert g.failures == 2
    rep = g.report()
    assert "2 regression(s)" in rep
    assert rep.count("REGRESSION") == 2 and "skipped" in rep


def test_bench_compare_committed_baselines_self_consistent():
    """The committed BENCH files must pass their own gate: dse compared
    against itself and the graph structural claims."""
    import benchmarks.bench_compare as bc
    committed = bc._load(bc.BENCH_DSE)
    if committed is None:
        pytest.skip("no committed BENCH_dse.json")
    g = bc.Gate()
    bc.compare_dse(g, tolerance=0.25, fresh_summary=committed)
    bc.compare_graph(g)
    assert g.failures == 0, g.report()

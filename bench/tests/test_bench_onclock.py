"""CPU tests of the readers of the program's own layers
(``bench/harness/onclock.py`` and the metrics that use it) on
synthetic windows: each reads its spans, counters or device time, and
returns None, never 0, where the run left nothing to read."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (BENCH, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from harness import onclock, registry, trace  # noqa: E402
from harness.runner import Window  # noqa: E402


def _span(name, dur_s, ts=0.0):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur_s * 1e6}


def _window(spans=None, counters=None, summary=None):
    return Window(cell="c", setup_s=1.0, timed_s=10.0, job_seconds=[5.0, 5.0],
                  ops=1, peak_rss_bytes=1, base_rss_bytes=0,
                  counters=counters or {}, compiles=0, spans=spans,
                  trace=summary, trace_window_s=None if summary is None
                  else 12.0)


SPANS = [
    _span("cascade:Gamma", 9.0), _span("gen:transform", 2.0),
    _span("gen:transform", 0.5), _span("gen:restore", 1.0),
    _span("model:intake", 0.25), _span("model:evaluate", 0.75),
    _span("einsum:Z", 4.0), _span("vec:lower", 0.1),
    _span("vec:to_csf", 0.6), _span("vec:to_ftensor", 0.4),
    _span("stage:materialize", 2.0), _span("seam:intersect_keys", 1.5),
    _span("device:intersect_keys", 1.2), _span("device:lookup_keys", 0.3),
    {"name": "downgrade:retry", "ph": "i", "ts": 0.0},
]


@pytest.mark.parametrize("metric,value", [
    ("transform_share", 0.25), ("restore_share", 0.1),
    ("model_share", 0.1), ("lower_share", 0.01),
    ("csf_convert_share", 0.1), ("device_wait_share", 0.15)])
def test_span_share_readers(metric, value):
    read = registry.metric_reader(metric).read
    assert read(_window(SPANS)) == pytest.approx(value)
    # an untraced run, and a traced run of a program without the span
    assert read(_window(None)) is None
    assert read(_window([_span("cascade:Gamma", 9.0)])) is None


def test_span_seconds_match_names_and_prefixes():
    w = _window(SPANS)
    assert onclock.span_seconds(w, "gen:transform") == pytest.approx(2.5)
    assert onclock.span_seconds(w, "model:") == pytest.approx(1.0)
    # a name without ':' at its end matches itself only
    assert onclock.span_seconds(w, "vec:to") is None


def _profile(device_ops, host):
    return {"/device:TPU:0": device_ops}, host


def test_intersect_roofline_reads_device_time_inside_the_seams(
        monkeypatch):
    host = [("job 0", 0, 1000), ("seam:intersect_keys", 100, 200),
            ("seam:lookup_keys", 300, 400), ("seam:union_k_keys", 500, 600)]
    ops = [("intersect_sorted", 120, 160), ("fusion", 150, 170),
           ("intersect_sorted", 380, 420), ("multi_merge_ranks", 510, 590)]
    monkeypatch.setattr(onclock, "profile", lambda: _profile(ops, host))
    monkeypatch.setattr(onclock, "device_kind", lambda: "TPU v5 lite")
    summary = trace.summarize(*_profile(ops, host), (0, 1000))
    w = _window(SPANS, {"kernel.seam_keys/intersect_keys": 3e3,
                        "kernel.seam_keys/lookup_keys": 1e3,
                        "kernel.seam_keys/union_k_keys": 9e9}, summary)
    # busy inside the two seams: [120, 170) + [380, 400) = 70 ns
    got = registry.metric_reader("intersect_roofline").read(w)
    assert got == pytest.approx(100 * 4 * 4e3 / 70e-9 / 819e9)


def test_intersect_roofline_is_none_without_its_inputs(monkeypatch):
    read = registry.metric_reader("intersect_roofline").read
    host = [("seam:intersect_keys", 100, 200)]
    keys = {"kernel.seam_keys/intersect_keys": 3e3}
    monkeypatch.setattr(onclock, "device_kind", lambda: "TPU v5 lite")
    summary = trace.summarize({}, host, (0, 1000))
    # no key counter (a program that counts none)
    monkeypatch.setattr(onclock, "profile", lambda: _profile(
        [("intersect_sorted", 120, 160)], host))
    assert read(_window(SPANS, {}, summary)) is None
    # an untraced run
    assert read(_window(SPANS, keys, None)) is None
    # no seam annotation on the profiler's clock
    monkeypatch.setattr(onclock, "profile", lambda: _profile(
        [("intersect_sorted", 120, 160)], [("job 0", 0, 1000)]))
    assert read(_window(SPANS, keys, summary)) is None
    # device idle inside the seams (they ran on the host)
    monkeypatch.setattr(onclock, "profile", lambda: _profile(
        [("fusion", 300, 400)], host))
    assert read(_window(SPANS, keys, summary)) is None
    # no profiler trace at all
    monkeypatch.setattr(onclock, "profile", lambda: None)
    assert read(_window(SPANS, keys, summary)) is None

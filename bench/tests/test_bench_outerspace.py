"""CPU tests that the plain OuterSPACE reference decides ``correct`` for
the cell ``outerspace-rmat``: a sound run at a tiny size comes out
correct with every count equal, and the float32 control, an altered Z
or a performance model that misses one kind of event does not."""
import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from harness import registry, runner  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "outerspace-rmat"


def tiny():
    """The cell at 64 rows and 300 nonzeros, on the numpy kernels."""
    cell = registry.find_cell(CELL)
    config = dict(cell.config, kernel_backend="numpy", rows=64,
                  nonzeros=300)
    traffic = dict(cell.traffic, job_cap=3, warmup_jobs=1)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run(cell, seed=2**31 + 11):
    return runner.run_cell(cell, seed, 0.05, False, CPU,
                           time.perf_counter(), runner.resident_bytes())


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**33 + 5])
def test_sound_run_is_correct_with_every_count_equal(seed):
    cell = tiny()
    res = run(cell, seed)
    assert res.correct, res.checks
    assert res.attempted >= 1 and res.failed == 0
    assert res.checks["count_gap"]["value"] == 0
    assert set(res.line()["metrics"]) == {m["name"]
                                          for m in cell.end_to_end}


def test_reference_counts_the_outer_product():
    """The reference's multiplies are sum_k nnz(X[k, :])**2, its adds
    multiplies - nnz(Z), and every product passes the merger once."""
    cell = tiny()
    _, jobs = cell.driver.prepare(7, cell.config, cell.traffic)
    job = jobs[0]
    ref = cell.reference.expected(job, cell.config)
    stats, z_nnz = ref["stats"], len(ref["z"][0])
    mul = cell.reference.ops(job)
    assert stats["count mul"] == mul
    assert stats["count merge_elem"] == mul
    assert stats["count add"] == mul - z_nnz
    assert "count isect_step" not in stats


def test_control_is_not_correct():
    """The reference in float32 in the program's place."""
    cell = tiny()
    _, jobs = cell.driver.prepare(5, cell.config, cell.traffic)
    limits = cell.config["limits"]
    for job in jobs:
        nums = cell.reference.compare(
            cell.reference.control(job, cell.config),
            cell.reference.expected(job, cell.config))
        assert any(nums[k] > limits[k] for k in limits), nums


def test_altered_answer_is_not_correct(monkeypatch):
    """One reduced value of Z nudged by one part in 1e9 where the
    kernel seam produces it."""
    from repro.kernels.backends import NumpyKernels

    orig = NumpyKernels.segmented_reduce

    def altered(self, vals, *a, **k):
        out = np.array(orig(self, vals, *a, **k), copy=True)
        if out.size:
            out[out.size // 2] *= 1.0 + 1e-9
        return out

    monkeypatch.setattr(NumpyKernels, "segmented_reduce", altered)
    res = run(tiny())
    assert not res.correct
    assert res.checks["z_rel_gap"]["value"] > \
        res.checks["z_rel_gap"]["limit"]


#: the kinds of event OuterSPACE makes: the sequencer's iterations,
#: the merger's swizzles, buffer and DRAM touches, multiplies and adds
EVENTS = ["iterate", "merge", "touch", "compute"]


@pytest.mark.parametrize("event", EVENTS)
def test_dropped_statistic_is_not_correct(event, monkeypatch):
    """The performance model misses every other event of one kind: its
    statistics move, and the plain model sees it."""
    from repro.core.components import PerformanceModel

    orig = getattr(PerformanceModel, event)
    calls = []

    def dropped(self, *a, **k):
        calls.append(1)
        if len(calls) % 2:
            return orig(self, *a, **k)

    monkeypatch.setattr(PerformanceModel, event, dropped)
    res = run(tiny())
    assert len(calls) > 1
    assert not res.correct
    assert res.checks["count_gap"]["value"] > 0 or \
        res.checks["model_rel_gap"]["value"] > \
        res.checks["model_rel_gap"]["limit"]


def test_design_makes_no_intersection_step(monkeypatch):
    """OuterSPACE has no intersection unit and the program sends it no
    intersection step: there is no such event to drop."""
    from repro.core.components import PerformanceModel

    orig = PerformanceModel.isect_step
    calls = []

    def counted(self, *a, **k):
        calls.append(1)
        return orig(self, *a, **k)

    monkeypatch.setattr(PerformanceModel, "isect_step", counted)
    res = run(tiny())
    assert res.correct, res.checks
    assert calls == []


def _window(spans=None, counters=None):
    return runner.Window(cell=CELL, setup_s=1.0, timed_s=10.0,
                         job_seconds=[5.0, 5.0], ops=1, peak_rss_bytes=1,
                         base_rss_bytes=0, counters=counters or {},
                         compiles=0, spans=spans)


def _span(name, dur_s):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_s * 1e6}


@pytest.mark.parametrize("metric,span", [
    ("partition_share", "gen:partition"), ("swizzle_share", "gen:swizzle")])
def test_transform_step_share_readers(metric, span):
    """Each reads its own spans inside ``gen:transform``, and nothing
    on an untraced run or from a program without the span."""
    read = registry.metric_reader(metric).read
    spans = [_span("gen:transform", 6.0), _span("gen:partition", 1.0),
             _span("gen:swizzle", 4.0), _span("gen:swizzle", 0.5)]
    want = {"gen:partition": 0.1, "gen:swizzle": 0.45}[span]
    assert read(_window(spans)) == pytest.approx(want)
    assert read(_window(None)) is None
    assert read(_window([_span("gen:transform", 6.0)])) is None


def test_swizzle_leaves_per_job_reader():
    read = registry.metric_reader("swizzle_leaves_per_job").read
    w = _window([], {"gen.swizzle_leaves": 1.8e6,
                     "gen.partition_leaves": 5.0})
    assert read(w) == pytest.approx(9e5)
    # a program that counts no swizzled leaves
    assert read(_window([], {"kernel.device_call/lookup_keys": 2.0})) \
        is None


def test_traced_run_reports_the_generator_step_metrics(tmp_path,
                                                       monkeypatch):
    """A ``--trace 1`` run of the tiny cell reads all three, above 0."""
    from harness import onclock

    monkeypatch.setattr(runner, "TRACE_DIR", tmp_path / "trace")
    onclock.profile.cache_clear()
    try:
        res = runner.run_cell(tiny(), 2**31 + 3, 0.05, True, CPU,
                              time.perf_counter(),
                              runner.resident_bytes())
    finally:
        onclock.profile.cache_clear()
    assert res.correct, res.checks
    for name in ("partition_share", "swizzle_share",
                 "swizzle_leaves_per_job"):
        assert res.metrics[name]["value"] > 0

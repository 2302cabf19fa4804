"""CPU tests that ``correct`` is decided by the plain reference: a
sound run at a tiny size comes out correct, and with the timed path
broken underneath (or the control in the program's place) it does
not."""
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


def tiny(cell_name, **cfg):
    """The cell at a size a CPU test holds, on the numpy kernels."""
    cell = registry.find_cell(cell_name)
    config = dict(cell.config, **dict({"kernel_backend": "numpy"}, **cfg))
    traffic = dict(cell.traffic, job_cap=3, warmup_jobs=1)
    return dataclasses.replace(cell, config=config, traffic=traffic)


CELLS = {
    "gamma-rmat": dict(rows=64, nonzeros=300),
    "bfs-kron": dict(scale=7),
}


def run(cell, seed=2**31 + 11):
    return runner.run_cell(cell, seed, 0.05, False, CPU,
                           time.perf_counter(), runner.resident_bytes())


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    cell = tiny(name, **CELLS[name])
    res = run(cell)
    assert res.correct, res.checks
    assert res.attempted >= 1 and res.failed == 0
    line = res.line()
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert {"sim_s", "sim_ops_per_s", "setup_s"} <= set(line["metrics"])


def test_gamma_in_declared_order_is_not_correct():
    """A handed in declared order [K, M]: the program reads it as
    [M, K] and computes another product, which the reference sees
    although the program's own engines agree with each other."""
    res = run(tiny("gamma-rmat", a_order=["K", "M"], **CELLS["gamma-rmat"]))
    assert not res.correct
    assert res.checks["z_pattern_diff"]["value"] > 0


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(name):
    """The control in the program's place: float32 SpMSpM, or a BFS
    stopped one iteration short."""
    cell = tiny(name, **CELLS[name])
    warm, jobs = cell.driver.prepare(5, cell.config, cell.traffic)
    limits = cell.config["limits"]
    for job in jobs:
        nums = cell.reference.compare(
            cell.reference.control(job, cell.config),
            cell.reference.expected(job, cell.config))
        assert any(nums[k] > limits[k] for k in limits), nums


#: how each cell's answer is altered where the seam produces it, and
#: the number that must catch it: a sum nudged by one part in 1e9, a
#: min-plus distance one hop longer
ALTERED = {"gamma-rmat": (lambda x: x * (1.0 + 1e-9), "z_rel_gap"),
           "bfs-kron": (lambda x: x + 1.0, "dist_diff")}


@pytest.mark.parametrize("name", sorted(ALTERED))
def test_altered_answer_is_not_correct(name, monkeypatch):
    """One reduced value altered where the kernel seam produces it."""
    from repro.kernels.backends import NumpyKernels

    alter, number = ALTERED[name]
    orig = NumpyKernels.segmented_reduce

    def altered(self, vals, *a, **k):
        out = np.array(orig(self, vals, *a, **k), copy=True)
        if out.size:
            out[out.size // 2] = alter(out[out.size // 2])
        return out

    monkeypatch.setattr(NumpyKernels, "segmented_reduce", altered)
    res = run(tiny(name, **CELLS[name]))
    assert not res.correct
    assert res.checks[number]["value"] > res.checks[number]["limit"]


@pytest.mark.parametrize("name", ["bfs-kron"])
def test_step_returning_its_state_unchanged_is_not_correct(name,
                                                           monkeypatch):
    """Every iteration hands back the properties it was given."""
    from repro.core.generator import CascadeSimulator

    orig = CascadeSimulator.run

    def unchanged(self, inputs, var_shapes=None):
        res = orig(self, inputs, var_shapes)
        res.tensors["P1"] = self._to_ftensor("P0", inputs["P0"]).copy("P1")
        return res

    monkeypatch.setattr(CascadeSimulator, "run", unchanged)
    res = run(tiny(name, **CELLS[name]))
    assert not res.correct
    assert res.checks["dist_diff"]["value"] > 0


#: the kinds of event each cell's design makes (Ours-VCP has no merger)
EVENTS = [(name, event) for name in sorted(CELLS)
          for event in ("isect_step", "merge", "touch")
          if (name, event) != ("bfs-kron", "merge")]


@pytest.mark.parametrize("name,event", EVENTS)
def test_dropped_statistic_is_not_correct(name, event, monkeypatch):
    """The performance model misses one kind of event (an intersection
    step, a merge, a buffer or DRAM touch): its statistics move, and
    the plain model sees it."""
    from repro.core.components import PerformanceModel

    orig = getattr(PerformanceModel, event)
    calls = []

    def dropped(self, *a, **k):
        calls.append(1)
        if len(calls) % 2:
            return orig(self, *a, **k)

    monkeypatch.setattr(PerformanceModel, event, dropped)
    res = run(tiny(name, **CELLS[name]))
    assert len(calls) > 1
    assert not res.correct
    assert res.checks["count_gap"]["value"] > 0 or \
        res.checks["model_rel_gap"]["value"] > \
        res.checks["model_rel_gap"]["limit"]


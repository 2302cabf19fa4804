"""CPU rehearsal of ``chip_smoke.py``: both phases at a tiny size under
the interpreted Pallas kernels and the jax-jit lowering, checked
against the numpy oracle the way the chip run checks them."""
import sys

import pytest

import chip_smoke
from repro.obs.metrics import metrics

PHASES = {
    "gamma": lambda: (chip_smoke.gamma_workload(n=96, nnz=700, seed=3),
                      chip_smoke.gamma_phase),
    "bfs": lambda: (chip_smoke.bfs_workload(side=12, seed=3),
                    lambda w, kb: chip_smoke.bfs_phase(w, kb, max_iters=6)),
}


@pytest.mark.parametrize("backend", ["pallas-interpret", "jax-jit"])
@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_matches_numpy_oracle(phase, backend):
    workload, run = PHASES[phase]()
    oracle = chip_smoke.fingerprint(*run(workload, "numpy"))
    before = metrics().snapshot()["counters"]
    res, iters = run(workload, backend)
    after = metrics().snapshot()["counters"]
    assert chip_smoke.native_failures(res) == []
    assert chip_smoke.fingerprint(res, iters) == oracle
    calls = chip_smoke._counter_deltas(before, after, "kernel.device_call/")
    assert sum(calls.values()) > 0


def test_refuses_to_run_without_a_chip(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 1
    assert capsys.readouterr().out == ""

"""What every cell checks of the program's own run."""
from __future__ import annotations

from typing import Dict, List


def native_failures(res) -> List[str]:
    """Einsums that fell back to the interpreter, and kernel-chain
    downgrades: on the chip each is a failure, not a recovery."""
    bad = [f"fallback {e}: {r}" for e, r in res.fallback_reasons.items()]
    for e, evs in res.downgrade_events.items():
        bad += [f"downgrade {e}: {ev}" for ev in evs]
    return bad


def model_stats(report) -> Dict[str, float]:
    """The performance model's statistics of a run: modeled seconds,
    DRAM bytes read and written, energy, and every action count."""
    out = {"seconds": float(report.seconds),
           "dram_read_bytes": float(report.dram_read_bytes),
           "dram_write_bytes": float(report.dram_write_bytes),
           "energy_pj": float(report.energy_pj)}
    out.update({f"count {k}": float(v)
                for k, v in report.action_counts.items()})
    return out

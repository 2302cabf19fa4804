"""The chips' published peaks, keyed by JAX's ``device_kind``
(``bench/peaks.json``, with its source).  A device that is not in the
table is an error, not a default."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]

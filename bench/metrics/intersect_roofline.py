"""``intersect_roofline``: the key-matching seams' share of the chip's
HBM bandwidth, in percent.  Bytes: 4 (an int32 key on the device) for every key
the ``intersect_keys`` and ``lookup_keys`` seam calls consumed and
returned (``kernel.seam_keys/*``, counted the same whatever kernel
serves the seam); seconds: the device's busy time inside those
calls' ``seam:`` spans, on the profiler's clock; peak:
``hbm_bytes_per_s`` of ``bench/peaks.json``."""
from harness import onclock, peaks

SEAMS = ("intersect_keys", "lookup_keys")
KEY_BYTES = 4


def read(w):
    keys = sum(w.counters.get("kernel.seam_keys/" + s, 0.0) for s in SEAMS)
    if not keys or w.trace is None:
        return None
    busy = onclock.device_seconds_inside(["seam:" + s for s in SEAMS])
    if busy is None:
        return None
    peak = peaks.peaks(onclock.device_kind())["hbm_bytes_per_s"]
    return 100.0 * KEY_BYTES * keys / busy / peak

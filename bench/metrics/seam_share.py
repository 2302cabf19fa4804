"""``seam_share``: share of the window's timed seconds inside
``seam:`` spans, the guarded kernel dispatch (host time, device round
trips included)."""
from harness.spans import level_seconds


def read(w):
    s = level_seconds(w)
    if s is None or s["seam"] == 0.0:
        return None
    return s["seam"] / w.timed_s

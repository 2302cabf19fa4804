"""``generator_share``: share of the window's timed seconds in the
generator: ``cascade:`` spans minus the ``einsum:`` spans inside them
(fibertree transforms, swizzle and merge detection,
``restore_declared``, the performance model)."""
from harness.spans import level_seconds


def read(w):
    s = level_seconds(w)
    if s is None or s["cascade"] == 0.0:
        return None
    return (s["cascade"] - s["einsum"]) / w.timed_s

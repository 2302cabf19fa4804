"""``stage_share``: share of the window's timed seconds in the vector
engine's stages: ``stage:`` spans minus the ``seam:`` spans inside
them.  Stage spans are the program's aggregates of its stage timers,
laid end to end inside their Einsum."""
from harness.spans import level_seconds


def read(w):
    s = level_seconds(w)
    if s is None or s["stage"] == 0.0:
        return None
    return (s["stage"] - s["seam"]) / w.timed_s

"""``convert_share``: share of the window's timed seconds in the
vector engine outside its stages: ``einsum:`` spans minus their
``stage:`` spans (plan lowering, FTensor <-> CSF conversion)."""
from harness.spans import level_seconds


def read(w):
    s = level_seconds(w)
    if s is None or s["einsum"] == 0.0:
        return None
    return (s["einsum"] - s["stage"]) / w.timed_s

"""``lower_share``: share of the window's timed seconds lowering
mapped Einsums to vector plans (``vec:lower`` spans)."""
from harness.onclock import share


def read(w):
    return share(w, "vec:lower")

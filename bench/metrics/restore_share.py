"""``restore_share``: share of the window's timed seconds rebuilding
each Einsum's output in its declared form (``gen:restore`` spans,
``restore_declared``)."""
from harness.onclock import share


def read(w):
    return share(w, "gen:restore")

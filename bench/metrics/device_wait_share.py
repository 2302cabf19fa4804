"""``device_wait_share``: share of the window's timed seconds spent
launching seam programs, waiting for them and reading their results
back (``device:`` spans, inside the ``seam:`` spans)."""
from harness.onclock import share


def read(w):
    return share(w, "device:")

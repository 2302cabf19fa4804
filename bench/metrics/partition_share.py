"""``partition_share``: share of the window's timed seconds in the
generator's partition steps (``gen:partition`` spans, inside
``gen:transform``: the flatten of a rank group and each
``uniform_shape`` / ``uniform_occupancy`` directive)."""
from harness.onclock import share


def read(w):
    return share(w, "gen:partition")

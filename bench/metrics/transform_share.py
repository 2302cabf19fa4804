"""``transform_share``: share of the window's timed seconds in the
generator's transforms (``gen:transform`` spans: partition, flatten
and swizzle of each Einsum's inputs, merge detection)."""
from harness.onclock import share


def read(w):
    return share(w, "gen:transform")

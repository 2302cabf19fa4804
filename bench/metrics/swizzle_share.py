"""``swizzle_share``: share of the window's timed seconds in the
generator's rank swizzles (``gen:swizzle`` spans, inside
``gen:transform``: the reorder of a rank group before its flatten and
the swizzle into the Einsum's execution order, which for OuterSPACE's
merge phase is the swizzle of the whole partial-product tensor T)."""
from harness.onclock import share


def read(w):
    return share(w, "gen:swizzle")

"""``csf_convert_share``: share of the window's timed seconds
converting between fibertrees and CSF around each Einsum
(``vec:to_csf`` and ``vec:to_ftensor`` spans)."""
from harness.onclock import share


def read(w):
    return share(w, "vec:to_csf", "vec:to_ftensor")

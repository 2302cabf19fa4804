"""``swizzle_leaves_per_job``: leaves the generator's rank swizzles
moved (``gen.swizzle_leaves`` counter, traced runs) per job of the
window; None where the program counts none."""


def read(w):
    leaves = w.counters.get("gen.swizzle_leaves")
    if leaves is None:
        return None
    return leaves / len(w.job_seconds)

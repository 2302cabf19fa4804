"""``sim_s``: the window's timed seconds over the jobs completed (host
clock around each whole job)."""


def read(w):
    return w.timed_s / len(w.job_seconds)

"""``sim_ops_per_s``: simulated operations of all the window's jobs,
counted by the plain reference from the inputs, over the window's
timed seconds."""


def read(w):
    return w.ops / w.timed_s

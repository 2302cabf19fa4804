"""``setup_s``: host seconds from the process's start to the window:
JAX and the chip, every job's inputs, and the warm-up jobs."""


def read(w):
    return w.setup_s

"""``window_compiles``: programs built inside the window, compiled or
read from the persistent compile cache (``jax.monitoring`` backend
compile events)."""


def read(w):
    return float(w.compiles)

"""``model_share``: share of the window's timed seconds in the
performance model (``model:`` spans: ``model:intake``, the model
taking the engine's and the generator's events, and
``model:evaluate``, its report)."""
from harness.onclock import share


def read(w):
    return share(w, "model:")

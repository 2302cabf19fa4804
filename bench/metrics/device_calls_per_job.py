"""``device_calls_per_job``: seam programs launched on the JAX device
(``kernel.device_call/*`` counters) per job of the window."""


def read(w):
    calls = sum(v for k, v in w.counters.items()
                if k.startswith("kernel.device_call/"))
    return calls / len(w.job_seconds)

"""``device_idle_share``: 1 minus the union of the device's op
intervals over the traced window (first job's start to last job's
end), from the profiler trace."""


def read(w):
    if w.trace is None or not w.trace.n_devices:
        return None
    return 1.0 - w.trace.busy_s / w.trace_window_s

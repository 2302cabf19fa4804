"""``host_peak_gb``: the simulator's own host memory, in GB (1e9
bytes): the process's peak resident set (``ru_maxrss``) once the
window has closed, less its resident set once JAX had found the chip,
which is the TPU runtime's (about 13.9 GB on a TPU v5e host).  What is
left is every job's inputs and the largest job's working set."""


def read(w):
    return (w.peak_rss_bytes - w.base_rss_bytes) / 1e9

"""Device (the TPU): the share of the traced window in which no op ran,
1 - (union of the device's op intervals) / (traced window), from the
profiler trace."""


def read(r):
    t = r.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

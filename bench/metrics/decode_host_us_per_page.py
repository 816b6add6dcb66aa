"""Engine decode dispatch, host side (`DatapathEngine._decode_bucket`,
kernels/ops.py): host microseconds per page decoded in a bucket, the
summed duration of the program's `engine.stack` (pages stacked into a
bucket), `ops.dispatch` (each counted launch) and `engine.split` (the
bucket's output sliced back into pages) spans in the traced window over
the pages `engine.stack` carries."""

from bench.metrics import _spans


def read(r):
    log = _spans.spans(r)
    if log is None:
        return None
    pages = _spans.count(log, "engine.stack", "pages")
    if not pages:
        return None
    ns = _spans.duration_ns(log, ("engine.stack", "ops.dispatch", "engine.split"))
    return ns / pages / 1e3

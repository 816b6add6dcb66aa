"""Service front / WFQ queue (datapath/service.py, datapath/scheduler.py):
the mean of the program's `pod.queued` spans in the traced window, each
a scan's wait from its submit to its first dispatch, in milliseconds."""

from bench.metrics import _spans


def read(r):
    log = _spans.spans(r)
    if log is None:
        return None
    waits = [t1 - t0 for n, _, t0, t1, _ in log if n == "pod.queued"]
    return sum(waits) / len(waits) / 1e6 if waits else None

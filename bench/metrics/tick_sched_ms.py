"""Scheduler (datapath/scheduler.py, `Pod.tick`): host milliseconds per
tick that no engine or kernel-API span covers, the self time of the
program's `pod.tick` spans in the traced window (each one's duration
less the union of the `engine.*` and `ops.*` spans nested in it on its
thread), averaged over the ticks."""

from bench.metrics import _spans


def read(r):
    log = _spans.spans(r)
    if log is None:
        return None
    ticks = _spans.self_ns(log, "pod.tick", ("engine.", "ops."))
    return sum(ticks) / len(ticks) / 1e6 if ticks else None

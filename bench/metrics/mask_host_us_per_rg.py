"""Engine predicate and validity mask (`DatapathEngine._row_mask`): host
microseconds per row group masked, the summed duration of the program's
`engine.mask` spans in the traced window over the row groups they cover.
A span that carries an `rgs` count covers that many row groups; one
without (a span per row group) covers one."""

from bench.metrics import _spans


def read(r):
    log = _spans.spans(r)
    if log is None:
        return None
    rgs = sum(c.get("rgs", 1) for n, _, _, _, c in log if n == "engine.mask")
    if not rgs:
        return None
    return _spans.duration_ns(log, ("engine.mask",)) / rgs / 1e3

"""Storage read + CRC (lakeformat/reader.py, `DatapathEngine._storage_read`):
host microseconds per encoded page read from storage and checked, the
summed duration of the program's `engine.storage_read` spans in the
traced window over the pages they carry."""

from bench.metrics import _spans


def read(r):
    log = _spans.spans(r)
    if log is None:
        return None
    pages = _spans.count(log, "engine.storage_read", "pages")
    if not pages:
        return None
    return _spans.duration_ns(log, ("engine.storage_read",)) / pages / 1e3

"""Engine (core/engine.py): fresh decode work, the sum over encodings of
`ScanStats.decode_work` (output bytes) that the window's ticks did, in
megabytes (1e6 bytes) per query completed in the window."""


def read(r):
    if not r.queries:
        return None
    return sum(r.window["decode_work"].values()) / 1e6 / r.queries

"""The program's span log (`repro.datapath.trace.span_log()`), as the
metrics that read spans see it.  A span is (name, thread id, t0_ns,
t1_ns, counts) on the host's clock; the log holds the spans of the
latest profiler session, which in a traced run is the harness's."""

import bisect


def spans(r):
    """The traced window's spans, or None: the run was not traced, the
    program keeps no span log, or the log is still collecting, dropped
    spans, is empty, or is stale (the launches its `ops.dispatch` spans
    count differ from the dispatch counter's change over the traced
    ticks)."""
    if r.trace is None or r.traced is None:
        return None
    try:
        from repro.datapath import trace
    except ImportError:
        return None
    span_log = getattr(trace, "span_log", None)
    if span_log is None:
        return None
    log = span_log()
    if log.active or log.dropped or not log.spans:
        return None
    launches = sum(c.get("n", 1) for n, _, _, _, c in log.spans if n == "ops.dispatch")
    if launches != r.traced["dispatches"]:
        return None
    return list(log.spans)


def duration_ns(log, names) -> int:
    return sum(t1 - t0 for n, _, t0, t1, _ in log if n in names)


def count(log, name: str, key: str) -> int:
    return sum(c.get(key, 0) for n, _, _, _, c in log if n == name)


def self_ns(log, parent: str, child_prefixes) -> list:
    """Each `parent` span's duration less what the spans nested in it on
    its thread whose names start with one of `child_prefixes` cover."""
    kids = {}
    for n, tid, t0, t1, _ in log:
        if n != parent and n.startswith(child_prefixes):
            kids.setdefault(tid, []).append((t0, t1))
    for v in kids.values():
        v.sort()
    starts = {tid: [t0 for t0, _ in v] for tid, v in kids.items()}
    out = []
    for n, tid, a, b, _ in log:
        if n != parent:
            continue
        v = kids.get(tid, [])
        covered, end = 0, a
        for t0, t1 in v[bisect.bisect_left(starts.get(tid, []), a):]:
            if t0 >= b:
                break
            t1 = min(t1, b)
            if t1 > end:
                covered += t1 - max(t0, end)
                end = t1
        out.append(b - a - covered)
    return out

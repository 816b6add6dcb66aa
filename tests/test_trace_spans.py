"""The span API (`trace.span`) on the CPU: off, it builds nothing; under
the JAX profiler its spans land in the trace's host plane, nested as the
code nests them and inside a caller's own annotation, and in the span
log the per-layer metrics read; the log keeps one profiler session,
bounded, and counts what it drops."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import BlockCache, Cmp, DatapathEngine, ScanPlan
from repro.datapath import DatapathService, StaticPolicy
from repro.datapath import trace
from repro.kernels import ops
from repro.lakeformat.reader import LakeReader
from repro.lakeformat.schema import ColumnSchema, TableSchema
from repro.lakeformat.writer import write_table

RG_ROWS = 1024


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    rng = np.random.default_rng(5)
    n = 4 * RG_ROWS
    cols = {
        "a": np.arange(n, dtype=np.int32),
        "d": rng.integers(0, 7, n).astype(np.int32),
        "b": rng.standard_normal(n).astype(np.float32),
    }
    schema = TableSchema("spans", [
        ColumnSchema("a", "int32", "bitpack"),
        ColumnSchema("d", "int32", "dict"),
        ColumnSchema("b", "float32", "plain"),
    ])
    path = str(tmp_path_factory.mktemp("spans") / "spans.lake")
    write_table(path, schema, cols, row_group_size=RG_ROWS)
    return LakeReader(path)


PLANS = [ScanPlan("spans", ["b", "d"], Cmp("a", "lt", 3000)),
         ScanPlan("spans", ["d", "b"], Cmp("d", "le", 3), compact=True)]


def serve(table, rate: float, around_tick=None):
    """Two tenants' scans through one Pod; returns the tickets."""
    svc = DatapathService(engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
                          policy=StaticPolicy("raw"), trace_sample_rate=rate)
    tickets = [svc.submit(f"t{i}", table, p) for i, p in enumerate(PLANS)]
    while svc.queue:
        if around_tick is None:
            svc.tick()
        else:
            with TraceAnnotation(around_tick):
                svc.tick()
    return tickets


def test_untraced_scan_builds_no_span(table, monkeypatch):
    """Recorder and profiler off: no span object, no counts, no log entry
    and no clock read of the span API, on any layer of a served scan."""
    calls = {"span": 0, "log": 0, "clock": 0}

    class CountingSpan(trace.Span):
        def __init__(self, *a):
            calls["span"] += 1
            super().__init__(*a)

    def count(key, fn):
        def stub(*a, **kw):
            calls[key] += 1
            return fn(*a, **kw)
        return stub

    monkeypatch.setattr(trace, "Span", CountingSpan)
    monkeypatch.setattr(trace.LOG, "add", count("log", trace.LOG.add))
    monkeypatch.setattr(trace.time, "perf_counter_ns", count("clock", trace.time.perf_counter_ns))
    assert not trace.profiling()
    tickets = serve(table, rate=0.0)
    assert all(t.status == "done" for t in tickets)
    assert calls == {"span": 0, "log": 0, "clock": 0}


def _xplane_events(path):
    """Host-plane events per line: [(name, start_ns, end_ns, stats)]."""
    f = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)[0]
    lines = []
    for p in ProfileData.from_file(f).planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns,
                               dict(e.stats) if e.name.startswith("engine.") else {})
                              for e in ln.events])
    return lines


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_land_in_the_profiler_trace_nested(table, tmp_path):
    """Under the profiler the program's spans share the trace's clock with
    a caller's annotation: engine spans inside `pod.tick`, inside the
    caller's `Pod.tick`; counts and request ids are event metadata."""
    d0 = ops.dispatch_count()
    with jax.profiler.trace(str(tmp_path)):
        tickets = serve(table, rate=0.0, around_tick="Pod.tick")
    launches = ops.dispatch_count() - d0
    assert all(t.status == "done" for t in tickets)
    found = set()
    for events in _xplane_events(str(tmp_path)):
        outer = [e for e in events if e[0] == "Pod.tick"]
        ticks = [e for e in events if e[0] == "pod.tick"]
        for name in ("engine.storage_read", "engine.stack"):
            for e in (e for e in events if e[0] == name):
                tick = next(t for t in ticks if _inside(e, t))
                assert any(_inside(tick, o) for o in outer)
                found.add(name)
        for e in events:
            if e[0] == "engine.storage_read":
                assert e[3]["pages"] >= 1 and e[3]["bytes"] > 0
                assert e[3]["req"] in {t.req_id for t in tickets}
    assert found == {"engine.storage_read", "engine.stack"}

    log = trace.span_log()
    assert not log.active and log.dropped == 0
    names = {n for n, *_ in log.spans}
    assert {"pod.submit", "pod.tick", "pod.queued", "sched.form_batch", "engine.storage_read",
            "engine.stack", "ops.dispatch", "engine.split", "engine.mask",
            "engine.finish", "pod.complete"} <= names
    assert sum(c["n"] for n, _, _, _, c in log.spans if n == "ops.dispatch") == launches
    assert all(t0 <= t1 for _, _, t0, t1, _ in log.spans)


def test_split_span_counts_pages_programs_and_traces(table, tmp_path):
    """Each `engine.split` span carries the pages it cut, the split
    programs it called (one per page length in the bucket) and the new
    traces among them; a second serve of the same scans replays every
    program, so it traces none."""
    serve(table, rate=0.0)
    with jax.profiler.trace(str(tmp_path)):
        tickets = serve(table, rate=0.0)
    assert all(t.status == "done" for t in tickets)
    splits = [c for n, _, _, _, c in trace.span_log().spans if n == "engine.split"]
    assert splits
    for c in splits:
        assert c["pages"] >= c["programs"] >= 1
        assert c["traces"] == 0


def test_mask_span_counts_programs_and_traces(table, tmp_path):
    """Each `engine.mask` span carries the mask programs it called (one a
    row group) and the new traces among them; a second serve of the same
    scans replays every program, so it traces none."""
    serve(table, rate=0.0)
    with jax.profiler.trace(str(tmp_path)):
        tickets = serve(table, rate=0.0)
    assert all(t.status == "done" for t in tickets)
    masks = [c for n, _, _, _, c in trace.span_log().spans if n == "engine.mask"]
    assert masks
    for c in masks:
        assert c["programs"] == 1 and c["traces"] == 0
        assert c["rows"] >= RG_ROWS


def test_mask_program_is_not_a_counted_launch(table):
    """The mask program is no kernel launch: a served scan dispatches as
    many kernels with a predicate as without one, when the predicate's
    columns are decoded either way."""
    pred = Cmp("a", "lt", 3000)
    launches = []
    for p in (pred, None):
        d0 = ops.dispatch_count()
        svc = DatapathService(engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
                              policy=StaticPolicy("raw"))
        tickets = [svc.submit("t", table, ScanPlan("spans", ["a", "b", "d"], p))]
        svc.drain()
        assert tickets[0].status == "done"
        launches.append(ops.dispatch_count() - d0)
    assert launches[0] == launches[1] > 0


def test_span_log_keeps_one_session_and_counts_drops(monkeypatch, tmp_path):
    monkeypatch.setattr(trace, "LOG", trace.SpanLog(capacity=3))
    with jax.profiler.trace(str(tmp_path / "a")):
        for _ in range(5):
            with trace.span("engine.stack", pages=2):
                pass
        trace.interval("pod.queued", 0.002, req=7)
    first = trace.span_log()
    assert not first.active and first.session == 1
    assert len(first.spans) == 3 and first.dropped == 3
    with jax.profiler.trace(str(tmp_path / "b")):
        with trace.span("pod.tick") as sp:
            sp.set(tick=4)
        trace.interval("pod.queued", 0.002, req=7)
        assert trace.span_log().active
    log = trace.span_log()
    assert log.session == 2 and log.dropped == 0 and not log.active
    (tick, queued) = log.spans
    assert tick[0] == "pod.tick" and tick[4] == {"tick": 4}
    assert queued[0] == "pod.queued" and queued[3] - queued[2] == 2_000_000
    # off again: spans neither log nor open a session
    with trace.span("pod.tick") as sp:
        assert sp is None
    trace.interval("pod.queued", 1.0)
    assert trace.span_log().spans == log.spans

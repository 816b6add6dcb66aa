"""Flight-recorder unit tests: the Tracer's sampling/ring/cap mechanics
on a deterministic counter clock, stage attribution against hand-built
trees, Chrome-trace export shape, and the service-level wiring (every
completed request reconstructable, telemetry `trace` section, p99.9 and
known_tenants satellites).

The hypothesis sweep over scheduler/batch/hold/store configurations —
including the traced-vs-untraced bit-identity property — lives in
tests/test_trace_props.py.
"""

import json

import numpy as np
import pytest

from repro.core import BlockCache, Cmp, DatapathEngine, ScanPlan
from repro.datapath import (
    STAGES,
    DatapathService,
    StaticPolicy,
    Telemetry,
    Tracer,
)
from repro.datapath import trace as trace_mod
from repro.lakeformat.reader import LakeReader
from repro.lakeformat.schema import ColumnSchema, TableSchema
from repro.lakeformat.writer import write_table


class FakeClock:
    """Monotonic counter clock: every read advances by `step`."""

    def __init__(self, step: float = 1.0):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def make_tracer(**kw) -> Tracer:
    kw.setdefault("clock", FakeClock())
    return Tracer(**kw)


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    rng = np.random.default_rng(3)
    n = 4096
    cols = {
        "a": np.arange(n, dtype=np.int32),
        "b": rng.standard_normal(n).astype(np.float32),
    }
    schema = TableSchema("smoke", [
        ColumnSchema("a", "int32", "bitpack"),
        ColumnSchema("b", "float32", "plain"),
    ])
    path = str(tmp_path_factory.mktemp("trace") / "smoke.lake")
    write_table(path, schema, cols, row_group_size=1024)
    return LakeReader(path)


def service(**kw):
    kw.setdefault("engine", DatapathEngine(backend="ref", cache=BlockCache(1 << 30)))
    kw.setdefault("policy", StaticPolicy("raw"))
    return DatapathService(**kw)


# ---------------------------------------------------------------------------
# sampling: deterministic fractional accumulator, no RNG
# ---------------------------------------------------------------------------

def test_sampling_is_deterministic_and_exact():
    tr = make_tracer(sample_rate=0.5)
    picks = [tr.start(i, "t", "tbl") is not None for i in range(8)]
    # accumulator: 0.5 (skip), 1.0 (sample), ... — every second request
    assert picks == [False, True] * 4
    assert tr.sampled == 4 and tr.skipped == 4
    # an identical tracer makes identical picks (no hidden RNG state)
    tr2 = make_tracer(sample_rate=0.5)
    assert [tr2.start(i, "t", "tbl") is not None for i in range(8)] == picks


def test_sampling_rate_one_traces_everything():
    tr = make_tracer(sample_rate=1.0)
    assert all(tr.start(i, "t", "tbl") is not None for i in range(5))
    assert tr.skipped == 0


def test_sampling_fractional_rate_hits_expected_count():
    tr = make_tracer(sample_rate=0.25)
    n = sum(tr.start(i, "t", "tbl") is not None for i in range(100))
    assert n == 25  # exact, not approximate: the accumulator never drifts


def test_rate_zero_disables_the_tracer_entirely(table):
    svc = service(trace_sample_rate=0.0)
    assert svc.tracer is None
    svc.submit("t", table, ScanPlan("smoke", ["b"]))
    svc.drain()
    rep = svc.telemetry.trace_report()
    assert rep == {"enabled": False, "completed": 0, "recorded": 0,
                   "requests": []}


# ---------------------------------------------------------------------------
# ring: bounded memory, completed counts keep running
# ---------------------------------------------------------------------------

def test_ring_keeps_last_capacity_traces():
    tr = make_tracer(capacity=3)
    for i in range(7):
        tr.start(i, f"tenant{i % 2}", "tbl")
        tr.finish(i, "done")
    rec = tr.recorder
    assert rec.completed == 7
    assert [rt.req_id for rt in rec.traces()] == [4, 5, 6]
    rep = tr.report()
    assert rep["completed"] == 7 and rep["recorded"] == 3
    assert [r["req_id"] for r in rep["requests"]] == [4, 5, 6]


# ---------------------------------------------------------------------------
# span cap: overflow drops spans but never desyncs the stack
# ---------------------------------------------------------------------------

def test_max_spans_drop_keeps_stack_discipline():
    tr = make_tracer(max_spans=3)  # root + 2 children
    rt = tr.start(1, "t", "tbl")
    tr.begin(rt, "slice_dispatch")
    tr.begin(rt, "fetch")          # 3rd span: at cap from here on
    tr.begin(rt, "decode_launch")  # dropped
    tr.begin(rt, "inner")          # dropped
    tr.end(rt)                     # matches dropped "inner"
    tr.end(rt)                     # matches dropped "decode_launch"
    tr.end(rt, name="fetch")       # closes the REAL fetch span
    tr.end(rt, name="slice_dispatch")
    tr.finish(1, "done")
    sm = rt.summary
    assert rt.dropped_spans == 2 and rt.drop_depth == 0
    assert sm["spans"] == 3 and sm["dropped_spans"] == 2
    (sd,) = rt.root["children"]
    assert sd["name"] == "slice_dispatch" and sd["t1"] is not None
    (fe,) = sd["children"]
    assert fe["name"] == "fetch" and fe["children"] == []


def test_named_end_closes_dangling_children():
    """An exception between begin(fetch) and its end leaves fetch open;
    the slice's named end must close it (at the same instant) instead of
    mis-attributing the rest of the run to fetch."""
    tr = make_tracer()
    rt = tr.start(1, "t", "tbl")
    tr.begin(rt, "slice_dispatch")
    tr.begin(rt, "fetch")
    # error path: no end for fetch
    tr.end(rt, name="slice_dispatch")
    assert len(rt.stack) == 1  # back at the root
    (sd,) = rt.root["children"]
    (fe,) = sd["children"]
    assert fe["t1"] == sd["t1"]  # closed together, zero residual width
    tr.finish(1, "error")
    assert rt.summary["status"] == "error"


def test_unmatched_end_never_pops_the_root():
    tr = make_tracer()
    rt = tr.start(1, "t", "tbl")
    tr.end(rt)  # nothing open: must be a no-op
    assert rt.stack == [rt.root]
    tr.finish(1, "done")
    assert rt.root["t1"] >= rt.root["t0"]


# ---------------------------------------------------------------------------
# wait-state machine
# ---------------------------------------------------------------------------

def test_wait_extends_same_kind_and_switches_kinds():
    tr = make_tracer()
    rt = tr.start(1, "t", "tbl")
    tr.wait(rt, "hold_window")
    tr.wait(rt, "hold_window")
    tr.wait(rt, "hold_window")
    tr.wait(rt, "wfq_wait")  # kind switch closes the hold span
    tr.wait(rt, "wfq_wait")
    tr.end_wait(rt)
    hold, wfq = rt.root["children"]
    assert hold["name"] == "hold_window" and hold["args"]["ticks"] == 3
    assert wfq["name"] == "wfq_wait" and wfq["args"]["ticks"] == 2
    assert hold["t1"] <= wfq["t0"]  # waits never overlap
    assert rt.wait_kind is None
    tr.finish(1, "done")


def test_finish_closes_an_open_wait():
    tr = make_tracer()
    rt = tr.start(1, "t", "tbl")
    tr.wait(rt, "wfq_wait")
    tr.finish(1, "cancelled")
    (w,) = rt.root["children"]
    assert w["t1"] is not None and rt.summary["status"] == "cancelled"


# ---------------------------------------------------------------------------
# stage attribution
# ---------------------------------------------------------------------------

def test_attribution_maps_spans_and_never_double_bills():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    rt = tr.start(1, "t", "tbl")
    tr.begin(rt, "slice_dispatch")      # unmapped: recursed, not billed
    tr.begin(rt, "engine.storage_read")
    tr.event(rt, "store_hit")           # child of a mapped span: ignored
    tr.end(rt, name="engine.storage_read")
    tr.begin(rt, "engine.decode")
    tr.begin(rt, "ops.dispatch")        # child of a mapped span: ignored
    tr.end(rt, name="ops.dispatch")
    tr.end(rt, name="engine.decode")
    tr.begin(rt, "engine.mask")
    tr.end(rt, name="engine.mask")
    tr.end(rt, name="slice_dispatch")
    tr.finish(1, "done")
    sm = rt.summary
    assert set(sm["stages_s"]) == set(STAGES)
    # FakeClock ticks 1 s a read: each mapped span is its own two reads
    # apart, plus one read per span nested in it
    assert sm["stages_s"]["fetch"] == 2.0
    assert sm["stages_s"]["decode"] == 3.0  # engine.decode -> decode
    assert sm["stages_s"]["filter"] == 1.0
    assert sm["stages_s"]["admission"] == 0.0
    assert sm["attributed_s"] == sum(sm["stages_s"].values())
    assert sm["attributed_s"] <= sm["wall_s"] + 1e-12
    assert not any(k.endswith("_pct") for k in sm)


def test_report_rolls_up_by_tenant_with_paper_anchor():
    tr = make_tracer()
    for i, tenant in enumerate(("alice", "alice", "bob")):
        rt = tr.start(i, tenant, "tbl")
        tr.begin(rt, "engine.decode")
        tr.end(rt, name="engine.decode")
        tr.finish(i, "done")
    rep = tr.report()
    # host seconds per stage; no percentages beside the paper's device split
    assert not [k for k in rep if k.endswith("_pct")]
    assert set(rep["by_tenant"]) == {"alice", "bob"}
    assert rep["by_tenant"]["alice"]["n"] == 2
    assert rep["by_tenant"]["alice"]["stage_s"]["decode"] == 2.0
    assert rep["by_tenant"]["bob"]["stage_s"]["decode"] == 1.0
    assert rep["stage_s"]["decode"] == 3.0
    for bt in rep["by_tenant"].values():
        assert list(bt) == ["n", "wall_s", "stage_s"]
        assert sum(bt["stage_s"].values()) <= bt["wall_s"] + 1e-9
    # fleet wall is the sum of per-tenant walls
    assert abs(rep["wall_s"]
               - sum(bt["wall_s"] for bt in rep["by_tenant"].values())) < 1e-9


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_shape_and_determinism(tmp_path):
    tr = make_tracer()
    for i, tenant in enumerate(("alice", "bob")):
        rt = tr.start(i, tenant, "tbl")
        tr.begin(rt, "slice_dispatch")
        tr.event(rt, "store_hit", tier="decoded")
        tr.end(rt, name="slice_dispatch")
        tr.finish(i, "done")
    doc = tr.recorder.to_chrome_trace()
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    spans = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert {e["args"]["name"] for e in meta if e["name"] == "process_name"} \
        == {"alice", "bob"}
    assert all(e["dur"] > 0 and e["ts"] >= 0 for e in spans)
    assert all(e["s"] == "t" for e in instants)
    assert any(e["name"] == "store_hit" for e in instants)
    # export is deterministic and valid JSON
    assert json.dumps(doc, sort_keys=True) == json.dumps(
        tr.recorder.to_chrome_trace(), sort_keys=True)
    path = tmp_path / "trace.json"
    n = tr.recorder.save_chrome_trace(str(path))
    assert n == len(events)
    assert json.loads(path.read_text())["traceEvents"] == json.loads(
        json.dumps(events))


def test_chrome_trace_empty_ring():
    tr = make_tracer()
    assert tr.recorder.to_chrome_trace() == {"displayTimeUnit": "ms",
                                             "traceEvents": []}


# ---------------------------------------------------------------------------
# module-level slice context
# ---------------------------------------------------------------------------

def test_module_hooks_noop_without_slice_context():
    assert trace_mod._CUR is None and not trace_mod.profiling()
    # must not raise, must not allocate a trace anywhere
    with trace_mod.span("engine.storage_read") as sp:
        trace_mod.event("store_hit")
    assert sp is None
    assert trace_mod.span("engine.decode", pages=1) is trace_mod.NULL


def test_module_hooks_record_into_published_slice():
    tr = make_tracer()
    rt = tr.start(1, "t", "tbl")
    trace_mod.set_slice(tr, rt, 1)
    try:
        with trace_mod.span("engine.storage_read", rg=0) as sp:
            trace_mod.event("store_hit", tier="encoded")
            sp.set(bytes=10)
    finally:
        trace_mod.set_slice(None, None)
    (fe,) = rt.root["children"]
    assert fe["name"] == "engine.storage_read"
    assert fe["args"] == {"rg": 0, "bytes": 10}
    assert fe["children"][0]["name"] == "store_hit"
    tr.finish(1, "done")


# ---------------------------------------------------------------------------
# service integration: the full lifecycle is reconstructable
# ---------------------------------------------------------------------------

def test_service_traces_full_lifecycle(table):
    svc = service(hold_ticks=2, tick_bytes=1024 * 8, trace_capacity=8)
    svc.submit("alice", table, ScanPlan("smoke", ["b"],
                                        Cmp("a", "lt", 3000)))
    svc.submit("bob", table, ScanPlan("smoke", ["a", "b"]))
    svc.drain()
    rep = svc.telemetry.trace_report()
    assert rep["enabled"] and rep["completed"] == 2 == rep["recorded"]
    names_by_req = {}
    for rt in svc.tracer.recorder.traces():
        seen = set()
        stack = [rt.root]
        while stack:
            sp = stack.pop()
            seen.add(sp["name"])
            assert sp["t1"] is not None
            stack.extend(sp["children"])
        names_by_req[rt.req_id] = seen
        sm = rt.summary
        assert sm["status"] == "done"
        assert sm["attributed_s"] <= sm["wall_s"] + 1e-9
        assert sm["done_tick"] >= sm["submitted_tick"]
    for names in names_by_req.values():
        assert {"request", "admission", "slice_dispatch",
                "engine.decode"} <= names
    # the sliced multi-tick request waited in the WFQ queue at least once
    assert any("wfq_wait" in names for names in names_by_req.values())


def test_service_trace_survives_snapshot(table):
    svc = service(trace_capacity=4)
    svc.submit("t", table, ScanPlan("smoke", ["b"]))
    svc.drain()
    snap = svc.telemetry.snapshot()
    assert snap["trace"]["recorded"] == 1
    assert "tick_p999_s" in snap
    assert json.dumps(snap["trace"], sort_keys=True)  # JSON-serializable


# ---------------------------------------------------------------------------
# telemetry satellites: known_tenants union, p99.9 keys
# ---------------------------------------------------------------------------

def test_known_tenants_unions_actual_and_recon_seconds():
    tm = Telemetry()
    tm.observe_actual_cost("only-actual", 0.5)
    tm.observe_recon("only-recon", -0.1)
    assert "only-actual" in tm.known_tenants()
    assert "only-recon" in tm.known_tenants()
    cost = tm.cost_report()
    assert cost["only-actual"]["actual_s"] == 0.5
    assert cost["only-recon"]["recon_s"] == -0.1


def test_p999_in_latency_fairness_and_snapshot():
    tm = Telemetry()
    for i in range(1000):
        tm.observe_latency("t", float(i))
        tm.observe_tick(float(i) / 10.0)
    lat = tm.tenant_latency("t")
    assert lat["p999_s"] >= lat["p99_s"] >= lat["p50_s"]
    # nearest-rank half-up over 1000 samples: rank floor(0.999*999+0.5)=998
    assert lat["p999_s"] == 998.0
    fair = tm.fairness()
    assert fair["tenant_latency_p999_s"]["t"] == 998.0
    snap = tm.snapshot()
    assert snap["tick_p999_s"] >= snap["tick_p99_s"]

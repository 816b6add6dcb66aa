"""Property suite for the flight recorder's span invariants (DESIGN.md
§13), swept across the service's configuration space: sequential vs
batched dispatch, fifo vs wfq, hold windows, slice splits (tick_bytes)
and store-hit paths (preloaded / prefiltered repeats).

Invariants, per completed request:
  1. the span tree is WELL-FORMED — every span has t0 <= t1 and every
     child's interval nests inside its parent's (within eps);
  2. stage attribution never over-bills — attributed_s <= wall_s + eps,
     because mapped spans' children are not recursed and wait spans are
     closed before slice dispatch;
  3. every admitted request is reconstructable — root + admission spans,
     terminal status, done_tick >= submitted_tick;
  4. the Chrome-trace export is deterministic — two exports of the same
     ring serialize to byte-identical JSON, and every event carries
     JSON-safe key-sorted args;
  5. tracing never perturbs results — scan output (count, columns, mask)
     is bit-identical between a traced service and trace_sample_rate=0,
     with the JAX profiler collecting or not; and under the profiler the
     span log's spans nest on each thread as the code nests them.

Fixed cases always run; the hypothesis sweep (skipped without
`hypothesis`, same policy as tests/test_batch_decode.py) drives random
configuration mixes over the same invariants.
"""

import contextlib
import json
import tempfile

import jax
import numpy as np
import pytest

from repro.core import BlockCache, Cmp, DatapathEngine, ScanPlan
from repro.datapath import DatapathService, StaticPolicy, trace
from repro.lakeformat.reader import LakeReader
from repro.lakeformat.schema import ColumnSchema, TableSchema
from repro.lakeformat.writer import write_table

EPS = 1e-9
RG_ROWS = 900  # ragged: not a PACK_BLOCK multiple


@pytest.fixture(scope="module")
def mixed(tmp_path_factory):
    """Small mixed-encoding table, 5 ragged row groups — enough for
    multi-slice dispatch under a tight tick_bytes."""
    rng = np.random.default_rng(11)
    n = 4 * RG_ROWS + 420
    cols = {
        "ts": np.arange(n, dtype=np.int32),                       # delta
        "flag": np.repeat(rng.integers(0, 4, n // 60 + 1),
                          60)[:n].astype(np.int32),               # rle
        "price": rng.standard_normal(n).astype(np.float32),       # plain
        "key": rng.integers(0, 1 << 11, n).astype(np.int32),      # bitpack
    }
    schema = TableSchema("mixed", [
        ColumnSchema("ts", "int32", "delta"),
        ColumnSchema("flag", "int32", "rle"),
        ColumnSchema("price", "float32", "plain"),
        ColumnSchema("key", "int32", "bitpack"),
    ])
    path = str(tmp_path_factory.mktemp("traceprops") / "mixed.lake")
    write_table(path, schema, cols, row_group_size=RG_ROWS)
    return LakeReader(path)


PLANS = [
    ScanPlan("mixed", ["price"], Cmp("ts", "lt", 2 * RG_ROWS)),
    ScanPlan("mixed", ["price", "flag"], Cmp("key", "lt", 700)),
    ScanPlan("mixed", ["ts", "price"]),
    ScanPlan("mixed", ["flag"], Cmp("flag", "between", (1, 2))),
]


def build(c, tracing: bool) -> DatapathService:
    return DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
        policy=StaticPolicy(c["offload"]),
        scheduler=c["scheduler"],
        batch_decode=c["batch_decode"],
        hold_ticks=c["hold_ticks"],
        tick_bytes=c["tick_bytes"],
        trace_sample_rate=1.0 if tracing else 0.0,
        trace_capacity=16,
    )


def run_workload(svc, c, reader):
    tickets = []
    for i in range(c["n_reqs"]):
        tickets.append(svc.submit(f"tenant{i % 2}", reader,
                                  PLANS[i % len(PLANS)]))
        if c["hold_ticks"] and i == 0:
            svc.tick()  # let the first request enter its hold window
    svc.drain()
    if c["repeat"]:  # second pass hits the store (preloaded/prefiltered)
        tickets.append(svc.submit("tenant0", reader, PLANS[0]))
        svc.drain()
    return tickets


def check_tree(sp, lo, hi):
    """Recursive well-formedness: t0 <= t1, interval within [lo, hi]."""
    assert sp["t1"] is not None, sp["name"]
    assert sp["t0"] <= sp["t1"] + EPS, sp["name"]
    assert sp["t0"] >= lo - EPS and sp["t1"] <= hi + EPS, sp["name"]
    for c in sp["children"]:
        check_tree(c, sp["t0"], sp["t1"])


def check_span_invariants(svc, tickets):
    """Invariants 1-4 over a drained traced service's flight recorder."""
    traces = svc.tracer.recorder.traces()
    # (3) every admitted request is reconstructable
    assert len(traces) == len(tickets)
    assert {rt.req_id for rt in traces} == {t.req_id for t in tickets}
    for rt in traces:
        root = rt.root
        # (1) well-formed tree
        check_tree(root, root["t0"], root["t1"])
        assert root["name"] == "request"
        assert root["children"][0]["name"] == "admission"
        sm = rt.summary
        assert sm["status"] == "done"
        assert sm["done_tick"] >= sm["submitted_tick"]
        # (2) attribution never over-bills the wall
        assert sm["attributed_s"] <= sm["wall_s"] + EPS
        assert sum(sm["stages_s"].values()) == pytest.approx(sm["attributed_s"])
        assert all(v >= 0.0 for v in sm["stages_s"].values())
    # (4) deterministic export, JSON-safe key-sorted args
    doc = svc.tracer.recorder.to_chrome_trace()
    blob = json.dumps(doc, sort_keys=True)
    assert blob == json.dumps(svc.tracer.recorder.to_chrome_trace(),
                              sort_keys=True)
    for e in json.loads(blob)["traceEvents"]:
        assert list(e["args"]) == sorted(e["args"])


@contextlib.contextmanager
def profiler(on: bool):
    """The JAX profiler collecting (into a throwaway directory) or not."""
    if not on:
        yield
        return
    with tempfile.TemporaryDirectory() as d, jax.profiler.trace(d):
        yield


def check_log_nesting():
    """Spans of one thread in the span log are disjoint or nested
    (`pod.queued` is a wait logged after the fact, so it is left out)."""
    log = trace.span_log()
    assert not log.active and log.dropped == 0 and log.spans
    by_thread = {}
    for name, tid, t0, t1, _ in log.spans:
        if name != "pod.queued":
            by_thread.setdefault(tid, []).append((t0, -t1, name))
    for spans in by_thread.values():
        open_ends = []
        for t0, neg_t1, name in sorted(spans):
            while open_ends and open_ends[-1] <= t0:
                open_ends.pop()
            assert not open_ends or -neg_t1 <= open_ends[-1], name
            open_ends.append(-neg_t1)


def check_bit_identity(traced, plain):
    """Invariant 5: identical tickets from traced and untraced runs."""
    assert len(traced) == len(plain)
    for a, b in zip(traced, plain):
        ra, rb = a.result, b.result
        assert a.status == b.status == "done"
        assert int(ra.count) == int(rb.count)
        assert set(ra.columns) == set(rb.columns)
        for name in ra.columns:
            np.testing.assert_array_equal(
                np.asarray(ra.columns[name]), np.asarray(rb.columns[name]))
        if ra.mask is not None or rb.mask is not None:
            np.testing.assert_array_equal(
                np.asarray(ra.mask), np.asarray(rb.mask))


# ---------------------------------------------------------------------------
# fixed sweep — always runs; one case per scheduler/dispatch/hold/store axis
# ---------------------------------------------------------------------------

FIXED_CASES = [
    dict(scheduler="fifo", batch_decode=False, hold_ticks=0, tick_bytes=None,
         offload="raw", n_reqs=2, repeat=False),
    dict(scheduler="wfq", batch_decode=True, hold_ticks=0, tick_bytes=None,
         offload="raw", n_reqs=3, repeat=False),
    dict(scheduler="wfq", batch_decode=True, hold_ticks=2,
         tick_bytes=RG_ROWS * 4 * 2, offload="raw", n_reqs=4, repeat=False),
    dict(scheduler="wfq", batch_decode=False, hold_ticks=2,
         tick_bytes=RG_ROWS * 4 * 2, offload="preloaded", n_reqs=2,
         repeat=True),
    dict(scheduler="fifo", batch_decode=True, hold_ticks=0, tick_bytes=None,
         offload="prefiltered", n_reqs=2, repeat=True),
]

IDS = ["seq-fifo", "batch-wfq", "sliced-hold", "preloaded-repeat",
       "prefiltered-repeat"]


@pytest.mark.parametrize("c", FIXED_CASES, ids=IDS)
def test_span_invariants_fixed(mixed, c):
    svc = build(c, tracing=True)
    tickets = run_workload(svc, c, mixed)
    check_span_invariants(svc, tickets)


@pytest.mark.parametrize("c", FIXED_CASES, ids=IDS)
def test_bit_identity_fixed(mixed, c):
    check_bit_identity(run_workload(build(c, tracing=True), c, mixed),
                       run_workload(build(c, tracing=False), c, mixed))


@pytest.mark.parametrize("c", FIXED_CASES, ids=IDS)
def test_bit_identity_under_profiler_fixed(mixed, c):
    with profiler(True):
        traced = run_workload(build(c, tracing=True), c, mixed)
    check_log_nesting()
    check_bit_identity(traced, run_workload(build(c, tracing=False), c, mixed))


def test_ring_and_sampler_accounting(mixed):
    for n_reqs, rate in [(1, 1.0), (4, 0.5), (5, 0.5), (5, 1.0)]:
        svc = DatapathService(
            engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
            policy=StaticPolicy("raw"),
            trace_sample_rate=rate, trace_capacity=2,
        )
        for i in range(n_reqs):
            svc.submit("t", mixed, PLANS[i % len(PLANS)])
        svc.drain()
        tr = svc.tracer
        expect_sampled = int(n_reqs * rate)  # exact: fractional accumulator
        assert tr.sampled == expect_sampled
        assert tr.sampled + tr.skipped == n_reqs
        rep = tr.report()
        assert rep["completed"] == expect_sampled
        assert rep["recorded"] == min(2, expect_sampled)  # ring capacity
        assert rep["live"] == 0


# ---------------------------------------------------------------------------
# hypothesis sweep
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    cfg = st.fixed_dictionaries({
        "scheduler": st.sampled_from(["fifo", "wfq"]),
        "batch_decode": st.booleans(),
        "hold_ticks": st.sampled_from([0, 2]),
        "tick_bytes": st.sampled_from([None, RG_ROWS * 4 * 2]),
        "offload": st.sampled_from(["raw", "preloaded", "prefiltered"]),
        "n_reqs": st.integers(1, 4),
        "repeat": st.booleans(),  # re-run plan 0 => store-hit path
        "profiler": st.booleans(),  # the JAX profiler collecting
    })

    class TestTraceSweep:
        @given(cfg)
        @settings(deadline=None, max_examples=15)
        def test_span_invariants(self, mixed, c):
            svc = build(c, tracing=True)
            tickets = run_workload(svc, c, mixed)
            check_span_invariants(svc, tickets)

        @given(cfg)
        @settings(deadline=None, max_examples=15)
        def test_bit_identity(self, mixed, c):
            with profiler(c["profiler"]):
                traced = run_workload(build(c, tracing=True), c, mixed)
            if c["profiler"]:
                check_log_nesting()
            check_bit_identity(
                traced, run_workload(build(c, tracing=False), c, mixed))

"""The metrics that read the program's span log: each reader against a
hand-built log, the cases in which each reads nothing, a traced run of
each cell on the CPU that reports them, and span names that stay clear
of the names the trace reduction labels idle gaps by."""

import glob
import os
import re
import time

import pytest

from bench import harness, spec, trace_reduce
from bench.tests.test_bench_harness import small
from repro.datapath import trace

SPAN_METRICS = ("storage_read_us_per_page", "decode_host_us_per_page", "tick_sched_ms",
                "scan_wait_ms")
MS = 1_000_000  # ns


class Log:
    def __init__(self, spans, active=False, dropped=0):
        self.spans, self.active, self.dropped = spans, active, dropped


# thread 1 ticks; thread 2 is a stream.  Tick one: 10 ms, of which
# engine spans cover 3 + 3 + 0.5 ms (nested ones counted once); tick
# two: 2 ms, 0.5 of them an engine span, and a span of another thread
# that overlaps it counts for nothing.
SPANS = [
    ("pod.submit", 2, 0, MS // 2, {"tick": 0}),
    ("pod.tick", 1, 1 * MS, 11 * MS, {"tick": 1}),
    ("sched.form_batch", 1, 1 * MS, 2 * MS, {}),
    ("pod.queued", 1, 0, 4 * MS, {"req": 0}),
    ("engine.prepare", 1, 2 * MS, 5 * MS, {}),
    ("engine.storage_read", 1, 2 * MS + MS // 2, 4 * MS + MS // 2, {"pages": 4, "bytes": 64}),
    ("engine.decode", 1, 5 * MS, 8 * MS, {"pages": 4}),
    ("engine.stack", 1, 5 * MS, 6 * MS, {"pages": 4}),
    ("ops.dispatch", 1, 6 * MS, 7 * MS, {"kernel": "bitunpack_batch", "n": 1}),
    ("engine.split", 1, 7 * MS, 8 * MS, {"pages": 4}),
    ("sched.reconcile", 1, 8 * MS, 9 * MS, {}),
    ("engine.finish", 1, 9 * MS + MS // 2, 10 * MS, {"rows": 3}),
    ("pod.tick", 1, 20 * MS, 22 * MS, {"tick": 2}),
    ("pod.queued", 1, 14 * MS, 20 * MS, {"req": 1}),
    ("engine.storage_read", 1, 20 * MS + MS // 2, 21 * MS, {"pages": 1, "bytes": 16}),
    ("engine.mask", 2, 20 * MS, 22 * MS, {"rows": 1024}),
]
EXPECT = {
    "storage_read_us_per_page": (2.0 + 0.5) * 1e3 / 5,
    "decode_host_us_per_page": 3.0 * 1e3 / 4,
    "tick_sched_ms": ((10 - 6.5) + (2 - 0.5)) / 2,
    "scan_wait_ms": (4 + 6) / 2,
}


def readings(traced=True, dispatches=1):
    return harness.Readings(queries=2, window={}, trace=object() if traced else None,
                            traced={"dispatches": dispatches} if traced else None, peaks=None)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_reads_a_hand_built_log(name, monkeypatch):
    monkeypatch.setattr(trace, "span_log", lambda: Log(SPANS))
    got = spec.metric_reader(name).read(readings())
    assert got == pytest.approx(EXPECT[name], rel=1e-12)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_reads_nothing_it_cannot_trust(name, monkeypatch):
    read = spec.metric_reader(name).read
    monkeypatch.setattr(trace, "span_log", lambda: Log(SPANS))
    assert read(readings(traced=False)) is None  # an untraced run
    assert read(readings(dispatches=2)) is None  # stale: another session's launches
    for log in (Log([]), Log(SPANS, dropped=1), Log(SPANS, active=True)):
        monkeypatch.setattr(trace, "span_log", lambda log=log: log)
        assert read(readings()) is None
    monkeypatch.delattr(trace, "span_log")  # a program that keeps no span log
    assert read(readings()) is None


def test_no_program_span_takes_a_benchmark_name():
    """`bench/trace_reduce.py` labels idle gaps by the benchmark's own host
    spans; a program span of the same name would move `breakdown`."""
    src = os.path.join(spec.ROOT, "src", "repro")
    names = {"ops.dispatch"}
    for path in glob.glob(os.path.join(src, "**", "*.py"), recursive=True):
        with open(path) as f:
            names |= set(re.findall(r'\b_?(?:span|interval)\(\s*"([^"]+)"', f.read()))
    assert {"pod.tick", "pod.submit", "pod.queued", "engine.storage_read"} <= names
    reserved = {trace_reduce.WINDOW_SPAN, trace_reduce.WAIT_SPAN, *trace_reduce.HOST_SPANS}
    for n in names:
        assert n not in reserved and not n.startswith(trace_reduce.QUERY_PREFIX), n
        assert re.fullmatch(r"(pod|sched|engine|ops)\.[a-z_]+", n), n


@pytest.mark.parametrize("cell_name", ["stream.power", "cached.tp4"])
def test_traced_run_reports_the_span_metrics(cell_name):
    bench = spec.load()
    cell = spec.workload(bench, cell_name)
    res = harness.run(cell, 2**31 + 11, 2.0, True, time.perf_counter(), bench,
                      log=lambda s: None, cfg=small(spec.config(cell["config"])),
                      compile_cache=False)
    assert res["correct"], res["checks"]
    wanted = {m["name"] for m in spec.metrics_for(bench, cell_name, "per_layer")
              if m["name"] in SPAN_METRICS}
    assert wanted == ({"tick_sched_ms", "scan_wait_ms"} if cell_name == "cached.tp4"
                      else set(SPAN_METRICS))
    for name in wanted:
        assert res["metrics"][name]["value"] > 0, name
    assert not {n for n, *_ in trace.span_log().spans} & set(trace_reduce.HOST_SPANS)

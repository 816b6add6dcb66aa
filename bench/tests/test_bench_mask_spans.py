"""`mask_host_us_per_rg` against hand-built span logs: a span per row
group (no `rgs` count) covers one row group, a span with an `rgs` count
covers that many, and the reader reads nothing it cannot trust."""

import pytest

from bench import harness, spec
from repro.datapath import trace

MS = 1_000_000  # ns
NAME = "mask_host_us_per_rg"


class Log:
    def __init__(self, spans, active=False, dropped=0):
        self.spans, self.active, self.dropped = spans, active, dropped


BASE = [
    ("pod.tick", 1, 0, 20 * MS, {"tick": 1}),
    ("ops.dispatch", 1, 1 * MS, 2 * MS, {"kernel": "bitunpack_batch", "n": 1}),
    ("engine.finalize", 1, 3 * MS, 19 * MS, {"rgs": 6}),
]
# one span per row group, as the program opens them: 3 spans, 3 row groups
PER_RG = [("engine.mask", 1, (4 + i) * MS, (4 + i) * MS + MS // 2, {"rg": i, "rows": 1024})
          for i in range(3)]
# a span over several row groups: 4 ms over 5 of them
WIDE = [("engine.mask", 1, 10 * MS, 14 * MS, {"rgs": 5, "rows": 5 * 1024})]
CASES = {
    "per_rg": (PER_RG, 1.5 * 1e3 / 3),
    "wide": (WIDE, 4.0 * 1e3 / 5),
    "both": (PER_RG + WIDE, (1.5 + 4.0) * 1e3 / (3 + 5)),
}


def readings(traced=True, dispatches=1):
    return harness.Readings(queries=2, window={}, trace=object() if traced else None,
                            traced={"dispatches": dispatches} if traced else None, peaks=None)


@pytest.mark.parametrize("case", sorted(CASES))
def test_reads_both_kinds_of_mask_span(case, monkeypatch):
    spans, want = CASES[case]
    monkeypatch.setattr(trace, "span_log", lambda: Log(BASE + spans))
    got = spec.metric_reader(NAME).read(readings())
    assert got == pytest.approx(want, rel=1e-12)


def test_reads_nothing_it_cannot_trust(monkeypatch):
    read = spec.metric_reader(NAME).read
    monkeypatch.setattr(trace, "span_log", lambda: Log(BASE))
    assert read(readings()) is None  # no mask span in the window
    monkeypatch.setattr(trace, "span_log", lambda: Log(BASE + PER_RG))
    assert read(readings(traced=False)) is None
    assert read(readings(dispatches=2)) is None
    for log in (Log(BASE + PER_RG, dropped=1), Log(BASE + PER_RG, active=True)):
        monkeypatch.setattr(trace, "span_log", lambda log=log: log)
        assert read(readings()) is None
    monkeypatch.delattr(trace, "span_log")
    assert read(readings()) is None


def test_the_benchmark_lists_it_in_both_cells():
    bench = spec.load()
    for cell in ("stream.power", "cached.tp4"):
        assert NAME in {m["name"] for m in spec.metrics_for(bench, cell, "per_layer")}

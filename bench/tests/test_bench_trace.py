"""The trace reduction: busy union, idle share, per-op and per-program
device time, and idle gaps named by the host span that covers them; on
hand-made planes and on a short trace recorded on one TPU v5e."""

import glob
import json
import os
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def decode_programs():
    with open(os.path.join(os.path.dirname(DATA), "..", "decode_ops.json")) as f:
        return json.load(f)["decode_programs"]


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start, stats=list(stats.items()))


def line(name, *events):
    return NS(name=name, events=list(events))


def plane(name, *lines):
    return NS(name=name, lines=list(lines))


def planes():
    host = plane("/host:CPU",
                 line("main", ev("bench.traced", 100, 1100)),
                 line("tick", ev("Pod.tick", 100, 400), ev("Pod.submit", 600, 650)),
                 line("stream0", ev("query.q1", 300, 1000), ev("scan.wait", 300, 700)))
    dev = plane("/device:TPU:0",
                line("XLA Ops",
                     ev("%fusion.1 = s32[8] fusion(s32[8] %x)", 50, 250),
                     ev("%fusion.2 = s32[8] fusion(s32[8] %y)", 200, 300),
                     ev("%copy.3 = f32[6] copy(f32[6] %z)", 500, 550),
                     ev("%fusion.1 = s32[8] fusion(s32[4] %w)", 900, 1200)),
                line("XLA Modules", ev("jit_bitunpack_pallas(123)", 50, 300),
                     ev("jit__take(5)", 500, 550), ev("jit_dict_decode_batch_pallas(7)", 900, 1200)))
    return [host, dev, plane("/device:TPU:0 SparseCore 0")]


def test_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.gaps_of([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.overlap([(0, 2), (5, 8)], 1, 6) == 2


def test_reduction_of_hand_made_planes():
    r = tr.reduce(planes())
    assert r.window == (100, 1100) and r.n_devices == 1
    # ops clipped to the window: [100, 300) and [500, 550) and [900, 1100)
    assert r.busy_ns == 200 + 50 + 200
    assert r.ops == {"jit_bitunpack_pallas:fusion.1": 150, "jit_bitunpack_pallas:fusion.2": 100,
                     "jit__take:copy.3": 50, "jit_dict_decode_batch_pallas:fusion.1": 200}
    assert r.modules == {"jit_bitunpack_pallas": 200, "jit__take": 50,
                         "jit_dict_decode_batch_pallas": 200}
    assert r.module_time_s(decode_programs()) == pytest.approx(400e-9)
    # gaps [550, 900) and [300, 500): q1's own host work is [700, 1000),
    # its span less the scan it waits for; Pod.tick covers [300, 400)
    assert r.gaps[0] == ("q1.host", 350.0)
    assert r.gaps[1][1] == 200.0 and r.gaps[1][0] == "Pod.tick"


def test_gap_without_a_host_span():
    p = planes()
    p[0] = plane("/host:CPU", line("main", ev("bench.traced", 100, 1100)))
    assert {g[0] for g in tr.reduce(p).gaps} == {"no host span"}


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([plane("/host:CPU", line("main"))])


def test_recorded_chip_trace():
    """A 0.25 s trace of `stream.power` at generator scale 0.5 (300,000
    lineitem rows), recorded on one TPU v5e by `harness.run` with
    `TRACE_SECONDS = 0.25`, with the harness's own host spans."""
    path, = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    r = tr.load(path)
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    assert sum(r.ops.values()) * 1e-9 >= r.busy_s  # ops may overlap, never less
    assert 0 < r.module_time_s(decode_programs()) < r.busy_s
    assert r.module_time_s(["^jit__take$"]) > r.module_time_s(decode_programs())
    assert r.gaps and all(ns > 0 for _, ns in r.gaps)
    assert r.gaps == sorted(r.gaps, key=lambda g: -g[1])

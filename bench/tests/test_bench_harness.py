"""The harness end to end on the CPU at a small size: the client shim,
the stream threads and the reference agree for both Pod configurations;
the bfloat16 control and each fault the cells can have come out as not
correct.  The chip check of `bench/run.py` is skipped by calling the
harness directly."""

import threading
import time

import numpy as np
import pytest

from bench import control, datagen, harness, reference, spec

SECONDS = 1.5


def small(cfg: dict) -> dict:
    """The configuration at 30,000 lineitem rows in 8,192-row groups."""
    return dict(cfg, data=dict(cfg["data"], generator_sf=0.05, row_group_size=8192))


def run(cell_name: str, seed: int, **kw) -> dict:
    bench = spec.load()
    cell = spec.workload(bench, cell_name)
    return harness.run(cell, seed, SECONDS, False, time.perf_counter(), bench,
                       log=lambda s: None, cfg=small(spec.config(cell["config"])),
                       compile_cache=False, **kw)


@pytest.mark.parametrize("cell", ["stream.power", "cached.tp4"])
def test_cell_is_correct(cell):
    res = run(cell, 2**32 + 17)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "queries_per_s", "query_p50_s", "query_p90_s"}
    assert list(res)[-1] == "checks"


def test_generator_matches_the_programs():
    from repro.core import tpch

    ours = datagen.base_tables(0.05, 5)
    theirs = tpch.gen_tables(0.05, 5)
    for table in ("lineitem", "orders", "part"):
        for col, v in theirs[table].items():
            mine = ours.table(table)[col]
            if isinstance(v, list):
                v = np.asarray(datagen.STRINGS[col])[mine].tolist() == v
                assert v, col
            else:
                np.testing.assert_array_equal(mine, v, err_msg=col)


def test_seeds_permute_the_same_row_groups():
    """Every seed has the same pages in another order; the short last row
    group stays last, and orders and part stay as they are."""
    a, b = datagen.gen_tables(0.05, 1, 8192), datagen.gen_tables(0.05, 2, 8192)
    full = 3 * 8192  # 30,000 rows: three full row groups and a short one
    for col, va in a.lineitem.items():
        vb = b.lineitem[col]
        assert sorted(va[:full].reshape(3, 8192).tolist()) == \
            sorted(vb[:full].reshape(3, 8192).tolist()), col
        np.testing.assert_array_equal(va[full:], vb[full:])
    assert not np.array_equal(a.lineitem["l_orderkey"], b.lineitem["l_orderkey"])
    for table in ("orders", "part"):
        for col, va in a.table(table).items():
            np.testing.assert_array_equal(va, b.table(table)[col])


def _control_queries(tables):
    """The reference in bfloat16, put in the program's place."""
    ctl = reference.Reference(tables, precision="bfloat16")

    def make(name):
        def q(client, readers, **params):
            return {k: v for k, v in ctl.answer(name, params).items() if k != "per_supplier"}
        return q
    return {n: make(n) for n in ("q1", "q6", "q12", "q14", "q15", "q19")}


def test_control_is_not_correct():
    seed = 31
    cfg = small(spec.config("tpch-sf1-stream"))
    data = cfg["data"]
    tables = datagen.gen_tables(data["generator_sf"], seed, data["row_group_size"])
    res = run("stream.power", seed, queries=_control_queries(tables))
    assert not res["correct"]
    assert res["checks"]["float_rel_err"]["value"] > res["checks"]["float_rel_err"]["limit"]


@pytest.mark.parametrize("mix", ["power", "throughput4"])
def test_control_readings_fail_the_limit(mix):
    """The readings `bench/control.py` takes at full size, here small:
    the control fails `float_rel_err` by more than three times."""
    got = control.readings(datagen.gen_tables(0.05, 8, 8192), spec.traffic(mix), 8)
    assert got["float_rel_err"] > 3 * spec.limits()["float_rel_err"]


@pytest.fixture
def half_the_row_groups(monkeypatch):
    """Each scan leaves out every other row group."""
    from repro.datapath.service import Pod

    submit = Pod.submit

    def halved(self, tenant, reader, plan, blooms=None, row_groups=None, scan_tag=None):
        return submit(self, tenant, reader, plan, blooms,
                      row_groups=range(0, reader.n_row_groups, 2), scan_tag=scan_tag)
    monkeypatch.setattr(Pod, "submit", halved)


@pytest.fixture
def decoded_values_altered(monkeypatch):
    """Every integer value a decode bucket produces is off by one."""
    from repro.core.engine import DatapathEngine

    decode = DatapathEngine._decode_bucket

    def altered(self, *a, **kw):
        out = decode(self, *a, **kw)
        return {k: v + 1 if v.dtype.kind in "iu" else v for k, v in out.items()}
    monkeypatch.setattr(DatapathEngine, "_decode_bucket", altered)


@pytest.fixture
def stale_results(monkeypatch):
    """The pre-filtered tier answers a plan with the result of another
    plan over the same table: its key leaves out the plan."""
    from repro.core.engine import DatapathEngine

    monkeypatch.setattr(DatapathEngine, "plan_cache_key",
                        lambda self, reader, plan, blooms=None, tag=None:
                        ("scan", reader.path, tuple(plan.all_columns())))


def test_answer_that_never_comes_is_not_correct(monkeypatch):
    """The first query after the warm-up pass never returns: it is
    recorded unanswered a timeout after the window closes."""
    from repro.core.queries import QUERIES

    monkeypatch.setattr(harness, "FINISH_TIMEOUT_S", 0.5)
    release, calls = threading.Event(), []

    def hang(q):
        def run_or_hang(client, readers, **params):
            calls.append(q)
            if len(calls) > 6:  # one pass of the power mix warms up
                release.wait()
            return QUERIES[q](client, readers, **params)
        return run_or_hang
    try:
        res = run("stream.power", 5, queries={q: hang(q) for q in QUERIES})
    finally:
        release.set()
    assert not res["correct"]
    assert res["checks"]["unanswered"]["value"] == res["failed"] == 1


@pytest.mark.parametrize("cell,fault", [
    ("stream.power", "half_the_row_groups"),
    ("cached.tp4", "half_the_row_groups"),
    ("stream.power", "decoded_values_altered"),
    ("cached.tp4", "decoded_values_altered"),
    ("cached.tp4", "stale_results"),
])
def test_fault_is_not_correct(cell, fault, request):
    request.getfixturevalue(fault)
    res = run(cell, 77)
    assert not res["correct"], res["checks"]

"""Batched multi-row-group decode ≡ sequential per-row-group decode.

The bucketed batch path (`kernels.ops.*_batch`, `engine.
scan_row_groups_batched`, `service batch_decode=True`) must be
bit-identical to the sequential path — same columns, masks, counts AND
the same ScanStats accounting (decoded bytes, fresh bytes, decode_work
by encoding, pool/page hits) — across encoding mixes, ragged last
groups, fused and non-fused predicates, and pool/cache residency
combinations.  Only `kernel_launches` / `batch_pad_blocks` may differ:
fewer launches is the whole point, and reconciliation prices the
difference.

Fixed cases always run; the hypothesis sweep (skipped without
`hypothesis`, same policy as tests/test_encodings.py) drives random
plans, predicates, offload modes, slice splits, and residency
prepopulation over a synthetic table whose columns hit every encoding
with a ragged (non-PACK_BLOCK-aligned) group shape.
"""

import dataclasses
import random

import numpy as np
import pytest

from repro.core import BlockCache, Cmp, DatapathEngine, ScanPlan, tpch
from repro.core.engine import ScanStats
from repro.datapath import CostModel, DatapathService, StaticPolicy
from repro.kernels import ops
from repro.lakeformat.encodings import RLE_OUT_BLOCK, padded_rows
from repro.lakeformat.reader import LakeReader
from repro.lakeformat.schema import ColumnSchema, TableSchema
from repro.lakeformat.writer import write_table

RG_ROWS = 6000  # deliberately NOT a PACK_BLOCK multiple: every group ragged


@pytest.fixture(scope="module")
def mixed(tmp_path_factory):
    """Synthetic table covering every encoding, 4 ragged row groups:
    delta (sorted ints), rle int + rle float (long runs), plain floats,
    dict ints whose DICTIONARY differs per region (per-block fused
    bounds), bitpack keys."""
    rng = np.random.default_rng(7)
    n = 3 * RG_ROWS + 1700
    base = np.arange(n, dtype=np.int64) // 3
    cols = {
        "ts": (base + rng.integers(0, 2, n)).astype(np.int32),  # delta
        "flag": np.repeat(
            rng.integers(0, 5, size=n // 64 + 1), 64)[:n].astype(np.int32),  # rle int
        "level": np.repeat(
            rng.standard_normal(n // 128 + 1).astype(np.float32), 128)[:n],  # rle f32
        "price": rng.standard_normal(n).astype(np.float32),  # plain
        # per-region value sets => per-row-group dictionaries differ
        "cat": (rng.integers(0, 40, n) + 100 * (np.arange(n) // RG_ROWS)).astype(np.int32),
        "key": rng.integers(0, 1 << 13, n).astype(np.int32),  # bitpack
    }
    schema = TableSchema("mixed", [
        ColumnSchema("ts", "int32", "delta"),
        ColumnSchema("flag", "int32", "rle"),
        ColumnSchema("level", "float32", "rle"),
        ColumnSchema("price", "float32", "plain"),
        ColumnSchema("cat", "int32", "dict"),
        ColumnSchema("key", "int32", "bitpack"),
    ])
    path = str(tmp_path_factory.mktemp("batchdec") / "mixed.lake")
    write_table(path, schema, cols, row_group_size=RG_ROWS)
    return LakeReader(path)


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch_batch")
    paths = tpch.write_tables(str(d), sf=0.05, seed=0, sorted_data=True,
                              row_group_size=8192)
    return LakeReader(paths["lineitem"])


STAT_FIELDS = [
    f.name for f in dataclasses.fields(ScanStats)
    if f.name not in ("kernel_launches", "batch_pad_blocks")
]


def _stats_dict(stats):
    return {name: getattr(stats, name) for name in STAT_FIELDS}


def _assert_result_identical(got, want):
    assert int(got.count) == int(want.count)
    assert got.mask.dtype == want.mask.dtype
    assert np.array_equal(np.asarray(got.mask), np.asarray(want.mask))
    assert set(got.columns) == set(want.columns)
    for name in want.columns:
        assert got.columns[name].dtype == want.columns[name].dtype, name
        assert np.array_equal(
            np.asarray(got.columns[name]), np.asarray(want.columns[name])
        ), name


def _run_pair(reader, plan, offload="raw", backend="ref", pools=None,
              caches=None, split_at=None):
    """Run the same scan sequentially and batched on independent engines
    (optionally with identical pre-populated pools/caches and a slice
    split) and assert full equivalence.  Returns the two results."""
    results = []
    for idx, batched in enumerate((False, True)):
        cache = caches[idx] if caches else BlockCache(1 << 30)
        eng = DatapathEngine(backend=backend, offload=offload, cache=cache)
        pool = pools[idx] if pools else None
        rs = eng.resumable_scan(reader, plan)
        if rs.result is None:
            pending = list(rs.pending)
            cut = len(pending) if split_at is None else max(1, min(split_at, len(pending)))
            for part in (pending[:cut], pending[cut:]):
                if not part or rs.result is not None:
                    continue
                if batched:
                    rs.advance_batched(part, pool=pool)
                else:
                    for rg in part:
                        rs.advance([rg], pool=pool)
        results.append(rs)
    seq, bat = results
    _assert_result_identical(bat.result, seq.result)
    assert _stats_dict(bat.stats) == _stats_dict(seq.stats)
    return seq, bat


# ---------------------------------------------------------------------------
# fixed cases
# ---------------------------------------------------------------------------

MIXED_PLANS = [
    ScanPlan("mixed", ["ts", "flag", "level", "price", "cat", "key"]),  # all encodings
    ScanPlan("mixed", ["price", "level"], Cmp("key", "le", 1000)),  # fused bitpack
    ScanPlan("mixed", ["price", "ts"], Cmp("cat", "between", (100, 140))),  # fused dict,
    # per-row-group dictionaries => per-block bounds in one launch
    ScanPlan("mixed", ["flag", "cat"], Cmp("ts", "between", (1000, 3000))),  # pruning
]


@pytest.mark.parametrize("idx", range(len(MIXED_PLANS)))
@pytest.mark.parametrize("offload", ["raw", "preloaded", "prefiltered"])
def test_batched_identical_mixed(mixed, idx, offload):
    seq, bat = _run_pair(mixed, MIXED_PLANS[idx], offload=offload)
    # batching must actually batch when >1 group decodes fresh
    if seq.stats.row_groups_scanned > 1 and seq.stats.decoded_bytes_fresh:
        assert bat.stats.kernel_launches < seq.stats.kernel_launches


@pytest.mark.parametrize("plan", [
    ScanPlan("lineitem", ["l_extendedprice", "l_discount", "l_tax", "l_quantity"]),
    ScanPlan("lineitem", ["l_extendedprice"], Cmp("l_quantity", "le", 10)),
    # fused over an int-DICT string column: bounds rewritten onto per-group codes
    ScanPlan("lineitem", ["l_extendedprice"], Cmp("l_returnflag", "eq", "R")),
    ScanPlan("lineitem", ["l_orderkey", "l_shipmode"],
             Cmp("l_shipdate", "between", (300, 900)), compact=True),
])
def test_batched_identical_lineitem(lineitem, plan):
    _run_pair(lineitem, plan)


def test_batched_identical_pallas_backend(mixed):
    for plan in MIXED_PLANS[:3]:
        _run_pair(mixed, plan, backend="pallas")


def test_batched_identical_with_split_slices(mixed):
    """A scan advanced in two slices — each slice batched — folds in
    identically to the sequential slice-by-slice advance."""
    for cut in (1, 2, 3):
        _run_pair(mixed, MIXED_PLANS[0], split_at=cut)


def test_batched_identical_with_pool_residency(mixed):
    """Pool residency combinations: some (rg, column) decodes already in
    the shared tick pool — batched hits/puts/stats must match exactly,
    including the fully-resident shortcut."""
    plan = MIXED_PLANS[0]
    # build a donor pool with every decoded column, then prepopulate both
    # paths with identical subsets of varying density
    donor = {}
    eng = DatapathEngine(backend="ref", offload="raw", cache=BlockCache(1 << 30))
    eng.scan(mixed, plan, pool=donor)
    keys = sorted(donor, key=repr)
    for density in (0.0, 0.3, 0.7, 1.0):
        rnd = random.Random(int(density * 10))
        subset = {k: donor[k] for k in keys if rnd.random() < density}
        seq, bat = _run_pair(mixed, plan,
                             pools=(dict(subset), dict(subset)))
        if density == 1.0:
            assert seq.stats.decoded_bytes_fresh == 0
            assert bat.stats.pool_hits == seq.stats.pool_hits > 0


def test_batched_identical_with_cache_residency(mixed):
    """Preloaded-mode cache residency: decoded-tier entries for a subset
    of (rg, column) pairs, identical on both sides."""
    plan = ScanPlan("mixed", ["ts", "flag", "price"])
    donor = DatapathEngine(backend="ref", offload="preloaded",
                           cache=BlockCache(1 << 30))
    donor.scan(mixed, plan)  # fills decoded + encoded tiers
    for density in (0.4, 1.0):
        caches = []
        for _ in range(2):
            cache = BlockCache(1 << 30)
            rnd = random.Random(int(density * 10))
            for rg in range(mixed.n_row_groups):
                for name in plan.columns:
                    key = donor.rg_cache_key(mixed, rg, name)
                    if rnd.random() < density:
                        e = donor.cache.store.peek(key)
                        cache.put(key, e.value, encoding=e.encoding)
            caches.append(cache)
        seq, bat = _run_pair(mixed, plan, offload="preloaded", caches=caches)
        if density == 1.0:
            assert bat.stats.encoded_bytes == seq.stats.encoded_bytes == 0


# ---------------------------------------------------------------------------
# the bucket split: one compiled program per bucket, page for page
# ---------------------------------------------------------------------------

# one bucket kind per plan: the projected columns' encodings, or the fused
# mask (predicate column not projected, so its pages never decode), with
# the pages a row group sends through the split
SPLIT_PLANS = {
    "plain": (ScanPlan("mixed", ["price"]), 1),
    "bitpack": (ScanPlan("mixed", ["key"]), 1),
    "dict": (ScanPlan("mixed", ["cat"]), 1),
    "delta": (ScanPlan("mixed", ["ts"]), 1),
    "rle": (ScanPlan("mixed", ["flag", "level"]), 2),  # an int and a float bucket
    "fused": (ScanPlan("mixed", ["price"], Cmp("key", "le", 1000)), 2),  # mask + price
}


def _pages(reader, plan, rgs, entry):
    """[(cols, mask)] per row group of `rgs`, through the named batched
    entry: `scan_row_groups_batched` over the whole list, or
    `scan_group_batched` with the list split between two requests."""
    eng = DatapathEngine(backend="ref", offload="raw", cache=BlockCache(1 << 30))
    if entry == "row_groups":
        rs = eng.resumable_scan(reader, plan)
        per_rg, _ = eng.scan_row_groups_batched(
            reader, rgs, rs.plan, rs.pred, rs.blooms, rs.stats)
        return per_rg
    cut = len(rgs) // 2
    items = []
    for part in (rgs[:cut], rgs[cut:]):
        rs = eng.resumable_scan(reader, plan)
        items.append({"reader": reader, "rgs": part, "plan": rs.plan, "pred": rs.pred,
                      "blooms": rs.blooms, "stats": rs.stats, "offload": None})
    return [p for per_rg, _ in eng.scan_group_batched(items) for p in per_rg]


def _assert_pages_identical(got, want):
    (gcols, gmask), (wcols, wmask) = got, want
    assert gmask.dtype == wmask.dtype
    assert np.array_equal(np.asarray(gmask), np.asarray(wmask))
    assert set(gcols) == set(wcols)
    for name, w in wcols.items():
        if w is None:
            assert gcols[name] is None, name
            continue
        assert gcols[name].dtype == w.dtype and gcols[name].shape == w.shape, name
        assert np.array_equal(np.asarray(gcols[name]), np.asarray(w)), name


@pytest.mark.parametrize("entry", ["row_groups", "group"])
@pytest.mark.parametrize("kind", sorted(SPLIT_PLANS))
def test_split_pages_match_sequential(mixed, monkeypatch, kind, entry):
    """Each bucket kind, split by the compiled program, equals the
    sequential per-row-group scan page for page and bit for bit —
    including the short last row group (its own L, its own program call)
    and RLE pages whose blocks fall short of L (zero-filled)."""
    from repro.core import engine as engine_mod

    plan, per_rg = SPLIT_PLANS[kind]
    rgs = list(range(mixed.n_row_groups))
    lengths = {padded_rows(mixed.row_group_meta(rg)["n"]) for rg in rgs}
    assert len(lengths) == 2  # the ragged last group has an L of its own
    if kind == "rle":
        short = [rg for rg in rgs
                 if mixed.read_encoded(rg, ["flag"])["flag"].buffers["rle_values"].shape[0]
                 * RLE_OUT_BLOCK < padded_rows(mixed.row_group_meta(rg)["n"])]
        assert short  # some RLE page is zero-filled up to L

    calls = []
    split = engine_mod.DatapathEngine._split

    def spy(out, blocks, lengths):
        calls.append(len(lengths))
        return split(out, blocks, lengths)

    monkeypatch.setattr(engine_mod.DatapathEngine, "_split", staticmethod(spy))
    got = _pages(mixed, plan, rgs, entry)
    assert sum(calls) == len(rgs) * per_rg

    eng = DatapathEngine(backend="ref", offload="raw", cache=BlockCache(1 << 30))
    rs = eng.resumable_scan(mixed, plan)
    for rg, page in zip(rgs, got):
        want = eng.scan_row_group(mixed, rg, rs.plan, rs.pred, rs.blooms, rs.stats)
        _assert_pages_identical(page, want)


def test_split_keeps_its_trace_across_row_group_order(mixed):
    """A second pass over the same row groups in another order replays
    the split programs of the first: the offsets are runtime arrays."""
    from repro.core.engine import _SPLIT_TRACES

    plan = MIXED_PLANS[0]
    first = _pages(mixed, plan, [0, 1, 2, 3], "row_groups")
    n0 = _SPLIT_TRACES[0]
    order = [3, 1, 0, 2]
    second = _pages(mixed, plan, order, "row_groups")
    assert _SPLIT_TRACES[0] == n0
    for rg, page in zip(order, second):
        _assert_pages_identical(page, first[rg])


@pytest.mark.parametrize("dtype", [np.int32, np.float32, np.bool_])
def test_split_keeps_its_trace_within_a_bucket_rung(dtype):
    """Five pages and six pad to the same rung of the page ladder, so the
    six replay the program the five called; each page equals its slice
    of the stacked output, zero-filled past its blocks and cut at L."""
    import jax.numpy as jnp

    from repro.core.engine import _SPLIT_TRACES

    L, per = 8192, 1024
    assert ops.bucket_blocks(5) == ops.bucket_blocks(6) == 6
    rng = np.random.default_rng(11)
    host = rng.integers(-5, 5, size=(48, per)).astype(dtype)  # 48 blocks of 1024
    out = jnp.asarray(host)
    for pages in (5, 6):
        blocks = [6, 8, 9, 7, 8, 6][:pages]  # 6 blocks short of L, 9 past it
        n0 = _SPLIT_TRACES[0]
        cols = DatapathEngine._split(out, blocks, [L] * pages)
        if pages == 6:  # the five-page call has traced (or found) the rung
            assert _SPLIT_TRACES[0] == n0
        flat, s = host.reshape(-1), 0
        for b, col in zip(blocks, cols):
            want = np.zeros(L, dtype)
            n = min(b * per, L)
            want[:n] = flat[s:s + n]
            assert col.dtype == want.dtype
            assert np.array_equal(np.asarray(col), want)
            s += b * per


# ---------------------------------------------------------------------------
# service end-to-end: batch_decode=True ≡ batch_decode=False
# ---------------------------------------------------------------------------

def _drain_service(reader, batch_decode, plans, hold_ticks=0, tick_bytes=None):
    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
        policy=StaticPolicy("raw"), batch_decode=batch_decode,
        hold_ticks=hold_ticks, tick_bytes=tick_bytes,
    )
    tickets = [svc.submit(f"t{i}", reader, p) for i, p in enumerate(plans)]
    svc.drain()
    return svc, tickets


def test_service_batched_equals_sequential(mixed):
    plans = [
        ScanPlan("mixed", ["ts", "price", "cat"]),
        ScanPlan("mixed", ["price", "level"], Cmp("key", "le", 2000)),
        ScanPlan("mixed", ["ts", "price"], Cmp("ts", "between", (0, 4000))),
    ]
    # tick budget sized so slices span multiple row groups: beneficiary-
    # split retention billing interleaves tenants more finely than the old
    # bill-the-decoder scheme, and at RG_ROWS*16 every slice degenerated
    # to a single row group — leaving the batched path nothing to amortize
    svc_a, tk_a = _drain_service(mixed, False, plans, hold_ticks=2,
                                 tick_bytes=RG_ROWS * 32)
    svc_b, tk_b = _drain_service(mixed, True, plans, hold_ticks=2,
                                 tick_bytes=RG_ROWS * 32)
    for a, b in zip(tk_a, tk_b):
        assert a.status == b.status == "done"
        _assert_result_identical(b.result, a.result)
        assert _stats_dict(b.result.stats) == _stats_dict(a.result.stats)
    ca, cb = svc_a.telemetry.counters, svc_b.telemetry.counters
    for key in ("decoded_bytes", "decoded_bytes_fresh", "encoded_bytes",
                "rows_out", "decoded_bytes_saved", "sim_fetch_encoded_bytes",
                "sim_fetch_decoded_bytes"):
        assert ca.get(key, 0) == cb.get(key, 0), key
    assert cb.get("batch_slices", 0) > 0
    assert cb["decode_launches"] < ca["decode_launches"]


def test_batched_launch_overhead_is_refunded(mixed):
    """With a calibrated per-launch overhead, the sequential path's honest
    estimate reconciles to ~zero while the batched path is REFUNDED the
    launch overhead its buckets amortized — and the charge ledger stays
    exact (sched + recon == actual) in both modes."""
    plan = ScanPlan("mixed", ["ts", "flag", "level", "price"])
    for batched in (False, True):
        cm = CostModel(launch_overhead_s=1e-4)
        svc = DatapathService(
            engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
            policy=StaticPolicy("raw"), batch_decode=batched, cost_model=cm,
        )
        svc.submit("t", mixed, plan)
        svc.drain()
        tel = svc.telemetry
        est = tel.tenant_sched_seconds["t"]
        recon = tel.tenant_recon_seconds.get("t", 0.0)
        actual = tel.tenant_actual_seconds["t"]
        assert est + recon == pytest.approx(actual, rel=1e-9)
        if batched:
            # 4 row groups x 4 columns sequential launches estimated; far
            # fewer buckets actually launched -> a strictly negative recon
            assert recon < -1e-4
        else:
            assert recon == pytest.approx(0.0, abs=1e-12)


def test_slice_clock_streams_overlap():
    """The cross-tick SliceClock hides each slice's fetch behind the
    previous slice's decode: fetch-bound stream -> everything but the
    trailing decode overlaps."""
    from repro.datapath.netsim import LinkModel, SliceClock

    clk = SliceClock(LinkModel(bandwidth_gbps=1.0, latency_us=0.0))
    for _ in range(3):
        clk.feed(1_000_000_000, 0.5)  # 1s fetch, 0.5s decode
    assert clk.slices == 3
    assert clk.serial_s == pytest.approx(4.5)
    assert clk.overlapped_s == pytest.approx(3.5)  # decodes hidden, last one trails
    assert clk.saved_s == pytest.approx(1.0)


def test_batched_slices_pipeline_across_ticks(mixed):
    """One slice per tick: the stateless per-tick simulation sees no
    overlap, but the streaming clock must — the next slice's fetch is in
    flight while this slice's batch decode runs."""
    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
        policy=StaticPolicy("raw"), batch_decode=True,
        tick_bytes=RG_ROWS * 8,  # ~one row group's decoded bytes per tick
    )
    svc.submit("t", mixed, ScanPlan("mixed", ["ts", "price", "cat"]))
    svc.drain()
    c = svc.telemetry.counters
    assert c["sim_pipe_slices"] >= 3
    assert c["sim_pipe_overlapped_s"] < c["sim_pipe_serial_s"]
    assert c["sim_pipe_saved_s"] > 0.0


# ---------------------------------------------------------------------------
# hypothesis sweep
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    COLS = ["ts", "flag", "level", "price", "cat", "key"]
    PREDS = [
        None,
        Cmp("key", "le", 1000),  # fused bitpack when key not projected
        Cmp("cat", "between", (100, 240)),  # fused dict when cat not projected
        Cmp("ts", "between", (500, 9000)),  # prunable
        Cmp("flag", "eq", 2),
    ]

    @settings(deadline=None, max_examples=40)
    @given(
        cols=st.sets(st.sampled_from(COLS), min_size=1, max_size=4),
        pred_idx=st.integers(0, len(PREDS) - 1),
        offload=st.sampled_from(["raw", "preloaded", "prefiltered"]),
        split=st.integers(0, 4),
        pool_density=st.sampled_from([None, 0.3, 1.0]),
        compact=st.booleans(),
    )
    def test_batched_equivalence_sweep(mixed, cols, pred_idx, offload, split,
                                       pool_density, compact):
        plan = ScanPlan("mixed", sorted(cols), PREDS[pred_idx], compact=compact)
        pools = None
        if pool_density is not None:
            donor = {}
            eng = DatapathEngine(backend="ref", offload="raw",
                                 cache=BlockCache(1 << 30))
            eng.scan(mixed, plan, pool=donor)
            rnd = random.Random(split)
            subset = {k: v for k, v in sorted(donor.items(), key=lambda kv: repr(kv[0]))
                      if rnd.random() < pool_density}
            pools = (dict(subset), dict(subset))
        _run_pair(mixed, plan, offload=offload, pools=pools,
                  split_at=split or None)


# ---------------------------------------------------------------------------
# batch kernel entry points: parity + bucketing
# ---------------------------------------------------------------------------

def test_bucket_blocks_ladder_and_pow2():
    # default mode: the two-rung ladder {2^m, 3*2^(m-1)}
    assert [ops.bucket_blocks(n) for n in (1, 2, 3, 4, 5, 6, 7, 8, 9, 13, 17, 64, 100)] == \
        [1, 2, 3, 4, 6, 6, 8, 8, 12, 16, 24, 64, 128]
    # legacy pow2 mode, kept for A/B benching
    assert [ops.bucket_blocks(n, mode="pow2") for n in (1, 2, 3, 5, 8, 9, 64, 100)] == \
        [1, 2, 4, 8, 8, 16, 64, 128]
    for n in range(1, 2048):
        lad = ops.bucket_blocks(n, mode="ladder")
        p2 = ops.bucket_blocks(n, mode="pow2")
        assert n <= lad <= p2  # ladder pads no more than pow2, ever
        assert lad - n <= n  # bounded waste: never more than 2x the payload


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_batch_ops_match_sequential(backend):
    """Each *_batch entry point must equal per-page sequential calls bit
    for bit — including ragged pages, per-page dictionaries, and per-block
    fused bounds — while issuing ONE counted dispatch."""
    import jax.numpy as jnp

    from repro.lakeformat import encodings as E

    rng = np.random.default_rng(3)
    # bitpack: ragged pages
    pages = [rng.integers(0, 1 << 9, size=n).astype(np.uint64)
             for n in (4096, 9000, 100)]
    packs = [E.bitpack_encode(v, 9) for v in pages]
    before = ops.dispatch_count()
    out = ops.bitunpack_batch(np.concatenate(packs, axis=0), 9, backend=backend)
    assert ops.dispatch_count() == before + 1
    s = 0
    for p, v in zip(packs, pages):
        nb = p.shape[0]
        seq = ops.bitunpack(jnp.asarray(p), 9, backend=backend)
        assert np.array_equal(np.asarray(out[s:s + nb]), np.asarray(seq))
        s += nb

    # dict: per-page dictionaries of different sizes (int + float sweep)
    for dtype, values in (
        (np.float32, np.array([1.5, 2.5, 9.0, -3.0], np.float32)),
        (np.int32, np.array([3, 17, 99, 2048, 70000], np.int64)),
    ):
        vals = [rng.choice(values[: 3 + (i % 2)], size=n).astype(dtype)
                for i, n in enumerate((5000, 4096))]
        encs = [E.dict_encode(v) for v in vals]
        ks = [int(b.pop("_k")[0]) for b in encs]
        if ks[0] != ks[1]:
            continue  # only same-k pages share a bucket
        dmax = max(b["dictionary"].shape[0] for b in encs)
        dt = np.int32 if np.dtype(dtype).kind in "iu" else dtype
        dicts = np.zeros((2, dmax), dt)
        sizes = np.zeros(2, np.int32)
        for i, b in enumerate(encs):
            d = b["dictionary"].astype(dt)
            dicts[i, : len(d)] = d
            sizes[i] = len(d)
        page = np.concatenate(
            [np.full(b["packed"].shape[0], i, np.int32) for i, b in enumerate(encs)])
        out = ops.dict_decode_batch(
            np.concatenate([b["packed"] for b in encs], axis=0),
            dicts, sizes, page, ks[0], backend=backend)
        s = 0
        for b, v in zip(encs, vals):
            nb = b["packed"].shape[0]
            seq = ops.dict_decode(jnp.asarray(b["packed"]),
                                  jnp.asarray(b["dictionary"].astype(dt)),
                                  ks[0], backend=backend)
            assert np.array_equal(np.asarray(out[s:s + nb]), np.asarray(seq))
            s += nb

    # fused: per-block bounds
    packs = [E.bitpack_encode(rng.integers(0, 1 << 8, size=n).astype(np.uint64), 8)
             for n in (8192, 5000)]
    blocks = [p.shape[0] for p in packs]
    bounds = [(10, 100), (50, 60)]
    lo = np.concatenate([np.full(b, lh[0], np.int32)
                         for b, lh in zip(blocks, bounds)])
    hi = np.concatenate([np.full(b, lh[1], np.int32)
                         for b, lh in zip(blocks, bounds)])
    m = ops.fused_scan_batch(np.concatenate(packs, axis=0), 8, lo, hi,
                             backend=backend)
    s = 0
    for p, (l, h) in zip(packs, bounds):
        nb = p.shape[0]
        seq_mask, _ = ops.fused_scan(jnp.asarray(p), 8, l, h, backend=backend)
        assert np.array_equal(np.asarray(m[s:s + nb]), np.asarray(seq_mask))
        s += nb

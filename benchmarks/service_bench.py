"""Multi-tenant service benchmark: shared-scan coalescing vs N independent
engines, the adaptive offload policy on a recurring workload, and the
fair-share scheduler under skew.

The coalescing workload is N tenants running TPC-H-style revenue scans
over the same lineitem table with per-tenant date windows (overlapping,
as concurrent dashboards do).  Independently, every tenant decodes every
hot column itself; through the service, one tick's DecodePool decodes
each (row group, column) once and feeds all N predicates — so fresh
decoded bytes stay near-flat while tenant count grows.

The `fairness` sub-report runs a skewed 1-elephant/3-mice workload (one
whole-table scan pinned behind three narrow window scans) under FIFO vs
WFQ with the same per-tick decode budget, reporting mice p99
ticks-to-complete against their solo value plus the Jain fairness index,
and measures the cross-tick coalescing hold window (decoded_bytes_saved
with hold_ticks=2 vs tick-scoped coalescing) on compatible requests that
arrive a tick apart.

The `costmodel` sub-report calibrates the per-encoding decode-rate table
(fast smoke sizes; nominal fallback) and runs the adversarial honesty
bench: an elephant whose requests under-estimate decode cost 4x competes
with an honest elephant under WFQ.  With actual-cost reconciliation on,
the cheat's decoded-byte share while both are backlogged must stay
within 10% of the honest baseline; with it off, the cheat pays off —
that delta is the reconciliation mechanism's measured value.

The `blockstore` sub-report exercises the unified tiered store: a LATE
partner arriving hold_ticks after a compatible scan dispatched serves
its overlapping row groups from the window-retained decoded tier
(re-decode seconds saved > 0 vs the old tick-scoped pool, which saves
exactly zero in the same scenario), and a capacity-pressured preloaded
workload shows the cost-ranked eviction keeping encoded pages (repeat
scans re-decode but never re-fetch) — per-tier hit/eviction rates come
from the store's ledger.

The `batchdecode` sub-report A/Bs the bucketed batch-decode dispatch
path (service batch_decode=True, the default) against the sequential
one-launch-per-(row group, column) loop on a >= 32-row-group,
multi-column whole-table scan: device dispatches (kernels.ops'
dispatch counter), wall time, decode launches, and — with the slice
pipeline — the netsim fetch/decode overlap at slice granularity.

The `trace` sub-report A/Bs the flight recorder (datapath/trace.py) on
the skewed elephant/mice workload: the same run with per-request span
tracing on (sample_rate=1) vs off (sample_rate=0), reporting the wall
overhead ratio (must stay under ~5%), result bit-identity, the Chrome-
trace event count, and the trace-derived per-request stage attribution
(decode/filter/rest % of wall) printed against the paper's Fig. 2
46/17/37 anchor — the observability claim as a measured point.

Reported rows:
    service.independent    N direct DatapathEngine.scan() calls
    service.coalesced      same scans through one DatapathService tick
    service.savings        fresh-decoded-byte ratio + wall speedup
    service.adaptive       repeated query mix under the adaptive policy
    service.fairness.*     solo / fifo / wfq mice latency + Jain index
    service.holdwindow     cross-tick vs tick-scoped coalescing savings
    service.costmodel.*    calibrated rates + 4x-under-estimator shares
    service.blockstore.*   late-partner retained reuse + tier ledger
    service.batchdecode.*  dispatch counts + wall, batched vs sequential
    service.pushdown       fused decode→aggregate vs scan-then-aggregate:
                           result-DMA bytes, wall, dispatch counts,
                           bit-identity of the grouped answer
    service.trace.*        tracing overhead + stage attribution vs Fig. 2
    service.kernels.roofline  rewritten-core rates vs the pre-rewrite
                           anchor + ladder-vs-pow2 pad-waste bytes
    service.fabric.*       pod-sharded fleet: aggregate simulated
                           throughput at 1/2/4 pods (makespan = max
                           per-pod busy seconds), scale-out peer-fetch
                           bytes vs the storage-hop equivalent, fleet
                           Jain index with the WFQ re-level on vs
                           per-pod local clocks, kill-one-pod
                           drain/replay with bit-identity
    service.faults.*       storage fault plane: fault-free vs 1%/5%
                           transient-error A/B (bit-identical results,
                           bounded p99 inflation, zero hung requests),
                           hedged-read tail seconds clawed back, and the
                           breaker-open load-shed rate with every
                           rejection typed Overloaded
"""

from __future__ import annotations

import os
import time

from repro.core import BlockCache, DatapathEngine, tpch
from repro.core.plan import Cmp, ScanPlan
from repro.core.queries import QUERIES, run_via_service
from repro.datapath import (
    AdaptiveOffloadPolicy,
    CostModel,
    DatapathService,
    StaticPolicy,
)
from repro.lakeformat.reader import LakeReader

from benchmarks.breakdown import setup
from benchmarks.common import DATA_DIR, row, timed


def tenant_plans(n_tenants: int):
    """Per-tenant revenue scans: same hot columns, shifted date windows."""
    plans = []
    for t in range(n_tenants):
        start = 200 + 45 * t  # overlapping year-long windows
        plans.append(
            ScanPlan(
                "lineitem",
                ["l_extendedprice", "l_discount"],
                Cmp("l_shipdate", "between", (start, start + 364)),
            )
        )
    return plans


def _run_independent(readers, plans):
    """One fresh raw engine per tenant — the seed library-call model."""
    fresh = 0
    for plan in plans:
        eng = DatapathEngine(backend="ref", offload="raw")
        res = eng.scan(readers["lineitem"], plan)
        fresh += res.stats.decoded_bytes_fresh
    return fresh


def _run_service(readers, plans):
    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        batch_per_tick=len(plans),
        policy=StaticPolicy("raw"),  # isolate coalescing from caching
    )
    for t, plan in enumerate(plans):
        svc.submit(f"tenant{t}", readers["lineitem"], plan)
    svc.drain()
    return svc


# ---------------------------------------------------------------------------
# fairness sub-report: 1 elephant / 3 mice, FIFO vs WFQ, hold window
# ---------------------------------------------------------------------------

FAIR_RG_ROWS = 8192  # small row groups: the scheduler's preemption quantum


def fairness_setup(sf: float = 0.1):
    """A sorted lineitem with small row groups so narrow window scans prune
    to 1-2 groups while the elephant spans them all."""
    d = os.path.join(DATA_DIR, f"tpch_fair_sf{sf}")
    if not os.path.exists(os.path.join(d, "lineitem.lake")):
        tpch.write_tables(d, sf=sf, seed=0, sorted_data=True,
                          row_group_size=FAIR_RG_ROWS)
    return LakeReader(os.path.join(d, "lineitem.lake"))


def _elephant_plan():
    return ScanPlan("lineitem", ["l_extendedprice", "l_quantity"])  # every group


def _mouse_plan(day: int):
    return ScanPlan("lineitem", ["l_extendedprice"],
                    Cmp("l_shipdate", "between", (day, day + 200)))


def _fair_service(scheduler: str, hold_ticks: int = 0):
    rg_cost = FAIR_RG_ROWS * 4 * 2  # decoded bytes per elephant row group
    return DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        policy=StaticPolicy("raw"),  # isolate scheduling from caching
        scheduler=scheduler,
        tick_bytes=int(rg_cost * 1.5),
        hold_ticks=hold_ticks,
    )


def _run_skewed(reader, scheduler: str, with_elephant: bool) -> dict:
    """1 elephant + 3 mice; returns mice p99 ticks-to-complete and the
    fairness snapshot."""
    svc = _fair_service(scheduler)
    elephant = svc.submit("elephant", reader, _elephant_plan()) if with_elephant else None
    mice = [svc.submit(f"mouse{i}", reader, _mouse_plan(d))
            for i, d in enumerate((300, 900, 1500))]
    svc.drain()
    ticks = sorted(t.done_tick - t.submitted_tick for t in mice)
    # NOTE: cumulative decoded bytes (and hence the Jain index over them)
    # are workload-determined — identical under FIFO and WFQ, which only
    # reorder WHEN work runs.  The scheduler discriminator is latency:
    # mice ticks-to-complete.  Shares are returned for the workload's
    # skew profile, not as an A/B metric.
    fair = svc.telemetry.fairness()
    return {
        "mice_ticks": ticks,
        "mice_p99_ticks": ticks[-1],
        "elephant_ticks": (elephant.done_tick - elephant.submitted_tick)
        if elephant else 0,
        "tenant_share": fair["tenant_share"],
    }


def _run_hold_window(reader, hold_ticks: int) -> int:
    """Two compatible scans arriving a tick apart; returns the decoded
    bytes the shared pool saved."""
    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        policy=StaticPolicy("raw"),
        hold_ticks=hold_ticks,
    )
    plan_a = ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                      Cmp("l_shipdate", "between", (300, 700)))
    plan_b = ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                      Cmp("l_shipdate", "between", (350, 750)))
    svc.submit("t0", reader, plan_a)
    svc.tick()  # without a hold, t0 decodes alone in this tick
    svc.submit("t1", reader, plan_b)
    svc.drain()
    return int(svc.telemetry.counters.get("decoded_bytes_saved", 0))


def run_fairness(sf: float = 0.1) -> dict:
    reader = fairness_setup(sf)
    solo = _run_skewed(reader, "wfq", with_elephant=False)
    fifo = _run_skewed(reader, "fifo", with_elephant=True)
    wfq = _run_skewed(reader, "wfq", with_elephant=True)
    saved_scoped = _run_hold_window(reader, hold_ticks=0)
    saved_window = _run_hold_window(reader, hold_ticks=2)

    row("service.fairness.solo", 0.0,
        f"mice_p99_ticks={solo['mice_p99_ticks']}")
    row("service.fairness.fifo", 0.0,
        f"mice_p99_ticks={fifo['mice_p99_ticks']};"
        f"elephant_ticks={fifo['elephant_ticks']}")
    row("service.fairness.wfq", 0.0,
        f"mice_p99_ticks={wfq['mice_p99_ticks']};"
        f"elephant_ticks={wfq['elephant_ticks']};"
        f"vs_solo={wfq['mice_p99_ticks'] / max(solo['mice_p99_ticks'], 1):.2f}x;"
        f"vs_fifo={fifo['mice_p99_ticks'] / max(wfq['mice_p99_ticks'], 1):.2f}x")
    row("service.holdwindow", 0.0,
        f"saved_tick_scoped={saved_scoped};saved_hold2={saved_window}")
    return {
        "solo": solo,
        "fifo": fifo,
        "wfq": wfq,
        "wfq_mice_p99_vs_solo": wfq["mice_p99_ticks"] / max(solo["mice_p99_ticks"], 1),
        "hold_window_saved_bytes": saved_window,
        "tick_scoped_saved_bytes": saved_scoped,
    }


# ---------------------------------------------------------------------------
# costmodel sub-report: calibration + the 4x-under-estimator honesty bench
# ---------------------------------------------------------------------------

def _run_adversarial(reader, cost_model, cheat: bool, reconcile: bool) -> dict:
    """Two whole-table elephants, one doctored to under-estimate its decode
    cost 4x.  Shares are measured while BOTH tenants stay backlogged (the
    only regime where scheduling decides anything)."""
    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        policy=StaticPolicy("raw"), scheduler="wfq",
        tick_bytes=int(FAIR_RG_ROWS * 4 * 2 * 1.5),
        cost_model=cost_model, reconcile=reconcile,
    )
    svc.submit("cheat", reader, ScanPlan("lineitem", ["l_extendedprice", "l_quantity"]))
    svc.submit("honest", reader, ScanPlan("lineitem", ["l_discount", "l_tax"]))
    if cheat:
        req = next(q for q in svc.queue if q.tenant == "cheat")
        req.rg_costs = tuple(c / 4 for c in req.rg_costs)
    while all(any(q.tenant == t and q.cursor < len(q.row_groups) for q in svc.queue)
              for t in ("cheat", "honest")):
        svc.tick()
    dec = svc.telemetry.tenant_decoded_bytes
    total = sum(dec.values())
    return {
        "cheat_share": dec.get("cheat", 0.0) / total if total else 0.0,
        "cost": svc.telemetry.cost_report(),
    }


def run_costmodel(sf: float = 0.1) -> dict:
    import time as _time

    reader = fairness_setup(sf)
    t0 = _time.perf_counter()
    cm = CostModel.calibrate(backend="ref", n=1 << 16, repeats=1)
    t_cal = _time.perf_counter() - t0
    rates = {k: round(v, 3) for k, v in sorted(cm.rates.items())}
    row("service.costmodel.calibration", t_cal,
        f"source={cm.source};rates_GBps={rates}")

    base = _run_adversarial(reader, cm, cheat=False, reconcile=True)
    recon_on = _run_adversarial(reader, cm, cheat=True, reconcile=True)
    recon_off = _run_adversarial(reader, cm, cheat=True, reconcile=False)
    gain_on = recon_on["cheat_share"] / max(base["cheat_share"], 1e-9)
    gain_off = recon_off["cheat_share"] / max(base["cheat_share"], 1e-9)
    cheat_err = recon_on["cost"]["cheat"]["rel_err"]
    row("service.costmodel.adversarial", 0.0,
        f"honest_share={base['cheat_share']:.3f};"
        f"cheat_share_recon={recon_on['cheat_share']:.3f} ({gain_on:.2f}x);"
        f"cheat_share_norecon={recon_off['cheat_share']:.3f} ({gain_off:.2f}x);"
        f"cheat_rel_err={cheat_err:.2f}")
    return {
        "rates_gbps": {k: cm.rates[k] for k in sorted(cm.rates)},
        "source": cm.source,
        "calibration_s": t_cal,
        "honest_share": base["cheat_share"],
        "cheat_share_reconcile_on": recon_on["cheat_share"],
        "cheat_share_reconcile_off": recon_off["cheat_share"],
        "cheat_gain_reconcile_on": gain_on,
        "cheat_gain_reconcile_off": gain_off,
        "cheat_rel_err_reconcile_on": cheat_err,
    }


# ---------------------------------------------------------------------------
# blockstore sub-report: retained-window reuse + tier ledger under pressure
# ---------------------------------------------------------------------------

def _run_late_partner(reader, hold_ticks: int) -> dict:
    """A scan dispatches alone (at its hold deadline); a compatible partner
    arrives AFTER it completed, within the hold window.  With the unified
    store the partner reuses the window-retained decodes; with the old
    tick-scoped pool (hold_ticks=0 control) it re-decodes everything."""
    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        policy=StaticPolicy("raw"), hold_ticks=hold_ticks,
    )
    plan_a = ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                      Cmp("l_shipdate", "between", (300, 700)))
    plan_b = ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                      Cmp("l_shipdate", "between", (350, 750)))
    early = svc.submit("early", reader, plan_a)
    while early.status == "queued":
        svc.tick()
    late = svc.submit("late", reader, plan_b)
    svc.drain()
    c = svc.telemetry.counters
    return {
        "reuse_bytes": int(c.get("retained_reuse_bytes", 0)),
        "redecode_saved_s": float(c.get("retained_redecode_saved_s", 0.0)),
        "late_fresh_bytes": int(late.result.stats.decoded_bytes_fresh),
        "late_pool_hits": int(late.result.stats.pool_hits),
        "retained_charge_s": float(c.get("retained_charge_seconds", 0.0)),
    }


def _run_tier_pressure(reader) -> dict:
    """Preloaded repeats through a store sized well under the decoded
    footprint: cost-ranked eviction churns PLAIN decodes but keeps encoded
    pages, so the repeat pass re-decodes without re-fetching."""
    plan = ScanPlan("lineitem", ["l_extendedprice", "l_discount"])
    enc_total = sum(
        reader.row_group_meta(rg)["columns"][c]["encoded_bytes"]
        for rg in range(reader.n_row_groups)
        for c in ("l_extendedprice", "l_discount")
    )
    eng = DatapathEngine(backend="ref",
                         cache=BlockCache(enc_total + FAIR_RG_ROWS * 4 * 3))
    first = eng.scan(reader, plan, offload="preloaded")
    second = eng.scan(reader, plan, offload="preloaded")
    tiers = eng.cache.stats()["tiers"]
    return {
        "first_fetch_bytes": int(first.stats.encoded_bytes),
        "repeat_fetch_bytes": int(second.stats.encoded_bytes),
        "repeat_page_hits": int(second.stats.page_hits),
        "decoded_evictions": int(tiers["decoded"]["evictions"]),
        "encoded_hits": int(tiers["encoded"]["hits"]),
        "decoded_hits": int(tiers["decoded"]["hits"]),
    }


def run_blockstore(sf: float = 0.1) -> dict:
    reader = fairness_setup(sf)
    scoped = _run_late_partner(reader, hold_ticks=0)  # old tick-scoped pool
    window = _run_late_partner(reader, hold_ticks=2)
    pressure = _run_tier_pressure(reader)
    row("service.blockstore.latepartner", 0.0,
        f"reuse_bytes={window['reuse_bytes']};"
        f"redecode_saved_s={window['redecode_saved_s']:.6f};"
        f"tick_scoped_saved_s={scoped['redecode_saved_s']:.6f};"
        f"retained_charge_s={window['retained_charge_s']:.6f}")
    row("service.blockstore.tiers", 0.0,
        f"repeat_fetch_bytes={pressure['repeat_fetch_bytes']}"
        f"/{pressure['first_fetch_bytes']};"
        f"page_hits={pressure['repeat_page_hits']};"
        f"decoded_evictions={pressure['decoded_evictions']}")
    return {
        "late_partner_window": window,
        "late_partner_tick_scoped": scoped,
        "tier_pressure": pressure,
    }


# ---------------------------------------------------------------------------
# trace sub-report: flight-recorder overhead + paper-anchored attribution
# ---------------------------------------------------------------------------

def _run_traced_skew(reader, sample_rate: float):
    """The fairness elephant/mice workload with the flight recorder at
    `sample_rate`; returns (service, results, wall_s)."""
    import time as _time

    rg_cost = FAIR_RG_ROWS * 4 * 2
    t0 = _time.perf_counter()
    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        policy=StaticPolicy("raw"), scheduler="wfq",
        tick_bytes=int(rg_cost * 1.5),
        trace_sample_rate=sample_rate, trace_capacity=16,
    )
    tickets = [svc.submit("elephant", reader, _elephant_plan())]
    tickets += [svc.submit(f"mouse{i}", reader, _mouse_plan(d))
                for i, d in enumerate((300, 900, 1500))]
    svc.drain()
    wall = _time.perf_counter() - t0
    return svc, tickets, wall


def run_trace(sf: float = 0.1) -> dict:
    import numpy as np

    reader = fairness_setup(sf)
    _run_traced_skew(reader, 0.0)  # warmup: jit compiles + file cache
    svc_off, res_off, wall_off = _run_traced_skew(reader, 0.0)
    svc_on, res_on, wall_on = _run_traced_skew(reader, 1.0)

    bit_identical = all(
        a.status == b.status == "done"
        and int(a.result.count) == int(b.result.count)
        and all(np.array_equal(np.asarray(a.result.columns[c]),
                               np.asarray(b.result.columns[c]))
                for c in a.result.columns)
        for a, b in zip(res_on, res_off)
    )
    overhead = wall_on / max(wall_off, 1e-9)

    rep = svc_on.telemetry.trace_report()
    st = rep["stage_s"]
    chrome_events = len(svc_on.tracer.recorder.to_chrome_trace()["traceEvents"])
    row("service.trace.overhead", wall_on,
        f"wall_off_s={wall_off:.3f};ratio={overhead:.3f}x;"
        f"bit_identical={bit_identical};"
        f"recorded={rep['recorded']}/{rep['completed']};"
        f"chrome_events={chrome_events}")
    row("service.trace.stages", 0.0,
        f"host seconds: decode={st['decode']:.4f};filter={st['filter']:.4f};"
        f"fetch={st['fetch']:.4f};wall={rep['wall_s']:.4f}")
    return {
        "wall_traced_s": wall_on,
        "wall_untraced_s": wall_off,
        "overhead_ratio": overhead,
        "bit_identical": bit_identical,
        "recorded": rep["recorded"],
        "completed": rep["completed"],
        "chrome_events": chrome_events,
        "stage_s": rep["stage_s"],
    }


# ---------------------------------------------------------------------------
# batchdecode sub-report: bucketed batch launches vs per-(rg, column) loop
# ---------------------------------------------------------------------------

BATCH_COLS = ["l_extendedprice", "l_discount", "l_tax", "l_quantity"]


def batchdecode_setup(sf: float = 0.1):
    """A lineitem with SMALL row groups so a whole-table scan spans >= 32
    groups — the dispatch-amplification regime the batch path collapses."""
    d = os.path.join(DATA_DIR, f"tpch_batch_sf{sf}")
    if not os.path.exists(os.path.join(d, "lineitem.lake")):
        tpch.write_tables(d, sf=sf, seed=0, sorted_data=True,
                          row_group_size=1024)
    return LakeReader(os.path.join(d, "lineitem.lake"))


def _run_batchmode(reader, batch_decode: bool, cost_model,
                   tick_bytes=None) -> dict:
    from repro.kernels import ops

    def once():
        svc = DatapathService(
            engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
            policy=StaticPolicy("raw"), batch_decode=batch_decode,
            cost_model=cost_model, tick_bytes=tick_bytes,
        )
        svc.submit("t", reader, ScanPlan("lineitem", list(BATCH_COLS)))
        svc.drain()
        return svc

    once()  # warmup: jit compiles + file cache
    d0 = ops.dispatch_count()
    import time as _time
    t0 = _time.perf_counter()
    svc = once()
    wall = _time.perf_counter() - t0
    c = svc.telemetry.counters
    return {
        "dispatches": ops.dispatch_count() - d0,
        "wall_s": wall,
        "decode_launches": int(c.get("decode_launches", 0)),
        "batch_slices": int(c.get("batch_slices", 0)),
        "sim_serial_s": float(c.get("sim_pipe_serial_s",
                                    c.get("sim_fetch_serial_s", 0.0))),
        "sim_overlapped_s": float(c.get("sim_pipe_overlapped_s",
                                        c.get("sim_fetch_overlapped_s", 0.0))),
        "sim_saved_s": float(c.get("sim_pipe_saved_s",
                                   c.get("sim_fetch_saved_s", 0.0))),
    }


def run_batchdecode(sf: float = 0.1) -> dict:
    reader = batchdecode_setup(sf)
    assert reader.n_row_groups >= 32, reader.n_row_groups
    # calibrated-ish model (fast smoke) so the per-launch overhead term is
    # real and the slice-level pipeline numbers carry it
    cm = CostModel.calibrate(backend="ref", n=1 << 16, repeats=1)

    seq = _run_batchmode(reader, False, cm)
    bat = _run_batchmode(reader, True, cm)
    ratio = seq["dispatches"] / max(bat["dispatches"], 1)
    speedup = seq["wall_s"] / max(bat["wall_s"], 1e-9)
    row("service.batchdecode", bat["wall_s"],
        f"rgs={reader.n_row_groups};cols={len(BATCH_COLS)};"
        f"dispatch_seq={seq['dispatches']};dispatch_batch={bat['dispatches']}"
        f" ({ratio:.1f}x fewer);"
        f"wall_seq_s={seq['wall_s']:.3f};wall_batch_s={bat['wall_s']:.3f}"
        f" ({speedup:.2f}x)")

    # sliced dispatch: tick_bytes carves the scan into multiple WFQ slices
    # so the NEXT slice's fetch overlaps THIS slice's bucketed batch decode
    slice_bytes = reader.n_rows * 4 * len(BATCH_COLS) // 6
    seq_p = _run_batchmode(reader, False, cm, tick_bytes=slice_bytes)
    bat_p = _run_batchmode(reader, True, cm, tick_bytes=slice_bytes)
    row("service.batchdecode.pipeline", 0.0,
        f"slices={bat_p['batch_slices']};"
        f"pipe_overlapped_s={bat_p['sim_overlapped_s']:.5f}"
        f"/serial={bat_p['sim_serial_s']:.5f}"
        f" (fetch_hidden_s={bat_p['sim_saved_s']:.5f});"
        f"seq_overlapped_s={seq_p['sim_overlapped_s']:.5f}")
    return {
        "row_groups": reader.n_row_groups,
        "columns": len(BATCH_COLS),
        "dispatch_sequential": seq["dispatches"],
        "dispatch_batched": bat["dispatches"],
        "dispatch_ratio": ratio,
        "wall_sequential_s": seq["wall_s"],
        "wall_batched_s": bat["wall_s"],
        "wall_speedup": speedup,
        "decode_launches_sequential": seq["decode_launches"],
        "decode_launches_batched": bat["decode_launches"],
        "launch_overhead_s": cm.launch_overhead_s,
        "pipeline": {
            "batch_slices": bat_p["batch_slices"],
            "sim_serial_s": bat_p["sim_serial_s"],
            "sim_overlapped_s": bat_p["sim_overlapped_s"],
            "sim_saved_s": bat_p["sim_saved_s"],
            "sim_overlapped_sequential_s": seq_p["sim_overlapped_s"],
        },
    }


# Pre-rewrite decode-core rates: BENCH_service.json point 5 (c07f74a),
# the last calibration before the RLE/DELTA/DICT core rewrite.  The
# roofline row measures today's cores against this fixed anchor so the
# speedup claim survives future bench points shifting the history.
PRE_REWRITE_RATES_GBPS = {
    "rle": 0.004586833545906182,
    "delta": 0.01498013821972042,
    "dict": 0.04571737105787406,
    "bitpack": 0.0693417894320781,
}


def run_kernel_roofline() -> dict:
    """Rewritten-core rates vs the pre-rewrite anchor, plus the two-size
    ladder's pad-waste bytes against pow2 bucketing (launch counts are
    identical by construction — one dispatch per batch call either way —
    so pad bytes are the whole cost difference)."""
    from repro.kernels import ops
    from repro.lakeformat.encodings import PACK_BLOCK

    cm = CostModel.calibrate(backend="ref", n=1 << 16, repeats=1)
    speedup = {
        enc: cm.rates.get(enc, 0.0) / old
        for enc, old in PRE_REWRITE_RATES_GBPS.items()
    }
    # analytic pad sweep over the realistic multi-row-group range
    # (1..64 blocks per bucket), int32 PACK_BLOCK payloads
    blk_bytes = PACK_BLOCK * 4
    pad_ladder = sum(
        (ops.bucket_blocks(n, mode="ladder") - n) * blk_bytes
        for n in range(1, 65)
    )
    pad_pow2 = sum(
        (ops.bucket_blocks(n, mode="pow2") - n) * blk_bytes
        for n in range(1, 65)
    )
    rates_fmt = ";".join(
        f"{e}={cm.rates.get(e, 0.0):.4f}/{PRE_REWRITE_RATES_GBPS[e]:.4f}"
        f" ({speedup[e]:.1f}x)"
        for e in sorted(PRE_REWRITE_RATES_GBPS)
    )
    row("service.kernels.roofline", 0.0,
        f"source={cm.source};backend={cm.backend};"
        f"rates_new/old_gbps:{rates_fmt};"
        f"pad_bytes_ladder={pad_ladder};pad_bytes_pow2={pad_pow2}"
        f" ({pad_pow2 / max(pad_ladder, 1):.2f}x)")
    return {
        "source": cm.source,
        "backend": cm.backend,
        "rates_gbps": {e: cm.rates.get(e, 0.0)
                       for e in sorted(PRE_REWRITE_RATES_GBPS)},
        "pre_rewrite_rates_gbps": dict(PRE_REWRITE_RATES_GBPS),
        "speedup": speedup,
        "launch_overhead_s": cm.launch_overhead_s,
        "pad_bytes_ladder": pad_ladder,
        "pad_bytes_pow2": pad_pow2,
        "pad_bytes_ratio": pad_pow2 / max(pad_ladder, 1),
    }


# ---------------------------------------------------------------------------
# fabric sub-report: pod-sharded fleet — scaling, peer fetch, fairness, drain
# ---------------------------------------------------------------------------

FABRIC_RG_ROWS = 2048  # small groups so every fleet size splits the table


def fabric_setup(sf: float = 0.1):
    d = os.path.join(DATA_DIR, f"tpch_fabric_sf{sf}")
    if not os.path.exists(os.path.join(d, "lineitem.lake")):
        tpch.write_tables(d, sf=sf, seed=0, sorted_data=True,
                          row_group_size=FABRIC_RG_ROWS)
    return LakeReader(os.path.join(d, "lineitem.lake"))


def _fabric_busy_s(fab) -> dict:
    """Per-pod occupancy in SIMULATED seconds — the same scheduled +
    reconciled + retention currency the WFQ clocks charge.  Fleet
    makespan is the max (pods run concurrently in real deployments even
    though the bench ticks them serially)."""
    return {
        pid: (sum(fab.pods[pid].telemetry.tenant_sched_seconds.values())
              + sum(fab.pods[pid].telemetry.tenant_recon_seconds.values())
              + sum(fab.pods[pid].telemetry.tenant_retained_seconds.values()))
        for pid in fab.live_pods
    }


def _run_fleet(reader, n_pods: int) -> dict:
    from repro.datapath import ScanFabric

    fab = ScanFabric(n_pods=n_pods, policy=StaticPolicy("raw"))
    plans = [ScanPlan("lineitem", ["l_extendedprice", "l_quantity"]),
             ScanPlan("lineitem", ["l_discount", "l_tax"]),
             ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                      Cmp("l_quantity", "le", 25))]
    for t, plan in enumerate(plans):
        fab.submit(f"tenant{t}", reader, plan)
    fab.drain()
    busy = _fabric_busy_s(fab)
    makespan = max(busy.values()) if busy else 0.0
    decoded = sum(sum(fab.pods[p].telemetry.tenant_decoded_bytes.values())
                  for p in fab.live_pods)
    return {
        "busy_s": busy,
        "makespan_s": makespan,
        "decoded_bytes": int(decoded),
        "throughput_gbps": decoded / max(makespan, 1e-12) / 1e9,
    }


def _run_fabric_peer(reader) -> dict:
    """Scale-out reuse: a 2-pod fleet warms its decoded/encoded tiers, a
    third pod joins and steals arcs — its cold misses pull warm blocks
    from the old owners over the inter-pod hop instead of re-fetching
    storage, and the hop is billed into the tenant's WFQ clock."""
    from repro.datapath import ScanFabric

    cm = CostModel()
    fab = ScanFabric(n_pods=2, policy=StaticPolicy("preloaded"),
                     cost_model=cm)
    plan = ScanPlan("lineitem", ["l_extendedprice", "l_quantity"],
                    Cmp("l_quantity", "le", 25))
    fab.scan(reader, plan)  # warm the original owners
    new_pid = fab.add_pod()
    res = fab.scan(reader, plan)  # stolen arcs peer-fetch
    store = fab.pods[new_pid].store
    peer_bytes = int(store.peer_hit_bytes)
    peer_s = float(store.peer_hit_seconds)
    # storage equivalent pays the round trip PER BLOCK, same as the peer
    # hop does (fetch_seconds is affine, so hits * latency + bytes / bw
    # is the exact per-block sum)
    lm = cm.link_model()
    storage_equiv_s = (store.peer_hits * lm.latency_us * 1e-6
                       + peer_bytes / (lm.bandwidth_gbps * 1e9))
    return {
        "peer_hits": int(store.peer_hits),
        "peer_bytes": peer_bytes,
        "peer_s": peer_s,
        "storage_equiv_s": storage_equiv_s,
        "hop_speedup": storage_equiv_s / max(peer_s, 1e-12),
        "billed_bytes": int(res.stats.peer_bytes),
        "billed_to_wfq": float(
            fab.pods[new_pid].telemetry.tenant_peer_seconds.get("default", 0.0)
        ) > 0.0,
    }


def _run_fabric_skew(reader, relevel: bool) -> dict:
    """1 elephant / 3 mice across a 2-pod fleet.  Without the fleet-level
    re-level, each pod's WFQ clock sees only LOCAL consumption, so a
    tenant spread over N pods gets up to N fresh clocks; the re-level
    charges queued tenants their foreign occupancy every tick."""
    from repro.datapath import ScanFabric, jain_index

    fab = ScanFabric(n_pods=2, policy=StaticPolicy("raw"),
                     tick_bytes=int(FABRIC_RG_ROWS * 4 * 2 * 1.5),
                     reconcile_fairness=relevel)
    fab.submit("elephant", reader,
               ScanPlan("lineitem", ["l_extendedprice", "l_quantity"]))
    fab.submit("elephant", reader,
               ScanPlan("lineitem", ["l_discount", "l_tax"]))
    mice = [fab.submit(f"mouse{i}", reader,
                       ScanPlan("lineitem", ["l_extendedprice"],
                                Cmp("l_shipdate", "between", (d, d + 200))))
            for i, d in enumerate((300, 900, 1500))]
    done_tick = {}
    ticks = 0
    while fab.active:
        ticks += 1
        fab.tick()
        for i, m in enumerate(mice):
            if m.status == "done" and i not in done_tick:
                done_tick[i] = ticks
    occ = {}
    for pid in fab.live_pods:
        tel = fab.pods[pid].telemetry
        for t in tel.known_tenants():
            occ[t] = (occ.get(t, 0.0)
                      + tel.tenant_decoded_bytes.get(t, 0.0)
                      + tel.tenant_retained_bytes.get(t, 0.0))
    charged = sum(fab.pods[p].telemetry.counters.get("fleet_vtime_seconds", 0.0)
                  for p in fab.live_pods)
    return {
        "jain": jain_index(list(occ.values())),
        "tenant_bytes": {k: int(v) for k, v in sorted(occ.items())},
        "mice_p99_ticks": max(done_tick.values()) if done_tick else 0,
        "total_ticks": ticks,
        "fleet_vtime_charged_s": charged,
        # the mechanism itself: with the re-level each pod's elephant
        # clock carries the elephant's FLEET-wide consumption, not just
        # the local slice
        "elephant_vtime_s": max(
            fab.pods[p]._vtime.get("elephant", 0.0) for p in fab.live_pods
        ),
    }


def _run_fabric_drain(reader) -> dict:
    """Kill one of three pods mid-scan; the fabric re-partitions only the
    dead pod's uncollected sub-scans and the merged result must still be
    bit-identical to the single-node engine."""
    import numpy as np

    from repro.datapath import ScanFabric

    plan = ScanPlan("lineitem", ["l_extendedprice", "l_quantity"],
                    Cmp("l_quantity", "le", 25))
    want = DatapathEngine(backend="ref").scan(reader, plan)
    fab = ScanFabric(n_pods=3, policy=StaticPolicy("raw"),
                     tick_bytes=1 << 16)
    t = fab.submit("t0", reader, plan)
    fab.tick()
    victims = [s.pod_id for s in t.subs.values() if s.ticket.status == "queued"]
    if victims:
        fab.fail_pod(victims[0])
    fab.drain()
    identical = (
        int(t.result.count) == int(want.count)
        and np.array_equal(np.asarray(t.result.mask), np.asarray(want.mask))
        and all(np.array_equal(np.asarray(t.result.columns[c]),
                               np.asarray(want.columns[c]))
                for c in want.columns)
    )
    d = fab.report()["drains"]
    return {
        "killed": victims[0] if victims else None,
        "reassigned": d[-1]["reassigned"] if d else 0,
        "replayed": d[-1]["replayed"] if d else 0,
        "replays": t.replays,
        "bit_identical": bool(identical),
    }


def run_fabric(sf: float = 0.1) -> dict:
    reader = fabric_setup(sf)
    scaling = {n: _run_fleet(reader, n) for n in (1, 2, 4)}
    base = scaling[1]["throughput_gbps"]
    row("service.fabric.scaling", 0.0,
        ";".join(f"pods{n}={s['throughput_gbps']:.3f}GBps"
                 f" ({s['throughput_gbps'] / max(base, 1e-12):.2f}x)"
                 for n, s in sorted(scaling.items()))
        + f";rgs={reader.n_row_groups}")

    peer = _run_fabric_peer(reader)
    row("service.fabric.peer", peer["peer_s"],
        f"peer_bytes={peer['peer_bytes']};hits={peer['peer_hits']};"
        f"peer_s={peer['peer_s']:.6f}"
        f"/storage_equiv_s={peer['storage_equiv_s']:.6f}"
        f" ({peer['hop_speedup']:.2f}x);"
        f"billed_to_wfq={peer['billed_to_wfq']}")

    skew_on = _run_fabric_skew(reader, relevel=True)
    skew_off = _run_fabric_skew(reader, relevel=False)
    row("service.fabric.fairness", 0.0,
        f"mice_p99_ticks_relevel={skew_on['mice_p99_ticks']}"
        f"/local_clocks={skew_off['mice_p99_ticks']};"
        f"jain={skew_on['jain']:.4f};"
        f"elephant_vtime_relevel={skew_on['elephant_vtime_s']:.6f}"
        f"/local={skew_off['elephant_vtime_s']:.6f};"
        f"fleet_vtime_charged_s={skew_on['fleet_vtime_charged_s']:.6f}")

    drain = _run_fabric_drain(reader)
    row("service.fabric.drain", 0.0,
        f"killed={drain['killed']};reassigned={drain['reassigned']};"
        f"replayed={drain['replayed']};bit_identical={drain['bit_identical']}")

    return {
        "scaling": {f"pods{n}": s for n, s in sorted(scaling.items())},
        "throughput_speedup_4pod": scaling[4]["throughput_gbps"] / max(base, 1e-12),
        "peer": peer,
        "fairness_relevel": skew_on,
        "fairness_local_clocks": skew_off,
        "drain": drain,
    }


# ---------------------------------------------------------------------------
# faults sub-report: fault-free vs 1%/5% transient-error A/B — correctness
# (bit-identical, zero hangs), bounded p99 inflation, hedge tail win, shed
# rate under breaker-open pressure (DESIGN.md §17)
# ---------------------------------------------------------------------------

FAULT_MAX_TICKS = 4000  # hang guard for the bench drain loop


def _faults_workload(reader):
    return [ScanPlan("lineitem", ["l_extendedprice", "l_quantity"],
                     Cmp("l_quantity", "le", 25)),
            ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                     Cmp("l_shipdate", "between", (365, 729))),
            ScanPlan("lineitem", ["l_discount", "l_tax"]),
            ScanPlan("lineitem", ["l_quantity"],
                     Cmp("l_quantity", "le", 3))]


def _run_faulted(reader, rate: float, seed: int = 0):
    """One chaos pass: 4 tenants under a transient-error + latency-spike
    schedule at `rate`, hedged reads on.  Returns results + the metrics
    the A/B compares.  `hung` counts requests that never reached a
    terminal state inside the tick guard — the bar is zero."""
    from repro.datapath import FaultPlan, RetryPolicy

    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        fault_plan=FaultPlan(seed=seed, transient_rate=rate,
                             spike_rate=rate, spike_s=2e-3),
        retry_policy=RetryPolicy(max_attempts=10, hedge_after_s=1e-3),
    )
    plans = _faults_workload(reader)
    t0 = time.perf_counter()
    tickets = [svc.submit(f"tenant{t}", reader, p)
               for t, p in enumerate(plans)]
    for _ in range(FAULT_MAX_TICKS):
        svc.tick()
        if not svc.queue:
            break
    wall = time.perf_counter() - t0
    hung = sum(tk.status == "queued" for tk in tickets)
    results = [svc.result(tk) for tk in tickets if tk.status == "done"]
    snap = svc.telemetry.snapshot()
    f = snap["faults"]
    p99s = [v["p99_s"] for v in snap["tenants"].values()]
    return {
        "results": results,
        "wall_s": wall,
        "hung": int(hung),
        "p99_s": max(p99s) if p99s else 0.0,
        "retries": int(f["transient_errors"]),
        "retry_successes": int(f["retry_successes"]),
        "retries_exhausted": int(f["retries_exhausted"]),
        "hedged": int(f["hedged_fetches"]),
        "hedge_wins": int(f["hedge_wins"]),
        "hedge_saved_s": float(f["fault_seconds"].get("hedge_saved", 0.0)),
        "fault_wait_s": float(
            sum(f["tenant_fault_seconds"].values())),
    }


def _run_fault_shed(reader) -> dict:
    """Breaker-open pressure: a permanently failing storage target behind
    a small queue — the breaker trips, admission degrades, and past the
    shed threshold rejects with typed Overloaded instead of collapsing."""
    from repro.datapath import FaultPlan, Overloaded, RetryPolicy

    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
        max_queue_depth=4,
        fault_plan=FaultPlan(transient_rate=1.0, fail_forever=True),
        retry_policy=RetryPolicy(max_attempts=5),
    )
    plan = _faults_workload(reader)[0]
    submitted = shed = other_reject = 0
    for i in range(16):
        try:
            svc.submit("t0", reader, plan)
            submitted += 1
        except Overloaded:
            shed += 1
        except Exception:  # noqa: BLE001 — QueueFull etc., also typed
            other_reject += 1
        if i % 4 == 3:
            svc.tick()
    for _ in range(FAULT_MAX_TICKS):
        if not svc.queue:
            break
        svc.tick()
    br = svc.telemetry.snapshot()["faults"]
    return {
        "submitted": submitted,
        "shed": shed,
        "other_rejected": other_reject,
        "shed_rate": shed / max(shed + submitted + other_reject, 1),
        "breaker_trips": int(br["breaker_trips"]),
        "degraded_admits": int(br["breaker_degraded_admits"]),
    }


def run_faults(sf: float = 0.1) -> dict:
    reader = fabric_setup(sf)
    _run_faulted(reader, 0.0)  # warmup: jit compilation out of the A/B
    base = _run_faulted(reader, 0.0)
    runs = {"rate1pct": _run_faulted(reader, 0.01),
            "rate5pct": _run_faulted(reader, 0.05)}

    def _identical(a, b):
        import numpy as np
        if len(a) != len(b):
            return False
        return all(
            int(x.count) == int(y.count)
            and np.array_equal(np.asarray(x.mask), np.asarray(y.mask))
            and all(np.array_equal(np.asarray(x.columns[c]),
                                   np.asarray(y.columns[c]))
                    for c in y.columns)
            for x, y in zip(a, b))

    row("service.faults.baseline", base["wall_s"],
        f"p99_ms={base['p99_s'] * 1e3:.3f};hung={base['hung']}")
    report = {}
    for name, r in runs.items():
        identical = _identical(r["results"], base["results"])
        inflation = r["p99_s"] / max(base["p99_s"], 1e-12)
        row(f"service.faults.{name}", r["wall_s"],
            f"p99_ms={r['p99_s'] * 1e3:.3f};p99_inflation={inflation:.2f}x;"
            f"retries={r['retries']};recovered={r['retry_successes']};"
            f"exhausted={r['retries_exhausted']};"
            f"fault_wait_s={r['fault_wait_s']:.6f};"
            f"identical={identical};hung={r['hung']}")
        report[name] = {k: v for k, v in r.items() if k != "results"}
        report[name]["identical"] = identical
        report[name]["p99_inflation"] = inflation

    hedge = runs["rate5pct"]
    row("service.faults.hedge", 0.0,
        f"hedged={hedge['hedged']};wins={hedge['hedge_wins']};"
        f"tail_saved_s={hedge['hedge_saved_s']:.6f}")

    shed = _run_fault_shed(reader)
    row("service.faults.shed", 0.0,
        f"submitted={shed['submitted']};shed={shed['shed']};"
        f"shed_rate={shed['shed_rate']:.2f};trips={shed['breaker_trips']};"
        f"typed=Overloaded")

    report["baseline"] = {k: v for k, v in base.items() if k != "results"}
    report["hedge"] = {"hedged": hedge["hedged"],
                       "wins": hedge["hedge_wins"],
                       "tail_saved_s": hedge["hedge_saved_s"]}
    report["shed"] = shed
    return report


def run_pushdown(sf: float = 0.1) -> dict:
    """Fused operator pushdown (DESIGN.md §16) vs scan-then-aggregate on
    a grouped revenue sum: the fused path DMAs only the (n_groups,)
    accumulator set where the post-scan path ships the filtered value +
    group columns and mask across the hop and aggregates on the consumer
    side with the SAME kernel — result-DMA bytes are the paper's
    PCIe-hop currency, and because both paths launch the same decode
    buckets plus one aggregate kernel, the dispatch count must not
    grow."""
    import time as _time

    import numpy as np

    from repro.core import agg
    from repro.core.plan import AggSpec
    from repro.kernels import ops

    from repro.lakeformat.encodings import PACK_BLOCK

    reader = setup(sf)["lineitem"]
    pred = Cmp("l_shipdate", "between", (365, 729))
    aplan = ScanPlan(
        "lineitem", [], pred,
        aggregates=(AggSpec("sum", "l_extendedprice"), AggSpec("count")),
        group_by="l_returnflag",
    )
    rplan = ScanPlan("lineitem", ["l_extendedprice", "l_returnflag"], pred)
    eng = DatapathEngine(backend="ref")
    n_groups = len(reader.string_dicts["l_returnflag"])

    def fused():
        return eng.scan(reader, aplan, batched=True)

    def post_scan():
        """Same aggregation math and launch count, but DOWNSTREAM of the
        result DMA: the scan ships filtered value + group columns + mask,
        then one grouped_agg_batch launch reduces them consumer-side with
        the canonical per-row-group fold (so the answer is bit-identical
        and the only difference is WHERE the hop sits)."""
        res = eng.scan(reader, rplan, batched=True)
        L = int(np.asarray(res.mask).shape[0])
        nb = L // PACK_BLOCK
        vals = np.asarray(res.columns["l_extendedprice"]).reshape(nb, PACK_BLOCK)
        gids = np.asarray(res.columns["l_returnflag"]).astype(np.int32).reshape(nb, PACK_BLOCK)
        m2 = np.asarray(res.mask).astype(np.int32).reshape(nb, PACK_BLOCK)
        planes = ops.grouped_agg_batch(vals, gids, m2, n_groups, backend="ref")
        from repro.core.engine import padded_rows
        from repro.core.zonemap import prune_row_groups
        from repro.core.plan import bind_expr
        rgs = prune_row_groups(reader, bind_expr(pred, reader))
        segs = [padded_rows(reader.row_group_meta(rg)["n"]) // PACK_BLOCK
                for rg in rgs]
        parts, off = [], 0
        for seg in segs:
            parts.append(agg.fold_blocks(
                tuple(np.asarray(p)[off:off + seg] for p in planes), True))
            off += seg
        merged = {"l_extendedprice": agg.merge_partials(parts)}
        return res, agg.finalize(aplan.aggregates, merged, n_groups)

    fused(); post_scan()  # warmup: jit compiles + file cache
    d0 = ops.dispatch_count()
    t0 = _time.perf_counter()
    fres = fused()
    t_fused = _time.perf_counter() - t0
    d_fused = ops.dispatch_count() - d0

    d0 = ops.dispatch_count()
    t0 = _time.perf_counter()
    rres, host_aggs = post_scan()
    t_post = _time.perf_counter() - t0
    d_post = ops.dispatch_count() - d0

    # the comparison is only meaningful if both answer identically
    identical = all(
        np.array_equal(np.asarray(fres.aggregates[k]), host_aggs[k])
        for k in host_aggs)
    dma_ratio = rres.stats.result_bytes / max(fres.stats.result_bytes, 1)
    row("service.pushdown", t_fused,
        f"dma_fused={fres.stats.result_bytes}"
        f"/post_scan={rres.stats.result_bytes} ({dma_ratio:.0f}x less);"
        f"dispatch_fused={d_fused}/post_scan={d_post};"
        f"wall_fused_s={t_fused:.4f}/post_scan_s={t_post:.4f};"
        f"bit_identical={identical}")
    return {
        "result_bytes_fused": int(fres.stats.result_bytes),
        "result_bytes_post_scan": int(rres.stats.result_bytes),
        "dma_reduction": float(dma_ratio),
        "dispatch_fused": d_fused,
        "dispatch_post_scan": d_post,
        "wall_fused_s": t_fused,
        "wall_post_scan_s": t_post,
        "bit_identical": bool(identical),
    }


def run(sf: float = 0.1, n_tenants: int = 6) -> dict:
    readers = setup(sf)
    plans = tenant_plans(n_tenants)

    t_ind = timed(lambda: _run_independent(readers, plans))
    ind_fresh = _run_independent(readers, plans)

    t_svc = timed(lambda: _run_service(readers, plans))
    svc = _run_service(readers, plans)
    counters = svc.telemetry.counters
    svc_fresh = int(counters["decoded_bytes_fresh"])
    saved = int(counters["decoded_bytes_saved"])

    row("service.independent", t_ind, f"fresh_decoded_bytes={ind_fresh}")
    row("service.coalesced", t_svc,
        f"fresh_decoded_bytes={svc_fresh};pool_saved_bytes={saved}")
    ratio = ind_fresh / max(svc_fresh, 1)
    row("service.savings", 0.0,
        f"decode_ratio={ratio:.2f}x;tenants={n_tenants};speedup={t_ind/t_svc:.2f}x")

    # adaptive policy on a recurring mix: all six queries, three rounds
    svc_a = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        batch_per_tick=8,
        policy=AdaptiveOffloadPolicy(),
    )

    def mix(service=svc_a):
        for name in QUERIES:
            run_via_service(service, name, readers, tenant=name)

    t_first = timed(mix, repeats=1, warmup=0)
    t_steady = timed(mix, repeats=3, warmup=0)
    decisions = dict(svc_a.policy.decisions)
    row("service.adaptive.first", t_first, f"decisions={decisions}")
    row("service.adaptive.steady", t_steady,
        f"speedup={t_first/max(t_steady,1e-9):.2f}x;"
        f"prefiltered_hits={int(svc_a.telemetry.counters.get('prefiltered_hits', 0))}")
    snap = svc_a.telemetry.snapshot()
    p99s = {t: round(v["p99_s"] * 1e3, 3) for t, v in snap["tenants"].items()}
    row("service.latency", snap["tick_p50_s"],
        f"tick_p99_ms={snap['tick_p99_s']*1e3:.2f};tenant_p99_ms={p99s}")
    row("service.netsim", 0.0,
        f"fetch_serial_s={counters['sim_fetch_serial_s']:.4f};"
        f"fetch_overlapped_s={counters['sim_fetch_overlapped_s']:.4f}")

    fairness = run_fairness(sf)
    costmodel = run_costmodel(sf)
    blockstore = run_blockstore(sf)
    batchdecode = run_batchdecode(sf)
    pushdown = run_pushdown(sf)
    tracing = run_trace(sf)
    kernels = run_kernel_roofline()
    fabric = run_fabric(sf)
    faults = run_faults(sf)

    return {
        "fabric": fabric,
        "faults": faults,
        "pushdown": pushdown,
        "fairness": fairness,
        "costmodel": costmodel,
        "blockstore": blockstore,
        "batchdecode": batchdecode,
        "trace": tracing,
        "kernels": kernels,
        "n_tenants": n_tenants,
        "independent_fresh_decoded_bytes": ind_fresh,
        "service_fresh_decoded_bytes": svc_fresh,
        "pool_saved_bytes": saved,
        "decode_ratio": ratio,
        "t_independent_s": t_ind,
        "t_service_s": t_svc,
        "adaptive_first_s": t_first,
        "adaptive_steady_s": t_steady,
        "adaptive_decisions": decisions,
        "tick_p50_s": snap["tick_p50_s"],
        "tick_p99_s": snap["tick_p99_s"],
        "sim_fetch_serial_s": counters["sim_fetch_serial_s"],
        "sim_fetch_overlapped_s": counters["sim_fetch_overlapped_s"],
        "sim_fetch_saved_s": counters["sim_fetch_saved_s"],
    }


if __name__ == "__main__":
    run()

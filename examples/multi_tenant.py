"""N concurrent tenants sharing one SmartNIC datapath service.

Each tenant interleaves its own mix of the six TPC-H-style queries plus a
per-tenant revenue window scan; everything funnels through ONE
DatapathService with admission control, per-tenant quotas, shared-scan
coalescing and the adaptive offload policy.  One deliberately
under-provisioned tenant ("freeloader") demonstrates quota rejection.

    PYTHONPATH=src python examples/multi_tenant.py [--tenants 4] [--sf 0.05]
"""

import argparse

from repro.core import BlockCache, DatapathEngine, tpch
from repro.core.plan import Cmp, ScanPlan
from repro.core.queries import QUERIES, run_via_service
from repro.datapath import DatapathService, QuotaExceeded, TenantQuota
from repro.lakeformat.reader import LakeReader


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--sf", type=float, default=0.05)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args()

    # sorted + small row groups: window scans prune, and a row group is a
    # meaningful preemption quantum for the fair scheduler (phase 4)
    paths = tpch.write_tables(f"/tmp/tpch_mt_{args.sf}_rg8192", sf=args.sf, seed=0,
                              sorted_data=True, row_group_size=8192)
    readers = {k: LakeReader(p) for k, p in paths.items()}

    svc = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        batch_per_tick=2 * args.tenants,
        quotas={"freeloader": TenantQuota(max_bytes=10_000)},
    )

    qnames = list(QUERIES)
    rejected = 0

    # Phase 1 — a coalesced burst: every tenant's window scan lands in the
    # same tick, so shared row groups decode once for all of them.
    tickets = []
    for t in range(args.tenants):
        plan = ScanPlan(
            "lineitem",
            ["l_extendedprice", "l_discount"],
            Cmp("l_shipdate", "between", (200 + 50 * t, 564 + 50 * t)),
        )
        tickets.append((t, svc.submit(f"tenant{t}", readers["lineitem"], plan)))
    svc.drain()
    print("phase 1 — coalesced revenue-window burst:")
    for t, tk in tickets:
        print(f"  tenant{t}: {int(tk.result.count):6d} rows, "
              f"{tk.result.stats.pool_hits} shared decodes reused")

    # Phase 2 — steady mixed load through the service-client query path.
    for rnd in range(args.rounds):
        for t in range(args.tenants):
            name = qnames[(t + rnd) % len(qnames)]
            run_via_service(svc, name, readers, tenant=f"tenant{t}")

    # Phase 3 — an under-quota tenant is rejected at admission (no bytes move).
    try:
        svc.submit("freeloader", readers["lineitem"],
                   ScanPlan("lineitem", ["l_extendedprice"]))
    except QuotaExceeded as e:
        rejected += 1
        print(f"\nphase 3 — admission control: {e}")

    # Phase 4 — fair-share scheduling: a weight-2 elephant scan is sliced at
    # row-group granularity so equal-weight mice are never stuck behind it.
    rg_cost = 8192 * 4 * 2
    fair = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        quotas={"elephant": TenantQuota(weight=2.0)},
        tick_bytes=int(rg_cost * 1.5),
        hold_ticks=1,
    )
    el = fair.submit("elephant", readers["lineitem"],
                     ScanPlan("lineitem", ["l_extendedprice", "l_quantity"]))
    mice = [
        fair.submit(f"mouse{i}", readers["lineitem"],
                    ScanPlan("lineitem", ["l_extendedprice"],
                             Cmp("l_shipdate", "between", (300 + 600 * i, 500 + 600 * i))))
        for i in range(args.tenants - 1)
    ]
    fair.drain()
    fsnap = fair.telemetry.fairness(weights={"elephant": 2.0})
    print("\nphase 4 — weighted fair queueing (elephant weight=2):")
    print(f"  elephant: {el.done_tick - el.submitted_tick} ticks "
          f"({int(fair.telemetry.counters.get('split_scans', 0))} scans split across ticks)")
    for i, m in enumerate(mice):
        print(f"  mouse{i}:   {m.done_tick - m.submitted_tick} ticks")
    print(f"  decoded-byte shares    : "
          + " ".join(f"{t}={s:.2f}" for t, s in fsnap["tenant_share"].items()))
    print(f"  jain index (weighted)  : {fsnap['jain_index']:.3f}")

    # Phase 5 — window-retained decodes: a LATE partner arriving after a
    # compatible scan already ran (but within hold_ticks) serves its
    # overlapping row groups from the store's retained decoded tier instead
    # of re-decoding — the unified BlockStore's cross-tick payoff.
    lake = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        hold_ticks=2,
    )
    plan_early = ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                          Cmp("l_shipdate", "between", (300, 700)))
    plan_late = ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                         Cmp("l_shipdate", "between", (350, 750)))
    early = lake.submit("early", readers["lineitem"], plan_early)
    while early.status == "queued":  # held to its deadline, dispatches alone
        lake.tick()
    late = lake.submit("late", readers["lineitem"], plan_late)
    lake.drain()
    c5 = lake.telemetry.counters
    st5 = lake.store.stats()
    print("\nphase 5 — late partner vs the retained decoded tier (hold=2):")
    print(f"  late partner waited    : {late.done_tick - late.submitted_tick} tick(s)"
          f" (dispatched immediately against the window)")
    print(f"  retained reuse         : {int(c5.get('retained_reuse_bytes', 0)):,} bytes"
          f" ({int(c5.get('retained_hits', 0))} blocks,"
          f" {c5.get('retained_redecode_saved_s', 0.0)*1e6:.1f}us re-decode saved)")
    print(f"  retention billed       : {c5.get('retained_charge_seconds', 0.0)*1e6:.1f}us"
          f" of vtime to the holder")
    print(f"  store ledger           : window_hits={st5['window_hits']} " + " ".join(
        f"{t}={v['hits']}h/{v['evictions']}e" for t, v in st5["tiers"].items()))

    # Phase 6 — the flight recorder: re-run the elephant/mice skew with
    # per-request span tracing, dump a Perfetto-loadable timeline of the
    # whole run, and print each tenant's decode/filter/rest split next to
    # the paper's Fig. 2 anchor (46% decode / 17% filter).
    rec = DatapathService(
        engine=DatapathEngine(backend="ref", cache=BlockCache(4 << 30)),
        quotas={"elephant": TenantQuota(weight=2.0)},
        tick_bytes=int(rg_cost * 1.5),
        hold_ticks=1,
        trace_capacity=32,
    )
    rec.submit("elephant", readers["lineitem"],
               ScanPlan("lineitem", ["l_extendedprice", "l_quantity"]))
    for i in range(args.tenants - 1):
        rec.submit(f"mouse{i}", readers["lineitem"],
                   ScanPlan("lineitem", ["l_extendedprice"],
                            Cmp("l_shipdate", "between",
                                (300 + 600 * i, 500 + 600 * i))))
    rec.drain()
    trep = rec.telemetry.trace_report()
    trace_path = "/tmp/multi_tenant_trace.json"
    n_events = rec.tracer.recorder.save_chrome_trace(trace_path)
    print("\nphase 6 — flight recorder (per-request span tracing):")
    print(f"  requests traced        : {trep['recorded']}/{trep['completed']}"
          f" (ring capacity {trep['capacity']})")
    print(f"  timeline export        : {trace_path} ({n_events} events —"
          f" load in ui.perfetto.dev)")
    print("  stage attribution (host seconds; launches are asynchronous, so")
    print("  a stage holds its host work and any device wait that falls in it):")
    print(f"    {'tenant':10s} {'n':>3s} {'wall':>8s} {'decode':>8s} {'filter':>8s}"
          f" {'fetch':>8s} {'wait':>8s}")
    rows = list(trep["by_tenant"].items()) + [
        ("fleet", {"n": trep["recorded"], "wall_s": trep["wall_s"],
                   "stage_s": trep["stage_s"]})]
    for t, bt in rows:
        st = bt["stage_s"]
        print(f"    {t:10s} {bt['n']:3d} {bt['wall_s']:8.4f} {st['decode']:8.4f}"
              f" {st['filter']:8.4f} {st['fetch']:8.4f}"
              f" {st['wfq_wait'] + st['hold_window']:8.4f}")

    snap = svc.telemetry.snapshot()
    c = snap["counters"]
    print("\nservice telemetry")
    print(f"  admitted/completed     : {int(c.get('admitted', 0))}/{int(c.get('completed', 0))}"
          f"  (rejected: {rejected})")
    print(f"  queue depth max/mean   : {snap['queue_depth_max']}/{snap['queue_depth_mean']:.1f}")
    print(f"  coalesced groups       : {int(c.get('coalesced_groups', 0))}"
          f" ({int(c.get('coalesced_requests', 0))} requests)")
    print(f"  decoded bytes          : {int(c.get('decoded_bytes', 0)):,}"
          f" (fresh {int(c.get('decoded_bytes_fresh', 0)):,},"
          f" pool-saved {int(c.get('decoded_bytes_saved', 0)):,})")
    print(f"  offload decisions      : raw={int(c.get('offload_raw', 0))}"
          f" preloaded={int(c.get('offload_preloaded', 0))}"
          f" prefiltered={int(c.get('offload_prefiltered', 0))}"
          f" (prefiltered hits {int(c.get('prefiltered_hits', 0))})")
    print(f"  tick latency p50/p99   : {snap['tick_p50_s']*1e3:.1f}ms"
          f" / {snap['tick_p99_s']*1e3:.1f}ms")
    print(f"  netsim fetch serial    : {c.get('sim_fetch_serial_s', 0)*1e3:.2f}ms"
          f" -> overlapped {c.get('sim_fetch_overlapped_s', 0)*1e3:.2f}ms")
    print("  per-tenant latency (p50/p99 ms):")
    for t, v in sorted(snap["tenants"].items()):
        print(f"    {t:10s} n={v['n']:3d}  {v['p50_s']*1e3:8.1f} / {v['p99_s']*1e3:8.1f}")


if __name__ == "__main__":
    main()

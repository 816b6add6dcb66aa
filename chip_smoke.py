#!/usr/bin/env python3
"""Bring-up smoke test: the multi-tenant scan service on one TPU.

Generates TPC-H data from `--seed` at SF1 row counts (6,000,000 lineitem
rows; this generator's sf=10), then drives the served path through the
entry points a user calls: one `Pod` over `DatapathEngine(backend="auto")`
runs the six queries of `repro.core.queries` via `run_via_service`, four
tenants submit concurrent Q6 scans that the scheduler stacks into one
bucket pass, and two pushed-down aggregate scans run the grouped and the
fused aggregate kernels.  Every answer is checked against a numpy oracle
over the generator's arrays.

    python3 chip_smoke.py                 # one chip
    python3 chip_smoke.py --chips 4       # ScanFabric(n_pods=4) vs one pod
    python3 chip_smoke.py --allow-cpu --sf 0.05   # CPU rehearsal

Exits non-zero, printing no result, unless JAX finds a TPU (or
`--allow-cpu` is given).  Per-phase times are host wall seconds of this
smoke run, not device metrics.  The last line of stdout is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.core import tpch  # noqa: E402
from repro.core.engine import DatapathEngine  # noqa: E402
from repro.core.plan import AggSpec, Cmp, ScanPlan  # noqa: E402
from repro.core.queries import (  # noqa: E402
    QUERIES, _Q19_BRANCHES, q6_answer, q6_plan, run_via_service)
from repro.datapath.fabric import ScanFabric  # noqa: E402
from repro.datapath.service import Pod  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.lakeformat.reader import LakeReader  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

RTOL = 1e-3  # tests/test_queries.py's relative tolerance on float sums
Q6_YEARS = (365, 730, 1095, 1460)  # one concurrent Q6 per tenant


class Compiles:
    """Counts backend compile requests in this process (a persistent-cache
    hit is still one, timed as its load) and the cache hits among them."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Checks:
    def __init__(self):
        self.n = 0
        self.failed = []
        self.worst = (0.0, "")  # largest relative error of a float check

    def __call__(self, ok: bool, what: str) -> None:
        self.n += 1
        if not ok:
            self.failed.append(what)
            print(f"FAIL {what}", flush=True)

    def close(self, name, got, want, rtol=RTOL):
        err = abs(got - want) / max(abs(want), 1.0)
        self.worst = max(self.worst, (err, name))
        self(err <= rtol, f"{name}: got {got!r}, oracle {want!r}")


def phase(name, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    print(f"phase {name}: {time.perf_counter() - t0:.3f} s smoke wall time "
          "(host clock, not a device metric)", flush=True)
    return out


# ---------------------------------------------------------------------------
# numpy oracles over tpch.gen_tables' arrays
# ---------------------------------------------------------------------------


def oracle_arrays(data):
    li, part, orders = data["lineitem"], data["part"], data["orders"]
    a = {k: (np.asarray(v) if isinstance(v, list) else v) for k, v in li.items()}
    a["rev"] = (a["l_extendedprice"] * (1 - a["l_discount"])).astype(np.float64)
    a["p_type"] = np.asarray(part["p_type"])
    a["p_brand"] = np.asarray(part["p_brand"])
    a["p_container"] = np.asarray(part["p_container"])
    a["p_size"] = part["p_size"]
    a["o_orderpriority"] = np.asarray(orders["o_orderpriority"])
    return a


def check_q1(chk, r, a):
    m = a["l_shipdate"] <= 2556 - 90
    price = a["l_extendedprice"].astype(np.float64)
    disc_price = price * (1 - a["l_discount"])
    cols = {"sum_qty": a["l_quantity"].astype(np.float64), "sum_base_price": price,
            "sum_disc_price": disc_price, "sum_charge": disc_price * (1 + a["l_tax"])}
    groups = {(rf, ls) for rf in tpch.RETURNFLAGS for ls in tpch.LINESTATUS}
    chk(set(r) == groups, f"q1 groups {sorted(r)}")
    for (rf, ls), row in r.items():
        sel = m & (a["l_returnflag"] == rf) & (a["l_linestatus"] == ls)
        chk(row["count"] == sel.sum(), f"q1 {rf}{ls} count {row['count']} != {sel.sum()}")
        for k, v in cols.items():
            chk.close(f"q1 {rf}{ls} {k}", row[k], float(v[sel].sum()))


def check_q6(chk, r, a, year_start=365):
    m = ((a["l_shipdate"] >= year_start) & (a["l_shipdate"] <= year_start + 364)
         & (a["l_discount"] >= 0.05 - 1e-4) & (a["l_discount"] <= 0.07 + 1e-4)
         & (a["l_quantity"] < 24))
    chk(r["rows"] == int(m.sum()), f"q6@{year_start} rows {r['rows']} != {m.sum()}")
    want = float((a["l_extendedprice"][m].astype(np.float64) * a["l_discount"][m]).sum())
    chk.close(f"q6@{year_start} revenue", r["revenue"], want)


def check_q12(chk, r, a):
    p = a["o_orderpriority"][a["l_orderkey"]]
    high = np.char.startswith(p, "1-") | np.char.startswith(p, "2-")
    d = a["l_receiptdate"]
    for mode in ("MAIL", "SHIP"):
        m = (a["l_shipmode"] == mode) & (d >= 730) & (d <= 730 + 364)
        want = {"high": int((m & high).sum()), "low": int((m & ~high).sum())}
        chk(r[mode] == want, f"q12 {mode} {r[mode]} != {want}")


def check_q14(chk, r, a):
    m = (a["l_shipdate"] >= 1000) & (a["l_shipdate"] <= 1029)
    promo = np.char.startswith(a["p_type"][a["l_partkey"][m]], "PROMO")
    rev = a["rev"][m]
    pct = 100.0 * rev[promo].sum() / rev.sum()
    chk(abs(r["promo_revenue_pct"] - pct) < 0.2,  # tests/test_queries.py's bound
        f"q14 promo_revenue_pct {r['promo_revenue_pct']} vs {pct}")
    chk.close("q14 total_revenue", r["total_revenue"], float(rev.sum()))


def check_q15(chk, r, a):
    m = (a["l_shipdate"] >= 365) & (a["l_shipdate"] <= 454)
    per = np.zeros(int(a["l_suppkey"].max()) + 1)
    np.add.at(per, a["l_suppkey"][m], a["rev"][m])
    chk(r["suppkey"] == int(per.argmax()), f"q15 suppkey {r['suppkey']} != {per.argmax()}")
    chk.close("q15 revenue", r["revenue"], float(per.max()))


def check_q19(chk, r, a):
    pk, qty = a["l_partkey"], a["l_quantity"]
    brand, cont, size = a["p_brand"][pk], a["p_container"][pk], a["p_size"][pk]
    keep = np.zeros(pk.shape, bool)
    for b, containers, qlo, qhi, shi in _Q19_BRANCHES:
        keep |= ((brand == b) & np.isin(cont, containers) & (qty >= qlo) & (qty <= qhi)
                 & (size >= 1) & (size <= shi))
    m = (keep & (qty >= 1) & (qty <= 30)
         & (a["l_shipinstruct"] == "DELIVER IN PERSON")
         & np.isin(a["l_shipmode"], ("AIR", "REG AIR")))
    chk(r["rows"] == int(m.sum()), f"q19 rows {r['rows']} != {m.sum()}")
    chk.close("q19 revenue", r["revenue"], float(a["rev"][m].sum()))


CHECKS = {"q1": check_q1, "q6": check_q6, "q12": check_q12, "q14": check_q14,
          "q15": check_q15, "q19": check_q19}

# pushed-down aggregates: grouped (grouped_agg kernel) and ungrouped over a
# BITPACK column no query above reads, so no decoded copy of it is cached
# and its pages go to the fused decode->aggregate kernel
AGG_PRED = Cmp("l_shipdate", "between", (365, 729))
AGG_GROUPED = ScanPlan("lineitem", [], AGG_PRED, group_by="l_returnflag",
                       aggregates=(AggSpec("sum", "l_extendedprice"), AggSpec("count")))
AGG_FUSED = ScanPlan("lineitem", [], AGG_PRED,
                     aggregates=(AggSpec("sum", "l_commitdate"), AggSpec("count")))


def check_aggs(chk, grouped, fused, a, flags):
    m = (a["l_shipdate"] >= 365) & (a["l_shipdate"] <= 729)
    for g, rf in enumerate(flags):
        sel = m & (a["l_returnflag"] == rf)
        chk(int(grouped["count(*)"][g]) == int(sel.sum()),
            f"agg count {rf} {grouped['count(*)'][g]} != {sel.sum()}")
        chk.close(f"agg sum(l_extendedprice) {rf}", float(grouped["sum(l_extendedprice)"][g]),
                  float(a["l_extendedprice"][sel].astype(np.float64).sum()))
    chk(int(fused["count(*)"][0]) == int(m.sum()), f"agg count {fused['count(*)']}")
    chk(int(fused["sum(l_commitdate)"][0]) == int(a["l_commitdate"][m].sum()),
        f"agg sum(l_commitdate) {fused['sum(l_commitdate)']} != {a['l_commitdate'][m].sum()}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def run_queries(scanner, readers):
    """All six queries; `scanner` is a Pod (through its service client)
    or anything engine-compatible (a ScanFabric)."""
    if isinstance(scanner, Pod):
        return {n: run_via_service(scanner, n, readers, tenant="smoke") for n in QUERIES}
    return {n: q(scanner, readers) for n, q in QUERIES.items()}


def concurrent_q6(pod, readers):
    """Four tenants' Q6 scans admitted before any tick, so the scheduler
    stacks their row groups into one cross-request bucket pass."""
    tickets = [pod.submit(f"tenant{i}", readers["lineitem"], q6_plan(y))
               for i, y in enumerate(Q6_YEARS)]
    pod.drain()
    return tickets


def agg_scans(pod, readers):
    client = pod.client("agg")
    return [client.scan(readers["lineitem"], p).aggregates for p in (AGG_GROUPED, AGG_FUSED)]


def array_devices(pod, readers):
    """Devices holding one scan result of this pod."""
    res = pod.client("where").scan(readers["part"], ScanPlan("part", ["p_size"]))
    return sorted(str(d) for d in res.columns["p_size"].devices())


def single_chip(chk, readers, a):
    pod = Pod(engine=DatapathEngine(backend="auto"))
    answers = phase("six queries via one Pod", run_queries, pod, readers)
    for name, r in answers.items():
        CHECKS[name](chk, r, a)
    tickets = phase("4 tenants x concurrent Q6", concurrent_q6, pod, readers)
    for tk, y in zip(tickets, Q6_YEARS):
        chk(tk.status == "done" and tk.error is None, f"q6@{y} ticket {tk.status} {tk.error!r}")
        if tk.result is not None:
            check_q6(chk, q6_answer(tk.result), a, y)
    grouped, fused = phase("pushed-down aggregates", agg_scans, pod, readers)
    check_aggs(chk, grouped, fused, a, readers["lineitem"].string_dicts["l_returnflag"])
    tel = pod.telemetry.counters
    chk(tel.get("failed", 0) == 0, f"failed tickets: {tel.get('failed')}")
    chk(tel.get("xreq_fallback", 0) == 0, f"xreq_fallback {tel.get('xreq_fallback')}")
    chk(tel.get("xreq_requests", 0) >= len(Q6_YEARS),
        f"cross-request stacking: xreq_requests {tel.get('xreq_requests', 0)}")
    print("pod telemetry: " + " ".join(
        f"{k}={int(tel.get(k, 0))}" for k in ("completed", "failed", "xreq_groups",
                                              "xreq_requests", "xreq_fallback",
                                              "decode_launches")), flush=True)


def four_chips(chk, readers, a):
    pod = Pod(engine=DatapathEngine(backend="auto"))
    want = phase("six queries via one Pod", run_queries, pod, readers)
    fabric = ScanFabric(n_pods=4, backend="auto")
    got = phase("six queries via ScanFabric(n_pods=4)", run_queries, fabric, readers)
    for name in QUERIES:
        CHECKS[name](chk, got[name], a)
        chk(_same(got[name], want[name]), f"{name} fabric {got[name]} != one pod {want[name]}")
    for pid in fabric.live_pods:
        print(f"{pid} arrays on {array_devices(fabric.pod(pid), readers)}", flush=True)


def _same(x, y, rtol=RTOL):
    if isinstance(x, dict):
        return x.keys() == y.keys() and all(_same(x[k], y[k], rtol) for k in x)
    if isinstance(x, float):
        return abs(x - y) <= rtol * max(abs(y), 1.0)
    return x == y


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sf", type=float, default=10.0,
                    help="generator scale; 10 = 6,000,000 lineitem rows (TPC-H SF1)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU (never reports a TPU)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: no TPU found (jax.devices()[0].platform={dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} devices",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    compiles = Compiles()
    chk = Checks()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)
    if dev.platform == "tpu":
        chk(ops._resolve("auto") == ("pallas", False),
            f"backend auto resolves to {ops._resolve('auto')}")

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        paths = phase("write tables", tpch.write_tables, d, args.sf, args.seed)
        data = phase("generate oracle arrays", tpch.gen_tables, args.sf, args.seed)
        readers = {k: LakeReader(p) for k, p in paths.items()}
        a = oracle_arrays(data)
        print(f"lineitem rows: {readers['lineitem'].n_rows}", flush=True)
        launches0 = ops.dispatch_count()
        (four_chips if args.chips == 4 else single_chip)(chk, readers, a)
    print(f"compiles: {compiles.n} ({compiles.cache_hits} from the persistent cache; "
          f"{compiles.seconds:.3f} s backend compile or cache load); "
          f"kernel launches: {ops.dispatch_count() - launches0}; "
          f"total {time.perf_counter() - t0:.3f} s smoke wall time", flush=True)
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache {cache_dir}: {n_cached} entries", flush=True)
    print(f"oracle checks: {chk.n - len(chk.failed)}/{chk.n} passed; largest relative "
          f"error {chk.worst[0]:.3g} ({chk.worst[1]}), limit {RTOL}", flush=True)
    if chk.failed:
        print(f"chip_smoke: {len(chk.failed)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

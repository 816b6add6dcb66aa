"""Mini analytical query suite over the datapath engine ("the DuckDB host").

Six TPC-H-shaped queries spanning the paper's spectrum (Fig. 2):
scan-heavy (Q6, Q14, Q15 — decode+filter dominate) through aggregation/
join-heavy (Q1, Q12, Q19).  Every filtered scan is pushed down to the
DatapathEngine; the host side only sees pre-filtered columns, masks and
counts.  Joins where the build side fits on-chip are expressed as device
gathers against the engine-decoded build table, and Q19 uses a pushed-down
bloom semijoin — the two streaming-join forms the paper's SmartNIC engine
supports.

Each query returns plain floats/dicts so results can be asserted against
the numpy oracles in tests/test_queries.py.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.core.engine import DatapathEngine
from repro.core.plan import And, BloomProbe, Cmp, InSet, Or, ScanPlan, and_, or_
from repro.kernels import ops

EPS = 1e-4  # float32 predicate tolerance on 2-decimal columns


def _msum(x, mask):
    return jnp.sum(jnp.where(mask, x.astype(jnp.float32), 0.0))


# ---------------------------------------------------------------------------
# Q1 — pricing summary report (aggregation-heavy)
# ---------------------------------------------------------------------------


def q1(engine: DatapathEngine, readers: Dict, delta_days: int = 90) -> dict:
    r = readers["lineitem"]
    plan = ScanPlan(
        "lineitem",
        ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
         "l_discount", "l_tax"],
        Cmp("l_shipdate", "le", 2556 - delta_days),
    )
    res = engine.scan(r, plan)
    c, m = res.columns, res.mask
    gid = c["l_returnflag"] * 2 + c["l_linestatus"]  # codes are small ints
    ngroups = 6
    onehot = (gid[:, None] == jnp.arange(ngroups)[None, :]) & m[:, None]
    ohf = onehot.astype(jnp.float32)
    disc_price = c["l_extendedprice"] * (1 - c["l_discount"])
    charge = disc_price * (1 + c["l_tax"])
    sums = {
        "sum_qty": ohf.T @ c["l_quantity"].astype(jnp.float32),
        "sum_base_price": ohf.T @ c["l_extendedprice"],
        "sum_disc_price": ohf.T @ disc_price,
        "sum_charge": ohf.T @ charge,
        "count": jnp.sum(onehot, axis=0).astype(jnp.float32),
    }
    rf_dict = r.string_dicts["l_returnflag"]
    ls_dict = r.string_dicts["l_linestatus"]
    out = {}
    for rf in range(min(3, len(rf_dict))):
        for ls in range(min(2, len(ls_dict))):
            g = rf * 2 + ls
            cnt = float(sums["count"][g])
            if cnt == 0:
                continue
            out[(rf_dict[rf], ls_dict[ls])] = {
                k: float(v[g]) for k, v in sums.items()
            }
    return out


# ---------------------------------------------------------------------------
# Q6 — forecasting revenue change (scan-heavy: pure filter + sum)
# ---------------------------------------------------------------------------


def q6_plan(year_start: int = 365) -> ScanPlan:
    return ScanPlan(
        "lineitem",
        ["l_extendedprice", "l_discount"],
        and_(
            Cmp("l_shipdate", "between", (year_start, year_start + 364)),
            Cmp("l_discount", "between", (0.05 - EPS, 0.07 + EPS)),
            Cmp("l_quantity", "lt", 24),
        ),
    )


def q6_answer(res) -> dict:
    """Q6's answer from the ScanResult of `q6_plan` (callers that submit
    the scan themselves, e.g. several tenants at once, finish with this)."""
    rev = _msum(res.columns["l_extendedprice"] * res.columns["l_discount"], res.mask)
    return {"revenue": float(rev), "rows": int(res.count)}


def q6(engine: DatapathEngine, readers: Dict, year_start: int = 365) -> dict:
    return q6_answer(engine.scan(readers["lineitem"], q6_plan(year_start)))


# ---------------------------------------------------------------------------
# Q12 — shipping modes and order priority (join via on-chip build side)
# ---------------------------------------------------------------------------


def q12(engine: DatapathEngine, readers: Dict, year_start: int = 730) -> dict:
    ro, rl = readers["orders"], readers["lineitem"]
    # Build side: whole orders priority column, decoded in the datapath.
    build = engine.scan(ro, ScanPlan("orders", ["o_orderkey", "o_orderpriority"]))
    prio = build.columns["o_orderpriority"]  # dense by orderkey (generator invariant)

    plan = ScanPlan(
        "lineitem",
        ["l_orderkey", "l_shipmode"],
        and_(
            InSet("l_shipmode", ("MAIL", "SHIP")),
            Cmp("l_receiptdate", "between", (year_start, year_start + 364)),
        ),
    )
    res = engine.scan(rl, plan)
    c, m = res.columns, res.mask
    l_prio = jnp.take(prio, c["l_orderkey"].astype(jnp.int32), mode="clip")
    pr_dict = ro.string_dicts["o_orderpriority"]
    high_codes = [i for i, s in enumerate(pr_dict) if s.startswith(("1-", "2-"))]
    is_high = jnp.zeros(l_prio.shape, jnp.bool_)
    for hc in high_codes:
        is_high = is_high | (l_prio == hc)
    out = {}
    sm_dict = rl.string_dicts["l_shipmode"]
    for mode in ("MAIL", "SHIP"):
        code = sm_dict.index(mode)
        sel = m & (c["l_shipmode"] == code)
        out[mode] = {
            "high": int(jnp.sum(sel & is_high)),
            "low": int(jnp.sum(sel & ~is_high)),
        }
    return out


# ---------------------------------------------------------------------------
# Q14 — promotion effect (join + arithmetic projection; scan-heavy)
# ---------------------------------------------------------------------------


def q14(engine: DatapathEngine, readers: Dict, month_start: int = 1000) -> dict:
    rp, rl = readers["part"], readers["lineitem"]
    build = engine.scan(rp, ScanPlan("part", ["p_partkey", "p_type"]))
    type_codes = build.columns["p_type"]  # dense by partkey
    tdict = rp.string_dicts["p_type"]
    promo = jnp.asarray(
        np.array([s.startswith("PROMO") for s in tdict], dtype=np.bool_)
    )
    part_is_promo = jnp.take(promo, type_codes.astype(jnp.int32), mode="clip")

    plan = ScanPlan(
        "lineitem",
        ["l_partkey", "l_extendedprice", "l_discount"],
        Cmp("l_shipdate", "between", (month_start, month_start + 29)),
    )
    res = engine.scan(rl, plan)
    c, m = res.columns, res.mask
    rev = c["l_extendedprice"] * (1 - c["l_discount"])
    is_promo = jnp.take(part_is_promo, c["l_partkey"].astype(jnp.int32), mode="clip")
    promo_rev = _msum(rev, m & is_promo)
    total_rev = _msum(rev, m)
    return {
        "promo_revenue_pct": float(100.0 * promo_rev / jnp.maximum(total_rev, 1e-9)),
        "total_revenue": float(total_rev),
    }


# ---------------------------------------------------------------------------
# Q15 — top supplier (scan-heavy + group-by)
# ---------------------------------------------------------------------------


def q15(engine: DatapathEngine, readers: Dict, quarter_start: int = 365, n_supp: int = None) -> dict:
    rl = readers["lineitem"]
    plan = ScanPlan(
        "lineitem",
        ["l_suppkey", "l_extendedprice", "l_discount"],
        Cmp("l_shipdate", "between", (quarter_start, quarter_start + 89)),
    )
    res = engine.scan(rl, plan)
    c, m = res.columns, res.mask
    if n_supp is None:
        n_supp = int(rl.zonemaps("l_suppkey")[0]["max"]) + 1
        for zm in rl.zonemaps("l_suppkey"):
            n_supp = max(n_supp, int(zm["max"]) + 1)
    rev = jnp.where(m, c["l_extendedprice"] * (1 - c["l_discount"]), 0.0)
    per_supp = jnp.zeros((n_supp,), jnp.float32).at[
        c["l_suppkey"].astype(jnp.int32)
    ].add(rev, mode="drop")
    best = int(jnp.argmax(per_supp))
    return {"suppkey": best, "revenue": float(per_supp[best])}


# ---------------------------------------------------------------------------
# Q19 — discounted revenue (disjunctive predicate + bloom semijoin pushdown)
# ---------------------------------------------------------------------------

_Q19_BRANCHES = [
    # (brand, containers, qty_lo, qty_hi, size_hi)
    ("Brand#12", ("SM CASE", "SM BOX", "SM PACK", "SM PKG"), 1, 11, 5),
    ("Brand#23", ("MED BOX", "MED PACK", "MED PKG", "MED CASE"), 10, 20, 10),
    ("Brand#34", ("LG CASE", "LG BOX", "LG PACK", "LG PKG"), 20, 30, 15),
]


def q19(engine: DatapathEngine, readers: Dict) -> dict:
    rp, rl = readers["part"], readers["lineitem"]

    # Build side: parts matching ANY branch -> bloom of partkeys (pushdown),
    # plus dense per-part attributes for the exact residual check.
    part_pred = or_(
        *[
            and_(Cmp("p_brand", "eq", b), InSet("p_container", c), Cmp("p_size", "le", s))
            for b, c, _, _, s in _Q19_BRANCHES
        ]
    )
    build = engine.scan(
        rp, ScanPlan("part", ["p_partkey"], part_pred, compact=True)
    )
    keys = build.columns["p_partkey"].astype(jnp.int32)
    nkeys = int(build.count)
    bloom = ops.bloom_build(keys[:nkeys], n_bits=1 << 15)

    attrs = engine.scan(rp, ScanPlan("part", ["p_brand", "p_container", "p_size"]))
    p_brand, p_cont, p_size = (
        attrs.columns["p_brand"],
        attrs.columns["p_container"],
        attrs.columns["p_size"],
    )

    plan = ScanPlan(
        "lineitem",
        ["l_partkey", "l_quantity", "l_extendedprice", "l_discount"],
        and_(
            BloomProbe("l_partkey", n_bits=1 << 15, name="q19"),
            Cmp("l_quantity", "between", (1, 30)),
            InSet("l_shipinstruct", ("DELIVER IN PERSON",)),
            InSet("l_shipmode", ("AIR", "REG AIR")),
        ),
    )
    res = engine.scan(rl, plan, blooms={"q19": bloom})
    c, m = res.columns, res.mask
    pk = c["l_partkey"].astype(jnp.int32)
    lb = jnp.take(p_brand, pk, mode="clip")
    lc = jnp.take(p_cont, pk, mode="clip")
    ls = jnp.take(p_size, pk, mode="clip")

    bdict = rp.string_dicts["p_brand"]
    cdict = rp.string_dicts["p_container"]
    keep = jnp.zeros(m.shape, jnp.bool_)
    for brand, containers, qlo, qhi, shi in _Q19_BRANCHES:
        bcode = bdict.index(brand) if brand in bdict else -1
        ccodes = [cdict.index(x) for x in containers if x in cdict]
        cm = jnp.zeros(m.shape, jnp.bool_)
        for cc in ccodes:
            cm = cm | (lc == cc)
        keep = keep | (
            (lb == bcode) & cm & (c["l_quantity"] >= qlo) & (c["l_quantity"] <= qhi)
            & (ls >= 1) & (ls <= shi)
        )
    rev = _msum(c["l_extendedprice"] * (1 - c["l_discount"]), m & keep)
    return {"revenue": float(rev), "rows": int(jnp.sum(m & keep))}


QUERIES = {"q1": q1, "q6": q6, "q12": q12, "q14": q14, "q15": q15, "q19": q19}
SCAN_HEAVY = ("q6", "q14", "q15")
AGG_HEAVY = ("q1", "q12", "q19")


# ---------------------------------------------------------------------------
# Service-client path: run any query through the shared DatapathService
# ---------------------------------------------------------------------------


def run_via_service(service, name: str, readers: Dict, tenant: str = "default", **kwargs):
    """Run one of the six queries through a repro.datapath.DatapathService.

    The service client is engine-compatible (`.scan(reader, plan, blooms)`),
    so every pushed-down scan in the query goes through admission control,
    the tick scheduler and shared-scan coalescing instead of calling the
    engine directly.  Results are bit-identical to the direct-engine path
    (tests/test_datapath.py)."""
    return QUERIES[name](service.client(tenant), readers, **kwargs)

"""The plain reference: numpy answers of the six queries over the
generator's arrays, and the comparison that decides `correct`.

Nothing here imports the program.  The query semantics (day windows,
predicate bounds, Q19's branches) are restated from the TPC-H queries as
the program's `repro.core.queries` defines them, in code space: string
columns are codes into `datagen`'s lists.  Per-day partial sums are built
once per query kind, so an answer for any parameter is a difference of
two prefix sums and hundreds of answers cost seconds.

`Reference(tables)` computes in float64 over the stored float32 values.
`Reference(tables, precision="bfloat16")` is the control: every stored
float and every product rounded to bfloat16 (sums stay wide), the step
below the configuration's float32 that a later change could be tempted by.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Tuple

import numpy as np

from bench import datagen as g

EPS = 1e-4  # the queries' tolerance on two-decimal float predicates
# Q19's branches: (brand, containers, quantity low, quantity high, size high)
Q19_BRANCHES = [
    ("Brand#12", ("SM CASE", "SM BOX", "SM PACK", "SM PKG"), 1, 11, 5),
    ("Brand#23", ("MED BOX", "MED PACK", "MED PKG", "MED CASE"), 10, 20, 10),
    ("Brand#34", ("LG CASE", "LG BOX", "LG PACK", "LG PKG"), 20, 30, 15),
]

# names of the numbers `correct` compares, in the order they are printed
CHECKS = ("unanswered", "wrong_exact", "float_rel_err")


def _rounder(precision: str) -> Callable[[np.ndarray], np.ndarray]:
    if precision == "float64":
        return lambda x: np.asarray(x, np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        return lambda x: np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


class Reference:
    """Answers by (query, parameters), each computed once."""

    def __init__(self, tables: g.Tables, precision: str = "float64"):
        self.t = tables
        self.rd = _rounder(precision)
        self._memo: Dict[Tuple, dict] = {}
        self._daily: Dict[str, object] = {}
        li = tables.lineitem
        rd = self.rd
        price, disc, tax = rd(li["l_extendedprice"]), rd(li["l_discount"]), rd(li["l_tax"])
        self.rev = rd(price * rd(1.0 - disc))  # extendedprice * (1 - discount)
        self.price, self.disc, self.tax = price, disc, tax
        self.nd = g.DAYS + 1

    def answer(self, name: str, params: dict) -> dict:
        key = (name, tuple(sorted(params.items())))
        if key not in self._memo:
            self._memo[key] = getattr(self, name)(**params)
        return self._memo[key]

    # -- per-day prefix sums ---------------------------------------------
    def _prefix(self, kind: str, build) -> np.ndarray:
        if kind not in self._daily:
            daily = build()
            self._daily[kind] = np.concatenate(
                [np.zeros((1,) + daily.shape[1:]), np.cumsum(daily, axis=0)])
        return self._daily[kind]

    def _window(self, pre: np.ndarray, lo: int, hi: int) -> np.ndarray:
        """Sum of per-day rows lo..hi inclusive, clipped to the calendar."""
        lo, hi = max(lo, 0), min(hi, self.nd - 1)
        if hi < lo:
            return np.zeros(pre.shape[1:])
        return pre[hi + 1] - pre[lo]

    def _bincount(self, day, key, n_keys, weights) -> np.ndarray:
        idx = day * n_keys + key
        return np.stack([np.bincount(idx, w, minlength=self.nd * n_keys)
                         .reshape(self.nd, n_keys) for w in weights], axis=-1)

    # -- the queries -----------------------------------------------------
    def q1(self, delta_days: int) -> dict:
        li = self.t.lineitem

        def build():
            gid = li["l_returnflag"] * 2 + li["l_linestatus"]
            disc_price = self.rev
            charge = self.rd(disc_price * self.rd(1.0 + self.tax))
            w = [np.ones(gid.shape), li["l_quantity"].astype(np.float64), self.price,
                 disc_price, charge]
            return self._bincount(li["l_shipdate"], gid, 6, w)

        s = self._window(self._prefix("q1", build), 0, g.DAYS - delta_days)
        out = {}
        for rf in range(3):
            for ls in range(2):
                row = s[rf * 2 + ls]
                if row[0] > 0:
                    out[(g.RETURNFLAGS[rf], g.LINESTATUS[ls])] = {
                        "count": int(round(row[0])), "sum_qty": row[1],
                        "sum_base_price": row[2], "sum_disc_price": row[3],
                        "sum_charge": row[4]}
        return out

    def q6(self, year_start: int) -> dict:
        li = self.t.lineitem

        def build():
            m = ((self.disc >= 0.05 - EPS) & (self.disc <= 0.07 + EPS)
                 & (li["l_quantity"] < 24))
            w = [m.astype(np.float64), np.where(m, self.rd(self.price * self.disc), 0.0)]
            return self._bincount(li["l_shipdate"], np.zeros_like(li["l_shipdate"]), 1, w)[:, 0]

        rows, rev = self._window(self._prefix("q6", build), year_start, year_start + 364)
        return {"revenue": rev, "rows": int(round(rows))}

    def q12(self, year_start: int) -> dict:
        li, orders = self.t.lineitem, self.t.orders

        def build():
            mode = li["l_shipmode"]
            mail, ship = g.SHIPMODES.index("MAIL"), g.SHIPMODES.index("SHIP")
            high = orders["o_orderpriority"][li["l_orderkey"]] <= 1  # 1-URGENT, 2-HIGH
            key = (mode == ship).astype(np.int64) * 2 + high
            w = [((mode == mail) | (mode == ship)).astype(np.float64)]
            return self._bincount(li["l_receiptdate"], key, 4, w)[..., 0]

        s = self._window(self._prefix("q12", build), year_start, year_start + 364)
        return {m: {"high": int(round(s[i * 2 + 1])), "low": int(round(s[i * 2]))}
                for i, m in enumerate(("MAIL", "SHIP"))}

    def q14(self, month_start: int) -> dict:
        li = self.t.lineitem

        def build():
            promo = np.array([t.startswith("PROMO") for t in g.TYPES])[
                self.t.part["p_type"][li["l_partkey"]]]
            w = [self.rev, np.where(promo, self.rev, 0.0)]
            return self._bincount(li["l_shipdate"], np.zeros_like(li["l_shipdate"]), 1, w)[:, 0]

        total, promo = self._window(self._prefix("q14", build), month_start, month_start + 29)
        return {"promo_revenue_pct": 100.0 * promo / max(total, 1e-9), "total_revenue": total}

    def q15(self, quarter_start: int) -> dict:
        li = self.t.lineitem
        sd = li["l_shipdate"]
        m = (sd >= quarter_start) & (sd <= quarter_start + 89)
        per = np.bincount(li["l_suppkey"][m], self.rev[m], minlength=self.t.n_supp)
        best = int(per.argmax())
        return {"suppkey": best, "revenue": float(per[best]), "per_supplier": per}

    def q19(self) -> dict:
        li, part = self.t.lineitem, self.t.part
        pk, qty = li["l_partkey"], li["l_quantity"]
        brand, cont, size = part["p_brand"][pk], part["p_container"][pk], part["p_size"][pk]
        keep = np.zeros(pk.shape, bool)
        for b, containers, qlo, qhi, shi in Q19_BRANCHES:
            codes = [g.CONTAINERS.index(c) for c in containers]
            keep |= ((brand == g.BRANDS.index(b)) & np.isin(cont, codes)
                     & (qty >= qlo) & (qty <= qhi) & (size >= 1) & (size <= shi))
        m = (keep & (qty >= 1) & (qty <= 30)
             & (li["l_shipinstruct"] == g.SHIPINSTRUCT.index("DELIVER IN PERSON"))
             & np.isin(li["l_shipmode"], [g.SHIPMODES.index("AIR"),
                                          g.SHIPMODES.index("REG AIR")]))
        return {"revenue": float(self.rev[m].sum()), "rows": int(m.sum())}


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------


def _rel(got, want) -> float:
    got = float(got)
    if not math.isfinite(got):
        return math.inf
    return abs(got - float(want)) / max(abs(float(want)), 1.0)


def compare(name: str, got: dict, want: dict) -> Tuple[int, float]:
    """(exact fields that differ, largest relative error of a float field)
    of one answer against the reference's.  An answer of the wrong shape
    counts as one wrong exact field."""
    try:
        return _compare(name, got, want)
    except (KeyError, TypeError, ValueError, IndexError):
        return 1, 0.0


def _compare(name, got, want):
    wrong, err = 0, 0.0
    if name == "q1":
        if set(got) != set(want):
            return 1, 0.0
        for grp, w in want.items():
            r = got[grp]
            wrong += int(round(float(r["count"]))) != w["count"]
            for k in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge"):
                err = max(err, _rel(r[k], w[k]))
    elif name == "q12":
        wrong += sum(int(got[m][h]) != want[m][h] for m in want for h in ("high", "low"))
    elif name in ("q6", "q19"):
        wrong += int(got["rows"]) != want["rows"]
        err = _rel(got["revenue"], want["revenue"])
    elif name == "q14":
        err = max(_rel(got["total_revenue"], want["total_revenue"]),
                  _rel(got["promo_revenue_pct"], want["promo_revenue_pct"]))
    elif name == "q15":
        per = want["per_supplier"]
        # the chosen supplier's true revenue below the best: a tie is no error
        err = max(_rel(got["revenue"], want["revenue"]),
                  _rel(per[int(got["suppkey"])], want["revenue"]))
    else:
        raise KeyError(name)
    return wrong, err


def judge(records: List, ref: Reference, limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """Compare every record's answer with the reference.  Returns
    (correct, {check name: {"value", "limit"}})."""
    unanswered = wrong = 0
    err = 0.0
    for rec in records:
        if rec.error is not None or rec.answer is None:
            unanswered += 1
            continue
        w, e = compare(rec.name, rec.answer, ref.answer(rec.name, rec.params))
        wrong += w
        err = max(err, e)
    values = {"unanswered": unanswered, "wrong_exact": wrong,
              "float_rel_err": err if math.isfinite(err) else 1e300}
    checks = {k: {"value": values[k], "limit": limits[k]} for k in CHECKS}
    correct = bool(records) and all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks

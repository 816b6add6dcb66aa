"""TPC-H-shaped tables from a seed, kept with the benchmark.

`base_tables` is a copy of the program's generator
(`repro.core.tpch.gen_tables`), drawn in the same order from the same
generator, so that at the same scale and seed the two give the same rows.
It is kept here so that a change to the program's generator cannot move
the yardstick.  String columns are kept as codes into the module's string
lists (code space): the reference compares codes, and the writer gets the
strings it stores.

`gen_tables` gives every run seed the same rows, drawn once from
`BASE_SEED`, and the seed puts lineitem's whole row groups in an order of
its own.  So every seed has the same pages, encodings and bucket shapes
(the same work, and every program in the compile cache after the first
run), in another order; orders and part stay dense by key, as the queries
need.

The tables are written through the program's own `lakeformat` writer,
because the lake file is the system's input.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict

import numpy as np

DAYS = 2556  # 1992-01-01 .. 1998-12-31 as day offsets
LI_PER_SF = 600_000
ORDERS_PER_SF = 150_000
PARTS_PER_SF = 20_000
SUPPS_PER_SF = 1_000

SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
RETURNFLAGS = ["A", "N", "R"]
LINESTATUS = ["O", "F"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]
CONTAINERS = [f"{s} {t}" for s in ["SM", "MED", "LG", "JUMBO"]
              for t in ["CASE", "BOX", "PACK", "PKG"]]
TYPES = [f"{a} {b} {c}" for a in ["STANDARD", "SMALL", "MEDIUM", "LARGE", "PROMO"]
         for b in ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
         for c in ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]]

# string column -> the list its codes index
STRINGS = {
    "l_returnflag": RETURNFLAGS, "l_linestatus": LINESTATUS,
    "l_shipmode": SHIPMODES, "l_shipinstruct": SHIPINSTRUCT,
    "o_orderpriority": PRIORITIES,
    "p_brand": BRANDS, "p_type": TYPES, "p_container": CONTAINERS,
}

# (name, dtype, encoding hint) per table, as the program's schemas state them
SCHEMAS = {
    "lineitem": [
        ("l_orderkey", "int32", "auto"), ("l_partkey", "int32", "bitpack"),
        ("l_suppkey", "int32", "bitpack"), ("l_quantity", "int32", "bitpack"),
        ("l_extendedprice", "float32", "plain"), ("l_discount", "float32", "dict"),
        ("l_tax", "float32", "dict"), ("l_returnflag", "str", "auto"),
        ("l_linestatus", "str", "auto"), ("l_shipdate", "int32", "auto"),
        ("l_commitdate", "int32", "bitpack"), ("l_receiptdate", "int32", "bitpack"),
        ("l_shipmode", "str", "auto"), ("l_shipinstruct", "str", "auto"),
    ],
    "orders": [
        ("o_orderkey", "int32", "auto"), ("o_orderdate", "int32", "auto"),
        ("o_orderpriority", "str", "auto"),
    ],
    "part": [
        ("p_partkey", "int32", "auto"), ("p_brand", "str", "auto"),
        ("p_type", "str", "auto"), ("p_container", "str", "auto"),
        ("p_size", "int32", "bitpack"),
    ],
}


@dataclasses.dataclass
class Tables:
    """Generated tables: column name -> numpy array (codes for strings)."""

    lineitem: Dict[str, np.ndarray]
    orders: Dict[str, np.ndarray]
    part: Dict[str, np.ndarray]
    n_supp: int

    def table(self, name: str) -> Dict[str, np.ndarray]:
        return getattr(self, name)


def rng_for(seed: int) -> np.random.Generator:
    """The generator a seed stands for; any integer, however large or
    negative, maps to a valid seed."""
    return np.random.default_rng(seed % (1 << 64))


BASE_SEED = 0


def gen_tables(sf: float, seed: int, row_group_size: int) -> Tables:
    """The tables of a run: the rows of `base_tables(sf, BASE_SEED)`, with
    lineitem's full row groups permuted by `seed` (a short last group
    stays last, so the pages are the same)."""
    t = base_tables(sf, BASE_SEED)
    n = len(t.lineitem["l_orderkey"])
    full = n // row_group_size
    order = rng_for(seed).permutation(full)
    rows = np.concatenate([(order[:, None] * row_group_size
                            + np.arange(row_group_size)).reshape(-1),
                           np.arange(full * row_group_size, n)])
    t.lineitem = {k: v[rows] for k, v in t.lineitem.items()}
    return t


def base_tables(sf: float, seed: int) -> Tables:
    """Tables at the generator's scale `sf` (10 = TPC-H SF1 row counts),
    unsorted, in dbgen-like random order."""
    rng = rng_for(seed)
    n_li = int(LI_PER_SF * sf)
    n_ord = int(ORDERS_PER_SF * sf)
    n_part = max(256, int(PARTS_PER_SF * sf))
    n_supp = max(64, int(SUPPS_PER_SF * sf))

    li_order = np.sort(rng.integers(0, n_ord, size=n_li))
    shipdate = rng.integers(0, DAYS, size=n_li)
    lineitem = {
        "l_orderkey": li_order,
        "l_partkey": rng.integers(0, n_part, size=n_li),
        "l_suppkey": rng.integers(0, n_supp, size=n_li),
        "l_quantity": rng.integers(1, 51, size=n_li),
        "l_extendedprice": (rng.random(n_li).astype(np.float32) * 10000 + 900).round(2),
        "l_discount": (rng.integers(0, 11, size=n_li) / 100).astype(np.float32),
        "l_tax": (rng.integers(0, 9, size=n_li) / 100).astype(np.float32),
        "l_returnflag": rng.integers(0, 3, size=n_li),
        "l_linestatus": rng.integers(0, 2, size=n_li),
        "l_shipdate": shipdate,
        "l_commitdate": np.clip(shipdate + rng.integers(-30, 60, size=n_li), 0, DAYS),
        "l_receiptdate": np.clip(shipdate + rng.integers(1, 30, size=n_li), 0, DAYS),
        "l_shipmode": rng.integers(0, len(SHIPMODES), size=n_li),
        "l_shipinstruct": rng.integers(0, 4, size=n_li),
    }
    orders = {
        "o_orderkey": np.arange(n_ord, dtype=np.int64),
        "o_orderdate": rng.integers(0, DAYS, size=n_ord),
        "o_orderpriority": rng.integers(0, 5, size=n_ord),
    }
    part = {
        "p_partkey": np.arange(n_part, dtype=np.int64),
        "p_brand": rng.integers(0, len(BRANDS), size=n_part),
        "p_type": rng.integers(0, len(TYPES), size=n_part),
        "p_container": rng.integers(0, len(CONTAINERS), size=n_part),
        "p_size": rng.integers(1, 51, size=n_part),
    }
    return Tables(lineitem, orders, part, n_supp)


def write_lake(tables: Tables, dirpath: str, row_group_size: int) -> Dict[str, str]:
    """Write each table as a lake file through the program's writer;
    returns table name -> path."""
    from repro.lakeformat.schema import ColumnSchema, TableSchema
    from repro.lakeformat.writer import write_table

    paths = {}
    for name, cols in SCHEMAS.items():
        data = tables.table(name)
        columns = {}
        for col, dtype, _ in cols:
            v = data[col]
            columns[col] = (np.asarray(STRINGS[col], dtype=object)[v].tolist()
                            if dtype == "str" else v)
        schema = TableSchema(name, [ColumnSchema(c, d, e) for c, d, e in cols])
        paths[name] = write_table(os.path.join(dirpath, f"{name}.lake"), schema,
                                  columns, row_group_size)
    return paths

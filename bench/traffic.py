"""The one traffic generator: query streams drawn from a mix's data file.

A mix (`bench/traffic/<name>.json`) names its streams, the queries each
pass runs, how many passes warm up, and each query's substitution
parameters as distributions:

    {"uniform_int": [lo, hi]}                  an integer in lo..hi
    {"jan1_of_year": [y0, y1]}                 1 January of a year in y0..y1
    {"first_of_month": ["YYYY-MM", "YYYY-MM"]} the first day of a month in range

Dates become the program's day offsets from 1992-01-01.  Each pass runs
every query of the mix once.  Stream k draws each pass's parameters from
a generator of its own that no run seed changes, and the run seed puts
each pass in an order of its own: every seed offers the same queries with
the same parameters, in another order.  (Parameters that recur decide
what the pre-filtered tier serves, so a seed that drew more repeats than
another would change the work.)  The streams differ from each other.
"""

from __future__ import annotations

import datetime
import itertools
from typing import Dict, Iterator, List, Tuple

import numpy as np

EPOCH = datetime.date(1992, 1, 1)

Query = Tuple[str, Dict[str, int]]


def day(d: datetime.date) -> int:
    return (d - EPOCH).days


def _months(lo: str, hi: str) -> List[datetime.date]:
    y, m = map(int, lo.split("-"))
    y1, m1 = map(int, hi.split("-"))
    out = []
    while (y, m) <= (y1, m1):
        out.append(datetime.date(y, m, 1))
        y, m = (y + 1, 1) if m == 12 else (y, m + 1)
    return out


def support(dist: dict) -> List[int]:
    """Every value a parameter distribution can draw, in order."""
    (kind, arg), = dist.items()
    if kind == "uniform_int":
        return list(range(arg[0], arg[1] + 1))
    if kind == "jan1_of_year":
        return [day(datetime.date(y, 1, 1)) for y in range(arg[0], arg[1] + 1)]
    if kind == "first_of_month":
        return [day(d) for d in _months(*arg)]
    raise ValueError(f"unknown parameter distribution {kind!r}")


def stream_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(seed % (1 << 64), spawn_key=(stream,)))


PARAMS_SEED = 0


def stream(mix: dict, seed: int, k: int) -> Iterator[Query]:
    """Stream k's endless query sequence."""
    order, draw = stream_rng(seed, k), stream_rng(PARAMS_SEED, k)
    names = list(mix["queries"])
    supports = {q: {p: support(d) for p, d in mix["params"].get(q, {}).items()}
                for q in names}
    while True:
        one_pass = [(q, {p: int(vals[draw.integers(len(vals))])
                         for p, vals in supports[q].items()}) for q in names]
        for i in order.permutation(len(names)):
            yield one_pass[i]


def first(mix: dict, seed: int, k: int, n: int) -> List[Query]:
    return list(itertools.islice(stream(mix, seed, k), n))

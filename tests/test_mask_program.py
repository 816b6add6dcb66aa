"""The compiled row-group mask (`engine._mask_program`, through
`DatapathEngine._row_mask`): bit-identical to an eager evaluation of the
predicate and row validity, over generated predicate trees on int32 and
float32 columns; and keyed on the predicate's shape alone, so that a new
draw of qgen's parameters replays every program the first draw traced."""

import operator
import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DatapathEngine, tpch
from repro.core import engine as engine_mod
from repro.core.plan import And, BloomProbe, Cmp, InSet, Or, bind_expr
from repro.core.queries import EPS, QUERIES, run_via_service
from repro.datapath import DatapathService, StaticPolicy
from repro.kernels import ops
from repro.lakeformat.encodings import RLE_OUT_BLOCK
from repro.lakeformat.reader import LakeReader

L = 2048
OPS = {"lt": operator.lt, "le": operator.le, "gt": operator.gt,
       "ge": operator.ge, "eq": operator.eq, "ne": operator.ne}


def _columns():
    rng = np.random.default_rng(3)
    return {
        "i": jnp.asarray(rng.integers(0, 40, L).astype(np.int32)),
        "k": jnp.asarray(rng.integers(0, 1 << 12, L).astype(np.int32)),
        # two-decimal money on float32, as lineitem's l_discount
        "f": jnp.asarray((rng.integers(0, 11, L) / 100).astype(np.float32)),
        "g": jnp.asarray(rng.standard_normal(L).astype(np.float32)),
    }


COLS = _columns()
BLOOM = ops.bloom_build(jnp.arange(0, 1 << 12, 3, dtype=jnp.int32), n_bits=1 << 12)
PROBE = BloomProbe("k", n_bits=1 << 12, name="b")


def _probe(keys):
    return ops.bloom_probe(keys.reshape(-1, RLE_OUT_BLOCK), BLOOM, PROBE.n_hashes,
                           backend="ref").reshape(-1)


def eager(e, cols):
    """The predicate one eager jnp op at a time, literals weakly typed."""
    if isinstance(e, Cmp):
        v = cols[e.column]
        if e.op == "between":
            lo, hi = e.value
            return (v >= lo) & (v <= hi)
        return OPS[e.op](v, e.value)
    if isinstance(e, InSet):
        m = jnp.zeros((L,), jnp.bool_)
        for val in e.values:
            m = m | (cols[e.column] == val)
        return m
    if isinstance(e, BloomProbe):
        return _probe(cols[e.column])
    m = eager(e.children[0], cols)
    for c in e.children[1:]:
        m = (m & eager(c, cols)) if isinstance(e, And) else (m | eager(c, cols))
    return m


# literals per column: int and float on the int32 column, EPS-shifted
# bounds and grid values on the two-decimal float32 one
LITERALS = {
    "i": [0, 7, 20, 39, 12.5, 7.0, -3, 40],
    "f": [0.05 - EPS, 0.07 + EPS, 0.05, 0.03, 0, 1, 0.1],
    "g": [0.0, -0.5, 1.25, 2],
}


def _leaf(rnd):
    col = rnd.choice(sorted(LITERALS))
    lit = lambda: rnd.choice(LITERALS[col])  # noqa: E731
    kind = rnd.random()
    if kind < 0.15:
        return PROBE
    if kind < 0.4:
        return InSet(col, tuple(lit() for _ in range(rnd.randint(1, 3))))
    op = rnd.choice(sorted(OPS) + ["between"])
    return Cmp(col, op, tuple(sorted((lit(), lit()))) if op == "between" else lit())


def _tree(rnd, depth=0):
    if depth == 3 or rnd.random() < 0.3:
        return _leaf(rnd)
    kids = tuple(_tree(rnd, depth + 1) for _ in range(rnd.randint(2, 3)))
    return And(kids) if rnd.random() < 0.5 else Or(kids)


HAND = [Cmp(c, op, v) for op in OPS for c, v in (("i", 20), ("i", 12.5), ("f", 0.05),
                                                  ("f", 0.07 + EPS), ("g", 0.0))]
HAND += [
    Cmp("i", "between", (7, 20)),
    Cmp("f", "between", (0.05 - EPS, 0.07 + EPS)),
    Cmp("i", "between", (6.5, 20)),
    InSet("i", (7,)), InSet("i", (7, 12.5)), InSet("f", (0.05, 0.03, 0.1)),
    PROBE,
    And((PROBE, Cmp("i", "between", (1, 30)), InSet("i", (3,)), InSet("i", (4, 5)))),
    Or((And((Cmp("f", "ge", 0.05 - EPS), Cmp("f", "le", 0.07 + EPS))),
        And((Or((Cmp("i", "lt", 24), InSet("i", (30, 31)))), Cmp("g", "gt", 0.0))))),
]
CASES = HAND + [_tree(random.Random(s)) for s in range(24)]


@pytest.mark.parametrize("n", [L, L - 300], ids=["n=L", "n<L"])
@pytest.mark.parametrize("bloom", ["operand", "probed"])
@pytest.mark.parametrize("pred", CASES, ids=[f"hand{i}" for i in range(len(HAND))]
                         + [f"gen{s}" for s in range(24)])
def test_mask_matches_eager(pred, bloom, n):
    eng = DatapathEngine(backend="ref")
    want = eager(pred, COLS) & (jnp.arange(L) < n)
    bmasks = {(PROBE.name, PROBE.column): _probe(COLS["k"])} if bloom == "operand" else None
    got = eng._row_mask(pred, COLS, {"b": BLOOM}, n, L, rg=0, bmasks=bmasks)
    assert got.dtype == want.dtype and got.shape == (L,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [L, L - 300], ids=["n=L", "n<L"])
def test_trivial_shapes_are_validity(n):
    """No predicate: validity alone; a given mask (the fused scan's, as
    (blocks, 1024) or (L,)): that mask & validity."""
    eng = DatapathEngine(backend="ref")
    valid = np.arange(L) < n
    got = eng._row_mask(None, {}, {}, n, L, rg=0)
    np.testing.assert_array_equal(np.asarray(got), valid)
    given = np.asarray(COLS["i"]) < 20
    for g in (jnp.asarray(given), jnp.asarray(given.reshape(-1, RLE_OUT_BLOCK))):
        got = eng._row_mask(Cmp("i", "lt", 20), COLS, {}, n, L, rg=0, given=g)
        np.testing.assert_array_equal(np.asarray(got), given & valid)


def test_literals_keep_their_python_types():
    """A literal is a device scalar typed as eager jnp types it: a Python
    number stays weak, so `int32 < 12.5` computes in float32 and
    `int32 < 12` in int32.  Equal literals of other types (2**24 and
    2**24 + 0.0, which `==` on predicates cannot tell apart) give their
    own operands, programs and masks."""
    _, columns, literals, _ = engine_mod._mask_shape(
        And((Cmp("i", "between", (1, 12.5)), Cmp("f", "le", 0.07 + EPS))))
    assert columns == ["i", "f"] and literals == [1, 12.5, 0.07 + EPS]
    ops_ = [engine_mod._operand(v) for v in literals]
    assert [(o.dtype, o.weak_type) for o in ops_] == [
        (jnp.int32, True), (jnp.float32, True), (jnp.float32, True)]
    big = {"i": jnp.full((L,), 2**24 + 1, jnp.int32)}
    eng = DatapathEngine(backend="ref")
    for value in (2**24, float(2**24)):
        pred = Cmp("i", "eq", value)
        got = eng._row_mask(pred, big, {}, L, L, rg=0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(eager(pred, big)))
    assert not bool(eng._row_mask(Cmp("i", "eq", 2**24), big, {}, L, L, rg=0)[0])
    assert bool(eng._row_mask(Cmp("i", "eq", float(2**24)), big, {}, L, L, rg=0)[0])


# ---------------------------------------------------------------------------
# retrace: qgen's parameters are runtime operands
# ---------------------------------------------------------------------------

PARAMS = [
    {"q1": {"delta_days": 90}, "q6": {"year_start": 365}, "q12": {"year_start": 730},
     "q14": {"month_start": 1000}, "q15": {"quarter_start": 365}, "q19": {}},
    {"q1": {"delta_days": 61}, "q6": {"year_start": 1096}, "q12": {"year_start": 1461},
     "q14": {"month_start": 1157}, "q15": {"quarter_start": 912}, "q19": {}},
]


@pytest.fixture(scope="module")
def readers(tmp_path_factory):
    # unsorted, so zone maps prune nothing and every draw scans every group
    paths = tpch.write_tables(str(tmp_path_factory.mktemp("retrace")), sf=0.01, seed=1,
                              row_group_size=8192)
    return {k: LakeReader(p) for k, p in paths.items()}


@pytest.mark.parametrize("path", ["sequential", "batched"])
def test_second_parameter_draw_traces_nothing(readers, path):
    if path == "sequential":
        client = DatapathEngine(backend="ref")
        run = lambda q, kw: QUERIES[q](client, readers, **kw)  # noqa: E731
    else:
        svc = DatapathService(engine=DatapathEngine(backend="ref"),
                              policy=StaticPolicy("raw"), batch_decode=True)
        run = lambda q, kw: run_via_service(svc, q, readers, **kw)  # noqa: E731
    for q, kw in PARAMS[0].items():
        run(q, kw)
    n0 = engine_mod._MASK_TRACES[0]
    for q, kw in PARAMS[1].items():
        run(q, kw)
    assert engine_mod._MASK_TRACES[0] == n0


def test_q14_and_q15_share_a_program(readers):
    """One `between` on l_shipdate, whatever its bounds: one shape, and the
    same operand types, so Q15 replays the program Q14 traced."""
    rl = readers["lineitem"]
    got = {}
    for q, days in (("q14", (1000, 1029)), ("q15", (365, 454))):
        pred = bind_expr(Cmp("l_shipdate", "between", days), rl)
        got[q] = engine_mod._mask_shape(pred)[0]
    assert got["q14"] == got["q15"]
    eng = DatapathEngine(backend="ref")
    QUERIES["q14"](eng, readers, month_start=1000)
    n0 = engine_mod._MASK_TRACES[0]
    QUERIES["q15"](eng, readers, quarter_start=365)
    assert engine_mod._MASK_TRACES[0] == n0

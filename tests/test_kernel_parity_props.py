"""Rewritten decode cores vs kernels/ref.py oracles: randomized parity
sweeps (seeded always; hypothesis-driven when available) across code
widths k, int/float dtypes, ragged block counts sitting on the two-size
ladder's bucket boundaries — plus the dispatch-count and pad-waste
invariants that make ladder bucketing strictly no worse than pow2.

Bit-identity is the contract: the RLE rank lookup gathers the single
owning run, the DELTA carry ladder reassociates int32 adds (associative
mod 2^32), and the DICT lane lookup is pure selection — so every compare
here is array_equal, never allclose.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.lakeformat import encodings as E
from repro.lakeformat.encodings import (
    LANES, PACK_BLOCK, RLE_OUT_BLOCK, RLE_WINDOW,
)

BACKENDS = ("ref", "pallas")

# block counts straddling the two-size ladder's bucket boundaries
# {1,2,3,4,6,8,12,16,24,32}: each boundary, one past it, and ragged
# mid-octave counts
LADDER_NS = (1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 13, 16, 17, 24, 25, 32, 33)


# ---------------------------------------------------------------------------
# generators (pure, seeded — shared by the fixed sweep and hypothesis)
# ---------------------------------------------------------------------------

def _rand_rle_blocks(rng, nb: int, float_vals: bool):
    """Writer-shaped RLE pages: per block, r <= RLE_WINDOW runs whose ends
    are strictly increasing cut points finishing at RLE_OUT_BLOCK, padding
    repeating the final value with end == RLE_OUT_BLOCK."""
    dtype = np.float32 if float_vals else np.int32
    vals = np.zeros((nb, RLE_WINDOW), dtype=dtype)
    ends = np.zeros((nb, RLE_WINDOW), dtype=np.int32)
    for b in range(nb):
        r = int(rng.integers(1, RLE_WINDOW + 1))
        cuts = np.sort(rng.choice(np.arange(1, RLE_OUT_BLOCK), size=r - 1,
                                  replace=False)) if r > 1 else np.empty(0, np.int64)
        e = np.concatenate([cuts, [RLE_OUT_BLOCK]]).astype(np.int32)
        v = (rng.standard_normal(r).astype(np.float32) if float_vals
             else rng.integers(-1000, 1000, r).astype(np.int32))
        vals[b, :r], ends[b, :r] = v, e
        vals[b, r:], ends[b, r:] = v[-1], RLE_OUT_BLOCK
    return vals, ends


def _check_rle(vals: np.ndarray, ends: np.ndarray):
    nb = vals.shape[0]
    want = E.rle_decode_np({"rle_values": vals, "rle_ends": ends},
                           nb * RLE_OUT_BLOCK).reshape(nb, RLE_OUT_BLOCK)
    for be in BACKENDS:
        got = np.asarray(ops.rle_decode_batch(vals, ends, backend=be))[:nb]
        assert got.dtype == want.dtype, be
        assert np.array_equal(got, want), be
    # single-call path (jitted ref wrapper)
    one = np.asarray(ops.rle_decode(jnp.asarray(vals[:1]), jnp.asarray(ends[:1]),
                                    RLE_OUT_BLOCK))
    assert np.array_equal(one, want.reshape(-1)[:RLE_OUT_BLOCK])


def _rand_delta(rng, nb: int, k: int):
    """Random k-bit zigzag deltas + int32 bases (delta[0] need not be 0 —
    the decoder must not rely on the writer's convention)."""
    zz = rng.integers(0, np.uint64(1) << np.uint64(k), size=nb * PACK_BLOCK,
                      dtype=np.uint64)
    packed = E.bitpack_encode(zz, k)
    bases = rng.integers(-(1 << 20), 1 << 20, nb).astype(np.int64)
    deltas = E._unzigzag(zz).reshape(nb, PACK_BLOCK)
    want = (np.cumsum(deltas, axis=1, dtype=np.int64)
            + bases[:, None]).astype(np.int32).reshape(nb, -1)
    return packed, bases, want


def _check_delta(packed: np.ndarray, bases: np.ndarray, k: int, want: np.ndarray):
    for be in BACKENDS:
        got = np.asarray(ops.delta_decode_batch(packed, bases, k, backend=be))[:len(want)]
        assert np.array_equal(got, want), (be, k)


def _rand_dict(rng, nb: int, k: int, float_vals: bool):
    """nb blocks of k-bit codes mapped onto P pages with per-page
    dictionaries; every code < the common dict size D <= 2^k."""
    D = int(rng.integers(1, min(1 << k, 4096) + 1))
    codes = rng.integers(0, D, size=nb * PACK_BLOCK, dtype=np.uint64)
    packed = E.bitpack_encode(codes, k)
    P = int(rng.integers(1, nb + 1))
    page = rng.integers(0, P, nb).astype(np.int32)
    dicts = (rng.standard_normal((P, D)).astype(np.float32) if float_vals
             else rng.integers(-10000, 10000, (P, D)).astype(np.int32))
    sizes = np.full(P, D, np.int32)
    want = dicts[page][
        np.arange(nb)[:, None], codes.reshape(nb, PACK_BLOCK).astype(np.int64)
    ].reshape(nb, E.SUBLANES, LANES)
    return packed, dicts, sizes, page, want


def _check_dict(packed, dicts, sizes, page, k: int, want):
    for be in BACKENDS:
        got = np.asarray(
            ops.dict_decode_batch(packed, dicts, sizes, page, k, backend=be))[:len(want)]
        assert got.dtype == want.dtype, (be, k)
        assert np.array_equal(got, want), (be, k)


# ---------------------------------------------------------------------------
# fixed seeded sweeps (always run — hypothesis is optional in this image)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("float_vals", [False, True], ids=["int32", "float32"])
def test_rle_parity_across_ladder_boundaries(float_vals):
    rng = np.random.default_rng(0 if float_vals else 1)
    for nb in LADDER_NS:
        _check_rle(*_rand_rle_blocks(rng, nb, float_vals))


def test_delta_parity_across_k_and_ladder_boundaries():
    # writer caps delta widths at 30 bits (zigzag of int deltas)
    rng = np.random.default_rng(2)
    for i, k in enumerate(range(1, 31)):
        nb = LADDER_NS[i % len(LADDER_NS)]
        packed, bases, want = _rand_delta(rng, nb, k)
        _check_delta(packed, bases, k, want)


@pytest.mark.parametrize("float_vals", [False, True], ids=["int32", "float32"])
def test_dict_parity_across_k_and_ladder_boundaries(float_vals):
    # k sweeps the full code width range: dictionaries of one 128-entry
    # lookup row up to 32 rows
    rng = np.random.default_rng(3 if float_vals else 4)
    for i, k in enumerate(range(1, 33)):
        nb = LADDER_NS[i % len(LADDER_NS)]
        packed, dicts, sizes, page, want = _rand_dict(rng, nb, k, float_vals)
        _check_dict(packed, dicts, sizes, page, k, want)


def test_dict_single_call_select_mux_matches_oracle():
    """The single-call lookup (one lane gather per 128-entry dictionary
    row, one row up to 32 rows) vs the take oracle, int and float
    dictionaries, including clip semantics for codes that are
    representable in k bits but >= the true dict size."""
    rng = np.random.default_rng(5)
    for k in range(1, 13):
        for float_vals in (False, True):
            D = int(rng.integers(1, (1 << k) + 1))
            # codes deliberately cover the full k-bit range: codes >= D
            # must clip to the last entry on every path
            codes = rng.integers(0, 1 << k, size=PACK_BLOCK, dtype=np.uint64)
            packed = E.bitpack_encode(codes, k)
            d = (rng.standard_normal(D).astype(np.float32) if float_vals
                 else rng.integers(-100, 100, D).astype(np.int32))
            want = d[np.minimum(codes.astype(np.int64), D - 1)].reshape(
                E.SUBLANES, LANES)
            for be in BACKENDS:
                got = np.asarray(ops.dict_decode(
                    jnp.asarray(packed), jnp.asarray(d), k, PACK_BLOCK,
                    backend=be)).reshape(E.SUBLANES, LANES)
                assert np.array_equal(got, want), (be, k, float_vals)


def test_bitunpack_parity_full_k_range():
    rng = np.random.default_rng(6)
    for k in range(1, 33):
        v = rng.integers(0, np.uint64(1) << np.uint64(k), size=2 * PACK_BLOCK,
                         dtype=np.uint64)
        packed = E.bitpack_encode(v, k)
        want = np.asarray(ref.bitunpack(jnp.asarray(packed), k))
        for be in BACKENDS:
            got = np.asarray(ops.bitunpack_batch(packed, k, backend=be))[:len(want)]
            assert np.array_equal(got, want), (be, k)


# ---------------------------------------------------------------------------
# ladder vs pow2: dispatch-count and pad-waste invariants
# ---------------------------------------------------------------------------

def test_ladder_launches_never_exceed_pow2():
    """Each batch call is exactly ONE dispatch in either bucketing mode,
    so over any workload the ladder's launch count equals (never exceeds)
    pow2's — the ladder buys its smaller pad waste for free."""
    rng = np.random.default_rng(7)
    workload = [int(rng.integers(1, 40)) for _ in range(12)]
    counts = {}
    for mode in ("ladder", "pow2"):
        prev = ops.set_bucket_mode(mode)
        try:
            ops.reset_dispatch_count()
            for nb in workload:
                vals, ends = _rand_rle_blocks(rng, nb, False)
                ops.rle_decode_batch(vals, ends, backend="ref")
            counts[mode] = ops.dispatch_count()
        finally:
            ops.set_bucket_mode(prev)
    assert counts["ladder"] == counts["pow2"] == len(workload)


def test_ladder_pad_waste_bounded_and_below_pow2():
    for n in range(1, 4097):
        lad = ops.bucket_blocks(n, mode="ladder")
        p2 = ops.bucket_blocks(n, mode="pow2")
        assert n <= lad <= p2, n              # never pads past pow2
        assert lad - n <= n, n                # waste bounded by ~50%
        assert (p2 & (p2 - 1)) == 0 and p2 >= n
    # distinct jit trace shapes per octave stay bounded: two sizes
    sizes = {ops.bucket_blocks(n) for n in range(33, 65)}
    assert sizes == {48, 64}


# ---------------------------------------------------------------------------
# hypothesis sweep (optional dependency — skipped when absent)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st_

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=25)
    @given(seed=st_.integers(0, 2**32 - 1),
           nb=st_.sampled_from(LADDER_NS),
           float_vals=st_.booleans())
    def test_rle_parity_hypothesis(seed, nb, float_vals):
        rng = np.random.default_rng(seed)
        _check_rle(*_rand_rle_blocks(rng, nb, float_vals))

    @settings(deadline=None, max_examples=25)
    @given(seed=st_.integers(0, 2**32 - 1),
           nb=st_.sampled_from(LADDER_NS),
           k=st_.integers(1, 30))
    def test_delta_parity_hypothesis(seed, nb, k):
        rng = np.random.default_rng(seed)
        packed, bases, want = _rand_delta(rng, nb, k)
        _check_delta(packed, bases, k, want)

    @settings(deadline=None, max_examples=25)
    @given(seed=st_.integers(0, 2**32 - 1),
           nb=st_.sampled_from(LADDER_NS),
           k=st_.integers(1, 32),
           float_vals=st_.booleans())
    def test_dict_parity_hypothesis(seed, nb, k, float_vals):
        rng = np.random.default_rng(seed)
        packed, dicts, sizes, page, want = _rand_dict(rng, nb, k, float_vals)
        _check_dict(packed, dicts, sizes, page, k, want)

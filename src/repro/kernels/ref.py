"""Pure-jnp oracles for every Pallas kernel.

These are the semantic ground truth: each Pallas kernel in this package is
asserted allclose against the function of the same name here, across shape
and dtype sweeps (tests/test_kernels.py).  They are also the production
decode path on non-TPU backends and inside the 512-device dry-run, where
Pallas TPU lowering is unavailable (DESIGN.md §2).

All decoders consume the block layouts defined in lakeformat/encodings.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.lakeformat.encodings import LANES, PACK_BLOCK, RLE_OUT_BLOCK, RLE_WINDOW, SUBLANES


# ---------------------------------------------------------------------------
# bitunpack
# ---------------------------------------------------------------------------


def bitunpack(packed: jax.Array, k: int) -> jax.Array:
    """(nblocks, k, 128) uint32 -> (nblocks, 32, 128) int32 values.

    Statically-unrolled 32-row shift/mask ladder; no gathers.
    """
    assert packed.ndim == 3 and packed.shape[1] == k and packed.shape[2] == LANES
    p = packed.astype(jnp.uint32)
    if k == 32:
        return p.astype(jnp.int32).reshape(packed.shape[0], SUBLANES, LANES)
    mask = jnp.uint32((1 << k) - 1)
    rows = []
    for s in range(SUBLANES):
        w0, sh = divmod(s * k, 32)
        val = jax.lax.shift_right_logical(p[:, w0, :], jnp.uint32(sh))
        if sh + k > 32:
            val = val | jax.lax.shift_left(p[:, w0 + 1, :], jnp.uint32(32 - sh))
        rows.append(val & mask)
    return jnp.stack(rows, axis=1).astype(jnp.int32)


def bitunpack_flat(packed: jax.Array, k: int, n: int) -> jax.Array:
    return bitunpack(packed, k).reshape(-1)[:n]


# ---------------------------------------------------------------------------
# dict decode
# ---------------------------------------------------------------------------


def dict_decode(packed: jax.Array, dictionary: jax.Array, k: int) -> jax.Array:
    """(nblocks,k,128) codes + (D,) dict -> (nblocks,32,128) values."""
    codes = bitunpack(packed, k)
    return jnp.take(dictionary, codes, axis=0, mode="clip")


# ---------------------------------------------------------------------------
# rle decode
# ---------------------------------------------------------------------------


def rle_decode(values: jax.Array, ends: jax.Array) -> jax.Array:
    """(nblk,128) run values + (nblk,128) exclusive ends -> (nblk,1024).

    Rank lookup: position j belongs to the first run whose exclusive end
    exceeds j, i.e. rank(j) = |{r : ends[r] <= j}|.  The writer pads the
    run window with end=1024 repeats of the final value, so clipping the
    rank into the window re-reads that value for any padded tail.  A
    gather of the single owning run is exact for every dtype (no
    accumulation at all), unlike the old dense (nblk,1024,128) one-hot
    contraction it replaces — and it never materializes the cube.
    """
    e = ends.astype(jnp.int32)
    j = jnp.arange(RLE_OUT_BLOCK, dtype=jnp.int32)
    rank = jax.vmap(lambda eb: jnp.searchsorted(eb, j, side="right"))(e)
    idx = jnp.minimum(rank, RLE_WINDOW - 1)
    return jnp.take_along_axis(values, idx, axis=1)


# ---------------------------------------------------------------------------
# delta decode
# ---------------------------------------------------------------------------


def _unzigzag_i32(z: jax.Array) -> jax.Array:
    zu = z.astype(jnp.uint32)
    return (
        jax.lax.shift_right_logical(zu, jnp.uint32(1)).astype(jnp.int32)
        ^ -(zu & jnp.uint32(1)).astype(jnp.int32)
    )


def delta_decode(packed: jax.Array, bases: jax.Array, k: int) -> jax.Array:
    """(nblocks,k,128) zigzag deltas + (nblocks,) bases -> (nblocks,4096) int32.

    Value order is v = s*128 + l, so prefix sum = lane cumsum + row carries.
    """
    z = bitunpack(packed, k)  # (nb,32,128) int32 (zigzag, < 2^31)
    d = _unzigzag_i32(z)
    lane_cs = jnp.cumsum(d, axis=2)  # within-row prefix
    row_tot = lane_cs[:, :, -1]  # (nb,32)
    row_carry = jnp.cumsum(row_tot, axis=1) - row_tot  # exclusive
    out = lane_cs + row_carry[:, :, None] + bases.astype(jnp.int32)[:, None, None]
    return out.reshape(packed.shape[0], PACK_BLOCK)


# ---------------------------------------------------------------------------
# predicate eval + stream compaction
# ---------------------------------------------------------------------------


def filter_compact(values: jax.Array, mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-block stream compaction.

    values: (nblk, B) any dtype; mask: (nblk, B) bool.
    Returns (compacted (nblk,B) with survivors packed to the front, counts (nblk,)).

    TPU-idiomatic form: permutation one-hot built from the mask prefix sum,
    contracted on the MXU.  Exact for f32 and for ints < 2^24 (the engine
    guarantees that for compacted int columns; larger ints are compacted in
    two f32 halves by the ops wrapper).
    """
    nblk, B = values.shape
    m = mask.astype(jnp.int32)
    pos = jnp.cumsum(m, axis=1) - 1  # target slot per survivor
    slots = jnp.arange(B, dtype=jnp.int32)[None, :, None]  # (1,B,1) target p
    onehot = ((pos[:, None, :] == slots) & mask[:, None, :])  # (nblk, p, j)
    # HIGHEST: on a TPU the default f32 matmul is one bf16 pass, which
    # would round every compacted value to 8 mantissa bits
    out = jnp.einsum(
        "bpj,bj->bp", onehot.astype(jnp.float32), values.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ).astype(values.dtype)
    return out, jnp.sum(m, axis=1)


# ---------------------------------------------------------------------------
# bloom probe
# ---------------------------------------------------------------------------

_BLOOM_C1 = jnp.uint32(0xCC9E2D51)
_BLOOM_C2 = jnp.uint32(0x1B873593)


def _mix(h: jax.Array) -> jax.Array:
    h = h ^ jax.lax.shift_right_logical(h, jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ jax.lax.shift_right_logical(h, jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ jax.lax.shift_right_logical(h, jnp.uint32(16))


def bloom_hashes(keys: jax.Array, n_hashes: int, n_bits: int):
    """Double hashing: idx_i = (h1 + i*h2) mod n_bits.  n_bits power of two."""
    ku = keys.astype(jnp.uint32)
    h1 = _mix(ku * _BLOOM_C1)
    h2 = _mix(ku * _BLOOM_C2) | jnp.uint32(1)
    mod = jnp.uint32(n_bits - 1)
    return [(h1 + jnp.uint32(i) * h2) & mod for i in range(n_hashes)]


def bloom_build(keys: jax.Array, n_bits: int, n_hashes: int = 4) -> jax.Array:
    """Build a bloom filter as (n_bits,) uint8 (byte-per-bit for gather-free probing)."""
    bits = jnp.zeros((n_bits,), jnp.uint8)
    for idx in bloom_hashes(keys, n_hashes, n_bits):
        bits = bits.at[idx].set(jnp.uint8(1))
    return bits


def bloom_probe(keys: jax.Array, bits: jax.Array, n_hashes: int = 4) -> jax.Array:
    """Membership mask (no false negatives)."""
    n_bits = bits.shape[0]
    out = jnp.ones(keys.shape, jnp.bool_)
    for idx in bloom_hashes(keys, n_hashes, n_bits):
        out = out & (jnp.take(bits, idx.astype(jnp.int32), mode="clip") > 0)
    return out


# ---------------------------------------------------------------------------
# fused scan: decode (bitpack|dict) -> range predicate -> mask + counts
# ---------------------------------------------------------------------------


def fused_scan(
    packed: jax.Array,
    k: int,
    lo: jax.Array,
    hi: jax.Array,
    dictionary: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Decode one filter column and evaluate lo <= v <= hi in one pass.

    Returns (mask (nblocks, 4096) bool, per-block survivor counts (nblocks,)).
    """
    vals = bitunpack(packed, k) if dictionary is None else dict_decode(packed, dictionary, k)
    vals = vals.reshape(packed.shape[0], PACK_BLOCK)
    mask = (vals >= lo.astype(vals.dtype)) & (vals <= hi.astype(vals.dtype))
    return mask, jnp.sum(mask.astype(jnp.int32), axis=1)


# ---------------------------------------------------------------------------
# grouped aggregate pushdown: per-block partial accumulators
# ---------------------------------------------------------------------------

# int32 sums are computed exactly as a 16-bit hi/lo split per block:
# v == (v >> 16) * 2^16 + (v & 0xFFFF) in two's complement, and both
# partial sums fit int32 for any 4096-row block (4096 * 0xFFFF < 2^28),
# so the host-side int64 recombination is EXACT — which is what makes the
# merge associative and the fabric's partial-aggregate reduction
# bit-identical under any bucket/row-group/pod split.
AGG_INT_SHIFT = 16
AGG_INT_MASK = 0xFFFF

# identity fills for (block, group) cells with no masked member.  Plain
# Python scalars on purpose: jnp constants would be captured by the
# pallas kernel bodies that call grouped_agg_tiles, which pallas_call rejects.
AGG_INT_MIN_IDENT = 2**31 - 1
AGG_INT_MAX_IDENT = -(2**31)
AGG_FLT_MIN_IDENT = float("inf")
AGG_FLT_MAX_IDENT = float("-inf")


def tile_reduce(fn, x: jax.Array) -> jax.Array:
    """(nblk, R, 128) -> (nblk, 1, 1), lanes first then sublanes (Mosaic
    reduces one axis at a time).  Shared with the Pallas kernels, so their
    interpret-mode float sums agree with this oracle bit-for-bit."""
    return fn(fn(x, axis=2, keepdims=True), axis=1, keepdims=True)


def grouped_agg_tiles(
    values: jax.Array, gids: jax.Array, mask: jax.Array, n_groups: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """`grouped_agg` on blocks in tile layout: (nblk, R, 128) inputs ->
    five (nblk, 1, n_groups) planes.  A loop over groups: one masked
    reduction per group, placed into its lane by a select; this body is also the
    grouped_agg / fused_agg Pallas kernels' body."""
    shape = (values.shape[0], 1, n_groups)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    live = mask.astype(jnp.int32) != 0
    gids = gids.astype(jnp.int32)
    is_float = jnp.issubdtype(values.dtype, jnp.floating)
    if is_float:
        v = values.astype(jnp.float32)
        lo_ident, hi_ident = AGG_FLT_MIN_IDENT, AGG_FLT_MAX_IDENT
    else:
        v = values.astype(jnp.int32)
        vhi, vlo = v >> AGG_INT_SHIFT, v & AGG_INT_MASK
        lo_ident, hi_ident = AGG_INT_MIN_IDENT, AGG_INT_MAX_IDENT

    def one_group(g, planes):
        cnt, s0, s1, mn, mx = planes
        m = live & (gids == g)
        at = lane == g
        cnt = jnp.where(at, tile_reduce(jnp.sum, m.astype(jnp.int32)), cnt)
        if is_float:
            s0 = jnp.where(at, tile_reduce(jnp.sum, jnp.where(m, v, 0.0)), s0)
        else:
            s0 = jnp.where(at, tile_reduce(jnp.sum, jnp.where(m, vhi, 0)), s0)
            s1 = jnp.where(at, tile_reduce(jnp.sum, jnp.where(m, vlo, 0)), s1)
        mn = jnp.where(at, tile_reduce(jnp.min, jnp.where(m, v, lo_ident)), mn)
        mx = jnp.where(at, tile_reduce(jnp.max, jnp.where(m, v, hi_ident)), mx)
        return cnt, s0, s1, mn, mx

    zeros = jnp.zeros(shape, jnp.int32)
    init = (zeros, jnp.zeros(shape, v.dtype), zeros,
            jnp.full(shape, lo_ident, v.dtype), jnp.full(shape, hi_ident, v.dtype))
    cnt, s0, s1, mn, mx = jax.lax.fori_loop(0, n_groups, one_group, init)
    return cnt, s0, s1, mn.astype(values.dtype), mx.astype(values.dtype)


@functools.partial(jax.jit, static_argnames=("n_groups",))
def grouped_agg(
    values: jax.Array, gids: jax.Array, mask: jax.Array, n_groups: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """(nblk, B) values + (nblk, B) int32 group ids + (nblk, B) mask ->
    per-block partial accumulators, each (nblk, n_groups):

      cnt  int32    masked member count
      s0   float32  block sums          (float values)
           int32    sum of (v >> 16)    (int values, arithmetic shift)
      s1   int32    sum of (v & 0xFFFF) (int values; zeros for float)
      mn   value dtype, min (identity fill where the cell is empty)
      mx   value dtype, max (identity fill where the cell is empty)

    All reductions are WITHIN a block, so computing any subset of
    blocks yields bit-identical rows — the pallas kernel's grid steps and
    this oracle agree exactly, and cross-block merging happens host-side
    in int64/float64 (core/agg.py).  B is a multiple of 128: each block
    is reduced in its (B // 128, 128) tile layout."""
    nblk, B = values.shape

    def tiles(x):
        return x.reshape(nblk, B // LANES, LANES)

    planes = grouped_agg_tiles(tiles(values), tiles(gids), tiles(mask), n_groups)
    return tuple(p.reshape(nblk, n_groups) for p in planes)


def fused_agg_scan(
    packed: jax.Array, k: int, mask: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fully-fused BITPACK decode -> masked ungrouped aggregate: the value
    column never exists outside the kernel.  Returns the same 5-tuple as
    `grouped_agg` with n_groups == 1 (shapes (nblk, 1))."""
    vals = bitunpack(packed, k).reshape(packed.shape[0], PACK_BLOCK)
    gids = jnp.zeros(vals.shape, jnp.int32)
    return grouped_agg(vals, gids, mask, 1)


# ---------------------------------------------------------------------------
# attention (oracle for flash_attention kernel)
# ---------------------------------------------------------------------------


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Plain softmax attention.  q: (B,H,Sq,D), k/v: (B,Hkv,Sk,D); GQA by head repeat."""
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else D ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    Sk = k.shape[2]
    qi = jnp.arange(Sq)[:, None] + (Sk - Sq)  # align ends (decode-friendly)
    ki = jnp.arange(Sk)[None, :]
    m = jnp.ones((Sq, Sk), jnp.bool_)
    if causal:
        m = m & (ki <= qi)
    if window is not None:
        m = m & (ki > qi - window)
    logits = jnp.where(m[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)

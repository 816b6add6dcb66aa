"""Public jit'd kernel API: dispatches Pallas kernel vs pure-jnp reference.

backend:
  'ref'    — pure jnp (what 'auto' picks off a TPU; also the dry-run path,
             since Pallas TPU lowering is unavailable on the CPU backend)
  'pallas' — pl.pallas_call, compiled on a TPU and interpreted elsewhere
  'auto'   — 'pallas' on TPU, 'ref' elsewhere (the default everywhere)

`_resolve` is the one place that decides interpret mode: the kernel
signatures take `interpret` without a default.

Every function here is shape/dtype-stable across backends; tests assert
exact agreement.

Batched entry points (`*_batch`): every encoding's block layout is
page-count-agnostic — BITPACK/DICT/DELTA pages are (nblocks, k, 128) and
RLE pages are (nblk, 128) — so compatible pages from MANY row groups
stack along the leading block axis and decode in ONE device dispatch.
Inputs are stacked host (numpy) buffers; the leading axis is padded to a
two-size-ladder bucket (see `bucket_blocks`) BEFORE the jitted call, so
the whole scan reuses a handful of compiled traces instead of re-tracing
per row-group count.

Single-call entry points on the 'ref' backend route through jitted
wrappers too: eager jnp issues one XLA executable per primitive, which
made a single RLE block decode ~100x slower than the same math compiled —
the dispatch-overhead wall the per-backend cost-model tables measure.
The module-level dispatch counter underneath `dispatch_count()` is the
benchmarks' device-dispatch metric: each public entry here counts the
launches it issues (a batch call counts ONE however many pages it
carries).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.agg_push import MAX_GROUPS, fused_agg_pallas, grouped_agg_pallas
from repro.kernels.bitunpack import bitunpack_pallas
from repro.kernels.bloom_probe import bloom_probe_pallas
from repro.kernels.delta_decode import delta_decode_pallas
from repro.kernels.dict_decode import dict_decode_batch_pallas, dict_decode_pallas
from repro.kernels.filter_compact import filter_compact_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_scan import fused_scan_batch_pallas, fused_scan_pallas
from repro.kernels.rle_decode import rle_decode_pallas


def _resolve(backend: str) -> Tuple[str, bool]:
    """-> (backend, interpret)"""
    on_tpu = jax.default_backend() == "tpu"
    if backend == "auto":
        backend = "pallas" if on_tpu else "ref"
    return backend, not on_tpu


# ---------------------------------------------------------------------------
# device-dispatch accounting (the batching benchmark's currency)
# ---------------------------------------------------------------------------

_DISPATCHES = 0


def _count(n: int = 1) -> None:
    global _DISPATCHES
    _DISPATCHES += n


def dispatch_count() -> int:
    """Device dispatches issued through this module since the last reset.
    One public decode/filter call counts one dispatch per kernel launch it
    issues (filter_compact's two-half int path counts two); a `*_batch`
    call counts ONE regardless of how many pages it carries."""
    return _DISPATCHES


def reset_dispatch_count() -> int:
    """Zero the dispatch counter; returns the value it had."""
    global _DISPATCHES
    n, _DISPATCHES = _DISPATCHES, 0
    return n


BUCKET_MODE = "ladder"  # 'ladder' (default) or 'pow2' (legacy, kept for A/B)


def set_bucket_mode(mode: str) -> str:
    """Switch the batch-padding bucket scheme; returns the previous mode."""
    global BUCKET_MODE
    assert mode in ("ladder", "pow2"), mode
    prev, BUCKET_MODE = BUCKET_MODE, mode
    return prev


def bucket_blocks(n: int, mode: Optional[str] = None) -> int:
    """Pad a stacked block count to its bucket, so batch launches hit a
    small, reused set of jit traces (shape-stable jit).

    'ladder' (default): two rungs per octave — {2^m, 3*2^(m-1)}, i.e.
    1, 2, 3, 4, 6, 8, 12, 16, 24, 32, ...  Worst-case pad waste drops
    from pow2's ~100% (n = 2^m + 1 pads to 2^(m+1)) to a bounded ~50%
    (~17% typical), at the cost of at most 2 compiled traces per octave
    instead of 1.  Each batch call is still exactly ONE launch, so the
    ladder never issues more dispatches than pow2 for the same workload
    (tests/test_batch_decode.py pins the invariant).
    'pow2': the legacy single-rung octave."""
    assert n > 0, n
    mode = mode or BUCKET_MODE
    p = 1 << (n - 1).bit_length()  # next power of two >= n
    if mode == "pow2" or p < 4:
        return p
    mid = 3 * (p // 4)  # the mid-octave rung 3*2^(m-2)
    return mid if n <= mid else p


def device_put(buf) -> jax.Array:
    """Counted host->device transfer: PLAIN 'decode' is a device put, and
    the dispatch metric must see it on both the sequential path (one put
    per page) and the batched path (one put per stacked bucket)."""
    _count()
    return jnp.asarray(buf)


# Jitted single-call reference paths.  The ref backend used to run these
# EAGERLY — one XLA executable per jnp primitive, so a single-page decode
# paid dozens of dispatches and the calibrated RLE/DELTA/DICT rates sat
# three orders of magnitude under PLAIN (BENCH_service.json point 5).
# Compiling each (shape, k) once and replaying it is the same trick the
# batch paths already used; the jit cache is keyed on page shape, which a
# real workload draws from a handful of values.

_ref_dict_decode = functools.partial(jax.jit, static_argnums=(2,))(ref.dict_decode)
_ref_bloom_probe = functools.partial(jax.jit, static_argnums=(2,))(ref.bloom_probe)
_ref_fused_scan = functools.partial(jax.jit, static_argnums=(1,))(ref.fused_scan)
_ref_filter_compact = jax.jit(ref.filter_compact)


@jax.jit
def _ref_filter_compact_int(values, mask):
    """Whole two-half int compaction fused into one executable."""
    v = values.astype(jnp.int32)
    hi16 = jax.lax.shift_right_arithmetic(v, 16)
    lo16 = v & 0xFFFF
    chi, cnt = ref.filter_compact(hi16, mask)
    clo, _ = ref.filter_compact(lo16, mask)
    out = jax.lax.shift_left(chi.astype(jnp.int32), 16) | clo.astype(jnp.int32)
    return out, cnt


def bitunpack(packed, k: int, n: Optional[int] = None, *, backend: str = "auto"):
    """(nblocks,k,128) uint32 -> flat (n,) int32 (or (nb,32,128) if n is None)."""
    backend, interp = _resolve(backend)
    _count()
    out = (
        bitunpack_pallas(packed, k, interpret=interp)
        if backend == "pallas"
        else _ref_bitunpack_batch(packed, k)
    )
    return out if n is None else out.reshape(-1)[:n]


def dict_decode(packed, dictionary, k: int, n: Optional[int] = None, *, backend="auto"):
    backend, interp = _resolve(backend)
    _count()
    out = (
        dict_decode_pallas(packed, dictionary, k, interpret=interp)
        if backend == "pallas"
        else _ref_dict_decode(packed, dictionary, k)
    )
    return out if n is None else out.reshape(-1)[:n]


def rle_decode(values, ends, n: Optional[int] = None, *, backend="auto"):
    backend, interp = _resolve(backend)
    _count()
    out = (
        rle_decode_pallas(values, ends, interpret=interp)
        if backend == "pallas"
        else _ref_rle_decode_batch(values, ends)
    )
    return out if n is None else out.reshape(-1)[:n]


def delta_decode(packed, bases, k: int, n: Optional[int] = None, *, backend="auto"):
    backend, interp = _resolve(backend)
    _count()
    out = (
        delta_decode_pallas(packed, bases, k, interpret=interp)
        if backend == "pallas"
        else _ref_delta_decode_batch(packed, bases, k)
    )
    return out if n is None else out.reshape(-1)[:n]


def filter_compact(values, mask, *, backend="auto"):
    """values (nblk,1024), mask (nblk,1024) -> (compacted, counts).

    Ints with |v| >= 2^24 are split into two 16-bit halves so the f32 MXU
    contraction stays exact.
    """
    backend, interp = _resolve(backend)
    if jnp.issubdtype(values.dtype, jnp.integer):
        # _count(2) on both backends: the pallas path launches two kernels,
        # and the ref path prices the same two logical compactions even
        # though jit fuses them into one executable
        _count(2)
        if backend != "pallas":
            out, cnt = _ref_filter_compact_int(values, mask)
            return out.astype(values.dtype), cnt
        v = values.astype(jnp.int32)
        hi16 = jax.lax.shift_right_arithmetic(v, 16)
        lo16 = v & 0xFFFF
        chi, cnt = filter_compact_pallas(hi16, mask, interpret=interp)
        clo, _ = filter_compact_pallas(lo16, mask, interpret=interp)
        out = jax.lax.shift_left(chi.astype(jnp.int32), 16) | clo.astype(jnp.int32)
        return out.astype(values.dtype), cnt
    _count()
    if backend == "pallas":
        return filter_compact_pallas(values, mask, interpret=interp)
    return _ref_filter_compact(values, mask)


def bloom_build(keys, n_bits: int, n_hashes: int = 4):
    return ref.bloom_build(keys, n_bits, n_hashes)


def bloom_probe(keys, bits, n_hashes: int = 4, *, backend="auto"):
    """keys (nblk,1024) -> membership (nblk,1024) bool."""
    backend, interp = _resolve(backend)
    _count()
    if backend == "pallas":
        return bloom_probe_pallas(keys, bits, n_hashes=n_hashes, interpret=interp) > 0
    return _ref_bloom_probe(keys, bits, n_hashes)


def fused_scan(packed, k: int, lo, hi, dictionary=None, *, backend="auto"):
    backend, interp = _resolve(backend)
    _count()
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    if backend == "pallas":
        mask, cnt = fused_scan_pallas(packed, k, lo, hi, dictionary, interpret=interp)
        return mask > 0, cnt
    return _ref_fused_scan(packed, k, lo, hi, dictionary)


# ---------------------------------------------------------------------------
# batched multi-page decode: one launch per (encoding, k, dtype) bucket
# ---------------------------------------------------------------------------
#
# The jitted reference implementations below are what makes the ref backend
# a single dispatch per bucket too: eager jnp would issue one executable
# per primitive, but jax.jit with a static k and a bucket-padded leading
# axis compiles each (k, bucket_blocks) shape once and replays it.


@functools.partial(jax.jit, static_argnames=("k",))
def _ref_bitunpack_batch(packed, k: int):
    return ref.bitunpack(packed, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _ref_dict_decode_batch(packed, dicts, sizes, k: int):
    codes = ref.bitunpack(packed, k)  # (nb, 32, 128) int32, >= 0
    lim = (sizes - 1).astype(jnp.int32)  # (nb, 1)
    c = jnp.clip(codes, 0, lim[:, :, None])  # per-block mode="clip"
    flat = jnp.take_along_axis(dicts, c.reshape(c.shape[0], -1), axis=1)
    return flat.reshape(codes.shape)


@functools.partial(jax.jit, static_argnames=("k",))
def _ref_delta_decode_batch(packed, bases, k: int):
    return ref.delta_decode(packed, bases, k)


@jax.jit
def _ref_rle_decode_batch(values, ends):
    return ref.rle_decode(values, ends)


@functools.partial(jax.jit, static_argnames=("k",))
def _ref_fused_scan_batch(packed, lohi, k: int):
    from repro.lakeformat.encodings import PACK_BLOCK

    vals = ref.bitunpack(packed, k).reshape(packed.shape[0], PACK_BLOCK)
    return (vals >= lohi[:, 0:1]) & (vals <= lohi[:, 1:2])


@functools.partial(jax.jit, static_argnames=("n_groups",))
def _ref_grouped_agg_batch(values, gids, mask, n_groups: int):
    return ref.grouped_agg(values, gids, mask, n_groups)


@functools.partial(jax.jit, static_argnames=("k",))
def _ref_fused_agg_batch(packed, mask, k: int):
    return ref.fused_agg_scan(packed, k, mask)


def _pad_blocks(arr: np.ndarray, target: int, fill=0) -> np.ndarray:
    """Host-side leading-axis pad to the bucket size.  Padding happens
    BEFORE the jitted call on purpose: padding inside the trace would key
    the jit cache on the raw block count and defeat bucketing."""
    nb = arr.shape[0]
    if nb == target:
        return arr
    pad = np.full((target - nb,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def bitunpack_batch(packed: np.ndarray, k: int, *, backend: str = "auto"):
    """Stacked (nblocks,k,128) uint32 pages -> (nblocks,32,128) int32 in
    ONE dispatch.  `packed` is a host (numpy) stack; the leading axis is
    bucket-padded host-side so jit traces are reused."""
    backend, interp = _resolve(backend)
    nb = packed.shape[0]
    padded = _pad_blocks(packed, bucket_blocks(nb))
    _count()
    out = (
        bitunpack_pallas(padded, k, interpret=interp)
        if backend == "pallas"
        else _ref_bitunpack_batch(padded, k)
    )
    return out[:nb]


def dict_decode_batch(
    packed: np.ndarray,
    dicts: np.ndarray,
    sizes: np.ndarray,
    page: np.ndarray,
    k: int,
    *,
    backend: str = "auto",
):
    """Multi-page dict decode in ONE dispatch.

    packed (nblocks,k,128) uint32 stacked codes; dicts (P, Dmax) page
    dictionaries padded to a common width; sizes (P,) true lengths;
    page (nblocks,) block -> source-page index.  Returns
    (nblocks,32,128) values of dicts.dtype, bit-identical per page to
    `dict_decode(packed_p, dicts[p, :sizes[p]], k)`.
    """
    backend, interp = _resolve(backend)
    nb = packed.shape[0]
    target = bucket_blocks(nb)
    padded = _pad_blocks(packed, target)
    page = _pad_blocks(np.asarray(page, np.int32), target)
    d_blocks = np.ascontiguousarray(dicts[page])  # (nb_pad, Dmax)
    s_blocks = np.asarray(sizes, np.int32)[page][:, None]  # (nb_pad, 1)
    np.maximum(s_blocks, 1, out=s_blocks)
    _count()
    out = (
        dict_decode_batch_pallas(padded, d_blocks, s_blocks, k, interpret=interp)
        if backend == "pallas"
        else _ref_dict_decode_batch(padded, d_blocks, s_blocks, k)
    )
    return out[:nb]


def delta_decode_batch(packed: np.ndarray, bases: np.ndarray, k: int, *, backend="auto"):
    """Stacked (nblocks,k,128) zigzag deltas + (nblocks,) bases ->
    (nblocks,4096) int32 in ONE dispatch (blocks are self-contained)."""
    backend, interp = _resolve(backend)
    nb = packed.shape[0]
    target = bucket_blocks(nb)
    padded = _pad_blocks(packed, target)
    bases = _pad_blocks(np.asarray(bases, np.int32), target)
    _count()
    out = (
        delta_decode_pallas(padded, bases, k, interpret=interp)
        if backend == "pallas"
        else _ref_delta_decode_batch(padded, bases, k)
    )
    return out[:nb]


def rle_decode_batch(values: np.ndarray, ends: np.ndarray, *, backend="auto"):
    """Stacked (nblk,128) run values + ends -> (nblk,1024) in ONE dispatch
    (the writer clips runs at block boundaries, so blocks are independent)."""
    backend, interp = _resolve(backend)
    nb = values.shape[0]
    target = bucket_blocks(nb)
    values = _pad_blocks(values, target)
    ends = _pad_blocks(ends, target)
    _count()
    out = (
        rle_decode_pallas(values, ends, interpret=interp)
        if backend == "pallas"
        else _ref_rle_decode_batch(values, ends)
    )
    return out[:nb]


def fused_scan_batch(packed: np.ndarray, k: int, lo: np.ndarray, hi: np.ndarray,
                     *, backend="auto"):
    """Batched fused decode+filter: stacked (nblocks,k,128) pages with
    PER-BLOCK int bounds lo/hi (nblocks,) -> survivor mask
    (nblocks,4096) bool in ONE dispatch.  Per-block bounds are what let
    DICT pages ride along: each row group's range is rewritten onto its
    own codes, so bounds differ across the stack."""
    backend, interp = _resolve(backend)
    nb = packed.shape[0]
    target = bucket_blocks(nb)
    padded = _pad_blocks(packed, target)
    lohi = np.stack([np.asarray(lo, np.int32), np.asarray(hi, np.int32)], axis=1)
    lohi = _pad_blocks(lohi, target)
    lohi[nb:, 0], lohi[nb:, 1] = 1, 0  # padded blocks match nothing
    _count()
    if backend == "pallas":
        return fused_scan_batch_pallas(padded, k, jnp.asarray(lohi),
                                       interpret=interp)[:nb] > 0
    return _ref_fused_scan_batch(padded, lohi, k)[:nb]


def _pad_blocks_dev(arr, target: int):
    """Leading-axis zero-pad that works for host numpy AND device arrays
    (decoded value blocks never round-trip to host just to be padded)."""
    nb = arr.shape[0]
    if nb == target:
        return arr
    if isinstance(arr, np.ndarray):
        return _pad_blocks(arr, target)
    return jnp.pad(arr, [(0, target - nb)] + [(0, 0)] * (arr.ndim - 1))


def grouped_agg_batch(values, gids, mask, n_groups: int, *, backend="auto"):
    """Batched grouped aggregate over stacked decoded blocks in ONE
    dispatch: values/gids/mask (nblocks, 4096) -> 5 x (nblocks, n_groups)
    partial accumulators (ref.grouped_agg layout).  Padded blocks carry
    mask == 0 so their rows are exact merge identities."""
    assert 1 <= n_groups <= MAX_GROUPS, n_groups
    backend, interp = _resolve(backend)
    nb = values.shape[0]
    target = bucket_blocks(nb)
    values = _pad_blocks_dev(values, target)
    gids = _pad_blocks_dev(gids, target)
    mask = _pad_blocks_dev(mask, target)
    _count()
    outs = (
        grouped_agg_pallas(values, gids, mask, n_groups, interpret=interp)
        if backend == "pallas"
        else _ref_grouped_agg_batch(values, gids, mask, n_groups)
    )
    return tuple(o[:nb] for o in outs)


def fused_agg_batch(packed: np.ndarray, k: int, mask, *, backend="auto"):
    """Fully-fused BITPACK decode -> masked ungrouped aggregate in ONE
    dispatch: stacked (nblocks, k, 128) pages + (nblocks, 4096) survivor
    mask -> 5 x (nblocks, 1) accumulators.  The decoded value column
    never leaves the kernel (the pushdown headline path)."""
    backend, interp = _resolve(backend)
    nb = packed.shape[0]
    target = bucket_blocks(nb)
    packed = _pad_blocks(packed, target)
    mask = _pad_blocks_dev(mask, target)
    _count()
    outs = (
        fused_agg_pallas(packed, k, mask, interpret=interp)
        if backend == "pallas"
        else _ref_fused_agg_batch(packed, mask, k)
    )
    return tuple(o[:nb] for o in outs)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None, backend="auto",
                    bq: int = 256, bk: int = 256):
    backend, interp = _resolve(backend)
    if backend == "pallas":
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale, bq=bq, bk=bk,
            interpret=interp,
        )
    return ref.mha(q, k, v, causal=causal, window=window, scale=scale)

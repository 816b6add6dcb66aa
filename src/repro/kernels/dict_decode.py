"""Pallas TPU kernel: fused bitunpack + dictionary lookup.

Decodes DICT(k) columns in one VMEM pass: the packed codes are unpacked
with the shared shift ladder (bitunpack.py) and immediately looked up in a
VMEM-resident dictionary, so codes never round-trip to HBM — the fusion the
paper's SmartNIC gets for free by being a pipeline.

The dictionary is held as (R, 128) rows and read with `lookup.lane_lookup`
(one lane gather per row), which is pure selection: bit-identical to
`jnp.take(..., mode="clip")` for ints and floats alike.  Codes are clipped
to the TRUE dictionary length first, exactly as the reference clips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitunpack import _ladder
from repro.kernels.lookup import as_rows, lane_lookup
from repro.lakeformat.encodings import LANES, SUBLANES

DEFAULT_GROUP = 4


def _kernel(k: int, n_true: int, packed_ref, dict_ref, out_ref):
    codes = jnp.clip(_ladder(packed_ref[...], k), 0, n_true - 1)
    d = dict_ref[...]  # (R, 128)
    for g in range(codes.shape[0]):
        out_ref[g] = lane_lookup(d, codes[g]).astype(out_ref.dtype)


def _batch_kernel(k: int, packed_ref, dict_ref, size_ref, out_ref):
    """Per-BLOCK dictionaries: each block of codes looks up its own
    dictionary rows (pre-gathered per block by the ops wrapper), clipped
    to its own dictionary's true length — exactly `jnp.take(dict_p, codes,
    mode="clip")` per source page, so batched == sequential bit-for-bit."""
    codes = _ladder(packed_ref[...], k)  # (G, 32, 128) int32
    for g in range(codes.shape[0]):
        lim = size_ref[g] - 1  # (1, 1)
        c = jnp.clip(codes[g], 0, lim)
        out_ref[g] = lane_lookup(dict_ref[g], c).astype(out_ref.dtype)


def _pad_group(x, group: int, fill=0):
    pad = (-x.shape[0]) % group
    if not pad:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=fill)


@functools.partial(jax.jit, static_argnames=("k", "group", "interpret"))
def dict_decode_batch_pallas(
    packed: jax.Array,
    dicts: jax.Array,
    sizes: jax.Array,
    k: int,
    *,
    group: int = DEFAULT_GROUP,
    interpret: bool,
) -> jax.Array:
    """Batched multi-page dict decode in ONE kernel launch.

    packed (nblocks, k, 128) uint32 codes stacked from many pages;
    dicts (nblocks, Dpad) per-block dictionary rows (page dictionaries
    padded to a common width and gathered per block by the caller);
    sizes (nblocks, 1) int32 true dictionary lengths.
    -> (nblocks, 32, 128) values of dicts.dtype.
    """
    nblocks = packed.shape[0]
    group = min(group, nblocks)
    packed = _pad_group(packed, group)
    dicts = _pad_group(dicts, group)
    sizes = _pad_group(sizes, group, fill=1)
    dpad = (-dicts.shape[1]) % LANES
    if dpad:
        dicts = jnp.pad(dicts, ((0, 0), (0, dpad)))
    rows = dicts.shape[1] // LANES
    dicts = dicts.reshape(dicts.shape[0], rows, LANES)
    sizes = sizes.astype(jnp.int32).reshape(-1, 1, 1)
    out = pl.pallas_call(
        functools.partial(_batch_kernel, k),
        grid=(packed.shape[0] // group,),
        in_specs=[
            pl.BlockSpec((group, k, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((group, rows, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((group, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((group, SUBLANES, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (packed.shape[0], SUBLANES, LANES), dicts.dtype
        ),
        interpret=interpret,
    )(packed, dicts, sizes)
    return out[:nblocks]


@functools.partial(jax.jit, static_argnames=("k", "group", "interpret"))
def dict_decode_pallas(
    packed: jax.Array,
    dictionary: jax.Array,
    k: int,
    *,
    group: int = DEFAULT_GROUP,
    interpret: bool,
) -> jax.Array:
    """(nblocks,k,128) uint32 codes + (D,) dict -> (nblocks,32,128) values."""
    nblocks = packed.shape[0]
    group = min(group, nblocks)
    packed = _pad_group(packed, group)
    n_true = dictionary.shape[0]
    d = as_rows(dictionary)
    out = pl.pallas_call(
        functools.partial(_kernel, k, n_true),
        grid=(packed.shape[0] // group,),
        in_specs=[
            pl.BlockSpec((group, k, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec(d.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((group, SUBLANES, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (packed.shape[0], SUBLANES, LANES), dictionary.dtype
        ),
        interpret=interpret,
    )(packed, d)
    return out[:nblocks]

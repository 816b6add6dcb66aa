"""Pallas TPU kernel: block-aligned RLE expansion by rank lookup.

The writer (lakeformat) clips runs at 1024-value block boundaries and pads
each block's run window to exactly RLE_WINDOW = 128 entries (repeating the
final value with end = 1024), so the kernel is fully static.  `ends` IS
the cumulative sum of run lengths, so the run owning output position j is
its rank:  rank(j) = |{r : ends[r] <= j}|.  `ends` is sorted, so the kernel
finds that rank by a 7-step branch-free binary search, each step one lane
gather over the block's 128 ends, then reads the owning run's value with
one more lane gather.  Reading the single owning run is exact for every
dtype, so there is no float/int split.

This keeps the *bounded decoder working set* property — the TPU analogue
of the paper's "decoders should share resources" co-design (DESIGN.md §4):
no data-dependent loop, deterministic VMEM footprint per block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.lakeformat.encodings import LANES, RLE_OUT_BLOCK, RLE_WINDOW

DEFAULT_GROUP = 4
_TILES = RLE_OUT_BLOCK // LANES  # 8 output rows of 128 per block


def _kernel(vals_ref, ends_ref, out_ref):
    shape = (_TILES, LANES)
    j = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
         + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    for g in range(out_ref.shape[0]):
        ends = jnp.broadcast_to(ends_ref[g].astype(jnp.int32), shape)
        # rank(j) clipped into the window, so the padded tail re-reads the
        # final (repeated) run value: the largest r <= 127 whose first r
        # ends are all <= j
        rank = jnp.zeros(shape, jnp.int32)
        step = RLE_WINDOW // 2
        while step:
            e = jnp.take_along_axis(ends, rank + (step - 1), axis=1)
            rank = jnp.where(e <= j, rank + step, rank)
            step //= 2
        vals = jnp.broadcast_to(vals_ref[g], shape)
        out_ref[g] = jnp.take_along_axis(vals, rank, axis=1).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def rle_decode_pallas(
    values: jax.Array, ends: jax.Array, *, group: int = DEFAULT_GROUP, interpret: bool
) -> jax.Array:
    """(nblk,128) run values + (nblk,128) ends -> (nblk,1024) decoded."""
    nblk = values.shape[0]
    group = min(group, nblk)
    pad = (-nblk) % group
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        ends = jnp.pad(ends, ((0, pad), (0, 0)), constant_values=RLE_OUT_BLOCK)
    nb = values.shape[0]
    spec = pl.BlockSpec((group, 1, RLE_WINDOW), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(nb // group,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((group, _TILES, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, _TILES, LANES), values.dtype),
        interpret=interpret,
    )(values.reshape(nb, 1, RLE_WINDOW), ends.reshape(nb, 1, RLE_WINDOW))
    return out.reshape(nb, RLE_OUT_BLOCK)[:nblk]

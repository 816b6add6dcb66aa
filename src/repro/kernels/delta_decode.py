"""Pallas TPU kernel: fused bitunpack + un-zigzag + blocked prefix sum.

DELTA(k) decode for sorted-ish integer columns (doc offsets, dates, keys).
Value order within a 4096 block is v = s*128 + l, so the prefix sum
decomposes into (a) a log2(128)-step shift/add scan along lanes and (b) a
32-row carry ladder — both static VPU work, fused with the unpack so
deltas never leave VMEM.

Critical path: the row carries are derived from plain row SUMS (a log-depth
tree reduction), not from the last lane of the materialized lane scan, so
the carry ladder and the single full-width lane scan are independent
dataflow — the old form ran `_lane_prefix_sum` twice with the second
waiting on the first's materialization.  int32 addition is associative
mod 2^32, so any association is bit-identical to the reference cumsum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitunpack import _ladder
from repro.lakeformat.encodings import LANES, PACK_BLOCK, SUBLANES

DEFAULT_GROUP = 4


def _prefix_sum(x: jax.Array, axis: int) -> jax.Array:
    """Inclusive prefix sum along `axis` via log-shift adds."""
    n = x.shape[axis]
    pads = [(0, 0)] * x.ndim
    sh = 1
    while sh < n:
        pads[axis] = (sh, 0)
        x = x + jnp.pad(jax.lax.slice_in_dim(x, 0, n - sh, axis=axis), pads)
        sh *= 2
    return x


def _kernel(k: int, packed_ref, bases_ref, out_ref):
    z = _ladder(packed_ref[...], k)  # (G,32,128) zigzag int32
    zu = z.astype(jnp.uint32)
    d = jax.lax.shift_right_logical(zu, jnp.uint32(1)).astype(jnp.int32) ^ -(
        zu & jnp.uint32(1)
    ).astype(jnp.int32)
    # row totals via tree reduction — does NOT wait on the lane scan
    row_tot = jnp.sum(d, axis=2, keepdims=True)  # (G,32,1)
    row_carry = _prefix_sum(row_tot, 1) - row_tot  # exclusive over rows
    lane_cs = _prefix_sum(d, 2)  # the single full-width lane scan
    out_ref[...] = lane_cs + row_carry + bases_ref[...]


@functools.partial(jax.jit, static_argnames=("k", "group", "interpret"))
def delta_decode_pallas(
    packed: jax.Array,
    bases: jax.Array,
    k: int,
    *,
    group: int = DEFAULT_GROUP,
    interpret: bool,
) -> jax.Array:
    """(nblocks,k,128) zigzag deltas + (nblocks,) int32 bases -> (nblocks,4096) int32."""
    nblocks = packed.shape[0]
    group = min(group, nblocks)
    pad = (-nblocks) % group
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0), (0, 0)))
        bases = jnp.pad(bases, (0, pad))
    nb = packed.shape[0]
    out = pl.pallas_call(
        functools.partial(_kernel, k),
        grid=(nb // group,),
        in_specs=[
            pl.BlockSpec((group, k, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((group, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((group, SUBLANES, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, SUBLANES, LANES), jnp.int32),
        interpret=interpret,
    )(packed, bases.astype(jnp.int32).reshape(nb, 1, 1))
    return out.reshape(nb, PACK_BLOCK)[:nblocks]

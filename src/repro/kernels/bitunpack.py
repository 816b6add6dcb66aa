"""Pallas TPU kernel: lane-transposed k-bit unpack.

This is the shared "decode core" of the datapath engine (DESIGN.md §4) —
the TPU stand-in for the SmartNIC's line-rate Parquet decoder.  The layout
(lakeformat/encodings.py) was designed so this kernel is gather-free:

  packed block (k, 128) uint32  ->  values block (32, 128) int32

with per-row *static* shifts, i.e. 32 unrolled VPU shift/or/and ops per
block of 4096 values.  Arithmetic intensity: reads 4*k bytes, writes 4*32
bytes per lane per block -> the kernel is purely HBM-bandwidth-bound, which
is exactly the property the paper wants from a datapath decoder (decode at
"line rate" = HBM rate, upstream of the consumer).

Grid: one step per group of GROUP blocks; BlockSpec stages
(GROUP, k, 128) packed words into VMEM and (GROUP, 32, 128) values out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.lakeformat.encodings import LANES, SUBLANES

DEFAULT_GROUP = 8


def _ladder(p: jax.Array, k: int) -> jax.Array:
    """(G, k, 128) uint32 -> (G, 32, 128) int32; statically unrolled."""
    if k == 32:
        return p.astype(jnp.int32)
    mask = jnp.uint32((1 << k) - 1)
    rows = []
    for s in range(SUBLANES):
        w0, sh = divmod(s * k, 32)
        val = jax.lax.shift_right_logical(p[:, w0, :], jnp.uint32(sh))
        if sh + k > 32:
            val = val | jax.lax.shift_left(p[:, w0 + 1, :], jnp.uint32(32 - sh))
        rows.append(val & mask)
    return jnp.stack(rows, axis=1).astype(jnp.int32)


def _kernel(k: int, packed_ref, out_ref):
    out_ref[...] = _ladder(packed_ref[...], k)


@functools.partial(jax.jit, static_argnames=("k", "group", "interpret"))
def bitunpack_pallas(
    packed: jax.Array, k: int, *, group: int = DEFAULT_GROUP, interpret: bool
) -> jax.Array:
    """(nblocks, k, 128) uint32 -> (nblocks, 32, 128) int32."""
    nblocks = packed.shape[0]
    group = min(group, nblocks)
    pad = (-nblocks) % group
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0), (0, 0)))
    steps = packed.shape[0] // group
    out = pl.pallas_call(
        functools.partial(_kernel, k),
        grid=(steps,),
        in_specs=[pl.BlockSpec((group, k, LANES), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((group, SUBLANES, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((packed.shape[0], SUBLANES, LANES), jnp.int32),
        interpret=interpret,
    )(packed)
    return out[:nblocks]

"""Pallas TPU kernel: the flagship fused datapath scan.

decode (BITPACK or DICT) -> range predicate -> mask + per-block counts,
one VMEM pass, no decoded-but-unfiltered bytes ever written to HBM.  This
is the direct analogue of the paper's SmartNIC pipeline: the consumer only
ever sees the survivor mask (and the engine materializes survivors on
demand with filter_compact).

Runtime predicate constants (lo, hi) arrive as a (1, 2) int32 operand so
one compiled kernel serves every query.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitunpack import _ladder
from repro.kernels.lookup import as_rows, lane_lookup
from repro.kernels.ref import tile_reduce
from repro.lakeformat.encodings import LANES, PACK_BLOCK, SUBLANES

DEFAULT_GROUP = 4


def _kernel(k: int, n_dict: int, *refs):
    if n_dict:
        packed_ref, dict_ref, lohi_ref, mask_ref, cnt_ref = refs
    else:
        packed_ref, lohi_ref, mask_ref, cnt_ref = refs
    codes = _ladder(packed_ref[...], k)  # (G,32,128) int32
    if n_dict:
        codes = jnp.clip(codes, 0, n_dict - 1)
        d = dict_ref[...]
        vals = jnp.stack([lane_lookup(d, codes[g]) for g in range(codes.shape[0])])
    else:
        vals = codes
    lo = lohi_ref[:, 0:1].astype(vals.dtype)  # (1, 1)
    hi = lohi_ref[:, 1:2].astype(vals.dtype)
    m = ((vals >= lo) & (vals <= hi)).astype(jnp.int32)
    mask_ref[...] = m
    cnt_ref[...] = tile_reduce(jnp.sum, m)


def _batch_kernel(k: int, packed_ref, lohi_ref, mask_ref):
    """Per-BLOCK predicate bounds: block b tests lohi_ref[b] — the batched
    form of the scalar kernel, so pages from many row groups (each with its
    own code-rewritten range, e.g. per-group DICT bounds) share one launch."""
    codes = _ladder(packed_ref[...], k)  # (G, 32, 128) int32
    lo = lohi_ref[:, :, 0:1]  # (G, 1, 1)
    hi = lohi_ref[:, :, 1:2]
    mask_ref[...] = ((codes >= lo) & (codes <= hi)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "group", "interpret"))
def fused_scan_batch_pallas(
    packed: jax.Array,
    k: int,
    lohi: jax.Array,
    *,
    group: int = DEFAULT_GROUP,
    interpret: bool,
) -> jax.Array:
    """Batched fused decode+filter over stacked pages in ONE launch.

    packed (nblocks, k, 128) uint32; lohi (nblocks, 2) int32 per-block
    bounds -> mask (nblocks, 4096) int32 (nonzero = survivor).
    """
    nblocks = packed.shape[0]
    group = min(group, nblocks)
    pad = (-nblocks) % group
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0), (0, 0)))
        # empty range (1, 0): padded blocks match nothing
        lohi = jnp.concatenate(
            [lohi, jnp.tile(jnp.array([[1, 0]], jnp.int32), (pad, 1))], axis=0
        )
    nb = packed.shape[0]
    mask = pl.pallas_call(
        functools.partial(_batch_kernel, k),
        grid=(nb // group,),
        in_specs=[
            pl.BlockSpec((group, k, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((group, 1, 2), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((group, SUBLANES, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, SUBLANES, LANES), jnp.int32),
        interpret=interpret,
    )(packed, lohi.astype(jnp.int32).reshape(nb, 1, 2))
    return mask.reshape(nb, PACK_BLOCK)[:nblocks]


@functools.partial(jax.jit, static_argnames=("k", "group", "interpret"))
def fused_scan_pallas(
    packed: jax.Array,
    k: int,
    lo: jax.Array,
    hi: jax.Array,
    dictionary: Optional[jax.Array] = None,
    *,
    group: int = DEFAULT_GROUP,
    interpret: bool,
):
    """Returns (mask (nblocks, 4096) int32, counts (nblocks,) int32)."""
    nblocks = packed.shape[0]
    group = min(group, nblocks)
    pad = (-nblocks) % group
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0), (0, 0)))
    nb = packed.shape[0]
    lohi = jnp.stack([lo, hi]).astype(jnp.int32)[None, :]  # (1, 2)
    in_specs = [pl.BlockSpec((group, k, LANES), lambda i: (i, 0, 0))]
    args = [packed]
    n_dict = 0
    if dictionary is not None:
        n_dict = dictionary.shape[0]
        d = as_rows(dictionary)
        in_specs.append(pl.BlockSpec(d.shape, lambda i: (0, 0)))
        args.append(d)
    in_specs.append(pl.BlockSpec((1, 2), lambda i: (0, 0)))
    args.append(lohi)
    mask, cnt = pl.pallas_call(
        functools.partial(_kernel, k, n_dict),
        grid=(nb // group,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((group, SUBLANES, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((group, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, SUBLANES, LANES), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return mask.reshape(nb, PACK_BLOCK)[:nblocks], cnt[:nblocks, 0, 0]

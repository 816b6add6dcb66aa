"""Pallas TPU kernels: fused operator pushdown — decode→aggregate.

The paper's SmartNIC wins by operating on data in the datapath; these
kernels extend that from filter+compact to aggregation (DESIGN.md §16).
Two entry points, both batched over pages stacked along the block axis
so many row groups share ONE launch per (encoding, k, dtype) bucket:

  grouped_agg_pallas   decoded value blocks + pre-decode int group ids
                       (a DICT/string column's codes) + survivor mask ->
                       per-block partial accumulators (count / hi-lo
                       split sums / min / max), each (nblocks, n_groups)
  fused_agg_pallas     BITPACK pages -> in-kernel unpack ladder -> masked
                       ungrouped aggregate; the value column NEVER exists
                       outside VMEM — the result DMA is (nblocks, 1)
                       accumulators instead of (nblocks, 4096) values

Both run `kernels/ref.py` `grouped_agg_tiles` as their body (the oracle
`grouped_agg` runs it too), on each block in its (32, 128) tile layout, so
parity is exact and every reduction stays within a block: grid steps,
bucket splits, row-group slices and pod shards all produce bit-identical
partial rows, and the host-side int64 / float64 merge (core/agg.py) is
order-independent by exactness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitunpack import _ladder
from repro.kernels.ref import grouped_agg_tiles
from repro.lakeformat.encodings import LANES, SUBLANES

# blocks per grid step; the body loops over groups, so every intermediate
# stays (group, 32, 128) whatever n_groups is
DEFAULT_GROUP = 2
MAX_GROUPS = 128  # pushdown eligibility ceiling (engine falls back above)


def _out_shapes(nblocks: int, n_groups: int, vdtype):
    """ShapeDtypeStructs for the 5 accumulator planes (cnt/s0/s1/mn/mx)."""
    sum_dt = jnp.float32 if jnp.issubdtype(vdtype, jnp.floating) else jnp.int32
    dts = (jnp.int32, sum_dt, jnp.int32, vdtype, vdtype)
    return [jax.ShapeDtypeStruct((nblocks, 1, n_groups), dt) for dt in dts]


def _write(out_refs, planes):
    for ref, plane in zip(out_refs, planes):
        ref[...] = plane


def _grouped_kernel(n_groups, vals_ref, gids_ref, mask_ref, *out_refs):
    _write(out_refs, grouped_agg_tiles(
        vals_ref[...], gids_ref[...], mask_ref[...], n_groups))


def _tiles(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[0], SUBLANES, LANES)


def _pad_rows(x: jax.Array, pad: int) -> jax.Array:
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x


def _agg_call(kernel, nblocks: int, group: int, in_blocks, n_groups: int,
              vdtype, interpret: bool, *args):
    """One pallas_call over padded (nb, ...) inputs -> 5 (nblocks, n_groups)
    planes; padded blocks carry mask == 0, so their rows are identities."""
    nb = args[0].shape[0]
    gspec = pl.BlockSpec((group, 1, n_groups), lambda i: (i, 0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(nb // group,),
        in_specs=[pl.BlockSpec((group,) + b, lambda i, n=len(b): (i,) + (0,) * n)
                  for b in in_blocks],
        out_specs=[gspec] * 5,
        out_shape=_out_shapes(nb, n_groups, vdtype),
        interpret=interpret,
    )(*args)
    return tuple(o.reshape(nb, n_groups)[:nblocks] for o in outs)


@functools.partial(jax.jit, static_argnames=("n_groups", "group", "interpret"))
def grouped_agg_pallas(
    values: jax.Array,
    gids: jax.Array,
    mask: jax.Array,
    n_groups: int,
    *,
    group: int = DEFAULT_GROUP,
    interpret: bool,
):
    """values (nblocks, 4096) int32|float32; gids/mask (nblocks, 4096)
    int32 -> 5 x (nblocks, n_groups): cnt, s0, s1, mn, mx (ref.grouped_agg
    layout).  Padded blocks carry mask == 0, so their rows are exact merge
    identities and the caller can simply drop them."""
    nblocks = values.shape[0]
    group = min(group, nblocks)
    pad = (-nblocks) % group
    args = [_tiles(_pad_rows(x, pad)) for x in
            (values, gids.astype(jnp.int32), mask.astype(jnp.int32))]
    tile = (SUBLANES, LANES)
    return _agg_call(functools.partial(_grouped_kernel, n_groups), nblocks, group,
                     [tile] * 3, n_groups, values.dtype, interpret, *args)


def _fused_kernel(k, packed_ref, mask_ref, *out_refs):
    vals = _ladder(packed_ref[...], k)  # (G, 32, 128) int32, in VMEM only
    gids = jnp.zeros(vals.shape, jnp.int32)
    _write(out_refs, grouped_agg_tiles(vals, gids, mask_ref[...], 1))


@functools.partial(jax.jit, static_argnames=("k", "group", "interpret"))
def fused_agg_pallas(
    packed: jax.Array,
    k: int,
    mask: jax.Array,
    *,
    group: int = DEFAULT_GROUP,
    interpret: bool,
):
    """packed (nblocks, k, 128) uint32 BITPACK pages + mask (nblocks,
    4096) int32 -> 5 x (nblocks, 1) accumulators, decode fused in-kernel
    (the flagship never-materialize path)."""
    nblocks = packed.shape[0]
    group = min(group, nblocks)
    pad = (-nblocks) % group
    args = [_pad_rows(packed, pad), _tiles(_pad_rows(mask.astype(jnp.int32), pad))]
    return _agg_call(functools.partial(_fused_kernel, k), nblocks, group,
                     [(k, LANES), (SUBLANES, LANES)], 1, jnp.int32, interpret, *args)

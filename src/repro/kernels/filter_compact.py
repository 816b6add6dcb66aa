"""Pallas TPU kernel: stream compaction (the pushed-down filter's output stage).

A streaming filter on an FPGA emits a variable-length stream; TPUs need
static shapes.  The TPU-idiomatic equivalent: per 1024-value block, build
the permutation one-hot P[p, j] = (prefix(mask)[j]-1 == p) & mask[j] and
contract it with the values on the MXU, packing survivors to the front.
Per-block survivor counts come along for free; the engine stitches blocks
with an exclusive scan over counts (core/engine.py).

Exactness: float columns are exact in f32; int columns are compacted via
the f32 MXU only when |v| < 2^24, else the ops wrapper splits into two
16-bit halves and recombines (two matmuls, still exact).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 1024


def _prefix_sum_last(x: jax.Array) -> jax.Array:
    n = x.shape[-1]
    sh = 1
    while sh < n:
        x = x + jnp.pad(x[..., :-sh], [(0, 0)] * (x.ndim - 1) + [(sh, 0)])
        sh *= 2
    return x


def _kernel(vals_ref, mask_ref, out_ref, cnt_ref):
    vals = vals_ref[0]  # (1, B)
    m = mask_ref[0]  # (1, B) int32
    pos = _prefix_sum_last(m) - 1  # (1, B)
    slots = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)  # (p, 1)
    onehot = (pos == slots) & (m > 0)  # (p, j)
    # HIGHEST: every output is one value times 1.0, exact only when the
    # MXU keeps all 24 mantissa bits (one bf16 pass keeps 8)
    out = jax.lax.dot_general(
        vals.astype(jnp.float32),
        onehot.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (1, p)
    out_ref[0] = out.astype(out_ref.dtype)
    cnt_ref[0] = jnp.sum(m, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def filter_compact_pallas(values: jax.Array, mask: jax.Array, *, interpret: bool):
    """values (nblk, 1024), mask (nblk, 1024) int32/bool ->
    (compacted (nblk, 1024), counts (nblk,))."""
    nblk = values.shape[0]
    spec = pl.BlockSpec((1, 1, BLOCK), lambda i: (i, 0, 0))
    out, cnt = pl.pallas_call(
        _kernel,
        grid=(nblk,),
        in_specs=[spec, spec],
        out_specs=[spec, pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((nblk, 1, BLOCK), values.dtype),
            jax.ShapeDtypeStruct((nblk, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(values.reshape(nblk, 1, BLOCK), mask.astype(jnp.int32).reshape(nblk, 1, BLOCK))
    return out.reshape(nblk, BLOCK), cnt[:, 0, 0]

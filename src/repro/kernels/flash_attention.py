"""Pallas TPU kernel: blocked causal/GQA flash attention (consumer side).

The datapath engine feeds models; their dominant compute hot-spot is
attention.  This kernel implements the standard online-softmax blocked
attention with:
  - GQA: grid is (batch, q_heads, q_blocks); K/V BlockSpecs map q-head ->
    kv-head via h // (H // Hkv), so kv blocks are fetched once per group,
  - causal block skipping: the fori_loop upper bound is trimmed to the
    last kv block visible to this q block,
  - optional sliding window (lower bound trimmed symmetrically).

K/V rows for one (batch, kv-head) are staged whole into VMEM, which bounds
supported context to ~8k at d=128 in f32; longer contexts use the jnp
blocked path (models/layers.py) — see DESIGN.md §Perf for the trade.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _kernel(bq: int, bk: int, scale: float, causal: bool, window: Optional[int],
            q_ref, k_ref, v_ref, o_ref):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
    Sk = k_ref.shape[2]
    nkb = Sk // bk
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    if causal:
        hi = jnp.minimum(nkb, ((qi + 1) * bq + bk - 1) // bk)
    else:
        hi = nkb
    if window is not None:
        lo = jnp.maximum(0, (qi * bq - window) // bk)
    else:
        lo = 0

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, 0, pl.ds(i * bk, bk), :].astype(jnp.float32)  # (bk, D)
        vb = v_ref[0, 0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        k_pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    D = q_ref.shape[-1]
    init = (
        jnp.full((bq, 1), NEG_INF, jnp.float32),
        jnp.zeros((bq, 1), jnp.float32),
        jnp.zeros((bq, D), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(lo, hi, body, init)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret", "scale")
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool,
) -> jax.Array:
    """q (B,H,Sq,D), k/v (B,Hkv,Sk,D) -> (B,H,Sq,D).  Sq % bq == Sk % bk == 0."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    assert Sq == Sk or not causal, "causal kernel assumes aligned q/k (training)"
    rep = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    grid = (B, H, Sq // bq)
    out = pl.pallas_call(
        functools.partial(_kernel, bq, bk, scale, causal, window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out

"""Pallas TPU kernel: bloom-filter probe (pushed-down semijoin).

The paper's on-NIC engine applies "bloom filters for probe-side filtering
in joins" to the decoded stream.  Here the byte-per-bit bloom filter of
`ref.bloom_build` is packed into 32-bit words (n_bits <= 2^17 -> <= 16 KiB)
that stay VMEM-resident across all grid steps; keys are hashed with a
murmur-style double hash (identical constants to ref.bloom_hashes) and each
probe reads its word with `lookup.lane_lookup`, then tests the bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lookup import as_rows, lane_lookup
from repro.lakeformat.encodings import LANES

BLOCK = 1024
DEFAULT_GROUP = 4
_ROWS = BLOCK // LANES  # a 1024-key block is (8, 128)


def _mix(h):
    h = h ^ jax.lax.shift_right_logical(h, jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ jax.lax.shift_right_logical(h, jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ jax.lax.shift_right_logical(h, jnp.uint32(16))


def _kernel(n_hashes: int, n_bits: int, keys_ref, words_ref, out_ref):
    words = words_ref[...]  # (R, 128) int32 bit words
    mod = jnp.uint32(n_bits - 1)
    for g in range(keys_ref.shape[0]):
        ku = keys_ref[g].astype(jnp.uint32)  # (8, 128)
        h1 = _mix(ku * jnp.uint32(0xCC9E2D51))
        h2 = _mix(ku * jnp.uint32(0x1B873593)) | jnp.uint32(1)
        ok = jnp.ones(ku.shape, jnp.int32)
        for i in range(n_hashes):
            idx = ((h1 + jnp.uint32(i) * h2) & mod).astype(jnp.int32)
            w = lane_lookup(words, jax.lax.shift_right_logical(idx, 5))
            ok = ok & (jax.lax.shift_right_logical(w, idx & 31) & 1)
        out_ref[g] = ok


def pack_bits(bits: jax.Array) -> jax.Array:
    """(n_bits,) byte-per-bit filter -> (ceil(n_bits/32),) int32 words,
    bit i of the filter at bit (i % 32) of word i // 32."""
    pad = (-bits.shape[0]) % 32
    b = (jnp.pad(bits, (0, pad)).reshape(-1, 32) != 0).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=1, dtype=jnp.uint32).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_hashes", "group", "interpret"))
def bloom_probe_pallas(
    keys: jax.Array,
    bits: jax.Array,
    *,
    n_hashes: int = 4,
    group: int = DEFAULT_GROUP,
    interpret: bool,
) -> jax.Array:
    """keys (nblk, 1024) int32, bits (n_bits,) uint8 -> membership (nblk, 1024) int32."""
    nblk = keys.shape[0]
    n_bits = bits.shape[0]
    assert n_bits & (n_bits - 1) == 0, "n_bits must be a power of two"
    group = min(group, nblk)
    pad = (-nblk) % group
    if pad:
        keys = jnp.pad(keys, ((0, pad), (0, 0)))
    nb = keys.shape[0]
    words = as_rows(pack_bits(bits))
    spec = pl.BlockSpec((group, _ROWS, LANES), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, n_hashes, n_bits),
        grid=(nb // group,),
        in_specs=[spec, pl.BlockSpec(words.shape, lambda i: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nb, _ROWS, LANES), jnp.int32),
        interpret=interpret,
    )(keys.reshape(nb, _ROWS, LANES), words)
    return out.reshape(nb, BLOCK)[:nblk]

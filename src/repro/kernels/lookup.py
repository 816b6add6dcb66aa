"""Table lookup inside a Pallas TPU kernel.

Mosaic lowers a gather only along the lanes of a 2D array, so a table
of any length is held as (R, 128) rows: every row is one lane gather,
and a select keeps the row each index points into.  Pure selection, so
the result is bit-identical to `jnp.take` for every dtype; the work is
R gathers per (rows, 128) tile of indices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.lakeformat.encodings import LANES


def as_rows(table: jax.Array) -> jax.Array:
    """Flat (D,) table -> (ceil(D/128), 128) rows, zero-padded."""
    pad = (-table.shape[0]) % LANES
    if pad:
        table = jnp.pad(table, (0, pad))
    return table.reshape(-1, LANES)


def lane_lookup(rows: jax.Array, idx: jax.Array) -> jax.Array:
    """rows (R, 128) table, idx (S, 128) int32 in [0, R*128) ->
    (S, 128) `rows.reshape(-1)[idx]`."""
    row = jax.lax.shift_right_logical(idx, 7)
    lane = idx & (LANES - 1)
    out = None
    for r in range(rows.shape[0]):
        got = jnp.take_along_axis(
            jnp.broadcast_to(rows[r:r + 1, :], idx.shape), lane, axis=1
        )
        out = got if out is None else jnp.where(row == r, got, out)
    return out

"""In-kernel stream compaction that Mosaic lowers: a walk over a tile.

The kernels that compact matches (the fused scan join, the fused probe, the
closure descendants) cannot use a cumsum or a runtime-indexed vector
scatter on the TPU.  Instead each tile's matches are visited in ascending
order of a flat index ``(row << shift) | lane``: every step takes the
smallest index above the last one (one vector min over the tile) and hands
it to a callback that stores scalars, usually into an SMEM output.  The
walk's cost is one tile reduction per emitted match, and it stops at the
first match whose output position is past the capacity.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def lane_shift(width: int) -> int:
    """Bits of a flat index ``(row << shift) | lane`` taken by the lane."""
    return max(1, (width - 1).bit_length())


def flat_index(shape, shift: int) -> jax.Array:
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (rows << shift) | lanes


def walk(flat_ref, big: int, out_cap: int,
         place: Callable, write: Callable) -> None:
    """Visit the tile's matches in ascending flat order.

    ``flat_ref`` holds each entry's flat index, or ``big`` where nothing
    matched.  ``place(nxt, t)`` gives the output position of the ``t``-th
    match ``nxt``; ``write(pos, nxt)`` stores it.  Positions grow along the
    walk, so it ends at the first match placed at or past ``out_cap``.
    """

    def body(carry):
        last, t, _ = carry
        cand = flat_ref[...]
        nxt = jnp.min(jnp.where(cand > last, cand, big))
        pos = place(nxt, t)
        go = (nxt < big) & (pos < out_cap)

        @pl.when(go)
        def _():
            write(pos, nxt)

        return nxt, t + 1, go

    jax.lax.while_loop(lambda c: c[2], body,
                       (jnp.int32(-1), jnp.int32(0), jnp.bool_(True)))

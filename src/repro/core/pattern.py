"""Triple patterns and binding tables (the engine's relations).

A *compiled* plan fixes the variable universe: every variable gets a column in
a fixed-width binding table.  ``PAD_ID`` (0) doubles as SPARQL's *unbound*
value, which makes OPTIONAL's outer join a ``jnp.maximum`` merge.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from .rdf import PAD_ID


class SlotMode(enum.IntEnum):
    CONST = 0       # slot is a fixed term id
    BOUND = 1       # slot is a variable already bound at this plan step
    FREE = 2        # slot is a variable first bound by this pattern


@dataclasses.dataclass(frozen=True)
class Slot:
    mode: SlotMode
    const: int = 0      # term id when CONST
    var: int = -1       # variable column when BOUND/FREE

    @staticmethod
    def const_(term_id: int) -> "Slot":
        return Slot(SlotMode.CONST, const=int(term_id))

    @staticmethod
    def bound(var_col: int) -> "Slot":
        return Slot(SlotMode.BOUND, var=int(var_col))

    @staticmethod
    def free(var_col: int) -> "Slot":
        return Slot(SlotMode.FREE, var=int(var_col))


@dataclasses.dataclass(frozen=True)
class CompiledPattern:
    """One triple pattern with slot modes resolved against the plan state."""

    s: Slot
    p: Slot
    o: Slot

    def free_vars(self) -> Tuple[int, ...]:
        return tuple(
            sl.var for sl in (self.s, self.p, self.o) if sl.mode == SlotMode.FREE
        )

    def predicates(self) -> Tuple[int, ...]:
        return (self.p.const,) if self.p.mode == SlotMode.CONST else ()


class Bindings(NamedTuple):
    """Fixed-capacity solution-mapping table.

    ``cols``: ``[cap, num_vars]`` uint32, PAD_ID = unbound.
    ``valid``: ``[cap]`` bool.
    ``overflow``: scalar bool — capacity was exceeded somewhere upstream, so
    the result is a (deterministic, prefix-preserving) under-approximation.
    """

    cols: jax.Array
    valid: jax.Array
    overflow: jax.Array

    @property
    def capacity(self) -> int:
        return int(self.cols.shape[-2])

    @property
    def num_vars(self) -> int:
        return int(self.cols.shape[-1])

    def count(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32), axis=-1)


def empty_bindings(capacity: int, num_vars: int) -> Bindings:
    return Bindings(
        cols=jnp.zeros((capacity, num_vars), jnp.uint32),
        valid=jnp.zeros((capacity,), bool),
        overflow=jnp.zeros((), bool),
    )


def universe_bindings(capacity: int, num_vars: int) -> Bindings:
    """A single all-unbound solution (the BGP identity element)."""
    b = empty_bindings(capacity, num_vars)
    return b._replace(valid=b.valid.at[0].set(True))


_PREFIX_BLOCK = 128


def prefix_count(mask: jax.Array) -> jax.Array:
    """Inclusive running count of a 1-D bool ``mask``, as int32.

    Equal to ``cumsum(mask)``, computed in two levels: a triangular-ones
    matmul within 128-wide blocks (0/1 inputs and counts up to 128 are exact
    in bf16 x bf16 -> f32), plus a cumsum over the block totals.  A plain
    cumsum over a million entries takes the TPU compiler over 20 s; this
    takes under one.
    """
    n = mask.shape[0]
    b = _PREFIX_BLOCK
    nb = -(-n // b)
    blocks = jnp.pad(mask, (0, nb * b - n)).reshape(nb, b)
    tri = jnp.arange(b)[:, None] <= jnp.arange(b)[None, :]
    within = jnp.dot(blocks.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    totals = within[:, -1]
    before = jnp.cumsum(totals) - totals
    return (within + before[:, None]).reshape(nb * b)[:n]


def compact_rows(
    rows: jax.Array, mask: jax.Array, out_cap: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Order-preserving compaction of masked ``[n, ...]`` rows into ``out_cap``.

    Returns ``(rows_out [out_cap, ...], valid [out_cap], overflow [])``.
    """
    n = rows.shape[0]
    pos = prefix_count(mask) - 1
    total = jnp.sum(mask.astype(jnp.int32))
    tgt = jnp.where(mask & (pos < out_cap), pos, out_cap)
    idx = jnp.full((out_cap + 1,), -1, jnp.int32)
    idx = idx.at[tgt].set(jnp.where(mask, jnp.arange(n, dtype=jnp.int32), -1), mode="drop")
    idx = idx[:out_cap]
    safe = jnp.maximum(idx, 0)
    out = jnp.take(rows, safe, axis=0)
    valid = idx >= 0
    out = jnp.where(
        valid.reshape((out_cap,) + (1,) * (rows.ndim - 1)), out, jnp.zeros_like(out)
    )
    return out, valid, total > out_cap


def compact_index(
    mask: jax.Array, out_cap: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Where :func:`compact_rows` would take its rows from, without rows.

    Returns ``(src [out_cap] int32, valid [out_cap], overflow [])``: the
    first ``min(count, out_cap)`` set positions of the flat ``mask``, in
    order (``src`` past them points anywhere in range).  For a mask over a
    virtual product, the caller gathers only the ``out_cap`` winning rows
    instead of materializing every candidate row.
    """
    cum = prefix_count(mask)
    total = cum[-1]
    k = jnp.arange(out_cap, dtype=jnp.int32)
    src = jnp.searchsorted(cum, k + 1, side="left").astype(jnp.int32)
    src = jnp.minimum(src, mask.shape[0] - 1)
    return src, k < jnp.minimum(total, out_cap), total > out_cap

"""Pallas TPU kernel: boolean matrix product (transitive-closure step).

RDFS subclass reasoning is pointer-chasing on a CPU engine; on TPU the class
hierarchy becomes a dense boolean adjacency matrix and closure is log(depth)
repeated squarings — each squaring one MXU matmul with a saturating cast.

Classic three-loop tiling: grid ``(n/bm, n/bn, n/bk)`` with the K dimension
innermost so the f32 accumulator tile stays resident in VMEM; matmul tiles
are 128-aligned for the MXU.

:func:`descendants_pallas` fuses the *final* squaring with closure-set
extraction: reasoning queries only consume one column of the closure (the
descendants of a root class), so the last step collapses to a matvec whose
set entries are compacted in-kernel into a bounded id list — the squared
``[n, n]`` matrix of the final step never reaches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.walk import walk


def _bool_matmul_kernel(nk: int, a_ref, b_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _saturate():
        out_ref[...] = jnp.minimum(out_ref[...], 1.0)


def _descendants_kernel(out_cap: int, reach_ref, root_ref, ids_ref,
                        count_ref, flat_ref):
    """Fused final squaring + compaction for one root class.

    One ``[bm, n]`` row block per grid step: the block's slice of the final
    closure *column* is ``reach_block @ reach[:, root]`` (the full
    ``reach @ reach`` product for the last squaring never exists), taken as
    a lane reduction of the block against the root column laid out as a
    ``[1, n]`` row.  Set rows append their global indices to the SMEM id
    list in ascending order (:mod:`repro.kernels.walk`);
    ``count_ref`` carries the full running count across the sequential grid.
    """
    i = pl.program_id(0)
    bm = reach_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        count_ref[0] = 0

        def zero(t, carry):
            ids_ref[t] = 0
            return carry

        jax.lax.fori_loop(0, out_cap + 1, zero, 0)

    col = jnp.sum(reach_ref[...] * root_ref[...], axis=1, keepdims=True)
    row = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    hit = col > 0.5                                              # [bm, 1]
    base = count_ref[0]

    @pl.when(base < out_cap)
    def _():
        flat_ref[...] = jnp.where(hit, row, bm)

        def write(pos, nxt):
            ids_ref[pos] = i * bm + nxt

        walk(flat_ref, bm, out_cap, lambda nxt, t: base + t, write)

    count_ref[0] = base + jnp.sum(hit.astype(jnp.int32))


def descendants_pallas(
    reach: jax.Array,       # [n, n] f32 in {0, 1}: closure before last squaring
    rootcol: jax.Array,     # [n] f32: reach[:, root]
    out_cap: int,
    bm: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns ``(ids [out_cap] int32, count [] int32)``.

    ``ids[:min(count, out_cap)]`` are the ascending row indices i with
    ``min(reach @ reach, 1)[i, root] > 0.5`` — the root's descendant set,
    compacted in-kernel without materializing the final squared matrix;
    later entries are zero.
    """
    n = reach.shape[0]
    assert reach.shape == (n, n) and n % bm == 0, (reach.shape, bm)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    ids, count = pl.pallas_call(
        functools.partial(_descendants_kernel, out_cap),
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((out_cap + 1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.int32)],
        interpret=resolve_interpret(interpret),
        name="closure_descendants",
    )(reach, rootcol.reshape(1, n))
    return ids[:out_cap], count[0]


def closure_step_pallas(
    reach: jax.Array,           # [n, n] f32 in {0, 1}, n multiple of block
    bm: int = 128, bn: int = 128, bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    n = reach.shape[0]
    assert reach.shape == (n, n) and n % bm == 0 and n % bn == 0 and n % bk == 0
    nk = n // bk
    kern = functools.partial(_bool_matmul_kernel, nk)
    return pl.pallas_call(
        kern,
        grid=(n // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="closure_step",
    )(reach, reach)

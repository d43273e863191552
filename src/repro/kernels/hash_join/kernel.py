"""Pallas TPU kernels: tiled window-vs-KB join (match + fused compaction).

TPU adaptation of DSCEP's KB-scan join.  A CPU engine (C-SPARQL) walks hash
maps pointer-by-pointer; the TPU-native formulation streams the KB partition
through VMEM in ``bn``-wide blocks and evaluates all ``bm x bn`` slot-equality
predicates as vector compares (VPU).  Three entry points:

* :func:`match_matrix_pallas` — the original kernel: emits the full int8
  candidate matrix ``[M, N]`` that the caller compacts.  O(M*N) HBM traffic.
* :func:`join_compact_pallas` — the fused pipeline: match tiles never leave
  VMEM; the kernel emits, for each of the first ``out_cap`` matches in
  global row-major order, its ``(binding row, KB row)`` source pair.  The
  caller gathers those ``out_cap`` rows — bit-identical to materializing the
  candidate matrix and running :func:`repro.core.pattern.compact_rows`.
* :func:`probe_compact_pallas` — the probe-method analogue: over the
  ``[M, k_max]`` candidates a sorted-index probe gathered, re-check every
  slot exactly and emit the flat indices of the first ``out_cap`` matches.

The fused scan is classic two-phase stream compaction:

1. **count** — grid ``(M/bm, N/bn)`` accumulates per-binding-row match
   counts into an ``[M, 1]`` int32 vector (4 bytes/row vs N bytes/row for
   the candidate matrix).
2. exclusive cumsum of the counts in XLA -> global row offsets.
3. **emit** — same grid; each tile recomputes its match block (compares are
   ~free; recompute beats an HBM round-trip) and walks its matches in
   row-major order.  A match of binding row ``r`` lands at
   ``offset[r] + (matches of r already emitted)``.

How the kernels stay inside what Mosaic lowers: every id travels as int32
(a bit-preserving view of the uint32 ids), validity is an int32 0/1 column
``[M, 1]`` or row ``[1, N]`` so no bool vector is ever reshaped, and the
compaction is a walk rather than a cumsum plus a scatter.  Each step of the
walk takes the smallest flat index above the last one (one vector min over
the tile) and stores a scalar into an SMEM output.  Positions grow along the
walk, so it stops at the first match past ``out_cap``: a call does at most
``out_cap`` steps plus one per tile.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pattern import CompiledPattern, SlotMode
from repro.kernels import resolve_interpret
from repro.kernels.walk import flat_index, lane_shift, walk

DEFAULT_BM = 128
DEFAULT_BN = 1024


def as_i32(x: jax.Array) -> jax.Array:
    """uint32 ids keep their bits; bool masks become 0/1."""
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.astype(jnp.int32)


def _i32_const(term_id: int) -> int:
    """A uint32 term id as the int32 with the same bits."""
    return int(np.array(term_id, np.uint32).view(np.int32))


def _eq(a, b) -> jax.Array:
    return (a == b).astype(jnp.int32)


def _tile_match(pat: CompiledPattern, cols, bvalid, ks, kp, ko, kvalid):
    """All-slot equality for one tile, as int32 0/1 ``[bm, bn]``.

    ``cols [bm, nv]`` and ``bvalid [bm, 1]`` are the binding side,
    ``ks/kp/ko/kvalid [1, bn]`` the KB block; all int32.
    """
    kcols = (ks, kp, ko)
    slots = (pat.s, pat.p, pat.o)
    m = bvalid & kvalid
    for i, slot in enumerate(slots):
        if slot.mode == SlotMode.CONST:
            m = m & _eq(kcols[i], _i32_const(slot.const))
        elif slot.mode == SlotMode.BOUND:
            m = m & _eq(kcols[i], cols[:, slot.var:slot.var + 1])
    for i in range(3):
        for j in range(i + 1, 3):
            if (
                slots[i].mode != SlotMode.CONST
                and slots[j].mode != SlotMode.CONST
                and slots[i].var == slots[j].var
            ):
                m = m & _eq(kcols[i], kcols[j])
    return m


def _probe_match(pat: CompiledPattern, cols, bvalid, ms, mp, mo, ok):
    """Anchor/const re-check on gathered ``[bm, k]`` candidates (int32 0/1).

    Exact parity with :func:`repro.core.algebra.kb_join_probe`'s
    verification loop: the composite probe key hashes numeric literals, so
    anchors must be re-checked with true equality, and the non-anchored
    endpoint is verified here too.  Every input is int32.
    """
    m = ok & bvalid
    kcols = (ms, mp, mo)
    for i, slot in enumerate((pat.s, pat.p, pat.o)):
        if slot.mode == SlotMode.CONST:
            m = m & _eq(kcols[i], _i32_const(slot.const))
        elif slot.mode == SlotMode.BOUND:
            m = m & _eq(kcols[i], cols[:, slot.var:slot.var + 1])
    return m


# --------------------------------------------------------------------------
# original kernel: full candidate matrix
# --------------------------------------------------------------------------

def _match_kernel(pat: CompiledPattern, cols_ref, bvalid_ref, ks_ref, kp_ref,
                  ko_ref, kvalid_ref, out_ref):
    """One [bm, bn] tile: all-slot equality under the static pattern."""
    m = _tile_match(pat, cols_ref[...], bvalid_ref[...], ks_ref[...],
                    kp_ref[...], ko_ref[...], kvalid_ref[...])
    out_ref[...] = m.astype(jnp.int8)


def _scan_specs(bm: int, bn: int, nv: int):
    return [
        pl.BlockSpec((bm, nv), lambda i, j: (i, 0)),    # binding tile
        pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),     # binding validity
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),     # KB subject block
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),     # KB predicate block
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),     # KB object block
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),     # KB validity block
    ]


def match_matrix_pallas(
    cols: jax.Array,        # [M, NV] int32 (M multiple of bm)
    bvalid: jax.Array,      # [M, 1] int32 0/1
    ks: jax.Array, kp: jax.Array, ko: jax.Array,   # [1, N] int32 (N mult of bn)
    kvalid: jax.Array,      # [1, N] int32 0/1
    pat: CompiledPattern,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> jax.Array:
    m, nv = cols.shape
    n = ks.shape[1]
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    return pl.pallas_call(
        functools.partial(_match_kernel, pat),
        grid=(m // bm, n // bn),
        in_specs=_scan_specs(bm, bn, nv),
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=resolve_interpret(interpret),
        name="hash_join_match",
    )(cols, bvalid, ks, kp, ko, kvalid)


# --------------------------------------------------------------------------
# fused kernel: join -> compaction without the [M, N] round-trip
# --------------------------------------------------------------------------

def _count_kernel(pat: CompiledPattern, cols_ref, bvalid_ref, ks_ref, kp_ref,
                  ko_ref, kvalid_ref, counts_ref):
    """Phase 1: accumulate per-binding-row match counts across KB blocks."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    m = _tile_match(pat, cols_ref[...], bvalid_ref[...], ks_ref[...],
                    kp_ref[...], ko_ref[...], kvalid_ref[...])
    counts_ref[...] += jnp.sum(m, axis=1, keepdims=True)


def _emit_kernel(pat: CompiledPattern, out_cap: int, cols_ref, bvalid_ref,
                 ks_ref, kp_ref, ko_ref, kvalid_ref, offs_ref, bi_ref, kr_ref,
                 flat_ref, base_ref):
    """Phase 3: emit the source pair of every match placed before out_cap.

    ``bi_ref``/``kr_ref`` are whole ``[1, out_cap + 1]`` SMEM outputs (constant
    index map — the TPU grid is sequential, so every tile writes into the
    same resident buffer).  ``base_ref`` counts each binding row's emitted
    matches across ``j`` steps.  A row's matches stop being emitted only at
    or past ``out_cap``, where its later matches (and every later row's)
    also land, so the count is exact wherever it is read.
    """
    i, j = pl.program_id(0), pl.program_id(1)
    bm, bn = flat_ref.shape
    shift = lane_shift(bn)

    @pl.when(j == 0)
    def _():
        base_ref[...] = jnp.zeros_like(base_ref)

    offs = offs_ref[...]                                    # [bm, 1]

    @pl.when(jnp.min(offs) < out_cap)
    def _():
        m = _tile_match(pat, cols_ref[...], bvalid_ref[...], ks_ref[...],
                        kp_ref[...], ko_ref[...], kvalid_ref[...])
        big = bm << shift
        flat_ref[...] = jnp.where(m != 0, flat_index((bm, bn), shift), big)
        row = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)

        def place(nxt, t):
            return jnp.sum(jnp.where(row == (nxt >> shift),
                                     offs + base_ref[...], 0))

        def write(pos, nxt):
            r = nxt >> shift
            bi_ref[0, pos] = i * bm + r
            kr_ref[0, pos] = j * bn + (nxt & ((1 << shift) - 1))
            base_ref[...] += (row == r).astype(jnp.int32)

        walk(flat_ref, big, out_cap, place, write)


def join_compact_pallas(
    cols: jax.Array,        # [M, NV] int32 (M multiple of bm)
    bvalid: jax.Array,      # [M, 1] int32 0/1
    ks: jax.Array, kp: jax.Array, ko: jax.Array,   # [1, N] int32 (N mult of bn)
    kvalid: jax.Array,      # [1, N] int32 0/1
    pat: CompiledPattern,
    out_cap: int,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused join+compaction.

    Returns ``(bind_row [out_cap], kb_row [out_cap], counts [M, 1])``:
    entry ``k`` names the binding row and KB row of the k-th match of the
    (virtual) row-major candidate matrix.  Entries past the total match count
    are unspecified (callers mask with ``sum(counts)``).  The candidate
    matrix itself never exists in HBM.
    """
    m, nv = cols.shape
    n = ks.shape[1]
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    interpret = resolve_interpret(interpret)
    grid = (m // bm, n // bn)
    specs = _scan_specs(bm, bn, nv)
    counts = pl.pallas_call(
        functools.partial(_count_kernel, pat),
        grid=grid,
        in_specs=specs,
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.int32),
        interpret=interpret,
        name="hash_join_count",
    )(cols, bvalid, ks, kp, ko, kvalid)

    offsets = jnp.cumsum(counts, axis=0) - counts           # [M, 1], tiny

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    bi, kr = pl.pallas_call(
        functools.partial(_emit_kernel, pat, out_cap),
        grid=grid,
        in_specs=specs + [pl.BlockSpec((bm, 1), lambda i, j: (i, 0))],
        out_specs=[smem, smem],
        out_shape=[jax.ShapeDtypeStruct((1, out_cap + 1), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, 1), jnp.int32)],
        interpret=interpret,
        name="hash_join_emit",
    )(cols, bvalid, ks, kp, ko, kvalid, offsets)
    return bi[0, :out_cap], kr[0, :out_cap], counts


# --------------------------------------------------------------------------
# fused probe kernel: exact re-check + compaction of gathered candidates
# --------------------------------------------------------------------------

def _probe_kernel(pat: CompiledPattern, out_cap: int, cols_ref, bvalid_ref,
                  ms_ref, mp_ref, mo_ref, ok_ref, src_ref, counts_ref,
                  flat_ref, base_ref):
    """One ``[bm]`` binding tile: re-check, count, emit.

    The grid is 1-D over binding tiles and TPU grids run sequentially, so
    ``base_ref`` (SMEM scratch) carries the global running match count —
    output positions are globally row-major over the ``[M, k_max]``
    candidate block, bit-identical to compacting the unfused probe's
    extension.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        base_ref[0] = 0

    m = _probe_match(pat, cols_ref[...], bvalid_ref[...], ms_ref[...],
                     mp_ref[...], mo_ref[...], ok_ref[...])
    counts_ref[...] = jnp.sum(m, axis=1, keepdims=True)
    bm, k = m.shape
    shift = lane_shift(k)
    base = base_ref[0]

    @pl.when(base < out_cap)
    def _():
        big = bm << shift
        flat_ref[...] = jnp.where(m != 0, flat_index((bm, k), shift), big)

        def write(pos, nxt):
            src_ref[0, pos] = (i * bm + (nxt >> shift)) * k + (
                nxt & ((1 << shift) - 1))

        walk(flat_ref, big, out_cap, lambda nxt, t: base + t, write)

    base_ref[0] = base + jnp.sum(m)


def probe_compact_pallas(
    cols: jax.Array,        # [M, NV] int32 (M multiple of bm)
    bvalid: jax.Array,      # [M, 1] int32 0/1
    ms: jax.Array, mp: jax.Array, mo: jax.Array,   # [M, k] gathered candidates
    ok: jax.Array,          # [M, k] int32 0/1: candidate inside its range
    pat: CompiledPattern,
    out_cap: int,
    bm: int = DEFAULT_BM,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused probe re-check + compaction.

    Returns ``(src [out_cap], counts [M, 1])``: ``src[t]`` is the flat
    ``row * k + slot`` index of the t-th surviving candidate (row-major);
    entries past the total are unspecified.  The range search and the
    bounded gather that produce ``ms/mp/mo/ok`` run in XLA.
    """
    m, nv = cols.shape
    k = ms.shape[1]
    assert m % bm == 0, (m, bm)
    row_tile = pl.BlockSpec((bm, k), lambda i: (i, 0))
    src, counts = pl.pallas_call(
        functools.partial(_probe_kernel, pat, out_cap),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, nv), lambda i: (i, 0)),   # binding tile
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),    # binding validity
            row_tile, row_tile, row_tile, row_tile,
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, out_cap + 1), jnp.int32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, k), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)],
        interpret=resolve_interpret(interpret),
        name="hash_join_probe",
    )(cols, bvalid, ms, mp, mo, ok)
    return src[0, :out_cap], counts

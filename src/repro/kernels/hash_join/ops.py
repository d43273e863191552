"""Public wrappers: pad to block multiples, run the kernel, slice back.

Three join surfaces:

* :func:`match_matrix` — original path; returns the bool ``[M, N]`` candidate
  matrix that the caller compacts (kept for parity tests and as a fallback).
* :func:`join_compact` / :func:`join_compact_jnp` — fused path; returns the
  compacted, variable-extended :class:`Bindings` directly.  The Pallas
  version never materializes the candidate matrix in HBM; the jnp version
  still forms the bool matrix but gathers only the ``out_cap`` winning rows
  instead of materializing and compacting the ``[M, N, nv]`` extension —
  the dominant memory traffic of the unfused path.
* :func:`probe_compact` / :func:`probe_compact_jnp` — the probe-method
  analogue (``kb_method="probe"``/``"auto"``): searchsorted + bounded
  gather + anchor re-check + compaction, bit-identical to the unfused
  ``algebra.kb_join_probe`` pipeline.

Each fused pair differs only in how it finds the sources of the first
``out_cap`` matches (the kernel's walk, or a block search over the jnp
cumulative count); both then gather those rows the same way.  All are
bit-identical to the unfused ``match -> extend -> compact_rows`` pipeline,
including row order (global row-major), zeroed invalid rows, and the
overflow flag.

``interpret=None`` (the default) lets the platform decide: the Pallas
interpreter on the CPU backend, the Mosaic-compiled kernel elsewhere.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.kb import (
    KnowledgeBase, gather_matches, probe_range, probe_view,
)
from repro.core.pattern import (
    Bindings, CompiledPattern, SlotMode, compact_index,
)
from repro.core.rdf import composite_key

from . import kernel
from .kernel import as_i32
from .ref import match_matrix_ref


def _pad_to(x: jax.Array, mult: int, axis: int = 0, fill=0):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=fill)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def autotune_block_shapes(
    m: int, n: int, nv: int, vmem_budget: int = 4 * 1024 * 1024
) -> Tuple[int, int]:
    """Pick (bm, bn) for the fused join so a tile's working set fits VMEM.

    Deterministic heuristic (no measurement): a tile holds the ``[bm, bn]``
    int32 match block, its flat-index copy and the compare temporaries, so
    tile bytes ~= 4 * bm * bn * (nv + 2).  KB blocks want to be wide (lane
    dim 128-aligned) to amortize streaming; binding blocks deep enough to
    reuse each KB block across many rows.
    """
    bn = max(128, min(kernel.DEFAULT_BN, _round_up(n, 128)))
    bm = vmem_budget // max(1, 4 * bn * (nv + 2))
    bm = max(8, min(kernel.DEFAULT_BM, (bm // 8) * 8, _round_up(m, 8)))
    return int(bm), int(bn)


def _scan_operands(bind: Bindings, kb: KnowledgeBase, bm: int, bn: int):
    """int32 kernel operands: binding rows/validity padded to ``bm`` rows,
    KB columns/validity as ``[1, N]`` rows padded to ``bn`` lanes."""
    cols = _pad_to(as_i32(bind.cols), bm)
    bvalid = _pad_to(as_i32(bind.valid)[:, None], bm)
    kb_rows = tuple(_pad_to(as_i32(c)[None, :], bn, axis=1)
                    for c in (kb.s_ps, kb.p_ps, kb.o_ps, kb.valid))
    return (cols, bvalid) + kb_rows


def _extend_gathered(bind: Bindings, bi: jax.Array, free_vals, valid,
                     pat: CompiledPattern) -> jax.Array:
    """Binding rows ``bi`` extended with the FREE values, zeroed past
    ``valid``.  ``free_vals(i)`` gives slot ``i``'s value per output row."""
    rows = jnp.take(bind.cols, bi, axis=0, mode="clip")
    for i, slot in enumerate((pat.s, pat.p, pat.o)):
        if slot.mode == SlotMode.FREE:
            rows = rows.at[:, slot.var].set(free_vals(i))
    return jnp.where(valid[:, None], rows, jnp.zeros_like(rows))


def _compacted(total, out_cap: int):
    """``(valid, overflow)`` of a compaction with ``total`` matches."""
    return jnp.arange(out_cap) < jnp.minimum(total, out_cap), total > out_cap


def match_matrix(
    bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern,
    bm: int | None = None, bn: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Drop-in replacement for the engine's scan-method match matrix.

    Returns bool ``[bind.capacity, kb.capacity]``; callers compact it exactly
    as with the jnp path.
    """
    m, n = bind.capacity, kb.capacity
    bm = bm or min(kernel.DEFAULT_BM, _round_up(max(8, m), 32))
    bn = bn or min(kernel.DEFAULT_BN, _round_up(n, 128))
    out = kernel.match_matrix_pallas(
        *_scan_operands(bind, kb, bm, bn), pat, bm=bm, bn=bn,
        interpret=interpret)
    return out[:m, :n].astype(bool)


def join_compact(
    bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern, out_cap: int,
    bm: int | None = None, bn: int | None = None,
    interpret: bool | None = None,
) -> Bindings:
    """Fused Pallas join: compacted extended bindings, no [M, N] in HBM."""
    m, n = bind.capacity, kb.capacity
    if bm is None or bn is None:
        abm, abn = autotune_block_shapes(m, n, bind.num_vars)
        bm, bn = bm or abm, bn or abn
    bi, kr, counts = kernel.join_compact_pallas(
        *_scan_operands(bind, kb, bm, bn), pat, out_cap, bm=bm, bn=bn,
        interpret=interpret)
    valid, overflow = _compacted(jnp.sum(counts), out_cap)
    kcols = (kb.s_ps, kb.p_ps, kb.o_ps)
    rows = _extend_gathered(
        bind, bi, lambda i: jnp.take(kcols[i], kr, mode="clip"), valid, pat)
    return Bindings(rows, valid, overflow | bind.overflow)


def _anchor_values(bind: Bindings, anchor) -> jax.Array:
    if anchor.mode == SlotMode.CONST:
        return jnp.full((bind.capacity,), jnp.uint32(anchor.const))
    return bind.cols[:, anchor.var]


def _probe_candidates(bind: Bindings, kb: KnowledgeBase,
                      pat: CompiledPattern, k_max: int):
    """The bounded ``[cap, k_max]`` gather of every probe implementation."""
    keys_sorted, kcols_v, anchor, _ = probe_view(kb, pat)
    qk = composite_key(jnp.uint32(pat.p.const), _anchor_values(bind, anchor))
    lo, hi = probe_range(keys_sorted, qk)
    return gather_matches(kcols_v, lo, hi, k_max)


def _probe_rows(bind, pat, gathered, src, valid, overflow, k_max, fan_rows):
    rows = _extend_gathered(
        bind, src // k_max,
        lambda i: jnp.take(gathered[i].reshape(-1), src, mode="clip"),
        valid, pat)
    overflow = overflow | jnp.any(fan_rows & bind.valid) | bind.overflow
    return Bindings(rows, valid, overflow)


def probe_compact(
    bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern, out_cap: int,
    k_max: int = 8, bm: int | None = None, interpret: bool | None = None,
) -> Bindings:
    """Fused Pallas probe join: re-check and compaction in one kernel pass.

    Bit-identical to the unfused :func:`repro.core.algebra.kb_join_probe`
    pipeline (probe_range -> gather_matches -> re-check -> compact_rows),
    including row order, zeroed invalid rows and both overflow sources
    (compaction past ``out_cap`` and probe ranges wider than ``k_max``).
    """
    gathered, ok, fan_rows = _probe_candidates(bind, kb, pat, k_max)
    m = bind.capacity
    bm = bm or min(kernel.DEFAULT_BM, _round_up(max(8, m), 8))
    src, counts = kernel.probe_compact_pallas(
        _pad_to(as_i32(bind.cols), bm),
        _pad_to(as_i32(bind.valid)[:, None], bm),
        *(_pad_to(as_i32(g), bm) for g in gathered),
        _pad_to(as_i32(ok), bm),
        pat, out_cap, bm=bm, interpret=interpret)
    return _probe_rows(bind, pat, gathered, src,
                       *_compacted(jnp.sum(counts), out_cap), k_max, fan_rows)


def probe_compact_jnp(
    bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern, out_cap: int,
    k_max: int = 8,
) -> Bindings:
    """Fused jnp probe twin: gather the ``out_cap`` winners directly.

    Same move as :func:`join_compact_jnp` applied to the probe method: the
    k-th output row is located by a block search on the cumulative match
    count over the ``[cap, k_max]`` candidate block, so the row extension
    is built only for rows that actually publish.
    """
    gathered, ok, fan_rows = _probe_candidates(bind, kb, pat, k_max)
    # the kernel's re-check helper keeps the verification semantics in one
    # place for both fused paths (ref.py stays independent as the oracle)
    m = kernel._probe_match(pat, as_i32(bind.cols),
                            as_i32(bind.valid)[:, None],
                            *(as_i32(g) for g in gathered), as_i32(ok))
    src, valid, overflow = compact_index(m.reshape(-1) != 0, out_cap)
    return _probe_rows(bind, pat, gathered, src, valid, overflow, k_max,
                       fan_rows)


def join_compact_jnp(
    bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern, out_cap: int,
) -> Bindings:
    """Fused jnp join: gather the out_cap winners instead of compacting M*N.

    The k-th output row is located by a block search on the running match
    count over the flattened row-major matrix
    (:func:`repro.core.pattern.compact_index`), so only ``out_cap`` extended
    rows are ever built.
    """
    m = match_matrix_ref(bind.cols, bind.valid, kb.s_ps, kb.p_ps, kb.o_ps,
                         kb.valid, pat)
    n = m.shape[1]
    src, valid, overflow = compact_index(m.reshape(-1), out_cap)
    kcols = (kb.s_ps, kb.p_ps, kb.o_ps)
    kr = src % n
    rows = _extend_gathered(
        bind, src // n, lambda i: jnp.take(kcols[i], kr, mode="clip"),
        valid, pat)
    return Bindings(rows, valid, overflow | bind.overflow)

"""Vectorized relational algebra over triple windows and KB partitions.

This is the RSP-engine compute core: every SPARQL feature the paper's
evaluation uses (§4.3 CQuery1 characteristics) has a static-shape, jit-able
operator here:

* basic graph patterns      -> ``scan_pattern`` + ``join``
* KB access (two methods)   -> ``kb_join`` (``method="scan" | "probe"``;
                               the planner's ``kb_method="auto"`` cost model
                               resolves the choice per join at plan time)
* FILTER (numeric / term-eq / set) -> ``filter_num`` / ``filter_in``
* UNION                     -> ``union``
* OPTIONAL                  -> ``optional_join``
* property paths (len<=3)   -> chained ``kb_join`` steps (planner emits them)
* CONSTRUCT                 -> ``construct``
* hierarchy reasoning       -> closure sets from :mod:`repro.core.reasoner`
                               consumed via ``filter_in`` / pruned KBs

Everything is deterministic and order-preserving so that the decomposed and
monolithic executions of a query produce identical results (paper: "All
results are the same" — property-tested in tests/test_equivalence.py).

The O(|bind| x |KB|) candidate matrix of the scan method is the compute
hotspot; :mod:`repro.kernels.hash_join` provides the Pallas TPU kernel with
identical semantics (``use_pallas=True`` switches the engine over), and
``fuse_compaction=True`` additionally fuses match + compaction so the
candidate matrix never round-trips through HBM (see kb_join_scan).
"""
from __future__ import annotations

import functools
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.metrics import stat_max
from repro.obs.trace import in_layer

from .kb import KnowledgeBase, gather_matches, probe_range
from .pattern import (
    Bindings, CompiledPattern, SlotMode, compact_index, compact_rows,
)
from .rdf import NUM_BASE, PAD_ID, TripleBatch, composite_key


# --------------------------------------------------------------------------
# pattern scan over a window
# --------------------------------------------------------------------------

def _slot_match(slot, col_vals, bind_row=None):
    if slot.mode == SlotMode.CONST:
        return col_vals == jnp.uint32(slot.const)
    if slot.mode == SlotMode.BOUND:
        assert bind_row is not None
        return col_vals == bind_row[..., slot.var]
    return jnp.ones_like(col_vals, dtype=bool)


@in_layer("scan")
def scan_pattern(
    window: TripleBatch, pat: CompiledPattern, num_vars: int, out_cap: int
) -> Bindings:
    """Match one triple pattern against the window; emit fresh bindings."""
    cols = {0: window.s, 1: window.p, 2: window.o}
    m = window.valid
    for i, slot in enumerate((pat.s, pat.p, pat.o)):
        m = m & _slot_match(slot, cols[i])
    # repeated free variables inside one pattern must agree
    slots = (pat.s, pat.p, pat.o)
    for i in range(3):
        for j in range(i + 1, 3):
            if (
                slots[i].mode != SlotMode.CONST
                and slots[j].mode != SlotMode.CONST
                and slots[i].var == slots[j].var
            ):
                m = m & (cols[i] == cols[j])

    n = window.capacity
    out = jnp.zeros((n, num_vars), jnp.uint32)
    for i, slot in enumerate(slots):
        if slot.mode != SlotMode.CONST:
            out = out.at[:, slot.var].set(cols[i])
    rows, valid, overflow = compact_rows(out, m, out_cap)
    return Bindings(rows, valid, overflow)


# --------------------------------------------------------------------------
# natural join (used by BGP conjunction and by the final aggregation operator)
# --------------------------------------------------------------------------

def _pair_mask(a: Bindings, b: Bindings, shared: Tuple[int, ...]):
    m = a.valid[:, None] & b.valid[None, :]
    for c in shared:
        m = m & (a.cols[:, None, c] == b.cols[None, :, c])
    return m


def _merge_pairs(a: Bindings, b: Bindings, src: jax.Array) -> jax.Array:
    """Row ``src = i * cb + j`` of the virtual ``[ca * cb]`` pair table:
    ``a[i]`` merged with ``b[j]`` (PAD=0, so max merges)."""
    cb = b.capacity
    return jnp.maximum(jnp.take(a.cols, src // cb, axis=0, mode="clip"),
                       jnp.take(b.cols, src % cb, axis=0, mode="clip"))


def _zero_invalid(rows: jax.Array, valid: jax.Array) -> jax.Array:
    return jnp.where(valid[:, None], rows, jnp.zeros_like(rows))


@in_layer("stream_join")
def join(a: Bindings, b: Bindings, shared: Tuple[int, ...], out_cap: int) -> Bindings:
    """Natural join on the static shared-variable columns.

    Bit-identical to compacting the row-major ``[ca * cb]`` table of merged
    pairs, but only the ``out_cap`` winning pairs are ever merged.
    """
    m = _pair_mask(a, b, shared)
    src, valid, overflow = compact_index(m.reshape(-1), out_cap)
    rows = _zero_invalid(_merge_pairs(a, b, src), valid)
    return Bindings(rows, valid, overflow | a.overflow | b.overflow)


@in_layer("stream_join")
def union(a: Bindings, b: Bindings, out_cap: int) -> Bindings:
    rows = jnp.concatenate([a.cols, b.cols], axis=0)
    mask = jnp.concatenate([a.valid, b.valid], axis=0)
    out, valid, overflow = compact_rows(rows, mask, out_cap)
    return Bindings(out, valid, overflow | a.overflow | b.overflow)


@in_layer("stream_join")
def optional_join(
    a: Bindings, b: Bindings, shared: Tuple[int, ...], out_cap: int
) -> Bindings:
    """SPARQL OPTIONAL: left outer join; unmatched left rows keep PAD columns.

    The candidate table is the merged pairs followed by the left rows; as
    in :func:`join`, only the ``out_cap`` winners are built.
    """
    npairs = a.capacity * b.capacity
    m = _pair_mask(a, b, shared)
    flat_mask = jnp.concatenate(
        [m.reshape(npairs), a.valid & ~jnp.any(m, axis=1)], axis=0)
    src, valid, overflow = compact_index(flat_mask, out_cap)
    left = jnp.take(a.cols, src - npairs, axis=0, mode="clip")
    rows = jnp.where((src < npairs)[:, None], _merge_pairs(a, b, src), left)
    rows = _zero_invalid(rows, valid)
    return Bindings(rows, valid, overflow | a.overflow | b.overflow)


# --------------------------------------------------------------------------
# KB access — the paper's two measured methods
# --------------------------------------------------------------------------

def _kb_scan_match(bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern):
    """O(cap x N) candidate matrix — the C-SPARQL "KB access" method."""
    kcols = {0: kb.s_ps, 1: kb.p_ps, 2: kb.o_ps}
    m = bind.valid[:, None] & kb.valid[None, :]
    for i, slot in enumerate((pat.s, pat.p, pat.o)):
        kv = kcols[i][None, :]
        if slot.mode == SlotMode.CONST:
            m = m & (kv == jnp.uint32(slot.const))
        elif slot.mode == SlotMode.BOUND:
            m = m & (kv == bind.cols[:, slot.var][:, None])
    slots = (pat.s, pat.p, pat.o)
    for i in range(3):
        for j in range(i + 1, 3):
            if (
                slots[i].mode != SlotMode.CONST
                and slots[j].mode != SlotMode.CONST
                and slots[i].var == slots[j].var
            ):
                m = m & (kcols[i][None, :] == kcols[j][None, :])
    return m


def _extend_rows(bind_cols, kb_row_cols, pat: CompiledPattern):
    """Extend binding rows with the pattern's FREE vars taken from KB rows."""
    out = bind_cols
    for i, slot in enumerate((pat.s, pat.p, pat.o)):
        if slot.mode == SlotMode.FREE:
            out = out.at[..., slot.var].set(kb_row_cols[i])
    return out


def kb_join_scan(
    bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern, out_cap: int,
    use_pallas: bool = False, fuse_compaction: bool = False,
    bm: Optional[int] = None, bn: Optional[int] = None,
) -> Bindings:
    """Join bindings against a KB partition by full scan.

    Cost is linear in the *total* partition size — this is precisely the
    behaviour of paper Figs. 6/7 (unused triples still cost time), and the
    reason KB pruning/partitioning wins.

    ``fuse_compaction=True`` selects the fused join->compaction pipeline
    (:mod:`repro.kernels.hash_join.ops`): with ``use_pallas`` the Pallas
    kernel compacts matches tile-by-tile so the ``[cap, N]`` candidate
    matrix never reaches HBM; without it, a gather-based jnp formulation
    skips the ``[cap, N, nv]`` row-extension materialization.  All four
    paths are bit-identical.
    """
    if fuse_compaction:
        from repro.kernels.hash_join import ops as hj_ops
        if use_pallas:
            return hj_ops.join_compact(bind, kb, pat, out_cap, bm=bm, bn=bn)
        return hj_ops.join_compact_jnp(bind, kb, pat, out_cap)
    if use_pallas:
        from repro.kernels.hash_join import ops as hj_ops
        m = hj_ops.match_matrix(bind, kb, pat, bm=bm, bn=bn)
    else:
        m = _kb_scan_match(bind, kb, pat)
    ca, n = m.shape
    bind_exp = jnp.broadcast_to(bind.cols[:, None, :], (ca, n, bind.num_vars))
    kb_rows = (kb.s_ps[None, :], kb.p_ps[None, :], kb.o_ps[None, :])
    kb_rows = tuple(jnp.broadcast_to(c, (ca, n)) for c in kb_rows)
    ext = _extend_rows(bind_exp, kb_rows, pat)
    rows, valid, overflow = compact_rows(
        ext.reshape(ca * n, bind.num_vars), m.reshape(ca * n), out_cap
    )
    return Bindings(rows, valid, overflow | bind.overflow)


def _probe_width_hw(bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern):
    """Widest probe range (``hi - lo``) over valid binding rows — the number
    ``k_max`` must dominate for the probe to be lossless.  Used by the fused
    probe paths, which never materialize ``lo``/``hi`` outside the kernel;
    only traced when metrics are enabled."""
    from .kb import probe_view

    ca = bind.capacity

    def anchor_val(slot):
        if slot.mode == SlotMode.CONST:
            return jnp.full((ca,), jnp.uint32(slot.const))
        return bind.cols[:, slot.var]

    sorted_keys, _, anchor, _ = probe_view(kb, pat)
    keys = composite_key(jnp.uint32(pat.p.const), anchor_val(anchor))
    lo, hi = probe_range(sorted_keys, keys)
    width = (hi - lo).astype(jnp.int32)
    return jnp.max(jnp.where(bind.valid, width, 0))


def kb_join_probe(
    bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern, out_cap: int,
    k_max: int = 8, use_pallas: bool = False, fuse_compaction: bool = False,
    bm: Optional[int] = None,
    stats: Optional[Dict[str, Any]] = None,
) -> Bindings:
    """Join bindings against the KB via sorted-index probes.

    The SPARQL-subquery/SERVICE analogue: per binding row one O(log N)
    searchsorted + <= k_max gathers, independent of unused-KB size.  Requires
    a CONST predicate and at least one CONST/BOUND endpoint (the planner
    guarantees this or falls back to scan).

    ``use_pallas=True`` runs the fused Pallas probe kernel
    (:func:`repro.kernels.hash_join.ops.probe_compact`: searchsorted +
    bounded gather + anchor re-check + compaction in one kernel pass);
    ``fuse_compaction=True`` without Pallas selects the winner-gather jnp
    twin.  All three paths are bit-identical, including both overflow
    sources (``out_cap`` clipping and probe ranges wider than ``k_max``).
    """
    if use_pallas or fuse_compaction:
        if stats is not None:
            stat_max(stats, "hw_probe_k", _probe_width_hw(bind, kb, pat))
        from repro.kernels.hash_join import ops as hj_ops
        if use_pallas:
            return hj_ops.probe_compact(bind, kb, pat, out_cap, k_max, bm=bm)
        return hj_ops.probe_compact_jnp(bind, kb, pat, out_cap, k_max)

    from .kb import probe_view

    p_const = jnp.uint32(pat.p.const)
    ca = bind.capacity

    def anchor_val(slot):
        if slot.mode == SlotMode.CONST:
            return jnp.full((ca,), jnp.uint32(slot.const))
        return bind.cols[:, slot.var]

    sorted_keys, cols, anchor, _ = probe_view(kb, pat)
    keys = composite_key(p_const, anchor_val(anchor))

    lo, hi = probe_range(sorted_keys, keys)
    if stats is not None:
        stat_max(stats, "hw_probe_k",
                 jnp.max(jnp.where(bind.valid, (hi - lo).astype(jnp.int32), 0)))
    (ms, mp, mo), ok, overflow_rows = gather_matches(cols, lo, hi, k_max)
    kcols = {0: ms, 1: mp, 2: mo}
    m = ok & bind.valid[:, None]
    # verify the non-anchored endpoint (and re-check anchors exactly: the
    # composite key hashes numeric literals, so equality must be confirmed)
    for i, slot in enumerate((pat.s, pat.p, pat.o)):
        if slot.mode == SlotMode.CONST:
            m = m & (kcols[i] == jnp.uint32(slot.const))
        elif slot.mode == SlotMode.BOUND:
            m = m & (kcols[i] == bind.cols[:, slot.var][:, None])

    bind_exp = jnp.broadcast_to(bind.cols[:, None, :], (ca, k_max, bind.num_vars))
    ext = _extend_rows(bind_exp, (ms, mp, mo), pat)
    rows, valid, overflow = compact_rows(
        ext.reshape(ca * k_max, bind.num_vars), m.reshape(ca * k_max), out_cap
    )
    any_overflow = overflow | jnp.any(overflow_rows & bind.valid) | bind.overflow
    return Bindings(rows, valid, any_overflow)


@in_layer("kb_join")
def kb_join(
    bind: Bindings, kb: KnowledgeBase, pat: CompiledPattern, out_cap: int,
    method: str = "scan", k_max: int = 8, use_pallas: bool = False,
    fuse_compaction: bool = False, bm: Optional[int] = None,
    bn: Optional[int] = None,
    stats: Optional[Dict[str, Any]] = None,
) -> Bindings:
    """Dispatch one KB join to its access method.

    ``method`` arrives resolved from the plan: the planner's
    ``kb_method="auto"`` cost model has already replaced itself with
    ``"scan"`` or ``"probe"`` (plus a derived ``k_max``) per
    :class:`~repro.core.engine.KBJoin` step, so no cost decision happens at
    trace time.  An ineligible probe (variable predicate or no anchored
    endpoint) still falls back to the scan, preserving semantics for
    hand-built plans.
    """
    if method == "probe" and pat.p.mode == SlotMode.CONST and not (
        pat.s.mode == SlotMode.FREE and pat.o.mode == SlotMode.FREE
    ):
        return kb_join_probe(bind, kb, pat, out_cap, k_max,
                             use_pallas=use_pallas,
                             fuse_compaction=fuse_compaction, bm=bm,
                             stats=stats)
    return kb_join_scan(bind, kb, pat, out_cap, use_pallas=use_pallas,
                        fuse_compaction=fuse_compaction, bm=bm, bn=bn)


# --------------------------------------------------------------------------
# filters / projection / dedup
# --------------------------------------------------------------------------

_NUM_OPS = ("lt", "le", "gt", "ge", "eq", "ne")


class BatchedConst(NamedTuple):
    """A filter literal whose *value* may be a traced uint32 scalar while its
    term-vs-numeric classification stays python-static.

    The comparison semantics below branch on ``value_id < NUM_BASE`` at
    trace time; cohort batching (repro.serve) vmaps one plan over a
    per-query constant axis, so the value becomes a tracer.  The planner's
    ``bind_plan_consts`` records the representative's static classification
    here (it is part of the cohort shape key, so every member agrees), and
    the traced ops stay identical to the unbatched plan's.
    """

    val: Any            # python int or traced uint32 scalar
    is_term: bool       # static: term-equality vs numeric-comparison leaf


def _num_cmp(bind: Bindings, var: int, op: str, value_id):
    """Shared comparison leaf: ``(true mask, error mask)``.

    Numeric right-hand sides (``value_id >= NUM_BASE``) compare fixed-point
    ids; the error mask marks non-numeric bindings (SPARQL type error).
    Term right-hand sides (IRI/string ids) are SPARQL *term equality* —
    only ``eq``/``ne``, no type coercion; the error mask marks unbound
    bindings.  Both ``filter_num`` and the boolean-tree evaluator consume
    this, so the comparison semantics live in exactly one place.
    """
    assert op in _NUM_OPS, op
    if isinstance(value_id, BatchedConst):
        value_id, is_term = value_id.val, value_id.is_term
    else:
        is_term = int(value_id) < int(NUM_BASE)
    v = bind.cols[:, var]
    t = jnp.uint32(value_id)
    if is_term:
        assert op in ("eq", "ne"), (
            "term comparisons support only eq/ne, got %r" % op)
        err = v == jnp.uint32(PAD_ID)
        cmp = (v == t) if op == "eq" else (v != t)
        return cmp & ~err, err
    is_num = v >= jnp.uint32(NUM_BASE)
    cmp = {
        "lt": v < t, "le": v <= t, "gt": v > t,
        "ge": v >= t, "eq": v == t, "ne": v != t,
    }[op]
    return cmp & is_num, ~is_num


def filter_num(bind: Bindings, var: int, op: str, value_id: int) -> Bindings:
    """Numeric FILTER — fixed-point literal ids are order-isomorphic to values."""
    val, err = _num_cmp(bind, var, op, value_id)
    return bind._replace(valid=bind.valid & val & ~err)


def _bool_eval(bind: Bindings, expr: Tuple) -> Tuple[jax.Array, jax.Array]:
    """Evaluate a compiled boolean filter tree to ``(true, error)`` row masks.

    SPARQL three-valued logic over fixed-shape masks: a comparison on a
    non-numeric binding is an *error*; ``!`` preserves errors; ``&&`` is
    false if any arg is definitely false (errors notwithstanding), ``||``
    true if any arg is definitely true; otherwise any arg error makes the
    result an error.  The representation keeps ``true & error == 0``.
    """
    kind = expr[0]
    if kind == "cmp":
        _, var, op, value_id = expr
        return _num_cmp(bind, var, op, value_id)
    if kind == "not":
        val, err = _bool_eval(bind, expr[1])
        return ~val & ~err, err
    vals, errs = zip(*(_bool_eval(bind, a) for a in expr[1:]))
    any_err = functools.reduce(jnp.logical_or, errs)
    if kind == "and":
        any_false = functools.reduce(
            jnp.logical_or, (~v & ~e for v, e in zip(vals, errs)))
        all_true = functools.reduce(jnp.logical_and, vals)
        return all_true & ~any_err, any_err & ~any_false
    if kind == "or":
        any_true = functools.reduce(jnp.logical_or, vals)
        return any_true, any_err & ~any_true
    raise ValueError("unknown filter expr %r" % (expr,))


def filter_bool(bind: Bindings, expr: Tuple) -> Bindings:
    """Boolean FILTER combination (compiled ``("and"|"or"|"not"|"cmp", ...)``
    tuple tree); keeps rows whose filter evaluates to definite true."""
    val, err = _bool_eval(bind, expr)
    return bind._replace(valid=bind.valid & val & ~err)


def filter_in(bind: Bindings, var: int, sorted_ids: jax.Array) -> Bindings:
    """Set-membership FILTER (e.g. subclass-closure sets from the reasoner)."""
    v = bind.cols[:, var]
    pos = jnp.searchsorted(sorted_ids, v)
    pos = jnp.minimum(pos, sorted_ids.shape[0] - 1)
    member = jnp.take(sorted_ids, pos) == v
    return bind._replace(valid=bind.valid & member)


def filter_bound(bind: Bindings, var: int) -> Bindings:
    return bind._replace(valid=bind.valid & (bind.cols[:, var] != PAD_ID))


def project(bind: Bindings, keep: Tuple[int, ...]) -> Bindings:
    mask = jnp.zeros((bind.num_vars,), bool).at[jnp.asarray(keep, jnp.int32)].set(True)
    return bind._replace(cols=jnp.where(mask[None, :], bind.cols, jnp.uint32(PAD_ID)))


def canonical_order(bind: Bindings, sig_cols: Tuple[int, ...]) -> Bindings:
    """Sort valid rows lexicographically by ``sig_cols`` (invalid last).

    Join order is an execution detail (monolithic vs decomposed plans visit
    patterns differently), but the *published* stream must not depend on it:
    the runtimes' bit-identical-across-modes guarantee needs one canonical
    row order for equal binding sets, not whatever order the joins happened
    to emit.  ``sig_cols`` lists the output columns most-significant first
    and must be derived from something plans share — the engine passes
    template columns ordered by *variable name*, since column numbering
    itself differs between a monolithic plan and a decomposed aggregator.
    Applied after the pre-CONSTRUCT distinct, where rows are the
    deduplicated projection onto template variables.

    The stable sort is a rank count over all row pairs (``[cap, cap]``
    compares) rather than a many-operand sort, which takes the TPU
    compiler tens of seconds at real capacities.
    """
    keys = [(~bind.valid).astype(jnp.uint32)]
    keys += [bind.cols[:, c] for c in sig_cols]
    idx = jnp.arange(bind.capacity, dtype=jnp.int32)
    # before[i, j]: row j sorts before row i (ties keep the original order)
    before = idx[None, :] < idx[:, None]
    for k in reversed(keys):
        before = (k[None, :] < k[:, None]) | ((k[None, :] == k[:, None])
                                              & before)
    rank = jnp.sum(before.astype(jnp.int32), axis=1)
    order = jnp.sum(jnp.where(rank[None, :] == idx[:, None], idx[None, :], 0),
                    axis=1)
    return Bindings(
        jnp.take(bind.cols, order, axis=0), jnp.take(bind.valid, order),
        bind.overflow,
    )


def distinct(bind: Bindings, out_cap: Optional[int] = None) -> Bindings:
    """Deduplicate valid rows (order of first occurrence preserved).

    A valid row is kept unless an earlier valid row equals it: ``[cap, cap]``
    compares, no sort.
    """
    out_cap = out_cap or bind.capacity
    idx = jnp.arange(bind.capacity, dtype=jnp.int32)
    dup = bind.valid[None, :] & (idx[None, :] < idx[:, None])
    for c in range(bind.num_vars):
        dup = dup & (bind.cols[None, :, c] == bind.cols[:, None, c])
    keep = bind.valid & ~jnp.any(dup, axis=1)
    rows, valid, overflow = compact_rows(bind.cols, keep, out_cap)
    return Bindings(rows, valid, overflow | bind.overflow)


# --------------------------------------------------------------------------
# CONSTRUCT — derive the output RDF stream
# --------------------------------------------------------------------------

def construct(
    bind: Bindings,
    templates: Sequence[Tuple],   # ((mode,val), (mode,val), (mode,val)) per triple
    ts: jax.Array,
    out_cap: int,
    graph_base: jax.Array | int = 0,
) -> Tuple[TripleBatch, jax.Array]:
    """Emit one RDF-graph event per binding row from CONSTRUCT templates.

    Template slots are ``("const", id)`` or ``("var", col)``.  The Publisher
    stamps every produced triple with ``ts`` (paper §2: the Publisher adds
    timestamps when the engine's output lacks them) and assigns graph ids so
    downstream operators see well-formed graph events.  Returns the output
    batch plus an overflow flag (set when ``out_cap`` clipped valid rows).
    """
    cap = bind.capacity
    t = len(templates)

    def slot_vals(spec):
        kind, val = spec
        if kind == "const":
            return jnp.full((cap,), jnp.uint32(val))
        if kind == "row":     # synthetic per-binding row node (ROW_BASE band,
            from .rdf import ROW_BASE           # val = operator namespace)
            return (jnp.arange(cap, dtype=jnp.uint32) + jnp.uint32(val)
                    + jnp.uint32(graph_base) + ROW_BASE)
        return bind.cols[:, val]

    s_list, p_list, o_list = [], [], []
    for spec_s, spec_p, spec_o in templates:
        s_list.append(slot_vals(spec_s))
        p_list.append(slot_vals(spec_p))
        o_list.append(slot_vals(spec_o))
    s = jnp.stack(s_list, axis=1).reshape(cap * t)      # row-major: graph-contiguous
    p = jnp.stack(p_list, axis=1).reshape(cap * t)
    o = jnp.stack(o_list, axis=1).reshape(cap * t)
    graph = (jnp.arange(cap, dtype=jnp.uint32)[:, None] + jnp.uint32(graph_base))
    graph = jnp.broadcast_to(graph, (cap, t)).reshape(cap * t)
    mask = jnp.repeat(bind.valid, t)
    rows = jnp.stack([s, p, o, jnp.broadcast_to(jnp.uint32(ts), s.shape), graph], axis=1)
    out, valid, overflow = compact_rows(rows, mask, out_cap)
    return TripleBatch(
        s=out[:, 0], p=out[:, 1], o=out[:, 2], ts=out[:, 3], graph=out[:, 4],
        valid=valid,
    ), overflow


# --------------------------------------------------------------------------
# incremental (delta) evaluation — slide-span tracking
# --------------------------------------------------------------------------
#
# Sliding count windows overlap on whole slides (window w = slides
# w..w+R-1, see core/window.py), and every plan step the planner emits for
# a window-alignable query is *monotone* in the stream triples it consumes:
# a joined binding row exists in window w iff all its contributing stream
# triples do.  So instead of re-running the join chain per window, the
# engine can evaluate the merged chunk ONCE, tracking for every binding row
# the interval [min_slide, max_slide] of contributing slides, and then
# select window w's rows with an interval test — the insert half of a
# classic delta evaluation.  The retract half is just as cheap: spans only
# grow under joins, so any row whose span already exceeds R-1 slides can
# never again belong to a window and is retracted eagerly
# (``delta_retract``), and per-window retraction of expired rows is the
# ``min_slide >= w`` side of the membership test (``delta_window_mask``).
#
# The interval rides in two extra uint32 columns appended after the
# ``num_vars`` variable columns, encoded so that the elementwise
# ``jnp.maximum`` merge ``join`` already performs combines spans correctly:
#
#   col nv     = max_slide + 1                  ("enc_max"; 0 = no triples)
#   col nv + 1 = SPAN_ENC_K - (min_slide + 1)   ("enc_min" complement)
#
# max of enc_max is the span's max; max of the complement is the span's
# min.  A row with no stream triples yet (the universe row, or KB-only
# derivations) has both columns 0 and belongs to every window.  All other
# operators (kb_join, filters, union, compaction) treat binding columns
# opaquely, so the span columns flow through the full step vocabulary
# except OPTIONAL (non-monotone — plans containing it fall back to
# per-window recompute; see planner.plan_supports_delta).

SPAN_ENC_K = 0xFFFFFFFF


@in_layer("delta")
def delta_universe(capacity: int, num_vars: int) -> Bindings:
    """The BGP identity with empty span columns attached."""
    from .pattern import universe_bindings
    return universe_bindings(capacity, num_vars + 2)


@in_layer("scan")
def scan_pattern_delta(
    stream: TripleBatch, pat: CompiledPattern, num_vars: int, out_cap: int,
    slide_of_row: jax.Array,
) -> Bindings:
    """``scan_pattern`` twin over the whole merged chunk: emits bindings
    with ``num_vars + 2`` columns, the extra two holding the row's slide as
    a degenerate span.  Rows the slide packing dropped (``slide_of_row ==
    -1``) are excluded, matching the window materialization."""
    cols = {0: stream.s, 1: stream.p, 2: stream.o}
    m = stream.valid & (slide_of_row >= 0)
    slots = (pat.s, pat.p, pat.o)
    for i, slot in enumerate(slots):
        m = m & _slot_match(slot, cols[i])
    for i in range(3):
        for j in range(i + 1, 3):
            if (
                slots[i].mode != SlotMode.CONST
                and slots[j].mode != SlotMode.CONST
                and slots[i].var == slots[j].var
            ):
                m = m & (cols[i] == cols[j])

    n = stream.capacity
    out = jnp.zeros((n, num_vars + 2), jnp.uint32)
    for i, slot in enumerate(slots):
        if slot.mode != SlotMode.CONST:
            out = out.at[:, slot.var].set(cols[i])
    enc = (jnp.maximum(slide_of_row, 0) + 1).astype(jnp.uint32)
    out = out.at[:, num_vars].set(enc)
    out = out.at[:, num_vars + 1].set(jnp.uint32(SPAN_ENC_K) - enc)
    rows, valid, overflow = compact_rows(out, m, out_cap)
    return Bindings(rows, valid, overflow)


@in_layer("delta")
def delta_retract(bind: Bindings, num_vars: int, max_span: int) -> Bindings:
    """Eagerly retract rows whose slide span exceeds ``max_span`` slides
    (0-based: a span of k means max_slide - min_slide == k).  Spans only
    grow under joins, so such rows can never re-enter any window."""
    enc_max = bind.cols[:, num_vars]
    enc_min = bind.cols[:, num_vars + 1]
    # uint32 wraparound makes this exact: (mx+1) + (K-(mn+1)) - K == mx - mn
    span = enc_max + enc_min - jnp.uint32(SPAN_ENC_K)
    keep = (enc_max == 0) | (span <= jnp.uint32(max_span))
    return bind._replace(valid=bind.valid & keep)


@in_layer("delta")
def delta_window_mask(
    bind: Bindings, num_vars: int, window: jax.Array, slides_per_window: int,
) -> jax.Array:
    """Validity mask of the rows belonging to window ``window`` (= slides
    ``window .. window + R - 1``): the row's slide span must sit inside
    that contiguous range.  Span-free rows (both columns 0) pass."""
    w = jnp.asarray(window).astype(jnp.uint32)
    enc_max = bind.cols[:, num_vars]
    enc_min = bind.cols[:, num_vars + 1]
    in_w = (enc_max <= w + jnp.uint32(slides_per_window)) \
        & (jnp.uint32(SPAN_ENC_K) - 1 - enc_min >= w)
    return bind.valid & in_w

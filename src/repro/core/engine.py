"""The vectorized RSP engine: executes compiled plans over triple windows.

A :class:`Plan` is a static list of steps (python-level control flow only);
executing it traces pure jnp ops, so a plan jit-compiles once per
(window-shape, KB-shape) and is ``vmap``-ed over the window axis — the
intra-operator parallel unit the runtime shards across the ``data`` mesh axis.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.obs.metrics import reduce_stats, stat_add, stat_max

from . import algebra
from .kb import KnowledgeBase
from .pattern import Bindings, CompiledPattern, compact_rows, universe_bindings
from .rdf import TripleBatch
from .window import SlideView, Windows


# --------------------------------------------------------------------------
# plan steps (static dataclasses — hashable, traceable control flow)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScanJoin:
    """Scan a stream pattern in the window, natural-join into the state."""

    pat: CompiledPattern
    shared: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class KBJoin:
    pat: CompiledPattern
    method: str = "scan"          # "scan" | "probe"  (paper's two methods)
    k_max: int = 8
    use_pallas: bool = False
    fuse_compaction: bool = False  # fused join->compaction (no [M, N] in HBM)
    bm: Optional[int] = None       # fused-kernel block shapes (None = autotune)
    bn: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class FilterNumStep:
    var: int
    op: str
    value_id: int


@dataclasses.dataclass(frozen=True)
class FilterBoolStep:
    """Boolean FILTER tree, compiled to a static nested-tuple expression:
    ``("cmp", col, op, value_id)`` leaves under ``("and"|"or"|"not", ...)``
    nodes (tuples keep the Plan hashable)."""

    expr: Tuple


@dataclasses.dataclass(frozen=True)
class FilterInStep:
    var: int
    set_name: str                 # env key holding a sorted uint32 id array


@dataclasses.dataclass(frozen=True)
class OptionalSteps:
    sub: Tuple["Step", ...]
    shared: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class UnionSteps:
    left: Tuple["Step", ...]
    right: Tuple["Step", ...]


@dataclasses.dataclass(frozen=True)
class DistinctStep:
    pass


@dataclasses.dataclass(frozen=True)
class ProjectStep:
    keep: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class BindingJoin:
    """Join a pre-joined upstream binding *table* into the state.

    The split aggregation sink (planner.split_agg_plan) replaces the
    binding-graph decode scans — one ScanJoin per published variable, each
    over the full augmented window — with a single natural join against the
    upstream operator's already-projected table of result rows.  ``cols[j]``
    is the sink-plan column the table's j-th column binds; ``shared`` are
    the columns joined on (recomputed by the rewriter from the actual
    bound-before set, like any ScanJoin).  ``replace=True`` marks the plan's
    very first step, where ``universe ⋈ T == T`` and the outer product is
    skipped entirely.
    """

    source: str
    cols: Tuple[int, ...]
    shared: Tuple[int, ...]
    replace: bool = False


Step = Union[
    ScanJoin, KBJoin, FilterNumStep, FilterBoolStep, FilterInStep,
    OptionalSteps, UnionSteps, DistinctStep, ProjectStep, BindingJoin,
]


@dataclasses.dataclass(frozen=True)
class Plan:
    """A compiled continuous query."""

    name: str
    num_vars: int
    var_names: Tuple[str, ...]            # col index -> variable name
    steps: Tuple[Step, ...]
    templates: Tuple[Tuple, ...]          # compiled construct templates
    scan_cap: int = 128                   # pattern-scan result capacity
    bind_cap: int = 256                   # working binding-table capacity
    out_cap: int = 512                    # constructed-triples capacity

    def var_col(self, name: str) -> int:
        return self.var_names.index(name)


# --------------------------------------------------------------------------
# execution
# --------------------------------------------------------------------------

Env = Dict[str, jax.Array]

# Optional per-step metrics dict (see repro.obs.metrics).  ``None`` — the
# default everywhere — means "collect nothing": every instrumentation site
# below is guarded by a *python-level* ``stats is not None`` branch, so the
# stats-off traced program is byte-identical to the pre-observability one
# (pinned by tests/test_obs.py).
Stats = Optional[Dict[str, jax.Array]]

# Upstream binding tables for the split aggregation sink: operator name ->
# ``(cols, valid)`` where ``cols`` is ``[rows, k]`` uint32 (one column per
# published variable; the delta variant appends the two span columns) and
# ``valid`` is ``[rows]`` bool.  Only BindingJoin steps consume these.
Tables = Optional[Dict[str, Tuple[jax.Array, jax.Array]]]


def _occ(b: Bindings) -> jax.Array:
    """Binding-table occupancy (valid rows) as an int32 scalar."""
    return jnp.sum(b.valid.astype(jnp.int32))


def plan_out_vars(plan: Plan) -> Tuple[int, ...]:
    """Columns the CONSTRUCT templates reference (the output signature)."""
    return tuple(sorted({
        val for tpl in plan.templates for kind, val in tpl if kind == "var"
    }))


def _binding_table(
    step: BindingJoin, tables: Tables, width: int, num_span: int = 0,
) -> Bindings:
    """Scatter an upstream table into a ``width``-column Bindings relation.

    ``num_span`` > 0 (the delta path) additionally maps the table's trailing
    span columns onto the state's span columns at ``width - num_span``.
    """
    assert tables is not None and step.source in tables, (
        "BindingJoin on %r but no table supplied — split-sink runners must "
        "pass the upstream tables" % step.source)
    tcols, tvalid = tables[step.source]
    k = len(step.cols)
    out = jnp.zeros((tcols.shape[0], width), jnp.uint32)
    for j, c in enumerate(step.cols):
        out = out.at[:, c].set(tcols[:, j])
    for j in range(num_span):
        out = out.at[:, width - num_span + j].set(tcols[:, k + j])
    # upstream clipping is reported as that operator's own overflow flag
    return Bindings(out, tvalid, jnp.zeros((), bool))


def _apply(
    step: Step, cur: Bindings, window: TripleBatch, kb: Optional[KnowledgeBase],
    env: Env, plan: Plan, stats: Stats = None, tables: Tables = None,
) -> Bindings:
    if isinstance(step, BindingJoin):
        b = _binding_table(step, tables, plan.num_vars)
        if stats is not None:
            stat_max(stats, "hw_scan", _occ(b))
        if step.replace:
            # first step: universe ⋈ T is T itself (shared is empty, the
            # max-merge with all-PAD is the identity) — clip to bind_cap
            # without the [1, rows] outer product
            rows, valid, ovf = compact_rows(b.cols, b.valid, plan.bind_cap)
            return Bindings(rows, valid, ovf | cur.overflow)
        return algebra.join(cur, b, step.shared, plan.bind_cap)
    if isinstance(step, ScanJoin):
        b = algebra.scan_pattern(window, step.pat, plan.num_vars, plan.scan_cap)
        if stats is not None:
            stat_max(stats, "hw_scan", _occ(b))
        return algebra.join(cur, b, step.shared, plan.bind_cap)
    if isinstance(step, KBJoin):
        assert kb is not None, "plan %s touches the KB but none attached" % plan.name
        return algebra.kb_join(
            cur, kb, step.pat, plan.bind_cap, method=step.method,
            k_max=step.k_max, use_pallas=step.use_pallas,
            fuse_compaction=step.fuse_compaction, bm=step.bm, bn=step.bn,
            stats=stats,
        )
    if isinstance(step, FilterNumStep):
        return algebra.filter_num(cur, step.var, step.op, step.value_id)
    if isinstance(step, FilterBoolStep):
        return algebra.filter_bool(cur, step.expr)
    if isinstance(step, FilterInStep):
        return algebra.filter_in(cur, step.var, env[step.set_name])
    if isinstance(step, OptionalSteps):
        sub = universe_bindings(plan.bind_cap, plan.num_vars)
        for s in step.sub:
            sub = _apply(s, sub, window, kb, env, plan, stats, tables)
        return algebra.optional_join(cur, sub, step.shared, plan.bind_cap)
    if isinstance(step, UnionSteps):
        left = cur
        for s in step.left:
            left = _apply(s, left, window, kb, env, plan, stats, tables)
        right = cur
        for s in step.right:
            right = _apply(s, right, window, kb, env, plan, stats, tables)
        return algebra.union(left, right, plan.bind_cap)
    if isinstance(step, DistinctStep):
        return algebra.distinct(cur)
    if isinstance(step, ProjectStep):
        return algebra.project(cur, step.keep)
    raise TypeError("unknown step %r" % (step,))


# Public alias: the serving layer (repro.serve.engine) drives step
# sequences directly — shared KB-join prefixes run once, per-query
# suffixes fan out — and must trace the exact ops run_plan would.
apply_step = _apply


def run_steps(
    plan: Plan, cur: Bindings, steps: Sequence[Step], window: TripleBatch,
    kb: Optional[KnowledgeBase], env: Env, stats: Stats = None,
    tables: Tables = None,
) -> Bindings:
    """Apply a step subsequence (same ops as the run_plan loop, including
    the per-step hw_bind gauge so stats stay comparable across paths)."""
    for step in steps:
        cur = _apply(step, cur, window, kb, env, plan, stats, tables)
        if stats is not None:
            stat_max(stats, "hw_bind", _occ(cur))
    return cur


def finalize_bindings(
    plan: Plan, cur: Bindings, ts: jax.Array,
    graph_base: jax.Array | int = 0, stats: Stats = None,
) -> Tuple[TripleBatch, jax.Array]:
    """The set-to-stream tail of :func:`run_plan`: project onto the
    CONSTRUCT variables, dedup, canonically order, construct.  Returns
    (output triples, overflow flag).  Split out so the serving layer's
    shared-prefix programs finalize each member with exactly these ops."""
    out_vars = plan_out_vars(plan)
    emit = cur
    if out_vars:
        # significance by variable *name*: column numbering is plan-local
        # (a decomposed aggregator numbers differently than the monolithic
        # plan), names are shared
        sig = tuple(sorted(out_vars, key=lambda c: plan.var_names[c]))
        emit = algebra.canonical_order(
            algebra.distinct(algebra.project(cur, out_vars)), sig)
    out, c_ovf = algebra.construct(emit, plan.templates, ts, plan.out_cap,
                                   graph_base)
    if stats is not None:
        stat_max(stats, "hw_out", jnp.sum(out.valid.astype(jnp.int32)))
    return out, cur.overflow | emit.overflow | c_ovf


def run_plan(
    plan: Plan, window: TripleBatch, kb: Optional[KnowledgeBase], env: Env,
    graph_base: jax.Array | int = 0, stats: Stats = None,
) -> Tuple[TripleBatch, Bindings, jax.Array]:
    """Execute ``plan`` on one window.

    Returns (constructed stream, final bindings, overflow flag).  Before
    CONSTRUCT the bindings are projected onto the template variables,
    deduplicated and **canonically ordered** — SPARQL CONSTRUCT emits a
    *graph* (set semantics), so join multiplicities in non-output variables
    must not inflate the output (they previously could silently exceed
    ``out_cap``), and the published row order (which assigns output graph
    ids) must be a function of the result *set*, never of the plan's join
    order — that is what makes monolithic and decomposed executions
    bit-identical for every query, not just the paper's.
    """
    cur = universe_bindings(plan.bind_cap, plan.num_vars)
    cur = run_steps(plan, cur, plan.steps, window, kb, env, stats)
    ts = jnp.max(jnp.where(window.valid, window.ts, 0))
    out, ovf = finalize_bindings(plan, cur, ts, graph_base, stats)
    return out, cur, ovf


def run_plan_windows(
    plan: Plan, windows: Windows, kb: Optional[KnowledgeBase], env: Env,
    with_stats: bool = False,
):
    """vmap the plan over a window batch.

    Returns a ``[W, out_cap]``-leaf TripleBatch plus a ``[W]`` overflow flag
    (monitoring hook: a set flag means capacities clipped that window).
    With ``with_stats`` a third element is returned: a flat dict of chunk
    scalars (per-window gauges reduced per the hw_/n_ convention, see
    repro.obs.metrics) — the stats-off call traces the exact same program
    as before instrumentation.
    """
    w = windows.num_windows

    def one(window, wid, wvalid):
        stats: Stats = {} if with_stats else None
        out, _, ovf = run_plan(
            plan, window, kb, env,
            graph_base=wid.astype(jnp.uint32) * plan.bind_cap, stats=stats,
        )
        out = out._replace(valid=out.valid & wvalid)
        if with_stats:
            return out, ovf, stats
        return out, ovf

    res = jax.vmap(one, in_axes=(0, 0, 0))(
        windows.triples, jnp.arange(w), windows.window_valid
    )
    if not with_stats:
        return res
    out, ovf, per_window = res
    stats = reduce_stats(per_window)
    stat_add(stats, "n_windows",
             jnp.sum(windows.window_valid.astype(jnp.int32)))
    return out, ovf, stats


# --------------------------------------------------------------------------
# incremental (delta) execution over slides
# --------------------------------------------------------------------------

def _apply_delta(
    step: Step, cur: Bindings, view: SlideView, kb: Optional[KnowledgeBase],
    env: Env, plan: Plan, max_span: int, stats: Stats = None,
    tables: Tables = None,
) -> Bindings:
    """One plan step over span-tracked bindings (``num_vars + 2`` columns).

    Every step here must be *monotone* (planner.plan_supports_delta gates
    plans to this vocabulary): stream scans stamp each match with its slide
    span, joins merge spans via the existing elementwise-max merge, and an
    eager retract after every stream join drops rows whose span can no
    longer fit inside any window.  KB joins and filters never look at the
    extra columns — they treat binding columns opaquely.

    BindingJoin is monotone too: an upstream table row carries the span of
    its contributing slides, the max-merge unions spans across the join, and
    a combined derivation fits a window iff every constituent span does —
    which is exactly the interval test ``delta_window_mask`` applies.
    """
    if isinstance(step, BindingJoin):
        b = _binding_table(step, tables, plan.num_vars + 2, num_span=2)
        if stats is not None:
            stat_max(stats, "hw_scan", _occ(b))
        if step.replace:
            rows, valid, ovf = compact_rows(b.cols, b.valid, plan.bind_cap)
            joined = Bindings(rows, valid, ovf | cur.overflow)
        else:
            joined = algebra.join(cur, b, step.shared, plan.bind_cap)
        retracted = algebra.delta_retract(joined, plan.num_vars, max_span)
        if stats is not None:
            stat_add(stats, "n_retract", _occ(joined) - _occ(retracted))
        return retracted
    if isinstance(step, ScanJoin):
        b = algebra.scan_pattern_delta(
            view.stream, step.pat, plan.num_vars, plan.scan_cap,
            view.slide_of_row,
        )
        if stats is not None:
            stat_max(stats, "hw_scan", _occ(b))
        joined = algebra.join(cur, b, step.shared, plan.bind_cap)
        retracted = algebra.delta_retract(joined, plan.num_vars, max_span)
        if stats is not None:
            stat_add(stats, "n_retract", _occ(joined) - _occ(retracted))
        return retracted
    if isinstance(step, KBJoin):
        assert kb is not None, "plan %s touches the KB but none attached" % plan.name
        return algebra.kb_join(
            cur, kb, step.pat, plan.bind_cap, method=step.method,
            k_max=step.k_max, use_pallas=step.use_pallas,
            fuse_compaction=step.fuse_compaction, bm=step.bm, bn=step.bn,
            stats=stats,
        )
    if isinstance(step, FilterNumStep):
        return algebra.filter_num(cur, step.var, step.op, step.value_id)
    if isinstance(step, FilterBoolStep):
        return algebra.filter_bool(cur, step.expr)
    if isinstance(step, FilterInStep):
        return algebra.filter_in(cur, step.var, env[step.set_name])
    if isinstance(step, UnionSteps):
        left = cur
        for s in step.left:
            left = _apply_delta(s, left, view, kb, env, plan, max_span,
                                stats, tables)
        right = cur
        for s in step.right:
            right = _apply_delta(s, right, view, kb, env, plan, max_span,
                                 stats, tables)
        return algebra.union(left, right, plan.bind_cap)
    raise TypeError(
        "step %r is not delta-safe — plan_supports_delta should have routed "
        "this plan to per-window recompute" % (step,)
    )


def run_plan_slides(
    plan: Plan, view: SlideView, slides_per_window: int, max_windows: int,
    kb: Optional[KnowledgeBase], env: Env, with_stats: bool = False,
    tables: Tables = None,
):
    """Incremental execution: one chunk-level pass, per-window selection.

    The join chain (the compute hotspot — every KBJoin is O(bind_cap x KB))
    runs ONCE over the merged stream with slide spans riding along, instead
    of once per window as in :func:`run_plan_windows`; each window then
    selects its rows with an O(bind_cap) interval test and runs only the
    cheap finalize tail (project -> distinct -> canonical_order ->
    construct).  Because that tail is the same set-to-stream function
    recompute uses and the selected binding *sets* are equal (monotone
    steps + exact span intervals), the published output is bit-identical to
    per-window recompute — the invariant the differential harness pins.

    Returns a ``[W, out_cap]``-leaf TripleBatch plus a ``[W]`` overflow
    flag (plus a chunk-scalar stats dict when ``with_stats`` — the delta
    chain runs once per chunk, so its gauges are chunk-level already).
    Note the chunk-level pass shares one scan_cap/bind_cap across
    the whole chunk where recompute gets them per window; overflow trips
    earlier here (size caps to the *sum* of window populations), which the
    flag reports exactly as usual.
    """
    r = slides_per_window
    stats: Stats = {} if with_stats else None
    cur = algebra.delta_universe(plan.bind_cap, plan.num_vars)
    for step in plan.steps:
        cur = _apply_delta(step, cur, view, kb, env, plan, r - 1, stats,
                           tables)
        if stats is not None:
            stat_max(stats, "hw_bind", _occ(cur))
    out_vars = plan_out_vars(plan)
    assert out_vars, (
        "plan %s has no output variables — plan_supports_delta should have "
        "routed it to per-window recompute" % plan.name)
    sig = tuple(sorted(out_vars, key=lambda c: plan.var_names[c]))
    chunk_ovf = cur.overflow

    widx = jnp.arange(max_windows)[:, None] + jnp.arange(r)[None, :]  # [W, R]
    w_ts = jnp.max(jnp.take(view.slide_ts, widx, axis=0), axis=1)
    w_valid = jnp.any(jnp.take(view.slide_valid, widx, axis=0), axis=1)

    def one(wid, ts, wvalid):
        memb = algebra.delta_window_mask(cur, plan.num_vars, wid, r)
        rows = Bindings(cur.cols[:, : plan.num_vars], memb, chunk_ovf)
        emit = algebra.canonical_order(
            algebra.distinct(algebra.project(rows, out_vars)), sig)
        out, c_ovf = algebra.construct(
            emit, plan.templates, ts, plan.out_cap,
            wid.astype(jnp.uint32) * plan.bind_cap,
        )
        out = out._replace(valid=out.valid & wvalid)
        return out, chunk_ovf | emit.overflow | c_ovf

    res = jax.vmap(one)(jnp.arange(max_windows), w_ts, w_valid)
    if not with_stats:
        return res
    out, ovf = res
    stat_max(stats, "hw_out",
             jnp.max(jnp.sum(out.valid.astype(jnp.int32), axis=-1)))
    stat_add(stats, "n_windows", jnp.sum(w_valid.astype(jnp.int32)))
    return out, ovf, stats


# --------------------------------------------------------------------------
# split aggregation sink: upstream table producers + sink runners
# --------------------------------------------------------------------------
#
# The binding-graph protocol (planner.decompose) ships upstream results as
# RDF triples — one graph event per result row — and the aggregation sink
# *re-parses* them: one decode ScanJoin per published variable over the
# augmented window, then the natural joins that stitch the row back
# together.  That re-parse dominated the sink stage (BENCH_pipeline
# stage_breakdown).  The split sink skips the round-trip entirely: each
# upstream publishes its final binding TABLE (already joined, projected,
# deduplicated and canonically ordered), and the rewritten sink plan
# (planner.split_agg_plan) joins those tables directly via BindingJoin.
# Output bits are unchanged: the published stream is a function of the
# binding *set* (finalize_bindings dedups and canonically orders), and the
# table rows are exactly the rows the decode scans would have reconstructed.

def _clip_table(
    emit: Bindings, pub_cols: Tuple[int, ...], rows_cap: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gather ``pub_cols`` from the leading ``rows_cap`` rows of ``emit``.

    ``emit`` must keep its valid rows as a prefix (distinct/canonical_order
    guarantee that), so the prefix clip drops exactly the rows the
    triple-publication path would have clipped at ``out_cap``.  Returns
    ``(cols [rows_cap, k], valid [rows_cap], clipped [])``.
    """
    take = min(rows_cap, emit.capacity)
    cols = jnp.stack([emit.cols[:take, c] for c in pub_cols], axis=1)
    valid = emit.valid[:take]
    clipped = (jnp.any(emit.valid[take:]) if take < emit.capacity
               else jnp.zeros((), bool))
    if take < rows_cap:
        pad = rows_cap - take
        cols = jnp.concatenate(
            [cols, jnp.zeros((pad, len(pub_cols)), jnp.uint32)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
    return cols, valid, clipped


def run_plan_window_tables(
    plan: Plan, windows: Windows, pub_cols: Tuple[int, ...], rows_cap: int,
    kb: Optional[KnowledgeBase], env: Env, with_stats: bool = False,
):
    """Upstream table producer, per-window: the operator's full step chain,
    then project → distinct → canonical_order (the exact emit relation the
    triple publication constructs from), clipped to ``rows_cap`` rows.

    Returns ``((cols [W, rows_cap, k], valid [W, rows_cap]), ovf [W])``
    (+ a chunk-scalar stats dict when ``with_stats``).
    """
    out_vars = plan_out_vars(plan)
    sig = tuple(sorted(out_vars, key=lambda c: plan.var_names[c]))

    def one(window, wvalid):
        stats: Stats = {} if with_stats else None
        cur = universe_bindings(plan.bind_cap, plan.num_vars)
        cur = run_steps(plan, cur, plan.steps, window, kb, env, stats)
        emit = algebra.canonical_order(
            algebra.distinct(algebra.project(cur, out_vars)), sig)
        cols, valid, clipped = _clip_table(emit, pub_cols, rows_cap)
        valid = valid & wvalid
        ovf = cur.overflow | emit.overflow | clipped
        if with_stats:
            stat_max(stats, "hw_out", jnp.sum(valid.astype(jnp.int32)))
            return (cols, valid), ovf, stats
        return (cols, valid), ovf

    res = jax.vmap(one)(windows.triples, windows.window_valid)
    if not with_stats:
        return res
    table, ovf, per_window = res
    stats = reduce_stats(per_window)
    stat_add(stats, "n_windows",
             jnp.sum(windows.window_valid.astype(jnp.int32)))
    return table, ovf, stats


def run_plan_slide_tables(
    plan: Plan, view: SlideView, pub_cols: Tuple[int, ...], rows_cap: int,
    slides_per_window: int, kb: Optional[KnowledgeBase], env: Env,
    with_stats: bool = False,
):
    """Upstream table producer, incremental: one chunk-level delta pass,
    emitting the span-tagged table (variable columns + the two span
    columns).  The sink's per-window interval test selects each window's
    rows, so the table is produced once per chunk, not once per window.

    Returns ``((cols [rows_cap, k+2], valid [rows_cap]), ovf [])``.
    """
    r = slides_per_window
    stats: Stats = {} if with_stats else None
    cur = algebra.delta_universe(plan.bind_cap, plan.num_vars)
    for step in plan.steps:
        cur = _apply_delta(step, cur, view, kb, env, plan, r - 1, stats)
        if stats is not None:
            stat_max(stats, "hw_bind", _occ(cur))
    nv = plan.num_vars
    out_vars = plan_out_vars(plan)
    # dedup over (variables, span): rows equal in both are interchangeable
    # for every window's interval test, so multiplicity can be dropped here
    emit = algebra.distinct(
        algebra.project(cur, tuple(out_vars) + (nv, nv + 1)))
    cols, valid, clipped = _clip_table(
        emit, tuple(pub_cols) + (nv, nv + 1), rows_cap)
    ovf = cur.overflow | emit.overflow | clipped
    if with_stats:
        stat_max(stats, "hw_out", jnp.sum(valid.astype(jnp.int32)))
        return (cols, valid), ovf, stats
    return (cols, valid), ovf


def run_sink_windows(
    plan: Plan, windows: Windows,
    tables: Dict[str, Tuple[jax.Array, jax.Array]],
    kb: Optional[KnowledgeBase], env: Env, with_stats: bool = False,
):
    """Split-sink twin of :func:`run_plan_windows`: vmaps the rewritten sink
    plan over the RAW windows with the per-window upstream tables as extra
    batched operands.  ``tables[name]`` leaves are ``[W, rows, k]`` /
    ``[W, rows]``.  The finalize tail (and therefore the published bits)
    is identical to the unsplit path — upstream publication triples carry
    their window's max timestamp, so the raw-window ts equals the augmented
    one.
    """
    w = windows.num_windows
    names = tuple(tables)

    def one(window, wid, wvalid, table_vals):
        stats: Stats = {} if with_stats else None
        tdict = dict(zip(names, table_vals))
        cur = universe_bindings(plan.bind_cap, plan.num_vars)
        cur = run_steps(plan, cur, plan.steps, window, kb, env, stats, tdict)
        ts = jnp.max(jnp.where(window.valid, window.ts, 0))
        out, ovf = finalize_bindings(
            plan, cur, ts, wid.astype(jnp.uint32) * plan.bind_cap, stats)
        out = out._replace(valid=out.valid & wvalid)
        if with_stats:
            return out, ovf, stats
        return out, ovf

    res = jax.vmap(one)(
        windows.triples, jnp.arange(w), windows.window_valid,
        tuple(tables[n] for n in names),
    )
    if not with_stats:
        return res
    out, ovf, per_window = res
    stats = reduce_stats(per_window)
    stat_add(stats, "n_windows",
             jnp.sum(windows.window_valid.astype(jnp.int32)))
    return out, ovf, stats


def run_sink_slides(
    plan: Plan, view: SlideView,
    tables: Dict[str, Tuple[jax.Array, jax.Array]],
    slides_per_window: int, max_windows: int,
    kb: Optional[KnowledgeBase], env: Env, with_stats: bool = False,
):
    """Split-sink twin of :func:`run_plan_slides`: the rewritten sink plan's
    delta pass over the merged chunk, joining chunk-level span-tagged
    upstream tables, then the standard per-window interval-select +
    finalize.  Shares :func:`run_plan_slides` outright so the set-to-stream
    tail can never diverge from the recompute path."""
    return run_plan_slides(plan, view, slides_per_window, max_windows,
                           kb, env, with_stats, tables=tables)

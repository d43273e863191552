"""Distributed DSCEP runtime: operator DAG execution over a device mesh.

Maps the paper's deployment (Docker containers + Kafka topics) onto SPMD:

* **inter-query parallelism** — independent `DSCEPRuntime`s (or operator
  subsets) run independent queries;
* **inter-operator parallelism** — sub-queries of one decomposed query are
  traced into one XLA program as independent dataflow branches (XLA's
  scheduler runs them concurrently) and/or placed on submeshes;
* **intra-operator parallelism** — the window batch of each operator is
  sharded across the ``data`` mesh axis; every device runs the identical
  engine program on its window slice (TPU analogue of Kafka consumer groups).

The runtime also provides the *straggler mitigation* hook: window packing is
load-aware (``balance_windows``) so devices receive equal triple counts, the
SPMD equivalent of work-stealing from a backlog.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.metrics import finalize_stats, merge_stats
from repro.obs.trace import Tracer, span_or_null

from .engine import Plan, run_plan_windows
from .kb import KnowledgeBase, collect_kb_stats, pad_to
from .operator import OperatorConfig, SCEPOperator
from .planner import (
    OperatorDAG, SubQuery, augment_kb_with_closures, compile_query,
    plan_supports_delta, prepare_env, prune_kb_for, split_agg_plan,
)
from .rdf import TripleBatch, Vocab, empty_triples
from .stream import merge_streams
from .window import (
    Windows, count_slides, count_windows, window_slides, windows_from_slides,
)


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    # frozen: a default-constructed config is shared freely across runtimes
    # without aliasing mutable state (and field edits go through
    # ``dataclasses.replace``, never in-place mutation).
    window_capacity: int = 1000
    max_windows: int = 8
    out_stream_cap: int = 2048
    # sliding count windows: STEP m slide size (None / >= capacity tumbles)
    window_step: Optional[int] = None
    # incremental (delta) evaluation: evaluate each chunk once with
    # slide-span tracking and select per-window results, instead of
    # re-running the join chain per window.  Bit-identical output; plans
    # with OPTIONAL (non-monotone) fall back per operator, and a sharding
    # mesh disables it (windows must be materialized to shard).
    incremental: bool = False
    # KB-access method: the paper's two measured methods plus cost-based
    # per-join selection — "scan" | "probe" | "auto" ("auto" profiles each
    # operator's used-KB slice at build time, picks probe-with-derived-k_max
    # or fused scan per join, and selectivity-orders the join sequence)
    kb_method: str = "scan"
    kb_capacity: Optional[int] = None
    scan_cap: int = 128
    bind_cap: int = 256
    out_cap: int = 512
    # capacity of window-aligned intermediate binding streams between
    # operators: the aggregator's scan cost grows with the augmented window
    # width (window_capacity + sum of upstream caps), so intermediates are
    # kept tighter than the final output (overflow is flagged per operator)
    intermediate_cap: int = 512
    use_pallas: bool = False
    # fused join->compaction for scan-method KB joins: the candidate matrix
    # never round-trips through HBM (kernels/hash_join).  Orthogonal to
    # ``use_pallas`` (fused jnp path when False, fused Pallas when True).
    fuse_compaction: bool = False
    # explicit (bm, bn) block shapes for the fused kernel; None autotunes
    # per join from the actual (bind_cap, used-KB capacity, num_vars) via
    # kernels.hash_join.ops.autotune_block_shapes at trace time.
    join_block_shapes: Optional[Tuple[int, int]] = None


# --------------------------------------------------------------------------
# legacy-constructor deprecation (the Session facade is the public surface)
# --------------------------------------------------------------------------

_INTERNAL = threading.local()


@contextlib.contextmanager
def _internal_construction():
    """Marks runtime construction driven by :class:`repro.core.session.Session`
    (or other in-package facades) so it skips the deprecation warning."""
    prev = getattr(_INTERNAL, "on", False)
    _INTERNAL.on = True
    try:
        yield
    finally:
        _INTERNAL.on = prev


def _warn_legacy_constructor(name: str, mode: str) -> None:
    if getattr(_INTERNAL, "on", False):
        return
    warnings.warn(
        "constructing %s directly is deprecated; use "
        "repro.core.session.Session(ExecutionConfig(mode=%r)) — the unified "
        "facade over all execution modes" % (name, mode),
        DeprecationWarning, stacklevel=3,
    )


def build_operators(
    dag: OperatorDAG, kb: KnowledgeBase, config: RuntimeConfig
) -> Dict[str, SCEPOperator]:
    """Compile one :class:`SCEPOperator` per DAG node (shared by the
    single-program :class:`DSCEPRuntime` and the streaming
    :class:`~repro.core.pipeline.PipelinedRuntime`)."""
    op_cfg = OperatorConfig(
        window_capacity=config.window_capacity,
        max_windows=config.max_windows,
        out_stream_cap=config.out_stream_cap,
        window_step=config.window_step,
        incremental=config.incremental,
    )
    join_bm, join_bn = config.join_block_shapes or (None, None)
    operators: Dict[str, SCEPOperator] = {}
    for name, sub in dag.subqueries.items():
        # the paper's core move: each operator gets its own used-KB slice.
        # Pruning runs first so closure-pair materialization works on the
        # predicate-sized slice, not the full KB (prune_kb_for keeps every
        # edge a closure path traverses); capacity padding comes last so
        # the synthetic pair rows fit inside it.  With kb_method="auto" the
        # finished slice is profiled (the KB is static, so this is pure
        # plan time) and its statistics drive per-join method selection and
        # selectivity ordering in compile_query.
        op_kb = None
        kb_stats = None
        if sub.touches_kb:
            op_kb = prune_kb_for(sub.query, kb)
            op_kb = augment_kb_with_closures(
                sub.query, op_kb, use_pallas=config.use_pallas)
            if config.kb_method == "auto":
                kb_stats = collect_kb_stats(op_kb)
            if config.kb_capacity:
                op_kb = pad_to(op_kb, config.kb_capacity)
        plan = compile_query(
            sub.query,
            kb_method=config.kb_method,
            scan_cap=config.scan_cap,
            bind_cap=config.bind_cap,
            out_cap=(config.out_cap if name == dag.final
                     else min(config.intermediate_cap, config.out_cap)),
            use_pallas=config.use_pallas,
            fuse_compaction=config.fuse_compaction,
            join_bm=join_bm, join_bn=join_bn,
            kb_stats=kb_stats,
        )
        env = prepare_env(sub.query, kb, use_pallas=config.use_pallas)
        operators[name] = SCEPOperator(name, plan, op_kb, env, op_cfg)
    return operators


# --------------------------------------------------------------------------
# split aggregation sink (see planner.split_agg_plan / engine's sink runners)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PubSpec:
    """How one upstream operator publishes its binding table to the sink."""

    vars: Tuple[str, ...]       # published variable names, table column order
    cols: Tuple[int, ...]       # upstream-plan columns, same order
    rows_cap: int               # windows-mode table rows (out_cap / templates)
    slide_rows_cap: int         # delta-mode table rows (the chain's bind_cap)


@dataclasses.dataclass(frozen=True)
class SplitSink:
    """A successfully split aggregation sink: the rewritten plan plus the
    per-upstream table publication specs.  ``delta=True`` routes the sink
    through the span-tagged slide path (one sink-chain pass per chunk)."""

    plan: Plan
    pub: Dict[str, PubSpec]
    delta: bool


def prepare_split_sink(
    dag: OperatorDAG, operators: Dict[str, SCEPOperator],
    config: RuntimeConfig, mesh: Optional[Mesh] = None,
) -> Optional[SplitSink]:
    """Try to split the aggregation sink for this DAG.

    Returns ``None`` — the caller keeps the augmented-window path — when the
    plan rewrite is outside the equivalent fragment
    (:func:`~repro.core.planner.split_agg_plan`), when a sharding mesh is
    attached (tables are not window-sharded), or when incremental mode is
    requested but any plan in the DAG cannot run the delta path (mixing
    per-window tables with a delta sink would need a third table format).

    ``rows_cap`` mirrors the triple path's clipping exactly: an upstream
    publishes ``templates-per-row * rows`` triples into ``out_cap``, so the
    decode path ever sees at most ``out_cap // templates`` complete rows —
    partial clipped rows decode to nothing.  The delta table instead carries
    the whole chunk-level chain state, which ``bind_cap`` already bounds.
    """
    if mesh is not None:
        return None
    res = split_agg_plan(operators[dag.final].plan, dag)
    if res is None:
        return None
    plan, pub_vars = res
    delta = False
    if config.incremental:
        if not all(plan_supports_delta(operators[u].plan) for u in pub_vars):
            return None
        if not plan_supports_delta(plan):
            return None
        delta = True
    pub = {
        u: PubSpec(
            vars=names,
            cols=tuple(operators[u].plan.var_col(v) for v in names),
            rows_cap=max(1, operators[u].plan.out_cap // max(1, len(names))),
            slide_rows_cap=operators[u].plan.bind_cap,
        )
        for u, names in pub_vars.items()
    }
    return SplitSink(plan=plan, pub=pub, delta=delta)


def augment_windows(
    dag: OperatorDAG, windows: Windows, upstream_out: Dict[str, TripleBatch]
) -> Windows:
    """Append upstream operator outputs to the very window that produced them.

    Window alignment is what makes decomposed == monolithic (paper: "All
    results are the same"); the concatenation order follows the final
    sub-query's declared inputs so every execution mode is bit-identical.
    """
    parts = [windows.triples] + [
        upstream_out[src]
        for src in dag.subqueries[dag.final].inputs
        if src != "stream"
    ]
    aug = TripleBatch(
        *(jnp.concatenate(cols, axis=-1) for cols in zip(*parts))
    )
    return Windows(aug, windows.window_valid)


class DSCEPRuntime:
    """Executes a decomposed query DAG over chunked input streams.

    The whole DAG traces into **one** XLA program per chunk shape: upstream
    sub-queries are independent dataflow branches (inter-operator parallelism
    — XLA schedules them concurrently), windows are the vmapped/shardable
    unit (intra-operator parallelism), and intermediate results stay
    **window-aligned**: operator G sees upstream outputs appended to the very
    window that produced them, which is what makes decomposed and monolithic
    results identical (paper: "All results are the same").
    """

    def __init__(
        self,
        dag: OperatorDAG,
        kb: KnowledgeBase,
        vocab: Vocab,
        config: Optional[RuntimeConfig] = None,
        mesh: Optional[Mesh] = None,
        data_axis: str = "data",
        tracer: Optional[Tracer] = None,
    ):
        _warn_legacy_constructor("DSCEPRuntime", "single_program")
        self.dag = dag
        self.config = config = config if config is not None else RuntimeConfig()
        self.mesh = mesh
        self.data_axis = data_axis
        self.vocab = vocab
        self.operators = build_operators(dag, kb, config)
        # split aggregation sink: upstream operators ship binding tables,
        # the sink joins them directly (None -> augmented-window path).
        # The sink operator's plan is swapped for the rewritten one so
        # every introspection surface (EXPLAIN, plan_caps, last_stats)
        # reports the plan that actually runs.
        self._split = prepare_split_sink(dag, self.operators, config, mesh)
        if self._split is not None:
            self.operators[dag.final].plan = self._split.plan
        self._jit_chunk = jax.jit(self._dag_impl)
        self.tracer = tracer
        self._collect = bool(tracer is not None and tracer.config.metrics)
        self._jit_chunk_stats = (
            jax.jit(functools.partial(self._dag_impl, with_stats=True))
            if self._collect else None)
        # lifetime device-side accumulators (host syncs only in reports)
        self._overflow_acc: Dict[str, jax.Array] = {
            n: jnp.zeros((), jnp.int32) for n in self.operators
        }
        self._stats_acc: Dict[str, Dict[str, jax.Array]] = {
            n: {} for n in self.operators
        }

    # -- the single-program DAG step -----------------------------------------
    def _dag_impl(
        self, chunk: TripleBatch, kbs: Dict[str, Optional[KnowledgeBase]],
        envs: Dict[str, Dict[str, jax.Array]], with_stats: bool = False,
    ):
        cfg = self.config
        merged = merge_streams([chunk])
        if self._split is not None:
            return self._dag_impl_split(merged, kbs, envs, with_stats)
        view = None
        if cfg.incremental and self.mesh is None:
            # delta evaluation needs the slide view; the materialized
            # windows still feed the aggregator (upstream outputs are
            # window-aligned batches with no slide structure to delta over)
            view = count_slides(
                merged, cfg.window_capacity, cfg.max_windows, cfg.window_step)
            windows = windows_from_slides(
                view, cfg.window_capacity, cfg.max_windows, cfg.window_step)
        else:
            windows = count_windows(
                merged, cfg.window_capacity, cfg.max_windows, cfg.window_step)
        if self.mesh is not None:
            windows = shard_windows(windows, self.mesh, self.data_axis)

        overflow: Dict[str, jax.Array] = {}
        stats: Dict[str, Dict[str, jax.Array]] = {}
        final = self.dag.final
        upstream_out: Dict[str, TripleBatch] = {}
        for name in self.dag.subqueries:
            if name == final:
                continue
            if view is not None:
                res = self.operators[name].process_slides(
                    view, kbs[name], envs[name], with_stats
                )
            else:
                res = self.operators[name].process_windows(
                    windows, kbs[name], envs[name], with_stats
                )
            if with_stats:
                out_w, ovf, stats[name] = res
            else:
                out_w, ovf = res
            upstream_out[name] = out_w
            overflow[name] = ovf

        # window-aligned augmentation for the aggregation operator
        aug_windows = augment_windows(self.dag, windows, upstream_out)
        res = self.operators[final].process_windows(
            aug_windows, kbs[final], envs[final], with_stats
        )
        if with_stats:
            out_w, ovf, stats[final] = res
        else:
            out_w, ovf = res
        overflow[final] = ovf
        out = self.operators[final]._publish(out_w)
        if with_stats:
            return out, overflow, stats
        return out, overflow

    def _dag_impl_split(
        self, merged: TripleBatch, kbs, envs, with_stats: bool = False,
    ):
        """The split-sink DAG step: upstream operators produce binding
        *tables* (windowed or span-tagged), the sink joins them via its
        rewritten BindingJoin plan over the raw windows — no augmented
        window, no binding-graph decode scans."""
        cfg = self.config
        split = self._split
        final = self.dag.final
        overflow: Dict[str, jax.Array] = {}
        stats: Dict[str, Dict[str, jax.Array]] = {}
        tables: Dict[str, Tuple[jax.Array, jax.Array]] = {}
        if split.delta:
            view = count_slides(
                merged, cfg.window_capacity, cfg.max_windows, cfg.window_step)
        else:
            windows = count_windows(
                merged, cfg.window_capacity, cfg.max_windows, cfg.window_step)
        for name in self.dag.subqueries:
            if name == final:
                continue
            spec = split.pub[name]
            if split.delta:
                res = self.operators[name].process_slide_tables(
                    view, spec.cols, spec.slide_rows_cap,
                    kbs[name], envs[name], with_stats)
            else:
                res = self.operators[name].process_window_tables(
                    windows, spec.cols, spec.rows_cap,
                    kbs[name], envs[name], with_stats)
            if with_stats:
                tables[name], ovf, stats[name] = res
            else:
                tables[name], ovf = res
            # delta tables are chunk-level: broadcast the scalar flag to the
            # per-window convention every overflow consumer expects
            overflow[name] = (jnp.broadcast_to(ovf, (cfg.max_windows,))
                              if ovf.ndim == 0 else ovf)
        if split.delta:
            res = self.operators[final].process_sink_slides(
                view, tables, kbs[final], envs[final], with_stats)
        else:
            res = self.operators[final].process_sink_windows(
                windows, tables, kbs[final], envs[final], with_stats)
        if with_stats:
            out_w, ovf_f, stats[final] = res
        else:
            out_w, ovf_f = res
        overflow[final] = ovf_f
        out = self.operators[final]._publish(out_w)
        if with_stats:
            return out, overflow, stats
        return out, overflow

    # -- orchestration ---------------------------------------------------------
    def process_chunk(self, chunk: TripleBatch) -> Tuple[TripleBatch, Dict[str, jax.Array]]:
        """Push one stream chunk through the DAG; returns (final output, overflow)."""
        kbs = {n: op.kb for n, op in self.operators.items()}
        envs = {n: op.env for n, op in self.operators.items()}
        with span_or_null(self.tracer, "chunk", mode="single_program") as sp:
            if self._collect:
                out, ovf, stats = self._jit_chunk_stats(chunk, kbs, envs)
                for name, st in stats.items():
                    merge_stats(self._stats_acc[name], st)
            else:
                out, ovf = self._jit_chunk(chunk, kbs, envs)
            sp.fence(out)
        for name, flags in ovf.items():
            self._overflow_acc[name] = (
                self._overflow_acc[name] + jnp.sum(flags.astype(jnp.int32)))
        return out, ovf

    def process_stream(
        self, chunks: Sequence[TripleBatch]
    ) -> Tuple[List[TripleBatch], Dict[str, int]]:
        """Push all chunks through the DAG, chunk-at-a-time.

        Returns ``(outputs, overflow)`` where ``overflow[op]`` counts windows
        whose capacities clipped results in operator ``op`` across this
        stream — per-operator flags are accumulated, never dropped, so the
        driver can assert capacity sufficiency (benchmarks do).  The counts
        accumulate device-side; the host syncs once at the end of the
        stream, not per chunk.
        """
        outs: List[TripleBatch] = []
        acc: Dict[str, jax.Array] = {
            n: jnp.zeros((), jnp.int32) for n in self.operators
        }
        for c in chunks:
            out, ovf = self.process_chunk(c)
            outs.append(out)
            for name, flags in ovf.items():
                acc[name] = acc[name] + jnp.sum(flags.astype(jnp.int32))
        return outs, {n: int(v) for n, v in acc.items()}

    # -- observability surfaces (uniform across all three runtimes) ----------
    def overflow_totals(self) -> Dict[str, int]:
        """Lifetime overflowed-window counts per operator."""
        return {n: int(v) for n, v in self._overflow_acc.items()}

    def channel_stats(self) -> Dict[str, Dict[str, int]]:
        """No inter-operator channels in the single-program mode — the DAG
        edges are dataflow inside one XLA program."""
        return {}

    def op_metrics(self) -> Dict[str, Dict[str, int]]:
        """Finalized per-operator engine metric counters (empty unless the
        runtime was built with a metrics-collecting tracer)."""
        return {n: finalize_stats(a) for n, a in self._stats_acc.items() if a}

    @property
    def degraded(self) -> bool:
        """Single-program mode has no channels to degrade around."""
        return False

    def recovery_stats(self) -> Dict[str, Any]:
        """Uniform recovery surface — fault machinery lives in the pipelined
        runtime only (one XLA program has no partial-failure boundary)."""
        from .recovery import empty_recovery_stats
        return empty_recovery_stats(False)


# --------------------------------------------------------------------------
# monolithic reference runtime (paper's "one C-SPARQL query" baseline)
# --------------------------------------------------------------------------

class MonolithicRuntime:
    """Single-operator execution of the *whole* query against the *full* KB.

    This is the paper's Table-2 baseline: one engine, no decomposition, no
    KB pruning.  Result equivalence with :class:`DSCEPRuntime` is the paper's
    "All results are the same" claim (tested in tests/test_equivalence.py).
    """

    def __init__(self, q, kb: KnowledgeBase, config: Optional[RuntimeConfig] = None,
                 tracer: Optional[Tracer] = None):
        _warn_legacy_constructor("MonolithicRuntime", "monolithic")
        config = config if config is not None else RuntimeConfig()
        join_bm, join_bn = config.join_block_shapes or (None, None)
        # closure-pair relations for variable-length paths (no-op otherwise)
        kb = augment_kb_with_closures(q, kb, use_pallas=config.use_pallas)
        plan = compile_query(
            q, kb_method=config.kb_method, scan_cap=config.scan_cap,
            bind_cap=config.bind_cap, out_cap=config.out_cap,
            use_pallas=config.use_pallas,
            fuse_compaction=config.fuse_compaction,
            join_bm=join_bm, join_bn=join_bn,
            kb_stats=(collect_kb_stats(kb)
                      if config.kb_method == "auto" and kb is not None
                      else None),
        )
        env = prepare_env(q, kb, use_pallas=config.use_pallas)
        if config.kb_capacity:
            kb = pad_to(kb, config.kb_capacity)
        self.operator = SCEPOperator(
            q.name, plan, kb, env,
            OperatorConfig(config.window_capacity, config.max_windows,
                           config.out_stream_cap,
                           window_step=config.window_step,
                           incremental=config.incremental),
        )
        self.tracer = tracer
        self._collect = bool(tracer is not None and tracer.config.metrics)
        self._overflow_acc = jnp.zeros((), jnp.int32)
        self._stats_acc: Dict[str, jax.Array] = {}

    def process_chunk(self, chunk: TripleBatch) -> Tuple[TripleBatch, jax.Array]:
        op = self.operator
        with span_or_null(self.tracer, "chunk", mode="monolithic") as sp:
            if self._collect:
                out, ovf, stats = op.process_stats([chunk])
                merge_stats(self._stats_acc, stats)
            else:
                out, ovf = op.process([chunk])
            sp.fence(out)
        self._overflow_acc = self._overflow_acc + jnp.sum(ovf.astype(jnp.int32))
        return out, ovf

    # -- observability surfaces (uniform across all three runtimes) ----------
    def overflow_totals(self) -> Dict[str, int]:
        return {self.operator.name: int(self._overflow_acc)}

    def channel_stats(self) -> Dict[str, Dict[str, int]]:
        return {}

    def op_metrics(self) -> Dict[str, Dict[str, int]]:
        if not self._stats_acc:
            return {}
        return {self.operator.name: finalize_stats(self._stats_acc)}

    @property
    def degraded(self) -> bool:
        """The monolithic baseline *is* the degradation target — never set."""
        return False

    def recovery_stats(self) -> Dict[str, Any]:
        from .recovery import empty_recovery_stats
        return empty_recovery_stats(False)


# --------------------------------------------------------------------------
# SPMD window sharding (intra-operator parallelism on a mesh)
# --------------------------------------------------------------------------

def shard_windows(windows: Windows, mesh: Mesh, axis: str = "data") -> Windows:
    """Constrain a window batch to live across a mesh axis (jit-side).

    Each device gets a window slice and runs the identical engine program —
    the SPMD version of the paper's consumer-group load balancing.  Any
    mesh works: ``jax.make_mesh`` builds Explicit axes, which a sharding
    constraint may not name, so the constraint uses an Auto-typed view of
    the same devices.
    """
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))
    return jax.tree.map(
        lambda leaf: jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, P(*((axis,) + (None,) * (leaf.ndim - 1)))),
        ),
        windows,
    )


def balance_windows(stream: TripleBatch, num_engines: int, window_capacity: int,
                    max_windows: int, window_step: Optional[int] = None) -> Windows:
    """Straggler-aware packing: windows padded to equal triple counts so every
    engine (device) receives balanced work before sharding."""
    w = count_windows(stream, window_capacity, max_windows, window_step)
    # count-based packing already equalizes triple counts up to one graph;
    # round window count up to a multiple of the engine count so the shard
    # axis divides evenly.
    W = w.num_windows
    if W % num_engines:
        pad = num_engines - (W % num_engines)
        w = Windows(
            triples=jax.tree.map(
                lambda col: jnp.concatenate(
                    [col, jnp.zeros((pad,) + col.shape[1:], col.dtype)]
                ),
                w.triples,
            ),
            window_valid=jnp.concatenate([w.window_valid, jnp.zeros((pad,), bool)]),
        )
    return w

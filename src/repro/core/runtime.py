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

from .engine import (
    Plan, run_plan_slide_tables, run_plan_slides, run_plan_window_tables,
    run_plan_windows,
)
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
    dag: OperatorDAG, kb: KnowledgeBase, config: RuntimeConfig,
    tracer: Optional[Tracer] = None,
) -> Dict[str, SCEPOperator]:
    """Compile one :class:`SCEPOperator` per DAG node (shared by the
    single-program :class:`DSCEPRuntime` and the streaming
    :class:`~repro.core.pipeline.PipelinedRuntime`).  Each registration
    step is a ``dscep.*`` span on ``tracer``, per operator."""
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
            with span_or_null(tracer, "dscep.prune", operator=name):
                op_kb = prune_kb_for(sub.query, kb)
            with span_or_null(tracer, "dscep.closures", operator=name):
                op_kb = augment_kb_with_closures(
                    sub.query, op_kb, use_pallas=config.use_pallas)
            if config.kb_method == "auto":
                with span_or_null(tracer, "dscep.kb_stats", operator=name):
                    kb_stats = collect_kb_stats(op_kb)
            if config.kb_capacity:
                op_kb = pad_to(op_kb, config.kb_capacity)
        with span_or_null(tracer, "dscep.plan", operator=name):
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
        with span_or_null(tracer, "dscep.env", operator=name):
            env = prepare_env(sub.query, kb, use_pallas=config.use_pallas)
        operators[name] = SCEPOperator(name, plan, op_kb, env, op_cfg)
    return operators


# --------------------------------------------------------------------------
# the DAG's chunk step: source -> upstream operators -> aggregation sink
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
    per-upstream table publication specs."""

    plan: Plan
    pub: Dict[str, PubSpec]


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
    if config.incremental and not all(
            plan_supports_delta(p) for p in
            [plan] + [operators[u].plan for u in pub_vars]):
        return None
    pub = {
        u: PubSpec(
            vars=names,
            cols=tuple(operators[u].plan.var_col(v) for v in names),
            rows_cap=max(1, operators[u].plan.out_cap // max(1, len(names))),
            slide_rows_cap=operators[u].plan.bind_cap,
        )
        for u, names in pub_vars.items()
    }
    return SplitSink(plan=plan, pub=pub)


def _zero_windows(num_windows: int, capacity: int) -> TripleBatch:
    """Zeros shaped like ``num_windows`` windows of ``capacity`` triples."""
    return jax.tree.map(
        lambda col: jnp.zeros((num_windows,) + col.shape, col.dtype),
        empty_triples(capacity))


class DagStep:
    """The operator DAG's chunk step, and the one place that decides the
    sink's format.

    Built once per registration from ``(dag, operators, config, mesh)``.
    The single-program runtime jits :meth:`__call__`; the pipelined runtime
    runs :meth:`source`, :meth:`upstream` and :meth:`sink` as its stages,
    cut at channel boundaries, and jits :meth:`__call__` as its degraded
    fallback.  The sink takes one of three formats:

    * *augmented windows* (``split is None``): upstream publications are
      appended to the very window that produced them and the sink's plan
      decodes them again — window alignment is what makes decomposed ==
      monolithic (paper: "All results are the same").  Kept for plans
      outside the split fragment, and under a mesh;
    * *window tables*: upstreams publish per-window binding tables that the
      rewritten sink plan joins directly (:func:`prepare_split_sink`);
    * *slide tables* (window tables with ``slides``): one span-tagged table
      per upstream per chunk, joined on the delta path.

    ``slides``: upstream operators consume the chunk's slide view (delta
    evaluation, incremental mode) rather than materialized windows; a mesh
    turns it off, since windows must be materialized to shard.
    """

    def __init__(
        self, dag: OperatorDAG, operators: Dict[str, SCEPOperator],
        config: RuntimeConfig, mesh: Optional[Mesh] = None,
        data_axis: str = "data",
    ):
        self.__name__ = "dag_step"     # the jitted program's name in traces
        self.dag = dag
        self.operators = operators
        self.config = config
        self.mesh = mesh
        self.data_axis = data_axis
        self.final = dag.final
        # DAG insertion order: every runtime evaluates (and pairs channels
        # with) the upstream operators in this order
        self.upstream_names: List[str] = [
            n for n in dag.subqueries if n != dag.final]
        self.split = prepare_split_sink(dag, operators, config, mesh)
        if self.split is not None:
            # every introspection surface (EXPLAIN, plan_caps, last_stats)
            # reports the plan that actually runs
            operators[dag.final].plan = self.split.plan
        self.slides = config.incremental and mesh is None
        self._slide_cap, self._r = window_slides(config.window_capacity,
                                                 config.window_step)

    def _geometry(self):
        cfg = self.config
        return cfg.window_capacity, cfg.max_windows, cfg.window_step

    def source(self, chunk: TripleBatch):
        """Merge, then window: ``(sink_in, op_in)``, what the sink and the
        upstream operators consume — the same windows, or the slide view
        for the upstreams (materialized windows for an augmented sink,
        the view itself for a slide-table sink)."""
        merged = merge_streams([chunk])
        if not self.slides:
            windows = count_windows(merged, *self._geometry())
            if self.mesh is not None:
                windows = shard_windows(windows, self.mesh, self.data_axis)
            return windows, windows
        view = count_slides(merged, *self._geometry())
        if self.split is not None:
            return view, view
        return windows_from_slides(view, *self._geometry()), view

    def upstream(
        self, name: str, op_in, kb: Optional[KnowledgeBase],
        env: Dict[str, jax.Array], with_stats: bool = False,
    ):
        """Upstream operator ``name`` on its :meth:`source` output.

        Returns ``(publication, overflow [W], stats)``: the publication is
        ``[W, out_cap]`` triples for an augmented sink, else the binding
        table the sink joins; ``stats`` is None unless ``with_stats``.
        """
        cfg = self.config
        plan = self.operators[name].plan
        if self.split is not None:
            spec = self.split.pub[name]
            if self.slides:
                res = run_plan_slide_tables(
                    plan, op_in, spec.cols, spec.slide_rows_cap, self._r,
                    kb, env, with_stats)
            else:
                res = run_plan_window_tables(
                    plan, op_in, spec.cols, spec.rows_cap, kb, env,
                    with_stats)
        elif self.slides and plan_supports_delta(plan):
            res = run_plan_slides(plan, op_in, self._r, cfg.max_windows, kb,
                                  env, with_stats)
        else:
            if self.slides:
                # not delta-safe (OPTIONAL): recompute per window on the
                # materialized slides — the same output bits
                op_in = windows_from_slides(op_in, *self._geometry())
            res = run_plan_windows(plan, op_in, kb, env, with_stats)
        pub, ovf = res[0], res[1]
        if ovf.ndim == 0:
            # slide tables are chunk-level: broadcast the flag to the
            # per-window convention every overflow consumer expects
            ovf = jnp.broadcast_to(ovf, (cfg.max_windows,))
        return pub, ovf, (res[2] if with_stats else None)

    def sink(
        self, sink_in, payloads: Dict[str, Any], kb: Optional[KnowledgeBase],
        env: Dict[str, jax.Array], with_stats: bool = False,
    ):
        """The aggregation sink on its :meth:`source` output and every
        upstream's publication: augment or table-join, finalize, publish.
        Returns ``(published chunk, overflow [W], stats)``."""
        cfg = self.config
        plan = self.operators[self.final].plan
        if self.split is None:
            # the concatenation order follows the final sub-query's
            # declared inputs so every execution mode is bit-identical
            parts = [sink_in.triples] + [
                payloads[src] for src in self.dag.subqueries[self.final].inputs
                if src != "stream"]
            aug = Windows(
                TripleBatch(*(jnp.concatenate(cols, axis=-1)
                              for cols in zip(*parts))),
                sink_in.window_valid)
            res = run_plan_windows(plan, aug, kb, env, with_stats)
        elif self.slides:
            res = run_plan_slides(plan, sink_in, self._r, cfg.max_windows,
                                  kb, env, with_stats, tables=payloads)
        else:
            res = run_plan_windows(plan, sink_in, kb, env, with_stats,
                                   tables=payloads)
        out = self.operators[self.final]._publish(res[0])
        return out, res[1], (res[2] if with_stats else None)

    def payload_example(self, name: str):
        """Zeros shaped like upstream ``name``'s ``(publication, overflow)``:
        one slot of its channel into the sink."""
        w = self.config.max_windows
        ovf = jnp.zeros((w,), bool)
        if self.split is None:
            return _zero_windows(w, self.operators[name].plan.out_cap), ovf
        spec = self.split.pub[name]
        if self.slides:          # + the two span columns, once per chunk
            rows, k = (spec.slide_rows_cap,), len(spec.cols) + 2
        else:
            rows, k = (w, spec.rows_cap), len(spec.cols)
        return (jnp.zeros(rows + (k,), jnp.uint32),
                jnp.zeros(rows, bool)), ovf

    def sink_example(self) -> Optional[Windows]:
        """Zeros shaped like :meth:`source`'s ``sink_in``, or None where that
        is the slide view, whose stream is as long as the chunk."""
        if self.slides and self.split is not None:
            return None
        w = self.config.max_windows
        return Windows(_zero_windows(w, self._slide_cap * self._r),
                       jnp.zeros((w,), bool))

    def __call__(
        self, chunk: TripleBatch, kbs: Dict[str, Optional[KnowledgeBase]],
        envs: Dict[str, Dict[str, jax.Array]], with_stats: bool = False,
    ):
        """One chunk through the whole DAG: ``(published chunk, overflow)``
        with ``overflow[op]`` a ``[W]`` flag per operator, plus per-operator
        stats when ``with_stats``."""
        sink_in, op_in = self.source(chunk)
        payloads: Dict[str, Any] = {}
        overflow: Dict[str, jax.Array] = {}
        stats: Dict[str, Dict[str, jax.Array]] = {}
        for name in self.upstream_names:
            payloads[name], overflow[name], stats[name] = self.upstream(
                name, op_in, kbs[name], envs[name], with_stats)
        out, overflow[self.final], stats[self.final] = self.sink(
            sink_in, payloads, kbs[self.final], envs[self.final], with_stats)
        if with_stats:
            return out, overflow, stats
        return out, overflow


class DSCEPRuntime:
    """Executes a decomposed query DAG over chunked input streams.

    The whole DAG traces into **one** XLA program per chunk shape — the
    jitted :class:`DagStep`: upstream sub-queries are independent dataflow
    branches (inter-operator parallelism — XLA schedules them
    concurrently), windows are the vmapped/shardable unit (intra-operator
    parallelism), and intermediate results stay **window-aligned**.
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
        self.operators = build_operators(dag, kb, config, tracer)
        with span_or_null(tracer, "dscep.split_sink"):
            self.step = DagStep(dag, self.operators, config, mesh, data_axis)
        self._split = self.step.split
        self._jit_chunk = jax.jit(self.step)
        self.tracer = tracer
        self._seq = 0
        self._collect = bool(tracer is not None and tracer.config.metrics)
        self._jit_chunk_stats = (
            jax.jit(functools.partial(self.step, with_stats=True))
            if self._collect else None)
        # lifetime device-side accumulators (host syncs only in reports)
        self._overflow_acc: Dict[str, jax.Array] = {
            n: jnp.zeros((), jnp.int32) for n in self.operators
        }
        self._stats_acc: Dict[str, Dict[str, jax.Array]] = {
            n: {} for n in self.operators
        }

    # -- orchestration ---------------------------------------------------------
    def process_chunk(self, chunk: TripleBatch) -> Tuple[TripleBatch, Dict[str, jax.Array]]:
        """Push one stream chunk through the DAG; returns (final output, overflow)."""
        kbs = {n: op.kb for n, op in self.operators.items()}
        envs = {n: op.env for n, op in self.operators.items()}
        tr = self.tracer
        seq, self._seq = self._seq, self._seq + 1
        with span_or_null(tr, "dscep.chunk", seq=seq, mode="single_program"):
            with span_or_null(tr, "dscep.dispatch", seq=seq) as sp:
                if self._collect:
                    out, ovf, stats = self._jit_chunk_stats(chunk, kbs, envs)
                else:
                    out, ovf = self._jit_chunk(chunk, kbs, envs)
                sp.fence(out)
            with span_or_null(tr, "dscep.account", seq=seq):
                if self._collect:
                    for name, st in stats.items():
                        merge_stats(self._stats_acc[name], st)
                for name, flags in ovf.items():
                    self._overflow_acc[name] = (
                        self._overflow_acc[name]
                        + jnp.sum(flags.astype(jnp.int32)))
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
        with span_or_null(tracer, "dscep.closures", operator=q.name):
            kb = augment_kb_with_closures(q, kb, use_pallas=config.use_pallas)
        kb_stats = None
        if config.kb_method == "auto" and kb is not None:
            with span_or_null(tracer, "dscep.kb_stats", operator=q.name):
                kb_stats = collect_kb_stats(kb)
        with span_or_null(tracer, "dscep.plan", operator=q.name):
            plan = compile_query(
                q, kb_method=config.kb_method, scan_cap=config.scan_cap,
                bind_cap=config.bind_cap, out_cap=config.out_cap,
                use_pallas=config.use_pallas,
                fuse_compaction=config.fuse_compaction,
                join_bm=join_bm, join_bn=join_bn, kb_stats=kb_stats,
            )
        with span_or_null(tracer, "dscep.env", operator=q.name):
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
        self._seq = 0

    def process_chunk(self, chunk: TripleBatch) -> Tuple[TripleBatch, jax.Array]:
        op = self.operator
        tr = self.tracer
        seq, self._seq = self._seq, self._seq + 1
        with span_or_null(tr, "dscep.chunk", seq=seq, mode="monolithic"):
            with span_or_null(tr, "dscep.dispatch", seq=seq) as sp:
                if self._collect:
                    out, ovf, stats = op.process_stats([chunk])
                else:
                    out, ovf = op.process([chunk])
                sp.fence(out)
            with span_or_null(tr, "dscep.account", seq=seq):
                if self._collect:
                    merge_stats(self._stats_acc, stats)
                self._overflow_acc = (self._overflow_acc
                                      + jnp.sum(ovf.astype(jnp.int32)))
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

"""The unified execution facade: one ``Session`` over all three runtimes.

Before this module, running a semantic continuous query meant choosing among
three runtime classes with divergent constructors and drive loops
(:class:`~repro.core.runtime.MonolithicRuntime` — chunk-at-a-time,
:class:`~repro.core.runtime.DSCEPRuntime` — whole-DAG single XLA program,
:class:`~repro.core.pipeline.PipelinedRuntime` — per-operator steps over
device channels).  ``Session`` collapses that into one code path::

    cfg = ExecutionConfig(mode="pipelined", window_capacity=256)
    sess = Session(cfg, vocab=vocab, kb=kb)
    reg = sess.register(open("query.rq").read())     # text or Query AST
    outs, overflow = reg.run(chunks)                 # whole stream
    for out in reg.stream(chunks): ...               # incremental

A single frozen :class:`ExecutionConfig` consolidates every knob that was
spread over ``RuntimeConfig``, ``OperatorConfig`` and per-runtime constructor
arguments: window geometry, engine capacities, KB-access method, Pallas
selection (``use_pallas`` / ``fuse_compaction``), the mesh
for SPMD window sharding (``single_program`` mode), and operator placement +
channel depth (``pipelined`` mode).

All modes produce **bit-identical** output streams for the paper's queries
(tests/test_session.py pins this for cquery1), so switching ``mode`` is a
pure deployment decision, never a semantics change.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.obs.report import attach_saturation
from repro.obs.trace import TraceConfig, Tracer, resolve_trace

from . import query as Q
from .faults import FaultPlan
from .kb import KnowledgeBase, collect_kb_stats
from .pipeline import PipelinedRuntime
from .recovery import RecoveryConfig
from .planner import OperatorDAG, decompose, explain_plan, plan_caps
from .rdf import TripleBatch, Vocab
from .runtime import (
    DSCEPRuntime, MonolithicRuntime, RuntimeConfig, _internal_construction,
)
from .sparql import ParseInfo, parse_query_info, serialize_query

MODES = ("monolithic", "single_program", "pipelined")
KB_METHODS = ("scan", "probe", "auto")


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """One frozen config for every execution mode.

    The first block mirrors :class:`~repro.core.runtime.RuntimeConfig` (which
    itself subsumes :class:`~repro.core.operator.OperatorConfig`); the second
    block holds the mode selector and the distribution knobs that used to be
    per-runtime constructor arguments.
    """

    # -- engine / window geometry (RuntimeConfig superset) ------------------
    window_capacity: int = 1000
    max_windows: int = 8
    out_stream_cap: int = 2048
    # sliding count windows: slide size in triples (C-SPARQL ``STEP m``).
    # None or >= window_capacity tumbles; otherwise windows overlap on
    # ceil(window_capacity / step) consecutive slides (see core/window.py
    # for the graph-preserving packing and rounding rules)
    window_step: Optional[int] = None
    # incremental (delta) evaluation: evaluate each chunk once with
    # slide-span state carried across slides instead of re-running the join
    # chain per window — bit-identical output, large speedup at high
    # overlap.  Per-operator fallback to recompute for non-monotone plans
    # (OPTIONAL); disabled under a sharding mesh.
    incremental: bool = False
    kb_method: str = "scan"            # "scan" | "probe" | "auto" (cost-based)
    kb_capacity: Optional[int] = None
    scan_cap: int = 128
    bind_cap: int = 256
    out_cap: int = 512
    intermediate_cap: int = 512
    # Pallas kernels run through the interpreter exactly when the backend
    # is the CPU (repro.kernels.resolve_interpret); there is no knob for it
    use_pallas: bool = False
    fuse_compaction: bool = False
    join_block_shapes: Optional[Tuple[int, int]] = None

    # -- execution mode and distribution ------------------------------------
    mode: str = "single_program"       # monolithic | single_program | pipelined
    mesh: Optional[Any] = None         # SPMD window sharding (single_program)
    data_axis: str = "data"
    placement: Union[str, Dict[str, Any], None] = "round_robin"  # pipelined
    channel_capacity: int = 4          # chunks in flight (pipelined)
    # per-query window geometry: when True, a registered query's
    # ``[RANGE TRIPLES n STEP m]`` clause overrides ``window_capacity`` for
    # that RegisteredQuery only, so one Session hosts queries with
    # heterogeneous windows (``window_capacity`` stays the default for
    # queries without a RANGE clause)
    window_from_query: bool = False
    # observability (repro.obs): None/False = off — the runtimes compile the
    # exact pre-observability programs (pinned by tests/test_obs.py); True =
    # default TraceConfig (host spans + device-side engine metrics); or an
    # explicit repro.obs.TraceConfig.  Surfaced via RegisteredQuery.last_stats
    # and RegisteredQuery.explain().
    trace: Union[None, bool, TraceConfig] = None
    # fault tolerance (pipelined mode only): ``faults`` is a seeded
    # repro.core.faults.FaultPlan injected deterministically into the
    # driver (chaos runs replay exactly); ``recovery`` tunes the
    # checkpoint/retry/restart/degradation ladder
    # (repro.core.recovery.RecoveryConfig — a FaultPlan alone implies the
    # default ladder).  Both None = the fault machinery does not exist:
    # per-operator programs are byte-identical (tests/test_faults.py pin).
    faults: Optional[FaultPlan] = None
    recovery: Optional[RecoveryConfig] = None

    def __post_init__(self):
        resolve_trace(self.trace)     # validates the field type eagerly
        if self.mode not in MODES:
            raise ValueError(
                "unknown mode %r (expected one of %s)" % (self.mode, list(MODES)))
        if self.kb_method not in KB_METHODS:
            raise ValueError(
                "unknown kb_method %r (expected one of %s)"
                % (self.kb_method, list(KB_METHODS)))
        if self.mode == "pipelined" and self.mesh is not None:
            raise ValueError(
                "pipelined mode distributes via placement=, not mesh= "
                "(window sharding belongs to single_program mode)")
        if self.window_step is not None and self.window_step < 1:
            raise ValueError(
                "window_step must be >= 1 (triples per slide), got %d"
                % self.window_step)
        if self.faults is not None and not isinstance(self.faults, FaultPlan):
            raise TypeError(
                "faults= takes a repro.core.faults.FaultPlan, got %r"
                % type(self.faults).__name__)
        if self.recovery is not None and not isinstance(
                self.recovery, RecoveryConfig):
            raise TypeError(
                "recovery= takes a repro.core.recovery.RecoveryConfig, "
                "got %r" % type(self.recovery).__name__)
        if (self.faults is not None or self.recovery is not None) \
                and self.mode != "pipelined":
            raise ValueError(
                "fault injection / recovery (faults=, recovery=) require "
                "mode='pipelined' — the monolithic and single-program modes "
                "run one XLA program with no partial-failure boundary")

    def runtime_config(self) -> RuntimeConfig:
        """The engine-level slice of this config (shared by every mode)."""
        return RuntimeConfig(
            window_capacity=self.window_capacity,
            max_windows=self.max_windows,
            out_stream_cap=self.out_stream_cap,
            window_step=self.window_step,
            incremental=self.incremental,
            kb_method=self.kb_method,
            kb_capacity=self.kb_capacity,
            scan_cap=self.scan_cap,
            bind_cap=self.bind_cap,
            out_cap=self.out_cap,
            intermediate_cap=self.intermediate_cap,
            use_pallas=self.use_pallas,
            fuse_compaction=self.fuse_compaction,
            join_block_shapes=self.join_block_shapes,
        )

    def replace(self, **changes) -> "ExecutionConfig":
        return dataclasses.replace(self, **changes)


class RegisteredQuery:
    """A continuous query registered with a :class:`Session`.

    Owns the compiled runtime for the session's execution mode and exposes
    the unified drive surface: :meth:`run` (whole stream, overflow totals),
    :meth:`stream` (incremental generator) and :meth:`process_chunk`.
    """

    def __init__(self, session: "Session", query: Q.Query,
                 info: Optional[ParseInfo] = None):
        self.session = session
        self.query = query
        self.info = info
        cfg = session.config
        # per-query window geometry: the registration's RANGE TRIPLES clause
        # (and its STEP overlap, or tumbling when STEP is absent) overrides
        # the session-wide default when the config opts in
        self._range_applied = bool(
            cfg.window_from_query and info is not None and info.window_triples)
        if self._range_applied:
            cfg = cfg.replace(window_capacity=info.window_triples,
                              window_step=info.window_step)
        self.config = cfg
        self.mode = cfg.mode
        self.dag: Optional[OperatorDAG] = None
        tcfg = resolve_trace(cfg.trace)
        self.tracer: Optional[Tracer] = Tracer(tcfg) if tcfg else None
        self._runtime = self._build_runtime()

    @property
    def window_geometry(self) -> Tuple[int, Optional[int]]:
        """``(window_triples, window_step)`` for this registration.

        ``window_triples`` is the effective per-query window capacity.
        ``window_step`` is the slide size: the registration's STEP clause
        whenever the query text carries one (reported even when
        ``window_from_query=False`` left it without effect), else the
        session-wide ``ExecutionConfig.window_step``.  A step that is None
        or >= the capacity means tumbling; smaller steps are real overlap —
        each window spans ``ceil(window_triples / step)`` slides.
        """
        step = self.config.window_step
        if self.info is not None and self.info.window_step:
            step = self.info.window_step
        return (self.config.window_capacity, step)

    # -- construction --------------------------------------------------------
    def _build_runtime(self):
        cfg = self.config
        rcfg = cfg.runtime_config()
        vocab, kb = self.session.vocab, self.session.kb
        if kb is None and self.query.kb_predicates():
            raise ValueError(
                "query %r touches the KB (GRAPH <kb> patterns) but the "
                "Session has no kb= attached" % self.query.name)
        with _internal_construction():
            if self.mode == "monolithic":
                return MonolithicRuntime(self.query, kb, rcfg,
                                         tracer=self.tracer)
            self.dag = decompose(self.query, vocab)
            if self.mode == "single_program":
                return DSCEPRuntime(self.dag, kb, vocab, rcfg,
                                    mesh=cfg.mesh, data_axis=cfg.data_axis,
                                    tracer=self.tracer)
            placement = cfg.placement
            if isinstance(placement, str):
                from repro.launch.mesh import place_operators
                placement = place_operators(
                    list(self.dag.subqueries), self.dag.final,
                    strategy=cfg.placement)
            return PipelinedRuntime(self.dag, kb, vocab, rcfg,
                                    placement=placement,
                                    channel_capacity=cfg.channel_capacity,
                                    tracer=self.tracer,
                                    faults=cfg.faults,
                                    recovery=cfg.recovery)

    # -- introspection -------------------------------------------------------
    @property
    def runtime(self):
        """The underlying runtime object (mode-dependent class)."""
        return self._runtime

    @property
    def operators(self) -> Dict[str, Any]:
        """Name -> SCEPOperator (one entry, the query itself, in monolithic)."""
        if self.mode == "monolithic":
            return {self.query.name: self._runtime.operator}
        return dict(self._runtime.operators)

    @property
    def text(self) -> str:
        """Canonical C-SPARQL serialization of the registered query (the
        original registration's PREFIX IRIs and dataset clauses — including
        per-query RANGE window geometry — are preserved when parsed from
        text)."""
        prefixes = dict(self.info.prefixes) if self.info else None
        return serialize_query(self.query, self.session.vocab, prefixes,
                               info=self.info)

    # -- unified drive surface ----------------------------------------------
    def process_chunk(self, chunk: TripleBatch) -> Tuple[TripleBatch, Dict[str, int]]:
        """Push one chunk through; returns (output chunk, overflow counts)."""
        out, ovf = self._runtime.process_chunk(chunk)
        return out, self._normalize_overflow(ovf)

    def run(self, chunks: Sequence[TripleBatch]) -> Tuple[List[TripleBatch], Dict[str, int]]:
        """Push a whole stream through; returns (outputs, overflow totals).

        Every mode returns one output chunk per input chunk, bit-identical
        across modes; ``overflow[op]`` counts windows whose engine capacities
        clipped results in operator ``op`` over this stream.
        """
        if self.mode == "monolithic":
            outs: List[TripleBatch] = []
            acc = jnp.zeros((), jnp.int32)
            for c in chunks:
                out, ovf = self._runtime.process_chunk(c)
                outs.append(out)
                acc = acc + jnp.sum(ovf.astype(jnp.int32))
            return outs, {self.query.name: int(acc)}
        outs, overflow = self._runtime.process_stream(chunks)
        return outs, dict(overflow)

    def stream(self, chunks: Sequence[TripleBatch]) -> Iterator[TripleBatch]:
        """Incremental execution: yield one output chunk per input chunk.

        In pipelined mode the schedule keeps ``channel_capacity`` chunks in
        flight, so outputs trail inputs by the pipeline depth; every mode
        still yields exactly ``len(chunks)`` outputs in input order.  The
        pipelined generator requires an idle runtime and drains any chunks
        left in flight when abandoned early, so a later ``run``/``stream``
        never sees another call's leftovers.
        """
        if self.mode != "pipelined":
            for c in chunks:
                yield self._runtime.process_chunk(c)[0]
            return
        rt = self._runtime
        rt._require_idle("stream")
        depth = self.config.channel_capacity
        try:
            for c in chunks:
                if rt._in_flight >= depth:
                    yield rt.drain()
                rt.feed(c)
            while rt._pending_count():
                yield rt.drain()
        finally:
            while rt._pending_count():          # generator closed mid-stream
                rt.drain()

    def overflow_totals(self) -> Dict[str, int]:
        """Lifetime per-operator overflow counts.  Uniform across all three
        modes: every runtime keeps device-side accumulators and syncs only
        when this is read."""
        return self._runtime.overflow_totals()

    def channel_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-edge channel statistics — populated in pipelined mode (the
        only mode with materialized inter-operator channels), ``{}``
        elsewhere, so callers never type-switch on the runtime."""
        return self._runtime.channel_stats()

    # -- observability --------------------------------------------------------
    @property
    def last_stats(self) -> Dict[str, Any]:
        """The uniform observability surface, identical in shape across all
        three modes::

            {
              "query": ..., "mode": ...,
              "overflow_totals": {op: windows clipped, ...},
              "channels": {edge: {...}, ...},      # {} outside pipelined
              "operators": {op: {"counters": ..., "caps": ...,
                                 "saturation": ...}, ...},
              "spans": {path: {"count", "first_s", "steady": {...}}, ...},
              "recovery": {"enabled", "injected", "retries", ...},
              "degraded": bool,
            }

        ``operators`` and ``spans`` fill in only when the session ran with
        ``ExecutionConfig(trace=...)`` enabled; ``recovery`` carries live
        counters only under pipelined ``faults=``/``recovery=``; the rest
        is always live.
        """
        ops: Dict[str, Any] = {}
        for name, counters in self._runtime.op_metrics().items():
            op = self.operators.get(name)
            caps = plan_caps(op.plan) if op is not None else {}
            ops[name] = attach_saturation(counters, caps)
        return {
            "query": self.query.name,
            "mode": self.mode,
            "overflow_totals": self._runtime.overflow_totals(),
            "channels": self._runtime.channel_stats(),
            "operators": ops,
            "spans": self.tracer.stats() if self.tracer is not None else {},
            "recovery": self._runtime.recovery_stats(),
            "degraded": self._runtime.degraded,
        }

    def explain(self) -> Dict[str, Any]:
        """The planner's decisions for this registration, per operator.

        Recomputes KB statistics for each operator's attached slice (pure
        host-side introspection over static data — never touches compiled
        step functions) so the reported estimates are exactly the numbers
        the ``kb_method="auto"`` cost model would compare.
        """
        win_cap, win_step = self.window_geometry
        operators: Dict[str, Any] = {}
        for name, op in self.operators.items():
            stats = collect_kb_stats(op.kb) if op.kb is not None else None
            entry = explain_plan(op.plan, stats, self.session.vocab)
            entry["kb_rows"] = stats.total_rows if stats is not None else 0
            operators[name] = entry
        return {
            "query": self.query.name,
            "mode": self.mode,
            "kb_method": self.config.kb_method,
            "incremental": self.config.incremental,
            "window": {"capacity": win_cap, "step": win_step},
            "operators": operators,
        }

    def _normalize_overflow(self, ovf) -> Dict[str, int]:
        if isinstance(ovf, dict):
            return {n: int(np.asarray(v).sum()) for n, v in ovf.items()}
        return {self.query.name: int(np.asarray(ovf).sum())}


class Session:
    """Entry point: register C-SPARQL text (or ASTs) and execute streams.

    ``vocab`` is the shared term interner the stream/KB encoders used (a
    fresh one is created when omitted — only useful for stream-only play);
    ``kb`` is the background knowledge base required by KB-touching queries.
    """

    def __init__(
        self,
        config: Optional[ExecutionConfig] = None,
        *,
        vocab: Optional[Vocab] = None,
        kb: Optional[KnowledgeBase] = None,
    ):
        self.config = config if config is not None else ExecutionConfig()
        self.vocab = vocab if vocab is not None else Vocab()
        self.kb = kb
        self.queries: Dict[str, RegisteredQuery] = {}

    def register(self, query: Union[str, Q.Query],
                 name: Optional[str] = None,
                 replace: bool = False) -> RegisteredQuery:
        """Register a continuous query: C-SPARQL text or a Query AST.

        Text is parsed against the session vocab (``REGISTER QUERY <n> AS``
        names the query; ``name=`` is the fallback).  Returns the
        :class:`RegisteredQuery` handle whose ``run``/``stream`` drive the
        configured execution mode.

        A duplicate query name raises ``ValueError`` showing both
        serializations (registering twice under one name used to *silently
        replace* the first runtime, orphaning its handle mid-stream);
        ``replace=True`` is the explicit escape hatch.
        """
        info: Optional[ParseInfo] = None
        if isinstance(query, str):
            query, info = parse_query_info(query, self.vocab, name)
        elif not isinstance(query, Q.Query):
            raise TypeError(
                "register() takes C-SPARQL text or a repro.core.query.Query, "
                "got %r" % type(query).__name__)
        existing = self.queries.get(query.name)
        if existing is not None and not replace:
            # checked before building the RegisteredQuery — runtime
            # construction compiles plans, too expensive to throw away
            prefixes = dict(info.prefixes) if info else None
            raise ValueError(
                "query %r is already registered.\n"
                "existing:\n%s\nnew:\n%s\n"
                "Pass replace=True to substitute the new registration."
                % (query.name, existing.text,
                   serialize_query(query, self.vocab, prefixes, info=info)))
        reg = RegisteredQuery(self, query, info)
        self.queries[query.name] = reg
        return reg

    def unregister(self, name: str) -> None:
        """Drop a registered query (its handle stays usable but unmanaged)."""
        del self.queries[name]

    def serve(self, **opts):
        """A multi-query :class:`~repro.serve.engine.ServeEngine` over this
        session — register hundreds of queries and process shared chunks
        with plan-dedup, shared KB-join prefixes and vmap cohort batching
        (outputs bit-identical to per-query single sessions)."""
        from repro.serve.engine import ServeEngine

        return ServeEngine(self, **opts)

    def register_file(self, path: str,
                      name: Optional[str] = None) -> RegisteredQuery:
        """Register a query from a ``.rq`` file."""
        with open(path) as f:
            return self.register(f.read(), name=name)

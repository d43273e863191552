"""ServeEngine: many standing C-SPARQL queries over one Session.

A :class:`~repro.core.session.Session` gives every registered query its own
isolated runtime; fine for a handful, hopeless for the "millions of users"
regime where most registrations are copies or near-copies of each other.
``ServeEngine`` keeps ONE compiled population and shares work at three
granularities, strictly preserving bit-identity with per-query single
sessions (pinned by tests/test_serve_engine.py and the differential suite):

1. **plan dedup** — registrations whose compiled plans have equal
   :func:`~repro.core.planner.plan_fingerprint` (the plan minus its name)
   on the same KB/env evaluate ONCE; the published chunk fans out to every
   member.  Closure-pair KB augmentations, ``kb_method="auto"`` statistics
   and reasoning closure sets are likewise built once per distinct spec and
   shared by construction (``_kb_cache`` / ``_env_cache``), so KB probe
   views (precomputed on the shared KB object) are shared too.
2. **shared KB-join prefixes** — distinct plans that start with the same
   step run (same caps; deterministic compilation means equal prefixes bind
   equal columns) and whose common prefix contains at least one KB join
   execute as one jitted program: the prefix binds once per window, then
   each member runs only its suffix + finalize tail
   (:func:`repro.core.engine.run_steps` /
   :func:`~repro.core.engine.finalize_bindings` — the exact ops
   ``run_plan`` uses).
3. **vmap cohorts** — plans with equal :func:`~repro.core.planner.plan_shape`
   (identical modulo constants) become one program ``vmap``-ed over a
   ``[Q, K]`` constant matrix and stacked env arrays
   (:func:`~repro.core.planner.bind_plan_consts` substitutes the traced
   constants inside the trace), so 64 filter variants cost one fixed-shape
   dispatch instead of 64.

Windowing (merge + count_windows) happens once per distinct window
geometry per chunk.  Registrations the batched paths cannot serve
losslessly (``incremental=True``, Pallas kernel configs) fall back to their
own :class:`~repro.core.operator.SCEPOperator` — dedup fan-out still
applies.  ``ServeEngine.last_stats`` reports the schedule (distinct plans,
shared-prefix hits, per-cohort batch sizes) plus per-query engine metrics
when the session config enables tracing.

The LM serving scaffolding that used to live here moved to
:mod:`repro.serve.lm`; module-level ``__getattr__`` shims keep the old
imports working with a ``DeprecationWarning``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import query as Q
from repro.core.engine import finalize_bindings, run_plan_windows, run_steps
from repro.core.kb import KnowledgeBase, collect_kb_stats, pad_to
from repro.core.operator import OperatorConfig, SCEPOperator, publish_chunk
from repro.core.pattern import universe_bindings
from repro.core.planner import (
    augment_kb_with_closures, bind_plan_consts, closure_env_entry,
    closure_path_specs, compile_query, count_kb_joins, plan_caps,
    plan_consts, plan_fingerprint, plan_set_names, plan_shape,
    shared_prefix_len,
)
from repro.core.rdf import TripleBatch
from repro.core.runtime import RuntimeConfig
from repro.core.session import Session
from repro.core.sparql import ParseInfo, parse_query_info, serialize_query
from repro.core.stream import merge_streams
from repro.core.window import count_windows
from repro.obs.metrics import finalize_stats, merge_stats, split_stats
from repro.obs.report import attach_saturation
from repro.obs.trace import resolve_trace


# --------------------------------------------------------------------------
# a registered serving unit
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ServeUnit:
    """One standing query as the engine sees it: compiled plan + shared
    KB/env + window geometry + a per-unit fallback operator."""

    name: str
    query: Q.Query
    info: Optional[ParseInfo]
    text: str
    plan: Any
    kb: Optional[KnowledgeBase]
    env: Dict[str, jax.Array]
    rcfg: RuntimeConfig
    op: SCEPOperator

    @property
    def geometry(self) -> Tuple:
        r = self.rcfg
        return (r.window_capacity, r.max_windows, r.window_step,
                r.incremental)

    @property
    def env_sig(self) -> Tuple:
        # env arrays come from the engine's shared cache, so identity
        # equality is exactly value equality here
        return tuple(sorted((k, id(v)) for k, v in self.env.items()))


@dataclasses.dataclass
class _Group:
    """A dedup group: one representative evaluation, fanned out."""

    rep: ServeUnit
    members: List[ServeUnit]


# --------------------------------------------------------------------------
# executables — one device program each
# --------------------------------------------------------------------------

class _OpExec:
    """Fallback / singleton: the group's own SCEPOperator step."""

    kind = "operator"

    def __init__(self, group: _Group):
        self.groups = [group]

    def run(self, engine: "ServeEngine", chunk: TripleBatch, wcache: Dict):
        g = self.groups[0]
        if engine._collect:
            out, ovf, stats = g.rep.op.process_stats([chunk])
            merge_stats(engine._stats_acc.setdefault(g.rep.name, {}), stats)
        else:
            out, ovf = g.rep.op.process([chunk])
        return [(g, out, ovf)]


class _PrefixExec:
    """Distinct plans sharing a KB-join-bearing step prefix: the prefix
    binds once per window, each member runs suffix + finalize + publish —
    all inside one jitted program."""

    kind = "prefix"

    def __init__(self, groups: List[_Group], prefix_len: int):
        self.groups = groups
        self.prefix_len = prefix_len
        rep0 = groups[0].rep
        self.kb_joins_shared = count_kb_joins(rep0.plan.steps[:prefix_len])
        plans = [g.rep.plan for g in groups]
        out_stream_cap = rep0.rcfg.out_stream_cap
        p = prefix_len

        def impl(windows, kb, envs):
            w = windows.num_windows

            def one(window, wid, wvalid):
                cur = universe_bindings(rep0.plan.bind_cap,
                                        rep0.plan.num_vars)
                cur = run_steps(rep0.plan, cur, rep0.plan.steps[:p],
                                window, kb, envs[0])
                ts = jnp.max(jnp.where(window.valid, window.ts, 0))
                outs = []
                for plan, env in zip(plans, envs):
                    c = run_steps(plan, cur, plan.steps[p:], window, kb, env)
                    out, ovf = finalize_bindings(
                        plan, c, ts, wid.astype(jnp.uint32) * plan.bind_cap)
                    outs.append((out._replace(valid=out.valid & wvalid), ovf))
                return tuple(outs)

            res = jax.vmap(one, in_axes=(0, 0, 0))(
                windows.triples, jnp.arange(w), windows.window_valid)
            return tuple(
                (publish_chunk(out_w, out_stream_cap), ovf)
                for out_w, ovf in res
            )

        self._fn = jax.jit(impl)

    def run(self, engine: "ServeEngine", chunk: TripleBatch, wcache: Dict):
        rep0 = self.groups[0].rep
        windows = engine._windows_for(rep0.geometry, chunk, wcache)
        envs = tuple(g.rep.env for g in self.groups)
        res = self._fn(windows, rep0.kb, envs)
        return [(g, out, ovf) for g, (out, ovf) in zip(self.groups, res)]


class _CohortExec:
    """Same-shaped plans as one program vmapped over the per-query
    constant axis (+ stacked env arrays)."""

    kind = "cohort"

    def __init__(self, groups: List[_Group]):
        self.groups = groups
        rep = groups[0].rep
        self._rep = rep
        out_stream_cap = rep.rcfg.out_stream_cap
        self.const_mat = jnp.asarray(
            np.stack([plan_consts(g.rep.plan) for g in groups]))  # [Q, K]
        # stacked closure-set envs under canonical __set%d keys: each
        # member's sorted array is edge-padded with its own max element,
        # which leaves searchsorted membership semantics unchanged
        self.env_stack: Dict[str, jax.Array] = {}
        names = [plan_set_names(g.rep.plan) for g in groups]
        for j in range(len(names[0])):
            arrays = [np.asarray(g.rep.env[names[i][j]])
                      for i, g in enumerate(groups)]
            width = max(a.shape[0] for a in arrays)
            self.env_stack["__set%d" % j] = jnp.asarray(np.stack([
                np.pad(a, (0, width - a.shape[0]), mode="edge")
                for a in arrays
            ]))

        def impl(windows, kb, const_mat, env_stack, with_stats=False):
            def per_query(consts, env):
                plan_q = bind_plan_consts(rep.plan, consts)
                res = run_plan_windows(plan_q, windows, kb, env,
                                       with_stats=with_stats)
                if with_stats:
                    out_w, ovf, stats = res
                    return publish_chunk(out_w, out_stream_cap), ovf, stats
                out_w, ovf = res
                return publish_chunk(out_w, out_stream_cap), ovf

            return jax.vmap(per_query, in_axes=(0, 0))(const_mat, env_stack)

        self._fn = jax.jit(impl, static_argnames=("with_stats",))

    def run(self, engine: "ServeEngine", chunk: TripleBatch, wcache: Dict):
        rep = self._rep
        windows = engine._windows_for(rep.geometry, chunk, wcache)
        if engine._collect:
            out_q, ovf_q, stats_q = self._fn(
                windows, rep.kb, self.const_mat, self.env_stack,
                with_stats=True)
            for i, g in enumerate(self.groups):
                merge_stats(engine._stats_acc.setdefault(g.rep.name, {}),
                            split_stats(stats_q, i))
        else:
            out_q, ovf_q = self._fn(
                windows, rep.kb, self.const_mat, self.env_stack)
        return [
            (g, jax.tree.map(lambda a, i=i: a[i], out_q), ovf_q[i])
            for i, g in enumerate(self.groups)
        ]


@dataclasses.dataclass
class _Schedule:
    groups: List[_Group]
    execs: List[Any]

    def prefix_execs(self) -> List[_PrefixExec]:
        return [e for e in self.execs if e.kind == "prefix"]

    def cohort_execs(self) -> List[_CohortExec]:
        return [e for e in self.execs if e.kind == "cohort"]


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

class ServeEngine:
    """Multi-query serving over one Session's vocab/KB/config.

    ``dedup=False`` disables fingerprint dedup AND prefix sharing (every
    registration evaluates; the benchmark's control arm); ``batch=False``
    additionally disables cohort vmap-batching, reducing the engine to N
    independent operators sharing only the windowing step.
    """

    def __init__(self, session: Session, dedup: bool = True,
                 batch: bool = True):
        self.session = session
        self.dedup = dedup
        self.batch = batch
        self.units: Dict[str, ServeUnit] = {}
        self._schedule: Optional[_Schedule] = None
        self._kb_cache: Dict[Tuple, KnowledgeBase] = {}
        self._kb_pad_cache: Dict[Tuple, KnowledgeBase] = {}
        self._kb_stats_cache: Dict[int, Any] = {}
        self._env_cache: Dict[Tuple, jax.Array] = {}
        self._win_fns: Dict[Tuple, Any] = {}
        self._ovf_acc: Dict[str, jax.Array] = {}
        self._stats_acc: Dict[str, Dict[str, jax.Array]] = {}
        self._admission = None
        tcfg = resolve_trace(session.config.trace)
        self._collect = bool(tcfg and tcfg.metrics)
        self.counters: Dict[str, int] = {
            "chunks": 0, "shared_plan_hits": 0, "shared_prefix_hits": 0,
        }

    # -- registration --------------------------------------------------------
    def register(self, query: Union[str, Q.Query], name: Optional[str] = None,
                 replace: bool = False) -> ServeUnit:
        """Register a standing query (C-SPARQL text or AST) into the serving
        population.  Duplicate names raise ``ValueError`` with both
        serializations unless ``replace=True`` (same contract as
        ``Session.register``)."""
        info: Optional[ParseInfo] = None
        if isinstance(query, str):
            query, info = parse_query_info(query, self.session.vocab, name)
        elif not isinstance(query, Q.Query):
            raise TypeError(
                "register() takes C-SPARQL text or a repro.core.query.Query, "
                "got %r" % type(query).__name__)
        prefixes = dict(info.prefixes) if info else None
        text = serialize_query(query, self.session.vocab, prefixes, info=info)
        existing = self.units.get(query.name)
        if existing is not None and not replace:
            raise ValueError(
                "query %r is already registered.\n"
                "existing:\n%s\nnew:\n%s\n"
                "Pass replace=True to substitute the new registration."
                % (query.name, existing.text, text))
        unit = self._build_unit(query, info, text)
        self.units[unit.name] = unit
        self._ovf_acc.setdefault(unit.name, jnp.zeros((), jnp.int32))
        self._schedule = None
        return unit

    def unregister(self, name: str) -> None:
        """Drop a standing query from the population."""
        del self.units[name]
        self._ovf_acc.pop(name, None)
        self._stats_acc.pop(name, None)
        self._schedule = None

    def _build_unit(self, query: Q.Query, info: Optional[ParseInfo],
                    text: str) -> ServeUnit:
        cfg = self.session.config
        if cfg.window_from_query and info is not None and info.window_triples:
            cfg = cfg.replace(window_capacity=info.window_triples,
                              window_step=info.window_step)
        rcfg = cfg.runtime_config()
        kb = self.session.kb
        if kb is None and query.kb_predicates():
            raise ValueError(
                "query %r touches the KB (GRAPH <kb> patterns) but the "
                "Session has no kb= attached" % query.name)
        # shared closure-pair augmentation: one materialization per distinct
        # closure-spec tuple; every query with the same paths reuses the
        # same KB object (and its precomputed probe-view arrays)
        akb = kb
        kb_stats = None
        if kb is not None:
            specs = tuple(closure_path_specs(query))
            akb = self._kb_cache.get(specs)
            if akb is None:
                akb = augment_kb_with_closures(
                    query, kb, use_pallas=rcfg.use_pallas)
                self._kb_cache[specs] = akb
            if rcfg.kb_method == "auto":
                kb_stats = self._kb_stats_cache.get(id(akb))
                if kb_stats is None:
                    kb_stats = collect_kb_stats(akb)
                    self._kb_stats_cache[id(akb)] = kb_stats
        join_bm, join_bn = rcfg.join_block_shapes or (None, None)
        plan = compile_query(
            query, kb_method=rcfg.kb_method, scan_cap=rcfg.scan_cap,
            bind_cap=rcfg.bind_cap, out_cap=rcfg.out_cap,
            use_pallas=rcfg.use_pallas,
            fuse_compaction=rcfg.fuse_compaction,
            join_bm=join_bm, join_bn=join_bn, kb_stats=kb_stats,
        )
        # shared reasoning closure sets: one array per distinct
        # (subclass_pred, super_class); env dicts alias them
        env: Dict[str, jax.Array] = {}
        for item in query.where:
            if isinstance(item, Q.FilterSubclass):
                ck = (item.subclass_pred, item.super_class, rcfg.use_pallas)
                if ck not in self._env_cache:
                    _, arr = closure_env_entry(
                        akb, item.subclass_pred, item.super_class,
                        rcfg.use_pallas)
                    self._env_cache[ck] = arr
                env["closure:%d" % item.super_class] = self._env_cache[ck]
        if rcfg.kb_capacity and akb is not None:
            pk = (id(akb), rcfg.kb_capacity)
            if pk not in self._kb_pad_cache:
                self._kb_pad_cache[pk] = pad_to(akb, rcfg.kb_capacity)
            akb = self._kb_pad_cache[pk]
        op = SCEPOperator(
            query.name, plan, akb, env,
            OperatorConfig(rcfg.window_capacity, rcfg.max_windows,
                           rcfg.out_stream_cap,
                           window_step=rcfg.window_step,
                           incremental=rcfg.incremental),
        )
        return ServeUnit(name=query.name, query=query, info=info, text=text,
                         plan=plan, kb=akb, env=env, rcfg=rcfg, op=op)

    # -- scheduling ----------------------------------------------------------
    def _build_schedule(self) -> _Schedule:
        units = list(self.units.values())
        groups: List[_Group] = []
        if self.dedup:
            by_fp: Dict[Tuple, _Group] = {}
            for u in units:
                key = (plan_fingerprint(u.plan), id(u.kb), u.env_sig,
                       u.geometry, u.rcfg.out_stream_cap)
                g = by_fp.get(key)
                if g is None:
                    g = by_fp[key] = _Group(rep=u, members=[])
                    groups.append(g)
                g.members.append(u)
        else:
            groups = [_Group(rep=u, members=[u]) for u in units]

        execs: List[Any] = []
        batchable: List[_Group] = []
        for g in groups:
            r = g.rep.rcfg
            # the batched paths re-trace the plan outside SCEPOperator;
            # kernel configs (Pallas / fused compaction) and incremental
            # evaluation keep their per-unit operator programs
            if (g.rep.geometry[3] or r.use_pallas or r.fuse_compaction
                    or not self.batch):
                execs.append(_OpExec(g))
            else:
                batchable.append(g)

        remaining = batchable
        if self.dedup:
            clusters, remaining = self._cluster_prefixes(batchable)
            execs.extend(_PrefixExec(gs, p) for gs, p in clusters)

        by_shape: Dict[Tuple, List[_Group]] = {}
        for g in remaining:
            key = (plan_shape(g.rep.plan), id(g.rep.kb), g.rep.geometry,
                   g.rep.rcfg.out_stream_cap)
            by_shape.setdefault(key, []).append(g)
        for gs in by_shape.values():
            if len(gs) >= 2:
                execs.append(_CohortExec(gs))
            else:
                execs.append(_OpExec(gs[0]))
        return _Schedule(groups=groups, execs=execs)

    @staticmethod
    def _cluster_prefixes(
        groups: List[_Group],
    ) -> Tuple[List[Tuple[List[_Group], int]], List[_Group]]:
        """Greedy clustering of distinct plans by common leading step run.

        A cluster only forms when the shared prefix contains a KB join (the
        work worth amortizing) and the plans agree on the binding-table
        geometry the prefix runs under; everything else falls through to
        cohort/singleton scheduling."""
        clusters: List[Dict[str, Any]] = []
        rest: List[_Group] = []
        for g in groups:
            u = g.rep
            placed = False
            for cl in clusters:
                seed = cl["members"][0].rep
                if (seed.plan.num_vars != u.plan.num_vars
                        or seed.plan.scan_cap != u.plan.scan_cap
                        or seed.plan.bind_cap != u.plan.bind_cap
                        or seed.geometry != u.geometry
                        or id(seed.kb) != id(u.kb)):
                    continue
                p = min(cl["prefix"], shared_prefix_len(seed.plan, u.plan))
                if p >= 1 and count_kb_joins(seed.plan.steps[:p]) >= 1:
                    cl["members"].append(g)
                    cl["prefix"] = p
                    placed = True
                    break
            if not placed:
                clusters.append({"members": [g], "prefix": len(u.plan.steps)})
        out: List[Tuple[List[_Group], int]] = []
        for cl in clusters:
            if len(cl["members"]) >= 2:
                out.append((cl["members"], cl["prefix"]))
            else:
                rest.extend(cl["members"])
        return out, rest

    def _windows_for(self, geometry: Tuple, chunk: TripleBatch,
                     cache: Dict) -> Any:
        """Windows for one geometry, computed once per chunk and shared by
        every batched program with that geometry (merge + count_windows —
        the same ops SCEPOperator's step starts with)."""
        if geometry not in cache:
            fn = self._win_fns.get(geometry)
            if fn is None:
                cap, max_w, step, _ = geometry

                def fn(c, cap=cap, max_w=max_w, step=step):
                    return count_windows(merge_streams((c,)), cap, max_w,
                                         step)

                fn = jax.jit(fn)
                self._win_fns[geometry] = fn
            cache[geometry] = fn(chunk)
        return cache[geometry]

    # -- drive surface -------------------------------------------------------
    @property
    def schedule(self) -> _Schedule:
        if self._schedule is None:
            self._schedule = self._build_schedule()
        return self._schedule

    def process_chunk(self, chunk: TripleBatch) -> Dict[str, TripleBatch]:
        """Push one chunk through every registered query; returns
        ``{query name: published output chunk}`` — each entry bit-identical
        to the query's own single-session output for this chunk."""
        sched = self.schedule
        outs: Dict[str, TripleBatch] = {}
        wcache: Dict = {}
        for ex in sched.execs:
            for g, out, ovf in ex.run(self, chunk, wcache):
                n_ovf = jnp.sum(ovf.astype(jnp.int32))
                for u in g.members:
                    outs[u.name] = out
                    self._ovf_acc[u.name] = self._ovf_acc[u.name] + n_ovf
        self.counters["chunks"] += 1
        self.counters["shared_plan_hits"] += sum(
            len(g.members) - 1 for g in sched.groups)
        self.counters["shared_prefix_hits"] += sum(
            (len(ex.groups) - 1) * ex.prefix_len
            for ex in sched.prefix_execs())
        return outs

    def run(self, chunks: Sequence[TripleBatch]
            ) -> Tuple[Dict[str, List[TripleBatch]], Dict[str, int]]:
        """Whole-stream drive: one output chunk per input chunk per query,
        plus per-query overflow totals (the same contract
        ``RegisteredQuery.run`` gives each member in its own session)."""
        outs: Dict[str, List[TripleBatch]] = {n: [] for n in self.units}
        for c in chunks:
            for n, o in self.process_chunk(c).items():
                outs[n].append(o)
        return outs, self.overflow_totals()

    def admission(self, **opts):
        """A :class:`~repro.serve.batcher.QueryAdmission` front-end bound to
        this engine (slot-based admission, per-tenant chunk queues,
        backpressure counters, ingest validation + tenant quarantine).

        Unless the caller supplies a ``validator``, the front-end gates
        chunks with :func:`repro.core.faults.validate_chunk` bound to this
        session's vocab, so malformed ingest is refused at the boundary
        instead of poisoning the shared engine."""
        import functools

        from ..core.faults import validate_chunk
        from .batcher import QueryAdmission

        if "validator" not in opts:
            opts["validator"] = functools.partial(
                validate_chunk, vocab=self.session.vocab)
        self._admission = QueryAdmission(self, **opts)
        return self._admission

    # -- observability -------------------------------------------------------
    def overflow_totals(self) -> Dict[str, int]:
        return {n: int(np.asarray(v)) for n, v in self._ovf_acc.items()}

    @property
    def last_stats(self) -> Dict[str, Any]:
        """Schedule + sharing effectiveness + per-query engine metrics::

            {
              "queries", "dedup", "batch", "distinct_plans",
              "shared_plan_hits", "shared_prefix_hits",   # cumulative
              "prefix_groups": [{"queries", "prefix_len",
                                 "kb_joins_shared"}, ...],
              "cohorts": [{"size", "queries"}, ...],
              "batch_sizes": [...],                       # per-cohort sizes
              "singletons", "chunks", "overflow_totals",
              "admission": {...},                         # when attached
              "operators": {name: {...}},                 # trace on only
            }
        """
        sched = self.schedule
        ops: Dict[str, Any] = {}
        for name, acc in self._stats_acc.items():
            unit = self.units.get(name)
            caps = plan_caps(unit.plan) if unit is not None else {}
            ops[name] = attach_saturation(finalize_stats(acc), caps)
        return {
            "queries": len(self.units),
            "dedup": self.dedup,
            "batch": self.batch,
            "distinct_plans": len(sched.groups),
            "shared_plan_hits": self.counters["shared_plan_hits"],
            "shared_prefix_hits": self.counters["shared_prefix_hits"],
            "prefix_groups": [
                {
                    "queries": [g.rep.name for g in ex.groups],
                    "prefix_len": ex.prefix_len,
                    "kb_joins_shared": ex.kb_joins_shared,
                }
                for ex in sched.prefix_execs()
            ],
            "cohorts": [
                {"size": len(ex.groups),
                 "queries": [g.rep.name for g in ex.groups]}
                for ex in sched.cohort_execs()
            ],
            "batch_sizes": [len(ex.groups) for ex in sched.cohort_execs()],
            "singletons": sum(1 for e in sched.execs if e.kind == "operator"),
            "chunks": self.counters["chunks"],
            "overflow_totals": self.overflow_totals(),
            "admission": (self._admission.stats()
                          if self._admission is not None else {}),
            "operators": ops,
        }


# --------------------------------------------------------------------------
# deprecation shims — the LM prefill/decode scaffolding moved to serve/lm.py
# --------------------------------------------------------------------------

_LM_NAMES = ("make_serve_fns", "greedy_token", "sample_token", "generate")


def __getattr__(name: str):
    if name in _LM_NAMES:
        warnings.warn(
            "repro.serve.engine.%s moved to repro.serve.lm (this module is "
            "now the SCEP multi-query serving engine)" % name,
            DeprecationWarning, stacklevel=2,
        )
        from . import lm
        return getattr(lm, name)
    raise AttributeError("module %r has no attribute %r" % (__name__, name))

"""Pipelined inter-operator dataflow runtime.

:class:`~repro.core.runtime.DSCEPRuntime` traces the whole operator DAG into
**one** XLA program and pushes chunks through it strictly one at a time.
This module is the alternative execution mode the paper actually deploys:
operators as *independently scheduled units* connected by bounded queues
("process part of the data and send it to other operators"), so the
aggregation operator can consume window *t* while the upstream enrichment
operators are already producing *t+1*.

Structure:

* every operator compiles to **its own jitted step** whose inbound/outbound
  :class:`~repro.core.channel.Channel` state is donated (ring buffers are
  updated in place — no per-chunk allocation on the steady path);
* every *buffering* DAG edge is a first-class capacity-bounded device
  channel (:mod:`repro.core.channel`): the ``source → aggregator`` edge
  carries window-aligned :class:`~repro.core.window.Windows`,
  ``op → aggregator`` edges carry the operator's
  ``(TripleBatch[W, out_cap], overflow[W])`` publication — the
  Publisher→Aggregator hop that the single-program runtime hides inside
  XLA.  Upstream operators consume their windows in the same tick they are
  produced, so that hand-off is a direct device transfer, not a queue —
  adding a pass-through channel there would only cost dispatches;
* a **placement** maps operators to devices
  (:func:`repro.launch.mesh.place_operators`); channels live on the
  *consumer's* device, so a producer→consumer ``device_put`` of the payload
  is the transport (a no-op on one device, a D2D copy across devices);
* the host driver runs a **software-pipelined schedule**: it feeds chunk
  *t+1* into the producer stages before draining chunk *t* from the sink,
  keeping ``depth`` chunks in flight (up to the channel capacity, default
  4).  All dispatch is async; only the sink output is ever blocked on.

With a tracer attached the driver records ``dscep.stage`` (one stage's
step, keyed by its operator or ``source``), ``dscep.transfer`` (a
payload's put onto its consumer's device, keyed by its edge) and
``dscep.drain`` (the sink's turn) spans; with metrics on it also counts
each operator's channel bytes per chunk
(:meth:`PipelinedRuntime.channel_traffic`).

Results are bit-identical to :class:`DSCEPRuntime` and
:class:`MonolithicRuntime` (tests/test_pipeline_runtime.py): the stages are
:class:`~repro.core.runtime.DagStep`'s source, upstream and sink — the
chunk step the single-program runtime jits whole — merely cut at the channel
boundaries instead of fused into one program.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from repro.obs.metrics import finalize_stats, merge_stats
from repro.obs.trace import Tracer, span_or_null

from . import channel
from .channel import Channel
from .faults import FaultInjector, FaultPlan, InjectedCrash, corrupt_batch, validate_chunk
from .kb import KnowledgeBase
from .planner import OperatorDAG
from .rdf import TripleBatch, Vocab
from .recovery import (
    ChannelDesyncError, Checkpoint, ChunkRejectedError, PipelineStalledError,
    RecoveryConfig, RecoveryExhaustedError, StageTimeoutError,
    copy_edge_stats, empty_recovery_stats, restore_tree, snapshot_stats_acc,
    snapshot_tree, tree_bytes, wait_until_ready,
)
from .runtime import (
    DagStep, RuntimeConfig, _warn_legacy_constructor, build_operators,
)


class PipelinedRuntime:
    """Streaming execution of a decomposed query DAG over device channels.

    Drop-in alternative to :class:`~repro.core.runtime.DSCEPRuntime` with the
    same constructor shape plus:

    * ``placement`` — optional ``{operator_name: jax.Device}`` (see
      :func:`repro.launch.mesh.place_operators`); ``None`` leaves every stage
      on the default device (still pipelined, transport becomes a no-op);
    * ``channel_capacity`` — slots per edge channel (≥ 2 for the
      double-buffered schedule; capacity bounds the chunks in flight —
      default 4, deep enough to hide a slow stage behind three fast ones).

    The driver decouples ``feed()`` from execution with dispatch queues:
    chunks land in a host-side source queue and a per-operator dispatch
    queue, and ``_pump()`` advances every stage whose outbound edge has
    room.  ``feed()`` therefore never raises on a full pipeline — excess
    chunks wait in the source queue until ``drain()`` frees a slot.
    """

    def __init__(
        self,
        dag: OperatorDAG,
        kb: KnowledgeBase,
        vocab: Vocab,
        config: Optional[RuntimeConfig] = None,
        mesh=None,
        data_axis: str = "data",
        placement: Optional[Dict[str, Any]] = None,
        channel_capacity: int = 4,
        tracer: Optional[Tracer] = None,
        faults: Optional[FaultPlan] = None,
        recovery: Optional[RecoveryConfig] = None,
    ):
        _warn_legacy_constructor("PipelinedRuntime", "pipelined")
        if channel_capacity < 2:
            raise ValueError(
                "pipelining needs channel_capacity >= 2 (double buffering), "
                "got %d" % channel_capacity
            )
        if mesh is not None:
            # SPMD window sharding belongs to the single-program runtime;
            # here single-device channel buffers would silently undo it.
            # Use `placement` for cross-device (inter-operator) parallelism.
            raise NotImplementedError(
                "PipelinedRuntime does not shard windows over a mesh; "
                "pass placement= instead (or use DSCEPRuntime with mesh=)"
            )
        self.dag = dag
        self.vocab = vocab
        self.config = cfg = config if config is not None else RuntimeConfig()
        self.mesh = mesh
        self.data_axis = data_axis
        self.channel_capacity = channel_capacity
        self.operators = build_operators(dag, kb, cfg, tracer)
        self.final = dag.final
        self.placement = dict(placement) if placement else None
        if self.placement is not None:
            missing = set(self.operators) - set(self.placement)
            if missing:
                raise ValueError("placement missing operators: %s" % sorted(missing))
            # pin each operator's KB slice and env onto its assigned device so
            # its step executes there (jit follows committed input placement)
            for name, op in self.operators.items():
                dev = self.placement[name]
                if op.kb is not None:
                    op.kb = jax.device_put(op.kb, dev)
                op.env = jax.device_put(op.env, dev)

        # --- the chunk step, cut into stages below; it decides what the
        # channels carry (windows or slide view, triples or tables)
        with span_or_null(tracer, "dscep.split_sink"):
            self.step = DagStep(dag, self.operators, cfg)
        self._split = self.step.split
        self.upstream: List[str] = self.step.upstream_names

        # --- per-edge channels (allocated on the consumer's device).  Only
        # the aggregator's inbound edges buffer across ticks; upstream
        # operators consume windows the tick they are produced, so they get
        # a direct transfer instead of a pass-through queue.
        self._empty_channels()

        # --- one jitted step per operator (channel state donated where a
        # step owns channels; windows are shared across consumers and are
        # therefore never donated)
        self._win_step = jax.jit(self._windows_impl)
        self._op_step = {
            name: jax.jit(functools.partial(self._op_impl, name))
            for name in self.upstream
        }
        self._sink_step = jax.jit(self._sink_impl, donate_argnums=(0, 1))
        self._in_flight = 0
        # high-water mark of chunks simultaneously in flight — the achieved
        # pipeline depth (benchmarks/CI assert >= 2, i.e. actual overlap)
        self.depth_hw = 0
        # dispatch queues: feed() only enqueues; _pump() advances any stage
        # whose outbound edge has room.  _src_q holds raw chunks not yet
        # windowed; _disp_q[name] holds windowed payloads operator `name`
        # has not yet executed (decouples upstream execution from feed()).
        self._src_q: Deque[TripleBatch] = deque()
        self._disp_q: Dict[str, Deque[Any]] = {
            name: deque() for name in self.upstream
        }
        # device-side running counters of clipped windows per operator —
        # O(1) state however long the stream runs, and no host sync on the
        # drain path (the driver reads them only at stream boundaries)
        self._overflow_acc: Dict[str, jax.Array] = {
            n: jnp.zeros((), jnp.int32) for n in self.operators
        }
        self._last_overflow: Dict[str, jax.Array] = {}

        # --- observability (off by default: the stats-collecting twins are
        # only *built* — and therefore only compiled — when a metrics tracer
        # is attached, so the plain steps keep their exact programs)
        self.tracer = tracer
        self._collect = bool(tracer is not None and tracer.config.metrics)
        self._stats_acc: Dict[str, Dict[str, jax.Array]] = {
            n: {} for n in self.operators
        }
        self._op_step_stats = self._sink_step_stats = None
        # metrics path: (inbound, outbound) payload bytes per chunk of each
        # operator, from the payloads' static shapes
        self._traffic: Dict[str, Tuple[int, int]] = {}
        if self._collect:
            self._op_step_stats = {
                name: jax.jit(
                    functools.partial(self._op_impl, name, with_stats=True))
                for name in self.upstream
            }
            self._sink_step_stats = jax.jit(
                functools.partial(self._sink_impl, with_stats=True),
                donate_argnums=(0, 1))
        # host-side per-edge schedule counters (pushes/pops happen on the
        # host driver, so these cost nothing on device)
        self._edge_stats: Dict[str, Dict[str, int]] = {
            e: {"pushes": 0, "pops": 0, "depth_hw": 0} for e in self._edges()
        }

        # --- fault tolerance (repro.core.faults / repro.core.recovery).
        # Everything below is host-side bookkeeping: the jitted stage steps
        # above are built identically whether or not faults/recovery are
        # enabled (zero-overhead pin in tests/test_faults.py).
        self._injector = FaultInjector(faults) if faults is not None else None
        if recovery is None and faults is not None:
            recovery = RecoveryConfig()      # chaos implies the default ladder
        self._rcfg = recovery
        self._resilient = recovery is not None
        # lifetime chunk sequence numbers: assigned at feed(), monotonically
        # increasing, never reused — the dedup key for replayed outputs
        self._next_seq = 0
        self._emitted_hw = -1                # highest seq whose output left drain()
        self._inflight_seqs: List[int] = []  # seqs windowed into channels, FIFO
        # bounded replay buffer: pristine fed chunks past the last
        # checkpoint's emitted watermark (pruned at every checkpoint)
        self._retained: Dict[int, TripleBatch] = {}
        self._degraded: Set[int] = set()     # seqs past max_restarts
        self._degraded_out: Dict[int, Tuple[TripleBatch, Dict[str, jax.Array]]] = {}
        self._fail_counts: Dict[int, int] = {}
        self._ckpt: Optional[Checkpoint] = None
        self._fallback_step = None           # channel-free per-chunk program
        # global restart budget: injected events fire once each, so any
        # recovery loop terminates well inside this bound — exceeding it
        # means a persistent non-chunk-attributable fault
        self._restart_budget = 64 + 4 * (len(faults.events) if faults else 0)
        self._rec: Dict[str, int] = {
            "retries": 0, "restarts": 0, "replayed": 0, "deduped": 0,
            "checkpoints": 0, "checkpoint_bytes": 0, "rejected": 0,
            "corrupt_recovered": 0,
        }

    def _edges(self) -> List[str]:
        return ["source->%s" % self.final] + [
            "%s->%s" % (name, self.final) for name in self.upstream
        ]

    # -- placement helpers ----------------------------------------------------
    def _on_device(self, tree, op_name: str):
        if self.placement is None:
            return tree
        return jax.device_put(tree, self.placement[op_name])

    def _transfer(self, payload, op_name: str, edge: str, seq: int):
        """A stage's payload on its consumer ``op_name``'s device: a
        device-to-device copy where the producer sits on another one."""
        if self.placement is None:
            return payload
        with span_or_null(self.tracer, "dscep.transfer", key=edge,
                          edge=edge, seq=seq) as sp:
            return sp.fence(self._on_device(payload, op_name))

    # -- host-side edge accounting (schedule facts, not device state) ----------
    def _edge_pushed(self, edge: str) -> None:
        e = self._edge_stats[edge]
        e["pushes"] += 1
        e["depth_hw"] = max(e["depth_hw"], e["pushes"] - e["pops"])

    def _edge_popped(self, edge: str) -> None:
        self._edge_stats[edge]["pops"] += 1

    def _empty_channels(self) -> None:
        """Allocate every sink-side channel empty, on the sink's device.  A
        sink that takes the slide view gets its window channel on the first
        feed (:meth:`_ensure_win_channel`): the view is as long as the
        chunk."""
        win_example = self.step.sink_example()
        self._win_sig = None
        self._agg_win_ch: Optional[Channel] = None
        if win_example is not None:
            self._agg_win_ch = self._on_device(
                channel.make_channel(win_example, self.channel_capacity),
                self.final)
        self._out_ch: Dict[str, Channel] = {
            n: self._on_device(
                channel.make_channel(self.step.payload_example(n),
                                     self.channel_capacity), self.final)
            for n in self.upstream
        }

    # -- stage implementations (each traces into its own XLA program) ----------
    def _windows_impl(self, chunk: TripleBatch):
        """Source stage: merge and window, ``(sink payload, operator
        payload)`` (:meth:`DagStep.source`)."""
        return self.step.source(chunk)

    def _op_impl(
        self, name: str, op_in, kb: Optional[KnowledgeBase],
        env: Dict[str, jax.Array], with_stats: bool = False,
    ):
        """Enrichment operator step (:meth:`DagStep.upstream`).  With
        ``with_stats`` (a separate jitted twin) the publication is returned
        alongside a flat dict of chunk-scalar engine metrics — the
        publication pushed onto the channel is unchanged either way."""
        pub, ovf, stats = self.step.upstream(name, op_in, kb, env, with_stats)
        if with_stats:
            return (pub, ovf), stats
        return pub, ovf

    def _sink_impl(
        self, win_ch: Channel, out_chs: Dict[str, Channel],
        kb: Optional[KnowledgeBase], env: Dict[str, jax.Array],
        with_stats: bool = False,
    ):
        """Aggregation operator step: pop every inbound edge, then
        :meth:`DagStep.sink`; a slot a pop found empty publishes nothing."""
        win_ch, sink_in, has = channel.pop(win_ch)
        payloads: Dict[str, Any] = {}
        overflow: Dict[str, jax.Array] = {}
        for name in self.upstream:
            out_chs[name], (payloads[name], ovf), h = channel.pop(
                out_chs[name])
            overflow[name] = ovf & h
        out, ovf_f, stats = self.step.sink(sink_in, payloads, kb, env,
                                           with_stats)
        overflow[self.final] = ovf_f & has
        out = out._replace(valid=out.valid & has)
        if with_stats:
            return win_ch, out_chs, out, overflow, stats
        return win_ch, out_chs, out, overflow

    # -- host-side async driver -------------------------------------------------
    def _edge_room(self, edge: str) -> bool:
        e = self._edge_stats[edge]
        return e["pushes"] - e["pops"] < self.channel_capacity

    def _ensure_win_channel(self, payload) -> None:
        """Lazily allocate the sink's window channel from the first payload
        (a slide-table sink takes the SlideView, whose stream leaf is sized
        by the chunk — unknown at construction time)."""
        sig = tuple((leaf.shape, leaf.dtype) for leaf in jax.tree.leaves(payload))
        if self._agg_win_ch is None:
            example = jax.tree.map(jnp.zeros_like, payload)
            self._agg_win_ch = self._on_device(
                channel.make_channel(example, self.channel_capacity),
                self.final)
            self._win_sig = sig
        elif self._win_sig is not None and self._win_sig != sig:
            raise RuntimeError(
                "split-delta pipelining requires uniform chunk shapes: the "
                "window channel was sized for a different chunk capacity")

    # -- fault-tolerant dispatch wrappers ------------------------------------
    def _run_stage(self, stage: str, seq: int, thunk, retryable: bool = True):
        """Dispatch one stage step through the fault ladder.

        Without recovery enabled this is a plain ``thunk()`` — zero
        overhead.  With it: injected crashes raise :class:`InjectedCrash`
        (handled by checkpoint restore), injected stalls and real per-stage
        timeouts surface as :class:`StageTimeoutError` and are retried with
        bounded exponential backoff.  ``retryable=False`` (the sink, whose
        step *donates* its channel state — re-invoking would read deleted
        buffers) escalates a real timeout straight to restore; injected
        stalls fire before dispatch and are always retryable.
        """
        if not self._resilient:
            return thunk()
        inj, rc = self._injector, self._rcfg
        if inj is not None and inj.take("crash_stage", stage, seq):
            raise InjectedCrash(stage, seq)
        attempts = 0
        while True:
            try:
                if inj is not None and inj.take("stall_stage", stage, seq):
                    raise StageTimeoutError(
                        stage, seq, rc.stage_timeout_s, injected=True)
                out = thunk()
                if rc.stage_timeout_s is not None and not wait_until_ready(
                        out, rc.stage_timeout_s):
                    raise StageTimeoutError(stage, seq, rc.stage_timeout_s)
            except StageTimeoutError as err:
                attempts += 1
                if attempts > rc.max_retries or (
                        not err.injected and not retryable):
                    raise
                self._rec["retries"] += 1
                time.sleep(rc.backoff_s * (2 ** (attempts - 1)))
                continue
            return out

    def _push_payload(self, stage: str, edge: str, seq: int, payload) -> None:
        """Push a stage's outbound payload, subject to transport faults.

        ``drop_payload`` skips both the push and the ledger — the host
        ledger mirrors device truth, and the loss surfaces as a
        :class:`ChannelDesyncError` when the sink's pre-pop audit compares
        the ledger against the chunks in flight.  ``duplicate_payload``
        pushes (and ledgers) twice — at-least-once transport without dedup.
        """
        inj = self._injector
        if inj is not None and inj.take("drop_payload", stage, seq):
            return
        dev_payload = self._transfer(payload, self.final, edge, seq)
        dup = inj is not None and inj.take("duplicate_payload", stage, seq)
        for _ in range(2 if dup else 1):
            if stage == "source":
                self._agg_win_ch = channel.push_jit(
                    self._agg_win_ch, dev_payload)
            else:
                self._out_ch[stage] = channel.push_jit(
                    self._out_ch[stage], dev_payload)
            self._edge_pushed(edge)

    def _check_desync(self) -> None:
        """Pre-pop audit: every edge must hold exactly one payload per chunk
        in flight, or the sink would join mismatched windows."""
        expected = self._in_flight
        for edge in self._edges():
            e = self._edge_stats[edge]
            actual = e["pushes"] - e["pops"]
            if actual != expected:
                raise ChannelDesyncError(edge, actual, expected)

    def _pump(self) -> None:
        """Advance every stage whose outbound edge has room.

        The schedule's one rule: a stage runs iff it has queued work AND a
        free slot to publish into.  With equal edge capacities the operator
        dispatch queues always empty within the same pump that windows their
        chunk; they exist so ``feed()`` never blocks on (or raises for) a
        full pipeline, and so per-edge capacities can diverge later without
        touching the driver.
        """
        tr = self.tracer
        src_edge = "source->%s" % self.final
        while self._src_q and self._edge_room(src_edge):
            seq, chunk = self._src_q.popleft()
            with span_or_null(tr, "dscep.stage", key="source",
                              operator="source", seq=seq) as sp:
                sink_payload, op_payload = self._run_stage(
                    "source", seq, lambda: self._win_step(chunk))
                sp.fence(sink_payload)
            self._ensure_win_channel(sink_payload)
            self._push_payload("source", src_edge, seq, sink_payload)
            for name in self.upstream:
                self._disp_q[name].append((seq, op_payload))
            self._in_flight += 1
            self._inflight_seqs.append(seq)
            self.depth_hw = max(self.depth_hw, self._in_flight)
        for name in self.upstream:
            edge = "%s->%s" % (name, self.final)
            q = self._disp_q[name]
            op = self.operators[name]
            while q and self._edge_room(edge):
                seq, payload = q.popleft()
                payload = self._transfer(
                    payload, name, "source->%s" % name, seq)
                with span_or_null(tr, "dscep.stage", key=name,
                                  operator=name, seq=seq) as sp:
                    def step(name=name, payload=payload, op=op):
                        if self._collect:
                            return self._op_step_stats[name](
                                payload, op.kb, op.env)
                        return self._op_step[name](
                            payload, op.kb, op.env), None
                    publication, stats = self._run_stage(name, seq, step)
                    if stats is not None:
                        merge_stats(self._stats_acc[name], stats)
                        self._traffic[name] = (
                            channel.payload_bytes(payload),
                            channel.payload_bytes(publication))
                    sp.fence(publication)
                self._push_payload(name, edge, seq, publication)

    def _pump_guarded(self) -> None:
        """``_pump`` under the recovery ladder: a stage fault during pumping
        restores the last checkpoint and pumps again (bounded by the global
        restart budget inside :meth:`_handle_fault`)."""
        if not self._resilient:
            self._pump()
            return
        while True:
            try:
                self._pump()
                return
            except (InjectedCrash, StageTimeoutError) as err:
                self._handle_fault(getattr(err, "stage", None),
                                   getattr(err, "seq", None))

    def feed(self, chunk: TripleBatch) -> None:
        """Accept one chunk and dispatch every stage with room (async).

        Never raises on a full pipeline: chunks beyond the channel capacity
        wait in the host-side source queue and are windowed/dispatched as
        ``drain()`` frees slots.  Nothing here blocks on device values.

        With recovery enabled the chunk first passes the
        :func:`~repro.core.faults.validate_chunk` ingest gate (a malformed
        chunk raises :class:`ChunkRejectedError` and leaves the pipeline
        untouched) and a pristine copy enters the bounded replay buffer
        before the — possibly corrupted-in-transit — ingest copy is queued.
        """
        if not self._resilient:
            self._src_q.append((self._next_seq, chunk))
            self._next_seq += 1
            self._pump()
            return
        rc = self._rcfg
        if rc.validate:
            reasons = validate_chunk(chunk, self.vocab, rc.max_graph_size)
            if reasons:
                self._rec["rejected"] += 1
                raise ChunkRejectedError(reasons)
        if self._ckpt is None:
            self._take_checkpoint()       # clean-state checkpoint 0
        seq = self._next_seq
        self._next_seq += 1
        self._retained[seq] = chunk       # pristine, pre-transit
        ingest = chunk
        inj = self._injector
        if inj is not None and inj.take("corrupt_chunk", "ingest", seq):
            ingest = corrupt_batch(chunk)
        if ingest is not chunk and validate_chunk(
                ingest, self.vocab, rc.max_graph_size):
            # the gate caught in-transit corruption: recover the pristine
            # replay-buffer copy instead of poisoning the jitted steps
            self._rec["corrupt_recovered"] += 1
            ingest = self._retained[seq]
        self._src_q.append((seq, ingest))
        self._pump_guarded()

    def drain(self) -> TripleBatch:
        """Dispatch the sink stage for the oldest in-flight chunk.

        Returns the final published chunk (a device array — block on it only
        when the host needs the values).  Per-operator overflow flags are
        accumulated device-side; read them with :meth:`overflow_totals`.
        """
        if self._resilient:
            return self._drain_resilient()
        self._pump()
        if self._in_flight == 0:
            if self._src_q:
                raise PipelineStalledError(self._stall_detail())
            raise RuntimeError("nothing in flight; feed() first")
        _seq, out = self._drain_once()
        self._pump()          # the pop freed a slot on every edge
        return out

    def _drain_once(self) -> Tuple[int, TripleBatch]:
        """The sink dispatch shared by the plain and resilient drains:
        pop every edge, join, accumulate overflow, retire the head seq."""
        # equal edge capacities guarantee the operator stages kept pace with
        # the source stage — the sink never pops an unmatched window
        assert all(not q for q in self._disp_q.values()), (
            "operator dispatch queues lag the window edge; per-edge "
            "capacities require a schedule-aware sink")
        seq = self._inflight_seqs[0] if self._inflight_seqs else -1
        final_op = self.operators[self.final]
        tr = self.tracer
        with span_or_null(tr, "dscep.drain", seq=seq):
            with span_or_null(tr, "dscep.stage", key=self.final,
                              operator=self.final, seq=seq) as sp:
                def step():
                    if self._collect:
                        return self._sink_step_stats(
                            self._agg_win_ch, self._out_ch, final_op.kb,
                            final_op.env)
                    return self._sink_step(
                        self._agg_win_ch, self._out_ch, final_op.kb,
                        final_op.env) + (None,)
                res = self._run_stage(self.final, seq, step, retryable=False)
                self._agg_win_ch, self._out_ch, out, overflow, stats = res
                if stats is not None:
                    merge_stats(self._stats_acc[self.final], stats)
                    # one slot of every inbound channel per chunk
                    popped = [self._agg_win_ch, *self._out_ch.values()]
                    self._traffic[self.final] = (
                        sum(channel.payload_bytes(ch.slots) // ch.capacity
                            for ch in popped),
                        channel.payload_bytes(out))
                sp.fence(out)
            for edge in self._edges():
                self._edge_popped(edge)
            self._accumulate_overflow(overflow)
        self._last_overflow = overflow
        self._in_flight -= 1
        if self._inflight_seqs:
            self._inflight_seqs.pop(0)
        return seq, out

    def _accumulate_overflow(self, overflow: Dict[str, jax.Array]) -> None:
        for name, flags in overflow.items():
            self._overflow_acc[name] = (
                self._overflow_acc[name] + jnp.sum(flags.astype(jnp.int32))
            )

    def _drain_resilient(self) -> TripleBatch:
        """Recovery-aware drain: emit the lowest pending seq exactly once.

        Replayed drains of already-emitted seqs advance channel state and
        re-accumulate their overflow (the accumulators were restored to the
        checkpoint, so totals stay exact) but their outputs are *discarded*
        — the sequence-number dedup that makes recovery bit-exact.
        Degraded seqs bypass the channels entirely via the fallback program.
        """
        self._pump_guarded()
        while True:
            # flush degraded outputs whose seqs were already emitted
            for s in [s for s in self._degraded_out
                      if s <= self._emitted_hw]:
                _out, ovf = self._degraded_out.pop(s)
                self._accumulate_overflow(ovf)
                self._rec["deduped"] += 1
            cand = []
            if self._inflight_seqs:
                cand.append(self._inflight_seqs[0])
            if self._degraded_out:
                cand.append(min(self._degraded_out))
            if not cand:
                if self._src_q:
                    raise PipelineStalledError(self._stall_detail())
                raise RuntimeError("nothing in flight; feed() first")
            s = min(cand)
            if s in self._degraded_out and (
                    not self._inflight_seqs or s < self._inflight_seqs[0]):
                out, ovf = self._degraded_out.pop(s)
                self._accumulate_overflow(ovf)
                self._last_overflow = ovf
                self._emitted_hw = s
                self._maybe_checkpoint()
                return out
            try:
                self._check_desync()
                seq, out = self._drain_once()
            except (InjectedCrash, StageTimeoutError,
                    ChannelDesyncError) as err:
                self._handle_fault(getattr(err, "stage", None),
                                   getattr(err, "seq", None))
                self._pump_guarded()
                continue
            if seq <= self._emitted_hw:
                self._rec["deduped"] += 1     # replayed output: discard
                self._pump_guarded()
                continue
            self._emitted_hw = seq
            self._maybe_checkpoint()
            self._pump_guarded()
            return out

    def _stall_detail(self) -> str:
        blocked = [e for e in self._edges() if not self._edge_room(e)]
        return (
            "%d chunk(s) queued at the source but nothing is in flight to "
            "drain and no stage can advance; blocked edge(s): %s"
            % (len(self._src_q),
               ", ".join(blocked) if blocked else
               "none (driver accounting bug)"))

    # -- checkpoint / restore ------------------------------------------------
    def _take_checkpoint(self) -> None:
        """Snapshot a consistent cut of driver + device state to host.

        Channel rings are deep-copied (their buffers are donated to the next
        step); queue payloads and raw chunks are produced by non-donating
        steps, so references suffice.  The replay buffer is pruned to seqs
        past the new checkpoint's emitted watermark.
        """
        ck = Checkpoint(
            fed=self._next_seq,
            emitted=self._emitted_hw,
            in_flight=self._in_flight,
            inflight_seqs=list(self._inflight_seqs),
            src_q=list(self._src_q),
            disp_q={n: list(q) for n, q in self._disp_q.items()},
            win_ch=snapshot_tree(self._agg_win_ch),
            win_sig=self._win_sig,
            out_ch={n: snapshot_tree(c) for n, c in self._out_ch.items()},
            overflow_acc=snapshot_tree(self._overflow_acc),
            stats_acc=snapshot_stats_acc(self._stats_acc),
            edge_stats=copy_edge_stats(self._edge_stats),
            envs={n: op.state() for n, op in self.operators.items()},
            degraded_out=dict(self._degraded_out),
        )
        ck.nbytes = (tree_bytes(ck.win_ch)
                     + tree_bytes(list(ck.out_ch.values()))
                     + tree_bytes(ck.envs))
        self._ckpt = ck
        self._rec["checkpoints"] += 1
        self._rec["checkpoint_bytes"] = ck.nbytes
        for s in [s for s in self._retained if s <= ck.emitted]:
            del self._retained[s]

    def _maybe_checkpoint(self) -> None:
        ce = self._rcfg.checkpoint_every
        if ce and (self._emitted_hw + 1) % ce == 0:
            self._take_checkpoint()

    def _final_device(self):
        return self.placement[self.final] if self.placement else None

    def _restore_common(self, ck: Checkpoint) -> None:
        self._overflow_acc = restore_tree(ck.overflow_acc)
        self._stats_acc = {
            n: (dict(restore_tree(a)) if a else {})
            for n, a in ck.stats_acc.items()
        }
        for n, op in self.operators.items():
            op.restore_state(
                ck.envs[n], self.placement[n] if self.placement else None)

    def _restore_full(self, ck: Checkpoint) -> None:
        """Restore the checkpoint state verbatim and re-feed every retained
        chunk that entered after it — the plain restart path."""
        fdev = self._final_device()
        self._agg_win_ch = restore_tree(ck.win_ch, fdev)
        self._win_sig = ck.win_sig
        self._out_ch = {n: restore_tree(c, fdev)
                        for n, c in ck.out_ch.items()}
        self._edge_stats = copy_edge_stats(ck.edge_stats)
        self._in_flight = ck.in_flight
        self._inflight_seqs = list(ck.inflight_seqs)
        self._src_q = deque(ck.src_q)
        self._disp_q = {n: deque(q) for n, q in ck.disp_q.items()}
        self._degraded_out = dict(ck.degraded_out)
        self._restore_common(ck)
        refed = sorted(s for s in self._retained
                       if ck.fed <= s < self._next_seq)
        for s in refed:
            if s in self._degraded:
                self._degraded_out[s] = self._run_fallback(s)
            else:
                self._src_q.append((s, self._retained[s]))
        self._rec["replayed"] += len(refed)

    def _rebuild_degraded(self, ck: Checkpoint) -> None:
        """Restart with a degraded seq pending: the faulting chunk cannot be
        allowed back into the channels (it would fault the same stage
        again), so the channels are rebuilt empty, every non-emitted seq is
        re-fed from the replay buffer, and degraded seqs are evaluated
        through the channel-free fallback program instead."""
        self._empty_channels()
        self._edge_stats = copy_edge_stats(ck.edge_stats)
        for e in self._edge_stats.values():
            e["pushes"] = e["pops"]          # rebuilt channels are empty
        self._in_flight = 0
        self._inflight_seqs = []
        self._src_q = deque()
        self._disp_q = {n: deque() for n in self.upstream}
        self._degraded_out = {}
        self._restore_common(ck)
        pending = sorted(s for s in self._retained
                         if ck.emitted < s < self._next_seq)
        for s in pending:
            if s in self._degraded:
                self._degraded_out[s] = self._run_fallback(s)
            else:
                self._src_q.append((s, self._retained[s]))
        self._rec["replayed"] += len(pending)

    def _handle_fault(self, stage: Optional[str], seq: Optional[int]) -> None:
        """One rung down the degradation ladder: account the failure to a
        seq, degrade it once it exhausts ``max_restarts``, and restore the
        last checkpoint (full restore, or the degraded rebuild when a
        pending seq is being routed around the channels)."""
        if self._ckpt is None:               # fault before any feed
            raise RecoveryExhaustedError(
                "fault in stage %r before any checkpoint exists" % stage)
        self._restart_budget -= 1
        if self._restart_budget < 0:
            raise RecoveryExhaustedError(
                "restart budget exhausted recovering stage %r (seq %s) — "
                "the fault is persistent and not attributable to one chunk"
                % (stage, seq))
        key = seq if seq is not None and seq >= 0 else (
            self._inflight_seqs[0] if self._inflight_seqs else -1)
        if key >= 0:
            self._fail_counts[key] = self._fail_counts.get(key, 0) + 1
            if self._fail_counts[key] > self._rcfg.max_restarts:
                self._degraded.add(key)
        self._rec["restarts"] += 1
        ck = self._ckpt
        if any(s > ck.emitted for s in self._degraded):
            self._rebuild_degraded(ck)
        else:
            self._restore_full(ck)

    # -- graceful degradation: the channel-free fallback program --------------
    def _run_fallback(self, seq: int):
        """Evaluate one degraded seq through the fallback program: the
        chunk step with the channels cut out, the very program the single
        program runtime jits — degraded output matches the piped (and
        monolithic) bytes exactly.  Compiled on first degradation; the happy
        path never builds it."""
        if self._fallback_step is None:
            self._fallback_step = jax.jit(self.step)
        chunk = self._retained[seq]
        kbs = {n: op.kb for n, op in self.operators.items()}
        envs = {n: op.env for n, op in self.operators.items()}
        if self.placement is not None:
            # one program cannot span devices: gather onto the sink's device
            fdev = self._final_device()
            chunk = jax.device_put(chunk, fdev)
            kbs = {n: (jax.device_put(kb, fdev) if kb is not None else None)
                   for n, kb in kbs.items()}
            envs = jax.device_put(envs, fdev)
        return self._fallback_step(chunk, kbs, envs)

    def _pending_count(self) -> int:
        """Chunks accepted but not yet emitted (drives the stream loops)."""
        degraded_pending = sum(
            1 for s in self._degraded_out if s > self._emitted_hw)
        return self._in_flight + len(self._src_q) + degraded_pending

    def _require_idle(self, what: str) -> None:
        # the whole-stream entry points own the schedule end to end; chunks
        # left in flight by manual feed() calls would surface as *this*
        # call's outputs/overflow and break the per-call contract
        if self._pending_count():
            raise RuntimeError(
                "%s with %d chunk(s) already in flight — drain() them first"
                % (what, self._pending_count())
            )

    def process_chunk(self, chunk: TripleBatch) -> Tuple[TripleBatch, Dict[str, jax.Array]]:
        """Synchronous single-chunk convenience (no overlap): feed + drain."""
        self._require_idle("process_chunk")
        self.feed(chunk)
        out = self.drain()
        return out, dict(self._last_overflow)

    def process_stream(
        self, chunks: Sequence[TripleBatch], depth: Optional[int] = None
    ) -> Tuple[List[TripleBatch], Dict[str, int]]:
        """Software-pipelined stream execution.

        ``depth`` chunks (default: the channel capacity, ≥ 2) are kept in
        flight: the sink consumes chunk *t* only after chunk *t+1*'s producer
        stages have been dispatched.  Only the last output is blocked on —
        every intermediate hand-off stays on device.  A ``depth`` beyond the
        channel capacity is allowed: the excess waits in the host-side
        source queue (accepted, not yet windowed), so in-flight device state
        never exceeds the channels.
        Returns ``(outputs, overflow)`` like ``DSCEPRuntime.process_stream``:
        the overflow counts cover exactly the chunks of *this* call.
        """
        depth = self.channel_capacity if depth is None else depth
        if depth < 1:
            raise ValueError("depth must be >= 1, got %d" % depth)
        self._require_idle("process_stream")
        target = min(depth, self.channel_capacity)
        before = dict(self._overflow_acc)    # device scalars, no sync
        outs: List[TripleBatch] = []
        for c in chunks:
            if self._in_flight >= target:
                outs.append(self.drain())
            self.feed(c)
        while self._pending_count():
            # no-progress watchdog: every drain must retire exactly one
            # chunk; anything else would formerly spin this loop forever
            pending = self._pending_count()
            outs.append(self.drain())
            if self._pending_count() >= pending:
                raise PipelineStalledError(
                    "drain() retired no chunk (%d still pending) — "
                    "wedged schedule; %s" % (pending, self._stall_detail()))
        if outs:
            jax.block_until_ready(outs[-1])  # sink-only synchronization
        if self._resilient:
            # stream-boundary checkpoint: prunes the replay buffer so
            # retained chunks never outlive their usefulness
            self._take_checkpoint()
        overflow = {
            n: int(self._overflow_acc[n] - before[n]) for n in self.operators
        }
        return outs, overflow

    # -- observability ------------------------------------------------------
    def overflow_totals(self) -> Dict[str, int]:
        """Lifetime windows clipped per operator (blocks on a few scalars)."""
        return {n: int(v) for n, v in self._overflow_acc.items()}

    def channel_stats(self) -> Dict[str, Dict[str, int]]:
        """Occupancy, dropped pushes and schedule counters for every edge.

        ``size``/``overflows`` come from device channel state; ``pushes``/
        ``pops``/``depth_hw`` are host-side schedule facts (the depth
        high-water says how much pipelining the driver actually achieved
        against ``capacity``).
        """
        stats: Dict[str, Dict[str, int]] = {}

        def one(edge: str, ch: Optional[Channel]) -> None:
            stats[edge] = {
                # a lazily-sized window channel reports its configured
                # capacity before the first feed allocates it
                "capacity": ch.capacity if ch is not None
                else self.channel_capacity,
                "size": int(ch.size) if ch is not None else 0,
                "overflows": int(ch.overflows) if ch is not None else 0,
                **self._edge_stats[edge],
            }

        one("source->%s" % self.final, self._agg_win_ch)
        for name, ch in self._out_ch.items():
            one("%s->%s" % (name, self.final), ch)
        return stats

    def op_metrics(self) -> Dict[str, Dict[str, int]]:
        """Finalized per-operator engine metric counters (empty unless the
        runtime was built with a metrics-collecting tracer)."""
        return {n: finalize_stats(a) for n, a in self._stats_acc.items() if a}

    def channel_traffic(self) -> Dict[str, Dict[str, int]]:
        """Per operator, once it ran on the metrics path: the bytes of the
        payloads it receives and publishes per chunk (from their static
        shapes), ``cross_device`` 1 where it is placed on another device
        than the sink, and the high-water depth of its edge into the sink
        (the window edge for the sink itself).  The window source runs
        where a host chunk lands, the default device, which ``round_robin``
        gives the sink: there the bytes that the operators with
        ``cross_device`` 1 receive and publish are the bytes that cross
        devices."""
        sink_dev = self._final_device()
        out: Dict[str, Dict[str, int]] = {}
        for name, (in_b, out_b) in self._traffic.items():
            src = "source" if name == self.final else name
            out[name] = {
                "in_bytes_per_chunk": in_b,
                "out_bytes_per_chunk": out_b,
                "cross_device": int(self.placement is not None
                                    and self.placement[name] != sink_dev),
                "depth_hw": self._edge_stats[
                    "%s->%s" % (src, self.final)]["depth_hw"],
            }
        return out

    @property
    def degraded(self) -> bool:
        """True when any chunk was routed around the channels through the
        lossless monolithic fallback (output still bit-exact)."""
        return bool(self._degraded)

    def recovery_stats(self) -> Dict[str, Any]:
        """The uniform fault-tolerance surface (``last_stats["recovery"]``):
        injected event counts per kind, retries/restarts/replays/dedups,
        checkpoint cadence + bytes, degraded seqs, ingest rejections."""
        st = empty_recovery_stats(self._resilient)
        st.update(self._rec)
        st["degraded_chunks"] = sorted(self._degraded)
        if self._injector is not None:
            st["injected"] = dict(self._injector.fired)
            st["scheduled"] = self._injector.plan.counts()
        return st

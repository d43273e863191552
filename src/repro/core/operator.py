"""SCEP Operator = Aggregator -> RSP engine(s) -> Publisher (paper §2, Fig 2a).

The operator owns a compiled plan, its pruned KB partition and the static
window geometry.  ``process`` is the jit-compiled whole-operator step:
merge/order input chunks, window them, vmap the engine over windows
(intra-operator parallelism), and publish the constructed output stream.

When a mesh is attached, windows are sharded across the ``data`` axis and the
KB partition is replicated or row-sharded across ``model`` (see
:mod:`repro.core.runtime` for the distributed wiring).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.trace import in_layer

from .engine import Plan, run_plan_slides, run_plan_windows
from .kb import KnowledgeBase
from .planner import plan_supports_delta
from .rdf import TripleBatch
from .stream import merge_streams
from .window import count_slides, count_windows, window_slides


@in_layer("publish")
def publish_chunk(out_w: TripleBatch, out_stream_cap: int) -> TripleBatch:
    """Publisher: flatten ``[W, cap]`` window outputs into one ordered chunk
    (order-preserving compaction of valid triples to the front).  Module
    level so the serving layer's batched steps publish with exactly the
    ops :class:`SCEPOperator` uses — publication is part of the
    bit-identity contract."""
    from .pattern import compact_rows

    flat = jax.tree.map(lambda col: col.reshape(-1), out_w)
    rows = jnp.stack([flat.s, flat.p, flat.o, flat.ts, flat.graph], axis=1)
    out, valid, _ = compact_rows(rows, flat.valid, out_stream_cap)
    return TripleBatch(
        s=out[:, 0], p=out[:, 1], o=out[:, 2], ts=out[:, 3], graph=out[:, 4],
        valid=valid,
    )


@dataclasses.dataclass(frozen=True)
class OperatorConfig:
    """Frozen so a default instance can never become shared mutable state
    across operator constructions (and so configs are hashable/jit-static)."""

    window_capacity: int = 1000      # paper: "window size is a maximum of 1000 RDF triples"
    max_windows: int = 8             # windows per processed chunk
    out_stream_cap: int = 2048       # published stream chunk capacity
    window_step: Optional[int] = None  # STEP m slide; None / >= capacity = tumbling
    incremental: bool = False        # delta evaluation over slides (when plan allows)


class SCEPOperator:
    """One deployable SCEP operator."""

    def __init__(
        self,
        name: str,
        plan: Plan,
        kb: Optional[KnowledgeBase],
        env: Dict[str, jax.Array],
        config: Optional[OperatorConfig] = None,
    ):
        self.name = name
        self.plan = plan
        self.kb = kb
        self.env = dict(env)
        self.config = config if config is not None else OperatorConfig()
        self._step = jax.jit(self._process_impl)
        self._step_stats = None   # stats-collecting twin, built on first use

    # -- the jitted operator step -------------------------------------------
    def _process_impl(
        self, chunks: Tuple[TripleBatch, ...], kb: Optional[KnowledgeBase],
        env: Dict[str, jax.Array], with_stats: bool = False,
    ):
        # ``with_stats`` is python-static: False (the default everywhere)
        # traces the exact pre-observability program; True additionally
        # returns a flat dict of chunk-scalar engine metrics.
        cfg = self.config
        merged = merge_streams(chunks)                       # Aggregator: merge+order
        if cfg.incremental and plan_supports_delta(self.plan):
            view = count_slides(
                merged, cfg.window_capacity, cfg.max_windows, cfg.window_step)
            _, r = window_slides(cfg.window_capacity, cfg.window_step)
            res = run_plan_slides(self.plan, view, r, cfg.max_windows, kb,
                                  env, with_stats)
        else:
            windows = count_windows(
                merged, cfg.window_capacity, cfg.max_windows, cfg.window_step)
            res = run_plan_windows(self.plan, windows, kb, env, with_stats)  # engines
        if with_stats:
            out_w, overflow, stats = res
            return self._publish(out_w), overflow, stats
        out_w, overflow = res
        return self._publish(out_w), overflow

    def _publish(self, out_w: TripleBatch) -> TripleBatch:
        """Publisher: flatten [W, cap] window outputs into one ordered chunk."""
        return publish_chunk(out_w, self.config.out_stream_cap)

    # -- checkpoint surface (repro.core.recovery) ------------------------------
    def state(self) -> Dict[str, jax.Array]:
        """Host snapshot of the operator's device-resident state — the env
        tables its steps read (published bindings, delta carry).  Blocks
        until pending computation on them completes, so a checkpoint is
        always a consistent cut."""
        return jax.device_get(self.env)

    def restore_state(self, snap: Dict[str, jax.Array], device=None) -> None:
        """Re-materialize a :meth:`state` snapshot (optionally committed to
        the operator's placed device, matching construction)."""
        self.env = (jax.device_put(snap, device) if device is not None
                    else jax.device_put(snap))

    # -- public API -----------------------------------------------------------
    def process(self, chunks: Sequence[TripleBatch]) -> Tuple[TripleBatch, jax.Array]:
        """Process one round of input chunks; returns (output chunk, overflow[W])."""
        return self._step(tuple(chunks), self.kb, self.env)

    def process_stats(self, chunks: Sequence[TripleBatch]):
        """``process`` with engine metrics: returns ``(output chunk,
        overflow[W], stats)`` where ``stats`` is a flat dict of device
        scalars (see repro.obs.metrics) — a separate jitted twin, so
        ``process`` keeps its pre-observability compiled program."""
        if self._step_stats is None:
            self._step_stats = jax.jit(
                functools.partial(self._process_impl, with_stats=True))
        return self._step_stats(tuple(chunks), self.kb, self.env)

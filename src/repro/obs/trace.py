"""Span-based host tracer with device-time fencing and a compile split.

Latency attribution in a JAX pipeline has two classic traps:

1. **Async dispatch** — ``jax.jit`` calls return before the device finishes,
   so a naive ``perf_counter`` pair around a stage times the *dispatch*, not
   the work.  A span can therefore carry a **fence**: a pytree of device
   arrays that is ``block_until_ready``-ed at span exit, so the recorded
   duration covers the device work that produced it.  Fencing serializes
   stages that would otherwise overlap — it changes *timing*, never
   *results* — which is exactly what per-stage attribution needs (the same
   trade MaxText's decode microbenchmarks make).
2. **JIT warmup** — the first execution of every jitted step pays tracing +
   XLA compilation, often orders of magnitude above steady state.  The
   tracer keeps the **first sample of every span path separate**
   (``first_s``) and aggregates only subsequent samples into the steady
   statistics, so one compile never pollutes a latency table.

Spans nest: a span opened while another is active records under the path
``outer/inner``, giving per-stage attribution inside a chunk-level span.

The tracer can also bridge into ``jax.profiler``: ``annotations=True`` wraps
every span in a :class:`jax.profiler.TraceAnnotation` (visible on the XLA
trace timeline), and ``profiler_dir=...`` brackets the stream between
``jax.profiler.start_trace``/``stop_trace`` via
:meth:`Tracer.start_profiler`/:meth:`Tracer.stop_profiler`.  Annotations
are best-effort (absent profiler support degrades to plain host spans); a
profiler trace that was asked for and cannot start raises.

This module deliberately imports nothing from :mod:`repro.core` — it is a
leaf utility the core wires in (see ``ExecutionConfig(trace=...)``), and
with tracing off the runtimes never touch it on the hot path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Union

import jax


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Frozen observability knobs (hashable, safe as a jit-static field).

    ``spans``       — record host wall-time spans;
    ``metrics``     — collect device-side engine metrics (binding/scan
                      occupancy high-water, probe saturation, retractions)
                      in the jitted step's carry;
    ``fence``       — ``block_until_ready`` span fences so durations cover
                      device work (serializes overlapped stages);
    ``annotations`` — wrap spans in ``jax.profiler.TraceAnnotation``;
    ``profiler_dir``— directory for ``jax.profiler.start_trace`` output
                      (enables :meth:`Tracer.start_profiler`).
    """

    spans: bool = True
    metrics: bool = True
    fence: bool = True
    annotations: bool = False
    profiler_dir: Optional[str] = None


def resolve_trace(trace: Union[None, bool, TraceConfig]) -> Optional[TraceConfig]:
    """Normalize the ``ExecutionConfig.trace`` field: None/False = off,
    True = default :class:`TraceConfig`, a config passes through."""
    if trace is None or trace is False:
        return None
    if trace is True:
        return TraceConfig()
    if isinstance(trace, TraceConfig):
        return trace
    raise TypeError(
        "trace= takes None/False, True, or a TraceConfig, got %r"
        % type(trace).__name__)


class _SpanHandle:
    """The in-flight span: ``fence(value)`` marks device results to block on
    at exit, so the span's duration attributes device time to this stage."""

    __slots__ = ("_fence",)

    def __init__(self) -> None:
        self._fence: Any = None

    def fence(self, value: Any) -> Any:
        self._fence = value
        return value


class _NullSpan:
    """No-op handle returned when tracing is off (keeps call sites branch-free)."""

    __slots__ = ()

    def fence(self, value: Any) -> Any:
        return value


_NULL_SPAN = _NullSpan()


@contextlib.contextmanager
def _null_span():
    yield _NULL_SPAN


def span_or_null(tracer: Optional["Tracer"], name: str, **meta):
    """Span on ``tracer`` when present, else a no-op span context — lets
    runtime call sites stay branch-free whether or not tracing is wired."""
    if tracer is None:
        return _null_span()
    return tracer.span(name, **meta)


class Tracer:
    """Records nested host spans with per-path compile/steady separation.

    Samples are kept as raw duration lists per span path (sample 0 is the
    first call — compile-inclusive for spans around jitted steps); ``stats``
    folds them into JSON-ready aggregates.
    """

    def __init__(self, config: Optional[TraceConfig] = None):
        self.config = config if config is not None else TraceConfig()
        self._samples: Dict[str, List[float]] = {}
        self._meta: Dict[str, Dict[str, Any]] = {}
        self._stack: List[str] = []
        self._profiling = False

    @property
    def enabled(self) -> bool:
        return self.config.spans

    # -- recording -----------------------------------------------------------
    def span(self, name: str, **meta):
        """Context manager for one timed span; nests under the active span.

        Usage::

            with tracer.span("sink") as sp:
                out = sink_step(...)
                sp.fence(out)        # block on the device result at exit
        """
        if not self.config.spans:
            return _null_span()
        return self._span_cm(name, meta)

    @contextlib.contextmanager
    def _span_cm(self, name: str, meta: Dict[str, Any]):
        path = "/".join(self._stack + [name])
        self._stack.append(name)
        handle = _SpanHandle()
        ann = None
        if self.config.annotations:
            try:
                ann = jax.profiler.TraceAnnotation(path)
                ann.__enter__()
            except Exception:
                ann = None
        t0 = time.perf_counter()
        try:
            yield handle
        finally:
            if handle._fence is not None and self.config.fence:
                jax.block_until_ready(handle._fence)
            dur = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            self._stack.pop()
            self._samples.setdefault(path, []).append(dur)
            if meta:
                self._meta.setdefault(path, {}).update(meta)

    # -- jax.profiler bridge ------------------------------------------------
    def start_profiler(self) -> bool:
        """Begin a ``jax.profiler`` trace into ``config.profiler_dir``.

        Returns whether this call started a trace: ``False`` without a
        ``profiler_dir`` or while this tracer's trace is already running.
        A trace that was asked for and cannot start raises, so a run never
        goes on silently untraced.
        """
        if not self.config.profiler_dir or self._profiling:
            return False
        try:
            jax.profiler.start_trace(self.config.profiler_dir)
        except Exception as err:
            raise RuntimeError(
                "cannot start the jax.profiler trace into %r: %s"
                % (self.config.profiler_dir, err)) from err
        self._profiling = True
        return True

    def stop_profiler(self) -> None:
        if self._profiling:
            try:
                jax.profiler.stop_trace()
            finally:
                self._profiling = False

    # -- aggregation ---------------------------------------------------------
    def reset(self) -> None:
        self._samples.clear()
        self._meta.clear()

    def stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-path aggregates with the compile/steady split.

        ``first_s`` is the path's first sample (compile-inclusive when the
        span wraps a jitted step's first execution); ``steady`` aggregates
        every later sample.  All plain floats/ints — JSON-ready.
        """
        out: Dict[str, Dict[str, Any]] = {}
        for path, samples in self._samples.items():
            steady = samples[1:]
            entry: Dict[str, Any] = {
                "count": len(samples),
                "first_s": samples[0],
                "steady": {
                    "count": len(steady),
                    "total_s": sum(steady),
                    "mean_s": (sum(steady) / len(steady)) if steady else 0.0,
                    "min_s": min(steady) if steady else 0.0,
                    "max_s": max(steady) if steady else 0.0,
                },
            }
            if path in self._meta:
                entry["meta"] = dict(self._meta[path])
            out[path] = entry
        return out

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
A span given a ``key`` records under ``name[key]``, so spans of one name
(one per pipeline stage, say) keep apart in the statistics while the
profiler sees the one name.

The program's host spans are named ``dscep.*``: ``dscep.register`` and
its steps, ``dscep.chunk`` (``/dscep.dispatch``, ``/dscep.account``) in
the one-program runtimes, and in the pipelined runtime ``dscep.stage``
(one stage's step, keyed by its operator or ``source``),
``dscep.transfer`` (a payload's copy to another stage's device, keyed by
its edge) and ``dscep.drain`` (the sink's turn: its step and the
overflow accounting).

Every recorded span is also a :class:`jax.profiler.TraceAnnotation`, so
when a ``jax.profiler`` trace runs, the program's spans sit on the same
clock as the device's ops (an annotation costs almost nothing while no
profiler runs).  Inside the jitted programs, :func:`layer_scope` names
each engine layer (``jax.named_scope("dscep.<layer>")``, one of
:data:`LAYERS`): the scope lands in every op's ``op_name`` metadata, not
in the jaxpr, and is always on.

This module deliberately imports nothing from :mod:`repro.core` — it is a
leaf utility the core wires in (see ``ExecutionConfig(trace=...)``), and
with tracing off the runtimes touch only its layer scopes, and those only
while a program is traced.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Union

import jax


# The engine layers a device op can belong to.  An op's layer is the
# innermost ``dscep.<layer>`` scope in its ``op_name`` (a KB join inside an
# OPTIONAL is ``kb_join``).
LAYERS = ("pack", "scan", "stream_join", "kb_join", "filter", "delta",
          "finalize", "publish")
SCOPE_PREFIX = "dscep."


def layer_scope(layer: str):
    """``jax.named_scope("dscep.<layer>")`` for one of :data:`LAYERS`."""
    if layer not in LAYERS:
        raise ValueError("unknown engine layer %r (have: %s)"
                         % (layer, ", ".join(LAYERS)))
    return jax.named_scope(SCOPE_PREFIX + layer)


def in_layer(layer: str) -> Callable[[Callable], Callable]:
    """Decorator: run the function under :func:`layer_scope` ``(layer)``."""
    layer_scope(layer)          # reject an unknown layer at definition time

    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with layer_scope(layer):
                return fn(*args, **kwargs)
        return scoped
    return wrap


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Frozen observability knobs (hashable, safe as a jit-static field).

    ``spans``       — record host wall-time spans (each also a
                      ``jax.profiler.TraceAnnotation``);
    ``metrics``     — collect device-side engine metrics (binding/scan
                      occupancy high-water, probe saturation, retractions)
                      in the jitted step's carry;
    ``fence``       — ``block_until_ready`` span fences so durations cover
                      device work (serializes overlapped stages).
    """

    spans: bool = True
    metrics: bool = True
    fence: bool = True


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


def span_or_null(tracer: Optional["Tracer"], name: str,
                 key: Optional[str] = None, **meta):
    """Span on ``tracer`` when present, else a no-op span context — lets
    runtime call sites stay branch-free whether or not tracing is wired."""
    if tracer is None:
        return _null_span()
    return tracer.span(name, key=key, **meta)


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

    @property
    def enabled(self) -> bool:
        return self.config.spans

    # -- recording -----------------------------------------------------------
    def span(self, name: str, key: Optional[str] = None, **meta):
        """Context manager for one timed span; nests under the active span.
        The profiler annotation is ``name`` with ``meta``; the statistics
        keep the span under ``name[key]`` when a ``key`` is given.

        Usage::

            with tracer.span("sink") as sp:
                out = sink_step(...)
                sp.fence(out)        # block on the device result at exit
        """
        if not self.config.spans:
            return _null_span()
        return self._span_cm(name, key, meta)

    @contextlib.contextmanager
    def _span_cm(self, name: str, key: Optional[str], meta: Dict[str, Any]):
        seg = name if key is None else "%s[%s]" % (name, key)
        path = "/".join(self._stack + [seg])
        self._stack.append(seg)
        handle = _SpanHandle()
        ann = jax.profiler.TraceAnnotation(name, **meta)
        ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield handle
        finally:
            if handle._fence is not None and self.config.fence:
                jax.block_until_ready(handle._fence)
            dur = time.perf_counter() - t0
            ann.__exit__(None, None, None)
            self._stack.pop()
            self._samples.setdefault(path, []).append(dur)
            if meta:
                self._meta.setdefault(path, {}).update(meta)

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

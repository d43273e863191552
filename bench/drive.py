"""Driving the system: warm-up, and the measured window in a closed or an
open loop, through the system's own ``RegisteredQuery.stream``.

The window hands chunks to the system from one thread.  In a closed loop
the next chunk goes in as soon as at most ``inflight`` results are
outstanding; in an open loop each chunk goes in when its last tweet is due
by a Poisson arrival schedule fixed from the seed, and results are fetched
as soon as they are ready.  A result counts once its rows are on the host.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import resource
import threading
import time
from collections import Counter, deque
from typing import Callable, List, Optional

import numpy as np

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads of the process,
    and keeps the clock time of every JAX compile-path event (tracing,
    lowering, compiling, cache retrieval)."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self) -> None:
        self.count = 0
        self.events: List[tuple] = []    # (clock, event, seconds)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on_event)
        return cls._instance

    def _on_event(self, event, duration, **_) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1
        self.events.append((time.perf_counter(), event, duration))


class StallWatch:
    """What the process did while results stopped coming, to tell the
    causes of a stall apart: a thread that wakes every ``period`` seconds
    and records how late it woke and the process CPU seconds meanwhile
    (late with no CPU: the process was not scheduled; late with CPU: a
    thread held the interpreter), the pauses of Python's collector, the
    process's context switches and CPU time, and the JAX compile-path
    events inside the window."""

    def __init__(self, period: float = 0.01, late: float = 0.05) -> None:
        self.period, self.threshold = period, late
        self.late: List[tuple] = []      # (clock, seconds late, cpu seconds)
        self.gc_pauses: List[tuple] = []  # (clock, seconds, generation)
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)

    def _beat(self) -> None:
        nxt = time.perf_counter() + self.period
        cpu = time.process_time()
        while not self._stop.wait(max(0.0, nxt - time.perf_counter())):
            now, c = time.perf_counter(), time.process_time()
            if now - nxt > self.threshold:
                self.late.append((nxt, now - nxt, c - cpu))
            cpu, nxt = c, now + self.period

    def _gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_pauses.append((self._gc_t0,
                                   time.perf_counter() - self._gc_t0,
                                   info["generation"]))

    def __enter__(self) -> "StallWatch":
        self.t0 = time.perf_counter()
        self.cpu0 = time.process_time()
        self.ru0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._gc)
        self.t1 = time.perf_counter()
        self.cpu1 = time.process_time()
        self.ru1 = resource.getrusage(resource.RUSAGE_SELF)

    def report(self, recs: List["ChunkRec"], events: List[tuple]) -> str:
        """One line: the longest wait between two results and what the
        process did in it, and the window's totals."""
        done = sorted(r.done for r in recs if r.done)
        gaps = [(b - a, a) for a, b in zip(done, done[1:])] or [(0.0, self.t0)]
        gap, at = max(gaps)
        inside = lambda t: at <= t <= at + gap
        late = max(self.late, key=lambda x: x[1], default=(at, 0.0, 0.0))
        gcs = max(self.gc_pauses, key=lambda x: x[1], default=(at, 0.0, 0))
        jax_ev = Counter(e for t, e, _ in events if self.t0 <= t <= self.t1)
        return ("gap_max_ms=%.1f at_s=%.2f gap_p50_ms=%.1f | in it: "
                "late_beats=%d cpu_s=%.3f gc_ms=%.1f | window: late_max_ms=%.1f "
                "(cpu_s %.3f, at_s %.2f) late_beats=%d gc_max_ms=%.1f (gen %d) "
                "cpu_s=%.2f of %.2f nivcsw=%d nvcsw=%d load=%.2f jax_events=%s"
                % (1e3 * gap, at - self.t0,
                   1e3 * sorted(g for g, _ in gaps)[len(gaps) // 2],
                   sum(inside(t) for t, _, _ in self.late),
                   sum(c for t, _, c in self.late if inside(t)),
                   1e3 * sum(d for t, d, _ in self.gc_pauses if inside(t)),
                   1e3 * late[1], late[2], late[0] - self.t0, len(self.late),
                   1e3 * gcs[1], gcs[2], self.cpu1 - self.cpu0,
                   self.t1 - self.t0, self.ru1.ru_nivcsw - self.ru0.ru_nivcsw,
                   self.ru1.ru_nvcsw - self.ru0.ru_nvcsw, os.getloadavg()[0],
                   dict(jax_ev) or "none"))


@dataclasses.dataclass
class ChunkRec:
    k: int                       # position in the endless replay
    triples: int
    due: float = 0.0             # open loop: seconds after the window opened
    unit_due: Optional[np.ndarray] = None
    handed: float = 0.0          # clock when the chunk went to the system
    done: float = 0.0            # clock when its rows were on the host
    out: Optional[tuple] = None  # host copy of the published (valid) rows


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    recs: List[ChunkRec]
    system_s: float              # host time inside the system's stream
    compiles: int                # compilations inside the window
    stalls: str                  # StallWatch.report of the window


def arrivals(world, rate_tweets_per_s: float, rng: np.random.Generator):
    """Due times, in seconds after the window opens, of chunk ``k`` and of
    each of its units, for Poisson tweet arrivals at the given rate.

    Every seed gets the same set of inter-arrival gaps (the exponential
    distribution's quantiles at ``(i + 1/2) / T``), in an order of its
    own: the seed changes when bursts come, not how much traffic a run
    offers."""
    c = world.chunks
    n = world.tweets
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_tweets_per_s
    arr = np.cumsum(rng.permutation(gaps))
    origin = arr[c.first_tweet[0]]
    period = arr[c.last_tweet[-1]] - origin + 1.0 / rate_tweets_per_s

    def due(k: int):
        base, cycle = k % world.n_chunks, k // world.n_chunks
        shift = cycle * period - origin
        return (float(arr[c.last_tweet[base]] + shift),
                arr[c.unit_last_tweet[base]] + shift)

    return due


def _span(tracing: bool, name: str):
    if not tracing:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def warm_up(reg, make_batch: Callable, ks: List[int]) -> float:
    """Run ``ks`` through the stream, fetching each result; returns the
    seconds until the first result was on the host (compile or cache load
    of every program included)."""
    import jax

    t0 = time.perf_counter()
    first = None
    for out in reg.stream([make_batch(k) for k in ks]):
        jax.device_get(out)
        if first is None:
            first = time.perf_counter() - t0
    return first


def run_window(reg, make_batch: Callable, triples_of: Callable,
               traffic: dict, seconds: float, due=None,
               tracing: bool = False) -> Window:
    import jax

    counter = CompileCounter.get()
    compiles0 = counter.count
    open_loop = traffic["loop"] == "open"
    inflight = int(traffic["inflight"])
    recs: List[ChunkRec] = []
    pending: deque = deque()
    n_out = [0]
    src_s = [0.0]

    def fetch() -> None:
        out = pending.popleft()
        with _span(tracing, "bench.fetch"):
            host = jax.device_get(out)
        rec = recs[n_out[0]]
        rec.done = time.perf_counter()
        valid = np.asarray(host.valid)
        rec.out = tuple(np.asarray(x)[valid] for x in host)
        n_out[0] += 1

    def harvest(block: bool) -> None:
        while pending and (block or pending[0].valid.is_ready()):
            fetch()

    t0 = time.perf_counter()

    def source():
        k = 0
        while True:
            t_in = time.perf_counter()
            rec = ChunkRec(k, triples_of(k))
            if open_loop:
                rec.due, rec.unit_due = due(k)
                if rec.due > seconds:
                    return
                with _span(tracing, "bench.wait"):
                    while True:
                        harvest(False)
                        left = t0 + rec.due - time.perf_counter()
                        if left <= 0:
                            break
                        time.sleep(min(left, 5e-4))
            elif t_in - t0 >= seconds:
                return
            batch = make_batch(k)
            recs.append(rec)
            rec.handed = time.perf_counter()
            src_s[0] += rec.handed - t_in
            yield batch
            k += 1

    it = reg.stream(source())
    system_s = 0.0
    with StallWatch() as watch, _span(tracing, "bench.window"):
        while True:
            t = time.perf_counter()
            with _span(tracing, "bench.system"):
                out = next(it, None)
            system_s += time.perf_counter() - t
            if out is None:
                break
            pending.append(out)
            if open_loop:
                harvest(False)
            while len(pending) > inflight:
                fetch()
        harvest(True)
    t_end = max(r.done for r in recs)
    return Window(t0, t_end, recs, system_s - src_s[0],
                  counter.count - compiles0,
                  watch.report(recs, counter.events))

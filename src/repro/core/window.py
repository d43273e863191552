"""Window management — the Aggregator's second half.

The paper (§4.4) uses count-based windows measured in *triples* but never
splits an RDF-graph event across windows: "DSCEP aggregates as many RDF graphs
that their sum of triples is a maximum of 1000 RDF triples".  We reproduce
exactly that packing, generalized to sliding count windows
(``[RANGE TRIPLES n STEP m]``), plus time-based tumbling/sliding windows.

The packing is next-fit over graph sizes: a unit (window or slide) takes
graphs in stream order until the next one would overflow it.  It is computed
from prefix sums of the sizes, one step per *unit* rather than per graph: the
next unit starts at the first graph whose inclusive prefix passes the current
unit's start prefix plus the capacity, so a chunk's packing costs
``max_units`` dense compare-and-count passes over its rows.

Sliding count windows factor through *slides*: the stream is packed into
slides of ``m`` triples, and window ``w`` is the
concatenation of slides ``w .. w + R - 1`` with ``R = ceil(n / m)``.  The
slide is the packing unit — a graph never splits across slides, and a graph
larger than ``m`` is truncated to ``m`` in a slide of its own, the same
bounded-buffer rule tumbling windows apply at capacity ``n``.  When ``m``
does not divide ``n`` the effective window capacity rounds up to ``R * m``.
``STEP >= RANGE`` (or no STEP) degenerates to tumbling: one slide per window,
bit-identical to the historical single-level packing.

Windows are materialized as a dense ``[num_windows, window_capacity]`` gather
of the ordered stream — the layout the SPMD engine shards across the ``data``
mesh axis (intra-operator parallelism: each device processes a window slice,
the TPU analogue of Kafka consumer groups).  Incremental (delta) evaluation
skips that materialization: :class:`SlideView` keeps the per-row slide
assignment so the engine can evaluate the whole chunk once and select each
window's results by slide-span intervals (see ``engine.run_plan_slides``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import in_layer

from .rdf import TripleBatch, take_rows


class Windows(NamedTuple):
    """A batch of triple windows: every field is ``[W, C]``."""

    triples: TripleBatch      # leaf arrays have shape [W, C]
    window_valid: jax.Array   # [W] bool — windows that contain >= 1 event

    @property
    def num_windows(self) -> int:
        return int(self.window_valid.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.triples.s.shape[-1])


class SlideView(NamedTuple):
    """Slide-level view of a merged stream (sliding count windows).

    Produced by :func:`count_slides`; consumed either by
    :func:`windows_from_slides` (materialize overlapping windows for
    per-window recompute) or by ``engine.run_plan_slides`` (incremental
    evaluation with slide-span tracking).  All geometry (slide capacity,
    slides per window) is static and recomputed from the config where
    needed, so this tuple carries arrays only and vmaps/jits cleanly.
    """

    stream: TripleBatch       # merged, ts-ordered stream [n]
    slide_of_row: jax.Array   # [n] int32 — slide ordinal, -1 = dropped/invalid
    slide_col: jax.Array      # [n] int32 — position of the row in its slide
    slide_valid: jax.Array    # [S] bool — slides holding >= 1 triple
    slide_ts: jax.Array       # [S] uint32 — max ts per slide (0 when empty)

    @property
    def num_slides(self) -> int:
        return int(self.slide_valid.shape[0])


def window_slides(window_capacity: int, step: Optional[int] = None) -> Tuple[int, int]:
    """Resolve ``STEP`` geometry to ``(slide_capacity, slides_per_window)``.

    ``step is None`` or ``step >= window_capacity`` means tumbling — one
    slide of the full capacity per window.  Otherwise the slide holds
    ``step`` triples and a window spans ``R = ceil(window_capacity / step)``
    consecutive slides.
    """
    if step is None or step >= window_capacity:
        return window_capacity, 1
    if step < 1:
        raise ValueError("window step must be >= 1, got %d" % step)
    return step, -(-window_capacity // step)


def _segment_first(values: jax.Array, seg_starts: jax.Array) -> jax.Array:
    return jnp.take(values, seg_starts, axis=-1)


def _pack_rows(
    stream: TripleBatch, capacity: int, max_units: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Next-fit graph-preserving packing of the stream into capacity-bounded
    units (windows or slides), stepped once per unit by prefix sums.

    The stream must be timestamp-ordered with invalid rows at the tail (the
    merge stage guarantees this).  Graph events are contiguous runs of equal
    ``graph`` id; a graph moves to the next unit when it would overflow the
    current one.  Graphs larger than ``capacity`` get a unit of their own
    (truncated to capacity, matching a bounded-buffer engine).  With sizes
    clipped to ``capacity`` and their exclusive prefix ``exc``, the unit
    starting at graph ``s`` ends before the first graph ``t`` with
    ``exc[t + 1] > exc[s] + capacity``; a loop of ``max_units`` trips finds
    the unit starts, and each graph's offset is ``exc[g] - exc[start]``.

    Returns ``(unit, col, ok)`` per row: unit ordinal, column within the
    unit, and whether the row landed (valid, within ``max_units``, within
    capacity).
    """
    n = stream.capacity
    valid = stream.valid
    g = stream.graph

    # --- per-row graph boundaries on the ordered stream
    prev_g = jnp.concatenate([g[:1], g[:-1]])
    new_graph = (jnp.arange(n) == 0) | (g != prev_g)
    new_graph = new_graph & valid

    graph_idx = jnp.cumsum(new_graph.astype(jnp.int32)) - 1          # [n] graph ordinal
    graph_idx = jnp.where(valid, graph_idx, -1)

    # --- graph sizes via segment sum over graph ordinals
    num_graphs = n  # upper bound
    sizes = jax.ops.segment_sum(
        valid.astype(jnp.int32), jnp.where(graph_idx < 0, num_graphs - 1, graph_idx),
        num_segments=num_graphs,
    )

    # --- next-fit by prefix sums, one step per unit: the unit starting at
    # graph s ends where the inclusive prefix passes exc[s] + capacity.
    # Every clipped size is <= capacity, so each unit takes >= 1 graph
    # until the starts reach n, where they stay.
    size_c = jnp.minimum(sizes, capacity)
    inc = jnp.cumsum(size_c)
    exc = jnp.concatenate([jnp.zeros((1,), inc.dtype), inc])          # [n + 1]

    def next_unit(u, starts):
        nxt = jnp.sum(inc <= exc[starts[u]] + capacity, dtype=jnp.int32)
        return starts.at[u + 1].set(nxt)

    starts = jax.lax.fori_loop(
        0, max_units, next_unit, jnp.zeros((max_units + 1,), jnp.int32))
    # unit of a graph = starts 1..max_units at or below it; graphs past the
    # last start read max_units, which ``ok`` drops
    marks = jnp.zeros((n + 1,), jnp.int32).at[starts[1:]].add(1)
    graph_wid = jnp.cumsum(marks[:n])
    graph_off = exc[:n] - exc[starts[graph_wid]]

    # position of a row within its graph = row index - index of graph start
    graph_start = jnp.where(new_graph, jnp.arange(n), 0)
    graph_start = jax.lax.associative_scan(jnp.maximum, graph_start)
    pos_in_graph = jnp.arange(n) - graph_start

    wid = jnp.where(graph_idx >= 0, jnp.take(graph_wid, jnp.maximum(graph_idx, 0)), -1)
    off = jnp.where(graph_idx >= 0, jnp.take(graph_off, jnp.maximum(graph_idx, 0)), 0)
    col = off + pos_in_graph
    in_cap = col < capacity
    ok = valid & (wid >= 0) & (wid < max_units) & in_cap
    return wid, col, ok


def _scatter_units(
    stream: TripleBatch, unit: jax.Array, col: jax.Array, ok: jax.Array,
    capacity: int, max_units: int,
) -> jax.Array:
    """Row-placement ``(unit, col, ok)`` -> dense ``[max_units, capacity]``
    gather indices (-1 = empty slot)."""
    n = stream.capacity
    flat_target = jnp.where(ok, unit * capacity + col, max_units * capacity)
    slot_of_row = jnp.full((max_units * capacity + 1,), -1, jnp.int32)
    slot_of_row = slot_of_row.at[flat_target].set(
        jnp.where(ok, jnp.arange(n, dtype=jnp.int32), -1), mode="drop"
    )
    return slot_of_row[: max_units * capacity].reshape(max_units, capacity)


@in_layer("pack")
def count_slides(
    stream: TripleBatch, window_capacity: int, max_windows: int,
    step: Optional[int] = None,
) -> SlideView:
    """Pack the stream into ``max_windows + R - 1`` slides of ``step``
    triples (paper §4.4 packing at slide granularity)."""
    slide_cap, r = window_slides(window_capacity, step)
    num_slides = max_windows + r - 1
    sid, col, ok = _pack_rows(stream, slide_cap, num_slides)
    seg = jnp.where(ok, sid, num_slides)
    slide_valid = jax.ops.segment_sum(
        ok.astype(jnp.int32), seg, num_segments=num_slides + 1)[:num_slides] > 0
    # uint32 segment max: empty segments fill with the dtype min == 0, the
    # same "no triples" ts the recompute path uses for empty windows
    slide_ts = jax.ops.segment_max(
        jnp.where(ok, stream.ts, 0), seg, num_segments=num_slides + 1)[:num_slides]
    return SlideView(
        stream=stream,
        slide_of_row=jnp.where(ok, sid, -1),
        slide_col=jnp.where(ok, col, 0),
        slide_valid=slide_valid,
        slide_ts=slide_ts,
    )


@in_layer("pack")
def windows_from_slides(
    view: SlideView, window_capacity: int, max_windows: int,
    step: Optional[int] = None,
) -> Windows:
    """Materialize overlapping windows: window ``w`` = slides ``w..w+R-1``.

    The physical window capacity is ``R * slide_capacity`` (== the window
    capacity when STEP divides RANGE, rounded up otherwise); rows duplicate
    across the up-to-``R`` windows sharing each slide.
    """
    slide_cap, r = window_slides(window_capacity, step)
    num_slides = max_windows + r - 1
    ok = view.slide_of_row >= 0
    slide_idx = _scatter_units(
        view.stream, view.slide_of_row, view.slide_col, ok, slide_cap, num_slides
    )                                                     # [S, slide_cap]
    widx = jnp.arange(max_windows)[:, None] + jnp.arange(r)[None, :]   # [W, R]
    gather_idx = jnp.take(slide_idx, widx, axis=0).reshape(
        max_windows, r * slide_cap
    )
    wt = take_rows(view.stream, gather_idx)
    window_valid = jnp.any(jnp.take(view.slide_valid, widx, axis=0), axis=1)
    return Windows(wt, window_valid)


@in_layer("pack")
def count_windows(
    stream: TripleBatch, window_capacity: int, max_windows: int,
    step: Optional[int] = None,
) -> Windows:
    """Greedy graph-preserving count windows (paper §4.4 semantics).

    Without ``step`` (or ``step >= window_capacity``) windows tumble exactly
    as the paper describes.  With ``step < window_capacity`` windows overlap:
    the stream packs into slides of ``step`` triples and each window holds
    ``ceil(window_capacity / step)`` consecutive slides (see module
    docstring for the truncation/rounding rules).
    """
    slide_cap, r = window_slides(window_capacity, step)
    if r == 1:
        wid, col, ok = _pack_rows(stream, window_capacity, max_windows)
        gather_idx = _scatter_units(
            stream, wid, col, ok, window_capacity, max_windows
        )
        wt = take_rows(stream, gather_idx)
        return Windows(wt, jnp.any(wt.valid, axis=-1))
    view = count_slides(stream, window_capacity, max_windows, step)
    return windows_from_slides(view, window_capacity, max_windows, step)


def time_windows(
    stream: TripleBatch,
    t0: int,
    width: int,
    slide: int,
    window_capacity: int,
    max_windows: int,
) -> Windows:
    """Time-based windows ``[t0 + w*slide, t0 + w*slide + width)``.

    Sliding windows (slide < width) duplicate rows across overlapping windows;
    tumbling windows are the slide == width special case.  Row placement per
    window is order-preserving; overflow beyond capacity is dropped (bounded
    buffer) — overflow is detectable via ``count == capacity``.

    All windows are placed by one batched scatter (no python-level unrolling
    over ``max_windows``), so the traced program size is independent of the
    window count.
    """
    n = stream.capacity
    ts = stream.ts.astype(jnp.int32)  # synthetic timestamps stay well below 2**31
    valid = stream.valid

    lo = t0 + jnp.arange(max_windows, dtype=jnp.int32) * slide          # [W]
    inw = valid[None, :] & (ts[None, :] >= lo[:, None]) \
        & (ts[None, :] < (lo + width)[:, None])                         # [W, n]
    # order-preserving compaction of member rows to the front (per window)
    pos = jnp.cumsum(inw.astype(jnp.int32), axis=1) - 1
    tgt = jnp.where(inw & (pos < window_capacity), pos, window_capacity)
    src = jnp.where(inw, jnp.arange(n, dtype=jnp.int32)[None, :], -1)
    widx = jnp.broadcast_to(
        jnp.arange(max_windows, dtype=jnp.int32)[:, None], (max_windows, n)
    )
    idx = jnp.full((max_windows, window_capacity + 1), -1, jnp.int32)
    idx = idx.at[widx, tgt].set(src, mode="drop")
    wt = take_rows(stream, idx[:, :window_capacity])
    return Windows(wt, jnp.any(inw, axis=1))


count_windows_jit = jax.jit(count_windows, static_argnums=(1, 2, 3))
time_windows_jit = jax.jit(time_windows, static_argnums=(2, 3, 4, 5))

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core.rdf import make_triples, sort_by_timestamp
from repro.core.window import (
    _pack_rows, count_slides, count_windows, time_windows, window_slides,
)


def _mk_stream(graph_sizes, ts_start=100, capacity=None):
    rows = []
    for gi, size in enumerate(graph_sizes):
        for k in range(size):
            rows.append((10 + gi, 1, 20 + k, ts_start + gi, gi + 1))
    cap = capacity if capacity is not None else max(1, sum(graph_sizes))
    return sort_by_timestamp(make_triples(rows, capacity=cap))


def test_count_windows_paper_semantics():
    # capacity 5: graphs of sizes 3,2 fill window 0; 4 goes to window 1
    stream = _mk_stream([3, 2, 4])
    w = count_windows(stream, window_capacity=5, max_windows=4)
    counts = np.asarray(w.triples.valid).sum(axis=1)
    assert list(counts) == [5, 4, 0, 0]
    assert list(np.asarray(w.window_valid)) == [True, True, False, False]


def test_count_windows_graph_never_split():
    stream = _mk_stream([2, 2, 2, 2])
    w = count_windows(stream, window_capacity=3, max_windows=4)
    g = np.asarray(w.triples.graph)
    v = np.asarray(w.triples.valid)
    # each graph's rows live in exactly one window
    for graph_id in (1, 2, 3, 4):
        in_window = [(g[i] == graph_id)[v[i]].any() for i in range(4)]
        assert sum(in_window) == 1


def test_count_windows_oversized_graph_truncated():
    stream = _mk_stream([7])
    w = count_windows(stream, window_capacity=4, max_windows=2)
    counts = np.asarray(w.triples.valid).sum(axis=1)
    assert counts[0] == 4 and counts[1] == 0   # bounded buffer, own window


def test_time_windows_tumbling_and_sliding():
    stream = _mk_stream([1, 1, 1, 1])          # ts = 100,101,102,103
    w = time_windows(stream, t0=100, width=2, slide=2, window_capacity=4, max_windows=2)
    counts = np.asarray(w.triples.valid).sum(axis=1)
    assert list(counts) == [2, 2]
    ws = time_windows(stream, t0=100, width=2, slide=1, window_capacity=4, max_windows=3)
    counts = np.asarray(ws.triples.valid).sum(axis=1)
    assert list(counts) == [2, 2, 2]           # overlap duplicates rows


def test_sliding_graph_straddling_slide_boundary_whole_in_every_window():
    # cap 6 STEP 3: graphs of 2 pack one per slide (2+2 > 3), so graph 2
    # lands in slide 1 and is shared by windows 0 and 1 — whole in both
    stream = _mk_stream([2, 2, 2])
    w = count_windows(stream, window_capacity=6, max_windows=4, step=3)
    g = np.asarray(w.triples.graph)
    v = np.asarray(w.triples.valid)
    per_window = [int(((g[i] == 2) & v[i]).sum()) for i in range(4)]
    # appears in >= 2 overlapping windows, and never partially
    assert per_window.count(2) >= 2
    assert all(c in (0, 2) for c in per_window)


def test_sliding_oversized_graph_truncated_to_slide_capacity():
    # a graph bigger than the slide (STEP) truncates to the slide capacity,
    # and every window containing it sees exactly that truncated prefix
    stream = _mk_stream([5, 2])
    w = count_windows(stream, window_capacity=6, max_windows=3, step=3)
    g = np.asarray(w.triples.graph)
    v = np.asarray(w.triples.valid)
    per_window = [int(((g[i] == 1) & v[i]).sum()) for i in range(3)]
    assert all(c in (0, 3) for c in per_window) and 3 in per_window


def test_sliding_empty_slides_invalidate_trailing_windows():
    # one small graph: only windows overlapping its slide are valid; windows
    # made purely of empty slides are invalid and carry zero rows
    stream = _mk_stream([2])
    w = count_windows(stream, window_capacity=6, max_windows=4, step=3)
    counts = np.asarray(w.triples.valid).sum(axis=1)
    assert list(counts) == [2, 0, 0, 0]
    assert list(np.asarray(w.window_valid)) == [True, False, False, False]


@pytest.mark.parametrize("sizes,cap", [([3, 2, 4], 5), ([2, 2, 2, 2], 3),
                                       ([7], 4), ([1, 6, 2, 1], 6)])
def test_step_equals_range_bit_exact_tumbling(sizes, cap):
    # STEP == RANGE is the degenerate 1-slide-per-window geometry; it must
    # reproduce the tumbling arrays bit for bit, not merely set-equal
    stream = _mk_stream(sizes)
    tumble = count_windows(stream, window_capacity=cap, max_windows=4)
    slide = count_windows(stream, window_capacity=cap, max_windows=4, step=cap)
    for ca, cb in zip(tumble.triples, slide.triples):
        assert bool(np.all(np.asarray(ca) == np.asarray(cb)))
    assert bool(np.all(np.asarray(tumble.window_valid)
                       == np.asarray(slide.window_valid)))


def test_time_windows_jaxpr_size_independent_of_max_windows():
    # the batched gather rewrite traces one fixed program: growing
    # max_windows only widens array shapes, it adds no equations
    import jax

    stream = _mk_stream([1, 1, 1, 1])
    small = jax.make_jaxpr(
        lambda s: time_windows(s, 100, 2, 1, 4, 2))(stream)
    big = jax.make_jaxpr(
        lambda s: time_windows(s, 100, 2, 1, 4, 16))(stream)
    assert len(small.jaxpr.eqns) == len(big.jaxpr.eqns)


def _jaxpr_loop_lengths(jaxpr):
    """Trip count of every loop in ``jaxpr`` and its sub-jaxprs (None for a
    ``while``, whose count is not static)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["length"]
        elif eqn.primitive.name == "while":
            yield None
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _jaxpr_loop_lengths(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _jaxpr_loop_lengths(sub)


@pytest.mark.parametrize("pack,cap,step,max_windows", [
    (count_windows, 1000, None, 8), (count_slides, 1000, 250, 29)])
def test_pack_jaxpr_has_no_per_row_loop(pack, cap, step, max_windows):
    # packing steps once per unit (window or slide), never once per row or
    # graph: no loop of the program may run as many trips as the chunk has rows
    n = 8000
    stream = make_triples([], capacity=n)
    jaxpr = jax.make_jaxpr(
        lambda s: pack(s, cap, max_windows, step))(stream).jaxpr
    lengths = list(_jaxpr_loop_lengths(jaxpr))
    assert all(length is not None and length < n for length in lengths), lengths


def _pack_rows_by_graph_scan(stream, capacity, max_units):
    """Reference packing: a next-fit scan carried graph by graph, the
    definition the unit-stepped ``_pack_rows`` must reproduce row for row."""
    n = stream.capacity
    valid = stream.valid
    g = stream.graph
    prev_g = jnp.concatenate([g[:1], g[:-1]])
    new_graph = ((jnp.arange(n) == 0) | (g != prev_g)) & valid
    graph_idx = jnp.cumsum(new_graph.astype(jnp.int32)) - 1
    graph_idx = jnp.where(valid, graph_idx, -1)
    sizes = jax.ops.segment_sum(
        valid.astype(jnp.int32), jnp.where(graph_idx < 0, n - 1, graph_idx),
        num_segments=n)

    def pack(carry, size_live):
        fill, wid = carry
        size, live = size_live
        size_c = jnp.minimum(size, capacity)
        overflow = fill + size_c > capacity
        new_wid = jnp.where(live, jnp.where(overflow, wid + 1, wid), wid)
        new_fill = jnp.where(overflow, size_c, fill + size_c)
        return ((jnp.where(live, new_fill, fill), new_wid),
                (new_wid, jnp.where(overflow, 0, fill)))

    _, (graph_wid, graph_off) = jax.lax.scan(
        pack, (jnp.int32(0), jnp.int32(0)), (sizes, sizes > 0))
    graph_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_graph, jnp.arange(n), 0))
    gi = jnp.maximum(graph_idx, 0)
    wid = jnp.where(graph_idx >= 0, graph_wid[gi], -1)
    col = jnp.where(graph_idx >= 0, graph_off[gi], 0) + jnp.arange(n) - graph_start
    ok = valid & (wid >= 0) & (wid < max_units) & (col < capacity)
    return wid, col, ok


_PACK_N = 256
_pack_unit_stepped = jax.jit(_pack_rows, static_argnums=(1, 2))
_pack_graph_scan = jax.jit(_pack_rows_by_graph_scan, static_argnums=(1, 2))


def _draw_sizes(rng, case, cap):
    """Graph sizes of one seeded stream of at most ``_PACK_N`` rows."""
    if case == "empty":
        return []
    if case == "every_row":
        return [1] * _PACK_N
    limit = _PACK_N if case == "overflow" else int(rng.integers(1, _PACK_N + 1))
    sizes = []
    while True:
        if case == "mixed":
            size = int(rng.integers(1, 2 * cap + 2))
        elif case == "exact_cap":
            size = cap if rng.random() < 0.5 else int(rng.integers(1, cap + 1))
        elif case == "one_row":
            size = 1
        else:                                   # overflow: more than U units
            size = int(rng.integers(1, cap + 1))
        if sum(sizes) + size > limit:
            return sizes
        sizes.append(size)


@pytest.mark.parametrize("case", [
    "mixed", "exact_cap", "one_row", "every_row", "empty", "overflow"])
@pytest.mark.parametrize("window,step,max_windows", [
    (24, None, 8),    # tumbling: U = max_windows units of 24 rows
    (24, 6, 8),       # sliding: U = max_windows + R - 1 = 11 slides of 6
])
def test_pack_rows_matches_graph_scan(case, window, step, max_windows):
    cap, r = window_slides(window, step)
    units = max_windows + r - 1
    rng = np.random.default_rng(16)
    for _ in range(8):
        stream = _mk_stream(_draw_sizes(rng, case, cap), capacity=_PACK_N)
        unit, col, ok = map(np.asarray, _pack_unit_stepped(stream, cap, units))
        ref_unit, ref_col, ref_ok = map(
            np.asarray, _pack_graph_scan(stream, cap, units))
        np.testing.assert_array_equal(ok, ref_ok)
        # rows of the units that exist (landed or cut at capacity) agree in
        # unit and column; rows past the last unit agree that they are past it
        kept = ref_unit < units
        np.testing.assert_array_equal(unit[kept], ref_unit[kept])
        np.testing.assert_array_equal(col[kept], ref_col[kept])
        np.testing.assert_array_equal(unit >= units, ref_unit >= units)
        if case == "overflow":
            assert (ref_unit >= units).any()


@settings(max_examples=25, deadline=None)
@given(
    sizes=st.lists(st.integers(min_value=1, max_value=6), min_size=1, max_size=12),
    cap=st.integers(min_value=6, max_value=12),
)
def test_count_windows_properties(sizes, cap):
    """Property: every valid row appears exactly once; no window exceeds cap;
    graphs with size <= cap are never split."""
    stream = _mk_stream(sizes)
    w = count_windows(stream, window_capacity=cap, max_windows=len(sizes) + 1)
    v = np.asarray(w.triples.valid)
    g = np.asarray(w.triples.graph)
    assert v.sum(axis=1).max() <= cap
    placed = {}
    for wi in range(v.shape[0]):
        for graph_id in np.unique(g[wi][v[wi]]):
            placed.setdefault(int(graph_id), set()).add(wi)
    for graph_id, windows_used in placed.items():
        assert len(windows_used) == 1
    total_placed = int(v.sum())
    expected = sum(min(s, cap) for s in sizes)
    assert total_placed == expected

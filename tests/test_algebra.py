import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import algebra, pattern
from repro.core.kb import kb_from_triples
from repro.core.pattern import (
    Bindings, CompiledPattern, Slot, compact_index, compact_rows,
    empty_bindings, prefix_count,
)
from repro.core.rdf import PAD_ID, Vocab, make_triples

V = Vocab()
P1 = V.pred("p1")
P2 = V.pred("p2")
A, B, C, D, E = (V.term(t) for t in "abcde")


def mk_bindings(rows, num_vars, cap=None):
    cap = cap or max(len(rows), 1)
    cols = np.zeros((cap, num_vars), np.uint32)
    valid = np.zeros((cap,), bool)
    for i, r in enumerate(rows):
        cols[i] = r
        valid[i] = True
    return Bindings(jnp.asarray(cols), jnp.asarray(valid), jnp.zeros((), bool))


def rows_of(b: Bindings):
    cols, valid = np.asarray(b.cols), np.asarray(b.valid)
    return sorted(tuple(int(x) for x in cols[i]) for i in range(len(valid)) if valid[i])


# --------------------------------------------------------------------------
def test_scan_pattern_consts_and_vars():
    w = make_triples([(A, P1, B, 5, 1), (C, P1, D, 5, 1), (A, P2, E, 6, 2)], capacity=8)
    pat = CompiledPattern(Slot.free(0), Slot.const_(P1), Slot.free(1))
    out = algebra.scan_pattern(w, pat, num_vars=2, out_cap=4)
    assert rows_of(out) == sorted([(A, B), (C, D)])


def test_scan_pattern_repeated_var():
    w = make_triples([(A, P1, A, 0, 1), (A, P1, B, 0, 1)], capacity=4)
    pat = CompiledPattern(Slot.free(0), Slot.const_(P1), Slot.free(0))
    out = algebra.scan_pattern(w, pat, num_vars=1, out_cap=4)
    assert rows_of(out) == [(A,)]


def test_join_natural():
    a = mk_bindings([(A, B, 0), (C, D, 0)], 3)
    b = mk_bindings([(A, 0, E), (A, 0, D)], 3)
    out = algebra.join(a, b, shared=(0,), out_cap=8)
    assert rows_of(out) == sorted([(A, B, E), (A, B, D)])


def test_join_overflow_flag():
    a = mk_bindings([(A, 0)], 2)
    b = mk_bindings([(A, B), (A, C), (A, D)], 2)
    out = algebra.join(a, b, shared=(0,), out_cap=2)
    assert bool(out.overflow)
    assert int(out.count()) == 2                    # prefix-preserving clip


def test_union_and_optional():
    a = mk_bindings([(A, B)], 2, cap=4)
    b = mk_bindings([(C, D)], 2, cap=4)
    u = algebra.union(a, b, out_cap=4)
    assert rows_of(u) == sorted([(A, B), (C, D)])

    left = mk_bindings([(A, 0), (C, 0)], 2, cap=4)
    right = mk_bindings([(A, B)], 2, cap=4)
    o = algebra.optional_join(left, right, shared=(0,), out_cap=8)
    assert rows_of(o) == sorted([(A, B), (C, 0)])   # unmatched keeps PAD


def test_filters():
    n1, n2 = Vocab.number(1.0), Vocab.number(3.0)
    b = mk_bindings([(A, n1), (B, n2)], 2)
    lo = algebra.filter_num(b, var=1, op="lt", value_id=Vocab.number(2.0))
    assert rows_of(lo) == [(A, n1)]
    member = algebra.filter_in(b, var=0, sorted_ids=jnp.asarray(sorted([B, D]), jnp.uint32))
    assert rows_of(member) == [(B, n2)]
    nb = mk_bindings([(A, 0)], 2)
    assert rows_of(algebra.filter_bound(nb, 1)) == []


def test_filter_negative_literals_order_isomorphic():
    n_neg, n_pos = Vocab.number(-5.0), Vocab.number(2.0)
    assert n_neg < Vocab.number(-4.99) < Vocab.number(0.0) < n_pos
    b = mk_bindings([(A, n_neg), (B, n_pos)], 2)
    gt = algebra.filter_num(b, var=1, op="gt", value_id=Vocab.number(-10.0))
    assert rows_of(gt) == sorted([(A, n_neg), (B, n_pos)])
    lt = algebra.filter_num(b, var=1, op="lt", value_id=Vocab.number(0.0))
    assert rows_of(lt) == [(A, n_neg)]


def test_filter_term_equality():
    """=/!= on IRI/string ids: exact id equality, unbound is an error
    (dropped for both operators), numerics are just different terms."""
    n1 = Vocab.number(1.0)
    b = mk_bindings([(A, B), (C, D), (A, 0), (A, n1)], 2, cap=8)
    eq = algebra.filter_num(b, var=1, op="eq", value_id=B)
    assert rows_of(eq) == [(A, B)]
    ne = algebra.filter_num(b, var=1, op="ne", value_id=B)
    assert rows_of(ne) == sorted([(C, D), (A, n1)])   # unbound row dropped


def test_project_and_distinct():
    b = mk_bindings([(A, B), (A, C), (A, B)], 2, cap=4)
    p = algebra.project(b, keep=(0,))
    assert rows_of(p) == [(A, 0)] * 3
    d = algebra.distinct(p)
    assert rows_of(d) == [(A, 0)]
    d2 = algebra.distinct(b)
    assert rows_of(d2) == sorted([(A, B), (A, C)])


# --------------------------------------------------------------------------
KB_ROWS = [(A, P1, B), (A, P1, C), (B, P1, C), (C, P2, D), (B, P2, D)]
KB = kb_from_triples(KB_ROWS, capacity=16)


def brute_kb_join(bind_rows, pat_modes, num_vars):
    """Python oracle for kb_join: pat_modes = ((mode, val), ...) per slot."""
    out = []
    for row in bind_rows:
        for (s, p, o) in KB_ROWS:
            trip = (s, p, o)
            new = list(row)
            ok = True
            for slot_i, (mode, val) in enumerate(pat_modes):
                tv = trip[slot_i]
                if mode == "const":
                    ok &= tv == val
                elif mode == "bound":
                    ok &= tv == row[val]
                else:
                    pass
            if ok:
                for slot_i, (mode, val) in enumerate(pat_modes):
                    if mode == "free":
                        new[val] = trip[slot_i]
                out.append(tuple(new))
    return sorted(out)


@pytest.mark.parametrize("method", ["scan", "probe"])
def test_kb_join_methods_match_oracle(method):
    bind = mk_bindings([(A, 0), (B, 0), (E, 0)], 2, cap=4)
    pat = CompiledPattern(Slot.bound(0), Slot.const_(P1), Slot.free(1))
    out = algebra.kb_join(bind, KB, pat, out_cap=16, method=method)
    oracle = brute_kb_join([(A, 0), (B, 0), (E, 0)],
                           (("bound", 0), ("const", P1), ("free", 1)), 2)
    assert rows_of(out) == oracle


def test_kb_join_probe_po_view():
    bind = mk_bindings([(0, C)], 2, cap=2)
    pat = CompiledPattern(Slot.free(0), Slot.const_(P1), Slot.bound(1))
    out = algebra.kb_join(bind, KB, pat, out_cap=8, method="probe")
    oracle = brute_kb_join([(0, C)], (("free", 0), ("const", P1), ("bound", 1)), 2)
    assert rows_of(out) == oracle


def test_kb_join_probe_overflow():
    rows = [(A, P1, V.term("o%d" % i)) for i in range(12)]
    kb = kb_from_triples(rows, capacity=16)
    bind = mk_bindings([(A, 0)], 2, cap=2)
    pat = CompiledPattern(Slot.bound(0), Slot.const_(P1), Slot.free(1))
    out = algebra.kb_join_probe(bind, kb, pat, out_cap=32, k_max=8)
    assert bool(out.overflow)                   # 12 matches > k_max=8
    assert int(out.count()) == 8


def test_construct_emits_graph_events():
    bind = mk_bindings([(A, B), (C, D)], 2, cap=4)
    out, ovf = algebra.construct(
        bind,
        templates=((("var", 0), ("const", P2), ("var", 1)),
                   (("var", 0), ("const", P1), ("const", E))),
        ts=jnp.uint32(42), out_cap=8,
    )
    assert not bool(ovf)
    v = np.asarray(out.valid)
    assert v.sum() == 4
    assert set(np.asarray(out.ts)[v]) == {42}
    # two triples per binding row share a graph id
    g = np.asarray(out.graph)[v]
    assert len(np.unique(g)) == 2


# --------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(
    a_rows=st.lists(st.tuples(st.integers(1, 5), st.integers(1, 5)), max_size=6),
    b_rows=st.lists(st.tuples(st.integers(1, 5), st.integers(1, 5)), max_size=6),
)
def test_join_matches_bruteforce(a_rows, b_rows):
    """Property: natural join == nested-loop python join (shared col 0)."""
    base = V.term("base")
    a_rows = [(base + x, base + y) for x, y in a_rows]
    b_rows = [(base + x, base + 100 + y) for x, y in b_rows]
    a = mk_bindings([(s, v, 0) for s, v in a_rows], 3, cap=8)
    b = mk_bindings([(s, 0, w) for s, w in b_rows], 3, cap=8)
    out = algebra.join(a, b, shared=(0,), out_cap=64)
    brute = sorted(
        (s1, v, w) for (s1, v) in a_rows for (s2, w) in b_rows if s1 == s2
    )
    assert rows_of(out) == brute


# --------------------------------------------------------------------------
# the row-gathering join / rank-count sort / pairwise distinct against the
# materialize-and-compact and lexsort formulations they replaced
# --------------------------------------------------------------------------

def _ref_pair_table(a, b, shared):
    m = a.valid[:, None] & b.valid[None, :]
    for c in shared:
        m = m & (a.cols[:, None, c] == b.cols[None, :, c])
    merged = jnp.maximum(a.cols[:, None, :], b.cols[None, :, :])
    return m, merged.reshape(a.capacity * b.capacity, a.num_vars)


def _ref_join(a, b, shared, cap):
    m, merged = _ref_pair_table(a, b, shared)
    return compact_rows(merged, m.reshape(-1), cap)


def _ref_optional_join(a, b, shared, cap):
    m, merged = _ref_pair_table(a, b, shared)
    rows = jnp.concatenate([merged, a.cols])
    mask = jnp.concatenate([m.reshape(-1), a.valid & ~jnp.any(m, axis=1)])
    return compact_rows(rows, mask, cap)


def _ref_canonical_order(b, sig_cols):
    keys = tuple(b.cols[:, c] for c in reversed(sig_cols))
    order = jnp.lexsort(keys + ((~b.valid).astype(jnp.uint32),))
    return jnp.take(b.cols, order, axis=0), jnp.take(b.valid, order)


def _ref_distinct(b, cap):
    nv = b.num_vars
    keys = tuple(b.cols[:, c] for c in range(nv - 1, -1, -1))
    order = jnp.lexsort(keys + ((~b.valid).astype(jnp.uint32),))
    sc, sv = jnp.take(b.cols, order, axis=0), jnp.take(b.valid, order)
    prev = jnp.concatenate([jnp.zeros((1, nv), jnp.uint32), sc[:-1]])
    new = jnp.any(sc != prev, axis=1) | (jnp.arange(b.capacity) == 0)
    return compact_rows(b.cols, jnp.take(sv & new, jnp.argsort(order)), cap)


def _random_bindings(rng, cap, nv, spread, wide=False):
    cols = rng.integers(0, spread, (cap, nv)).astype(np.uint32)
    if wide:                      # full uint32 range: ordering past 2^31
        cols[:, 0] = rng.integers(0, 2**32, cap, dtype=np.uint64)
    return Bindings(jnp.asarray(cols), jnp.asarray(rng.random(cap) < 0.7),
                    jnp.zeros((), bool))


def _assert_same(got, want, what):
    np.testing.assert_array_equal(np.asarray(got.cols), np.asarray(want[0]),
                                  err_msg=what)
    np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(want[1]),
                                  err_msg=what)
    if len(want) > 2:
        assert bool(got.overflow) == bool(want[2]), what


# seeds with a fixed shape: pair rows spanning several 128-wide blocks
_JOIN_SHAPES = {6: (300, 200)}


@pytest.mark.parametrize("seed", range(7))
def test_joins_match_materialized_reference(seed):
    rng = np.random.default_rng(seed)
    ca, cb = _JOIN_SHAPES.get(seed) or (int(x) for x in rng.integers(1, 24, 2))
    a = _random_bindings(rng, ca, 4, 4)
    b = _random_bindings(rng, cb, 4, 4)
    for cap in (1, 7, ca * cb + ca + 2):
        for shared in ((), (0,), (0, 1)):
            _assert_same(algebra.join(a, b, shared, cap),
                         _ref_join(a, b, shared, cap), "join")
            _assert_same(algebra.optional_join(a, b, shared, cap),
                         _ref_optional_join(a, b, shared, cap), "optional")


@pytest.mark.parametrize("seed", range(6))
def test_sorting_operators_match_lexsort_reference(seed):
    rng = np.random.default_rng(100 + seed)
    cap, nv = int(rng.integers(1, 40)), int(rng.integers(1, 5))
    b = _random_bindings(rng, cap, nv, 3, wide=seed % 2 == 1)
    for sig in (tuple(range(nv)), tuple(reversed(range(nv))), (nv - 1,)):
        _assert_same(algebra.canonical_order(b, sig),
                     _ref_canonical_order(b, sig), "canonical_order")
    for cap_out in (1, cap // 2 + 1, cap + 3):
        _assert_same(algebra.distinct(b, cap_out), _ref_distinct(b, cap_out),
                     "distinct")


@pytest.mark.parametrize("n", [0, 1, 127, 128, 129, 1000, 70_000])
def test_prefix_count_is_cumsum(n):
    mask = np.random.default_rng(n).random(n) < 0.4
    np.testing.assert_array_equal(np.asarray(prefix_count(jnp.asarray(mask))),
                                  np.cumsum(mask, dtype=np.int32))


# cquery1's sink: bind_cap x scan_cap pairs (join) and bind_cap^2 pairs plus
# the unmatched left rows (optional join)
_JOIN_PAIRS, _OPTIONAL_PAIRS = 4096 * 1024, 4096 * 4096 + 4096


@pytest.mark.parametrize("n, density, out_cap", [
    *((n, 0.4, rel) for n in (0, 1, 127, 128, 129, 70_000)
      for rel in ("below", "at", "above")),
    *((_JOIN_PAIRS, 0.02, rel) for rel in ("below", "at", "above")),
    # the sink's own shapes, which take a middle level
    (_JOIN_PAIRS, 2e-5, 4096), (_OPTIONAL_PAIRS, 2e-5, 4096),
])
def test_compact_index_matches_flatnonzero(n, density, out_cap):
    mask = np.random.default_rng(n).random(n) < density
    if n:
        mask[n // 2] = True
    count = int(mask.sum())
    if isinstance(out_cap, str):
        out_cap = max(count + {"below": -1, "at": 0, "above": 1}[out_cap], 0)
    if out_cap == 4096:
        assert out_cap * -(-n // 128) > pattern._DENSE_TOP
    src, valid, overflow = compact_index(jnp.asarray(mask), out_cap)
    src, valid = np.asarray(src), np.asarray(valid)
    want = np.flatnonzero(mask)[:out_cap]
    np.testing.assert_array_equal(valid, np.arange(out_cap) < len(want))
    np.testing.assert_array_equal(src[:len(want)], want)
    assert ((src >= 0) & (src < max(n, 1))).all()
    assert bool(overflow) == (count > out_cap)


@pytest.mark.parametrize("n", [_JOIN_PAIRS, _OPTIONAL_PAIRS])
def test_compact_index_has_no_loop_at_stream_join_shapes(n):
    """cquery1's join and optional join over 8 windows: a loop here is a
    dependent gather per round over the whole pair table."""
    search = jax.jit(jax.vmap(functools.partial(compact_index, out_cap=4096)))
    hlo = search.lower(jax.ShapeDtypeStruct((8, n), jnp.bool_)).as_text()
    assert "while" not in hlo

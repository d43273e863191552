"""Cost-based KB access: probe-path coverage + fused probe kernel parity.

The acceptance matrix for the ``kb_method="auto"`` work:

* bit-exact parity of the three probe implementations (unfused jnp, fused
  winner-gather twin, fused Pallas kernel in interpret mode) against the
  materialize-and-compact oracle across every anchored slot-mode shape;
* ``k_max`` overflow propagation (probe ranges wider than ``k_max`` flag
  the result), empty KB, duplicate keys spanning one probe range, and the
  composite-key collision re-check (hashed numeric anchors);
* the planner's cost model: per-join method selection, derived ``k_max``,
  greedy selectivity ordering, and scan-vs-probe-vs-auto bit-identity of a
  full Session run;
* Pallas ``interpret=True`` vs ``False`` parity (on a TPU backend only).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algebra
from repro.core import query as Q
from repro.core.engine import KBJoin
from repro.core.kb import (
    collect_kb_stats, kb_from_triples, probe_view,
)
from repro.core.pattern import Bindings, CompiledPattern, Slot
from repro.core.planner import (
    PROBE_K_CAP, _choose_kb_method, compile_query,
)
from repro.core.rdf import NUM_BASE, TERM_BITS, TERM_SPACE, Vocab
from repro.kernels.hash_join import ops as hj_ops
from repro.kernels.hash_join.ref import probe_compact_ref


BASE = 5000


def _world(m=24, n=160, nv=3, seed=0, spread=30, kb_rows=None):
    rng = np.random.default_rng(seed)
    cols = rng.integers(BASE, BASE + spread, size=(m, nv)).astype(np.uint32)
    bvalid = rng.random(m) < 0.9
    if kb_rows is None:
        kb_rows = [
            (int(rng.integers(BASE, BASE + spread)), int(rng.integers(1, 4)),
             int(rng.integers(BASE, BASE + spread)))
            for _ in range(max(0, n - 4))
        ]
    kb = kb_from_triples(kb_rows, capacity=n)
    bind = Bindings(jnp.asarray(cols), jnp.asarray(bvalid),
                    jnp.zeros((), bool))
    return bind, kb


PATTERNS = {
    "s_bound": CompiledPattern(Slot.bound(0), Slot.const_(1), Slot.free(1)),
    "o_bound": CompiledPattern(Slot.free(0), Slot.const_(2), Slot.bound(1)),
    "s_const": CompiledPattern(Slot.const_(BASE + 3), Slot.const_(1),
                               Slot.free(2)),
    "both_bound": CompiledPattern(Slot.bound(0), Slot.const_(2),
                                  Slot.bound(1)),
}


def _assert_probe_paths_match_oracle(bind, kb, pat, out_cap, k_max, bm=None):
    keys, (vs, vp, vo), _, anchor_is_s = probe_view(kb, pat)
    rows, valid, ovf = probe_compact_ref(
        bind.cols, bind.valid, vs, vp, vo, keys, pat, anchor_is_s,
        out_cap, k_max)
    ovf = bool(ovf) or bool(bind.overflow)
    for name, got in (
        ("unfused", algebra.kb_join_probe(bind, kb, pat, out_cap, k_max)),
        ("jnp-twin", hj_ops.probe_compact_jnp(bind, kb, pat, out_cap, k_max)),
        ("pallas", hj_ops.probe_compact(bind, kb, pat, out_cap, k_max,
                                        bm=bm)),
    ):
        np.testing.assert_array_equal(
            np.asarray(got.cols), np.asarray(rows), err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(got.valid), np.asarray(valid), err_msg=name)
        assert bool(got.overflow) == ovf, name


@pytest.mark.parametrize("pat_kind", sorted(PATTERNS))
@pytest.mark.parametrize("m,n,k_max,cap", [
    (16, 64, 4, 32), (24, 160, 8, 64), (50, 300, 8, 128),
])
def test_probe_paths_match_oracle(m, n, k_max, cap, pat_kind):
    bind, kb = _world(m=m, n=n, seed=m + n)
    _assert_probe_paths_match_oracle(bind, kb, PATTERNS[pat_kind], cap, k_max)


def test_probe_non_multiple_block_shape():
    bind, kb = _world(m=33, n=129, seed=7)
    _assert_probe_paths_match_oracle(bind, kb, PATTERNS["s_bound"], 64, 8,
                                     bm=16)


def test_probe_kmax_overflow_propagates():
    """Fan-out past k_max clips the gather and must set the overflow flag
    in every probe path, with all paths still bit-identical."""
    rows = [(BASE, 1, BASE + 100 + i) for i in range(12)]    # fan-out 12
    bind, kb = _world(m=4, n=16, kb_rows=rows)
    bind = bind._replace(cols=jnp.full_like(bind.cols, BASE),
                         valid=jnp.ones_like(bind.valid))
    pat = PATTERNS["s_bound"]
    for got in (
        algebra.kb_join_probe(bind, kb, pat, 64, 8),
        hj_ops.probe_compact_jnp(bind, kb, pat, 64, 8),
        hj_ops.probe_compact(bind, kb, pat, 64, 8),
    ):
        assert bool(got.overflow)
        assert int(np.asarray(got.count())) == 4 * 8   # clipped at k_max
    _assert_probe_paths_match_oracle(bind, kb, pat, 64, 8)
    # k_max covering the fan-out clears the flag and returns every match
    wide = algebra.kb_join_probe(bind, kb, pat, 64, 16)
    assert not bool(wide.overflow)
    assert int(np.asarray(wide.count())) == 4 * 12
    _assert_probe_paths_match_oracle(bind, kb, pat, 64, 16)


def test_probe_empty_kb():
    bind, kb = _world(m=8, n=4, kb_rows=[])
    for pat_kind in sorted(PATTERNS):
        _assert_probe_paths_match_oracle(bind, kb, PATTERNS[pat_kind], 16, 8)
        got = algebra.kb_join_probe(bind, kb, PATTERNS[pat_kind], 16, 8)
        assert int(np.asarray(got.count())) == 0 and not bool(got.overflow)


def test_probe_duplicate_keys_span_range():
    """Duplicate (p, s) rows must all surface from one probe range, in the
    sorted view's row order (bit-identical to the scan)."""
    rows = [(BASE, 1, BASE + 50 + i) for i in range(5)]
    rows += [(BASE + 1, 1, BASE + 90)]
    bind, kb = _world(m=2, n=8, kb_rows=rows)
    bind = bind._replace(cols=jnp.full_like(bind.cols, BASE),
                         valid=jnp.ones_like(bind.valid))
    pat = PATTERNS["s_bound"]
    got = algebra.kb_join_probe(bind, kb, pat, 32, 8)
    want = algebra.kb_join_scan(bind, kb, pat, 32)
    np.testing.assert_array_equal(np.asarray(got.cols), np.asarray(want.cols))
    np.testing.assert_array_equal(np.asarray(got.valid),
                                  np.asarray(want.valid))
    assert int(np.asarray(got.count())) == 2 * 5
    _assert_probe_paths_match_oracle(bind, kb, pat, 32, 8)


def _colliding_numeric(t1: int) -> int:
    """A different numeric id whose composite-key low bits collide with t1."""
    def low(t):
        return (t ^ (t >> TERM_BITS)) & (TERM_SPACE - 1)
    want = low(t1)
    for cand in range(t1 + 1, t1 + (1 << 22)):
        if low(cand) == want:
            return cand
    raise AssertionError("no collision found")


def test_probe_composite_collision_recheck():
    """Numeric anchors hash into the composite key; colliding ids share a
    probe range and must be filtered by the exact re-check."""
    t1 = int(NUM_BASE) + 5
    t2 = _colliding_numeric(t1)
    # KB rows under one predicate, subjects are the colliding numeric ids
    rows = [(t2, 1, BASE + 10), (t2, 1, BASE + 11), (t1, 1, BASE + 12)]
    kb = kb_from_triples(rows, capacity=8)
    cols = np.full((4, 3), t1, dtype=np.uint32)
    bind = Bindings(jnp.asarray(cols), jnp.ones((4,), bool),
                    jnp.zeros((), bool))
    pat = PATTERNS["s_bound"]
    # the shared composite key makes the probe range span t2's rows too
    keys, _, _, _ = probe_view(kb, pat)
    from repro.core.rdf import composite_key
    qk = composite_key(jnp.uint32(1), jnp.uint32(t1))
    width = int(jnp.searchsorted(keys, qk, side="right")
                - jnp.searchsorted(keys, qk, side="left"))
    assert width == 3, "collision did not share a probe range"
    got = algebra.kb_join_probe(bind, kb, pat, 32, 8)
    want = algebra.kb_join_scan(bind, kb, pat, 32)
    np.testing.assert_array_equal(np.asarray(got.cols), np.asarray(want.cols))
    assert int(np.asarray(got.count())) == 4      # only t1's own row matches
    _assert_probe_paths_match_oracle(bind, kb, pat, 32, 8)


def test_probe_interpret_parity():
    """interpret=True (Pallas interpreter) vs interpret=False (compiled)
    must agree bit-exactly; compiled mode needs a TPU, so the pair only runs
    on one.  A compile failure there fails the test."""
    if jax.default_backend() != "tpu":
        pytest.skip("compiled Pallas kernels need a TPU backend")
    bind, kb = _world(m=16, n=64, seed=3)
    pat = PATTERNS["s_bound"]
    want = hj_ops.probe_compact(bind, kb, pat, 32, 8, interpret=True)
    got = hj_ops.probe_compact(bind, kb, pat, 32, 8, interpret=False)
    np.testing.assert_array_equal(np.asarray(got.cols),
                                  np.asarray(want.cols))


# --------------------------------------------------------------------------
# the planner's cost model
# --------------------------------------------------------------------------

def test_collect_kb_stats():
    rows = [(BASE, 1, BASE + 10), (BASE, 1, BASE + 11), (BASE + 1, 1, BASE + 10),
            (BASE + 7, 2, BASE + 10)]
    stats = collect_kb_stats(kb_from_triples(rows, capacity=16))
    assert stats.total_rows == 4
    assert stats.preds[1].rows == 3
    assert stats.preds[1].k_ps == 2        # subject BASE carries two rows
    assert stats.preds[1].k_po == 2        # object BASE+10 carries two rows
    assert stats.preds[2] == (1, 1, 1)
    empty = collect_kb_stats(kb_from_triples([]))
    assert empty.total_rows == 0 and not empty.preds


def _fanout_kb(fanout: int, n_subjects: int = 20):
    rows = [(BASE + s, 1, BASE + 100 + s * fanout + i)
            for s in range(n_subjects) for i in range(fanout)]
    return kb_from_triples(rows)


def test_auto_selects_probe_with_derived_kmax():
    stats = collect_kb_stats(_fanout_kb(10))
    method, k = _choose_kb_method(PATTERNS["s_bound"], stats, 8)
    assert (method, k) == ("probe", 16)    # fan-out 10 rounds up to 16
    # un-anchored pattern: probe ineligible
    free_free = CompiledPattern(Slot.free(0), Slot.const_(1), Slot.free(1))
    assert _choose_kb_method(free_free, stats, 8) == ("scan", 8)
    # fan-out past the cap: fused scan wins
    wide = collect_kb_stats(_fanout_kb(PROBE_K_CAP + 1, n_subjects=4))
    assert _choose_kb_method(PATTERNS["s_bound"], wide, 8) == ("scan", 8)
    # predicate absent from the slice: probe is an instant miss
    method, k = _choose_kb_method(
        CompiledPattern(Slot.bound(0), Slot.const_(3), Slot.free(1)),
        stats, 8)
    assert (method, k) == ("probe", 8)
    # no statistics (kb_method="auto" without a KB): degrade to scan
    assert _choose_kb_method(PATTERNS["s_bound"], None, 8) == ("scan", 8)


def _two_join_query(v: Vocab):
    """Stream anchor + a high-fan-out join listed before a selective one."""
    ps = v.pred("tp:stream")
    p_wide = v.pred("tp:wide")
    p_narrow = v.pred("tp:narrow")
    q = Q.Query(
        name="order",
        where=(
            Q.Pattern(Q.Var("t"), Q.Const(ps), Q.Var("e"), Q.STREAM),
            # listed first, but unanchored until ?x exists: expensive
            Q.Pattern(Q.Var("y"), Q.Const(p_wide), Q.Var("x"), Q.KB),
            # anchored on the stream variable, fan-out 1: cheap
            Q.Pattern(Q.Var("e"), Q.Const(p_narrow), Q.Var("y"), Q.KB),
        ),
        construct=(Q.ConstructTemplate(Q.Var("t"), Q.Const(ps), Q.Var("x")),),
    )
    rows = [(BASE + i, p_narrow, BASE + 100 + i) for i in range(8)]
    rows += [(BASE + 100 + i, p_wide, BASE + 200 + (i % 3)) for i in range(8)]
    return q, kb_from_triples(rows), p_wide, p_narrow


def test_auto_orders_joins_by_selectivity():
    v = Vocab()
    q, kb, p_wide, p_narrow = _two_join_query(v)
    listed = compile_query(q, kb_method="scan")
    auto = compile_query(q, kb_method="auto",
                         kb_stats=collect_kb_stats(kb))
    def join_preds(plan):
        return [s.pat.p.const for s in plan.steps if isinstance(s, KBJoin)]
    assert join_preds(listed) == [p_wide, p_narrow]
    # the anchored narrow join runs first under the cost model, which also
    # anchors ?y and makes the wide join a probe instead of a scan
    assert join_preds(auto) == [p_narrow, p_wide]
    methods = [s.method for s in auto.steps if isinstance(s, KBJoin)]
    assert methods == ["probe", "probe"]


def test_auto_without_kb_runs_stream_only_query():
    """kb_method="auto" on a Session with no kb= must not try to profile a
    KB for stream-only queries (regression: MonolithicRuntime crashed)."""
    from repro.core.rdf import make_triples
    from repro.core.session import ExecutionConfig, Session

    v = Vocab()
    ps = v.pred("nk:p")
    q = Q.Query(
        name="streamonly",
        where=(Q.Pattern(Q.Var("a"), Q.Const(ps), Q.Var("b"), Q.STREAM),),
        construct=(Q.ConstructTemplate(Q.Var("a"), Q.Const(ps),
                                       Q.Var("b")),),
    )
    chunk = make_triples([(BASE + i, ps, BASE + 10 + i, i + 1, i + 1)
                          for i in range(4)], capacity=8)
    for mode in ("monolithic", "single_program"):
        cfg = ExecutionConfig(mode=mode, window_capacity=8, max_windows=2,
                              bind_cap=64, scan_cap=32, out_cap=64,
                              kb_method="auto")
        out, ovf = Session(cfg, vocab=v).register(q).process_chunk(chunk)
        assert not any(ovf.values())
        assert int(np.asarray(out.valid.sum())) == 4


def test_scan_probe_auto_sessions_bit_identical():
    """End-to-end: one query, one stream, three kb_method settings — the
    published streams must be bit-identical with zero overflow."""
    from repro.core.rdf import make_triples
    from repro.core.session import ExecutionConfig, Session

    v = Vocab()
    q, kb, _, _ = _two_join_query(v)
    ps = v.pred("tp:stream")
    chunk = make_triples(
        [(BASE + 200 + i, ps, BASE + (i % 8), i + 1, i + 1)
         for i in range(12)], capacity=32)
    outs = {}
    for method in ("scan", "probe", "auto"):
        cfg = ExecutionConfig(mode="monolithic", window_capacity=32,
                              max_windows=2, bind_cap=256, scan_cap=64,
                              out_cap=256, kb_method=method)
        reg = Session(cfg, vocab=v, kb=kb).register(q)
        out, ovf = reg.process_chunk(chunk)
        assert not any(ovf.values()), (method, ovf)
        outs[method] = out
    for method in ("probe", "auto"):
        for col, ca, cb in zip(outs["scan"]._fields, outs["scan"],
                               outs[method]):
            np.testing.assert_array_equal(
                np.asarray(ca), np.asarray(cb),
                err_msg="%s/%s" % (method, col))

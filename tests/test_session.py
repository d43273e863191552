"""Session/ExecutionConfig facade tests.

The acceptance bar of the API redesign: one Session code path constructs,
feeds and drains all three runtimes, and a cquery1 run produces
**bit-identical** output streams across ``monolithic``, ``single_program``
and ``pipelined`` modes.
"""
import dataclasses
import warnings

import numpy as np
import pytest

from repro.core import paper_queries as PQ
from repro.core.engine import KBJoin
from repro.core.rdf import Vocab, to_host_rows
from repro.core.session import ExecutionConfig, MODES, Session
from repro.data.dbpedia import KBConfig, generate_kb
from repro.data.tweets import (
    TweetSchema, TweetStreamConfig, generate_tweets, stream_chunks,
)

CFG = ExecutionConfig(window_capacity=96, max_windows=4, bind_cap=1024,
                      scan_cap=128, out_cap=1024, intermediate_cap=512)


class SessWorld:
    def __init__(self, num_tweets=36, seed=0):
        self.vocab = Vocab()
        self.kbd = generate_kb(
            self.vocab,
            KBConfig(num_artists=24, num_shows=12, filler_triples=80,
                     seed=seed),
        )
        self.tweets = TweetSchema.create(self.vocab)
        pool = np.concatenate([self.kbd.artist_ids, self.kbd.show_ids])
        rows = generate_tweets(
            self.vocab, self.tweets, pool,
            TweetStreamConfig(num_tweets=num_tweets, mentions_min=2,
                              mentions_max=3, seed=seed),
        )
        self.chunks = list(stream_chunks(rows, 96))

    def session(self, cfg):
        return Session(cfg, vocab=self.vocab, kb=self.kbd.kb)


@pytest.fixture(scope="module")
def sworld():
    w = SessWorld()
    assert len(w.chunks) >= 3
    return w


def assert_bit_identical(outs_a, outs_b, tag=""):
    assert len(outs_a) == len(outs_b)
    for i, (a, b) in enumerate(zip(outs_a, outs_b)):
        for col, ca, cb in zip(a._fields, a, b):
            assert bool(np.all(np.asarray(ca) == np.asarray(cb))), (
                f"{tag} chunk {i} column {col} diverges")


# --------------------------------------------------------------------------
# the acceptance criterion: one Session, three modes, identical streams
# --------------------------------------------------------------------------

def test_cquery1_bit_identical_across_all_modes(sworld):
    outs = {}
    for mode in MODES:
        reg = sworld.session(CFG.replace(mode=mode)).register(PQ.CQUERY1_RQ)
        outs[mode], overflow = reg.run(sworld.chunks)
        assert not {k: v for k, v in overflow.items() if v}, (mode, overflow)
    assert sum(len(to_host_rows(o)) for o in outs["monolithic"]) > 0
    assert_bit_identical(outs["monolithic"], outs["single_program"],
                         "single_program")
    assert_bit_identical(outs["monolithic"], outs["pipelined"], "pipelined")


def test_register_text_and_ast_agree(sworld):
    q = PQ.cquery1(sworld.vocab, sworld.tweets, sworld.kbd.schema)
    from_text = sworld.session(CFG).register(PQ.CQUERY1_RQ)
    from_ast = sworld.session(CFG).register(q)
    assert from_text.query == from_ast.query
    outs_t, _ = from_text.run(sworld.chunks)
    outs_a, _ = from_ast.run(sworld.chunks)
    assert_bit_identical(outs_t, outs_a, "text vs ast")


def test_stream_generator_matches_run(sworld):
    for mode in MODES:
        reg = sworld.session(CFG.replace(mode=mode)).register(PQ.Q15_RQ)
        ref, _ = reg.run(sworld.chunks)
        reg2 = sworld.session(CFG.replace(mode=mode)).register(PQ.Q15_RQ)
        got = list(reg2.stream(sworld.chunks))
        assert_bit_identical(ref, got, f"stream() {mode}")


def test_abandoned_pipelined_stream_leaves_runtime_clean(sworld):
    """Closing a pipelined stream() generator early must drain the chunks
    it left in flight; the next full stream() on the same handle yields
    exactly len(chunks) outputs, identical to a fresh run."""
    reg = sworld.session(CFG.replace(mode="pipelined")).register(PQ.Q15_RQ)
    gen = reg.stream(sworld.chunks)
    next(gen)
    gen.close()                      # abandon mid-stream
    assert reg.runtime._in_flight == 0
    got = list(reg.stream(sworld.chunks))
    assert len(got) == len(sworld.chunks)
    ref, _ = sworld.session(
        CFG.replace(mode="pipelined")).register(PQ.Q15_RQ).run(sworld.chunks)
    assert_bit_identical(ref, got, "post-abandon stream")


def test_overflow_normalized_per_operator(sworld):
    tiny = CFG.replace(out_cap=16, intermediate_cap=8)
    counts = {}
    for mode in MODES:
        reg = sworld.session(tiny.replace(mode=mode)).register(PQ.CQUERY1_RQ)
        _, overflow = reg.run(sworld.chunks)
        assert all(isinstance(v, int) for v in overflow.values())
        counts[mode] = overflow
        assert sum(overflow.values()) > 0, (mode, "expected clipping")
    # decomposed modes agree operator-by-operator
    assert counts["single_program"] == counts["pipelined"]
    # monolithic reports under the query's own name
    assert set(counts["monolithic"]) == {"cquery1"}


# --------------------------------------------------------------------------
# config consolidation + validation
# --------------------------------------------------------------------------

def test_execution_config_validates_mode_and_mesh():
    with pytest.raises(ValueError, match="unknown mode"):
        ExecutionConfig(mode="warp_speed")
    with pytest.raises(ValueError, match="placement"):
        ExecutionConfig(mode="pipelined", mesh=object())


def _spy_pallas(monkeypatch):
    """Record the ``interpret`` every kernel launch asks for, and run the
    launch through the interpreter so the test can check results on CPU."""
    from jax.experimental import pallas as pl

    seen = []
    real = pl.pallas_call

    def spy(*args, interpret=False, **kwargs):
        seen.append(interpret)
        return real(*args, interpret=True, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", spy)
    return seen


def _q16_pallas_run(sworld, window_capacity):
    cfg = CFG.replace(mode="monolithic", use_pallas=True, fuse_compaction=True,
                      window_capacity=window_capacity)
    reg = sworld.session(cfg).register(PQ.Q16_RQ)
    steps = [s for s in reg.runtime.operator.plan.steps
             if isinstance(s, KBJoin)]
    assert steps and all(s.use_pallas for s in steps)
    outs, _ = reg.run(sworld.chunks)
    want, _ = sworld.session(cfg.replace(use_pallas=False)).register(
        PQ.Q16_RQ).run(sworld.chunks)
    assert_bit_identical(outs, want, "pallas vs jnp")


def test_cpu_backend_interprets_kernels(sworld, monkeypatch):
    """On the CPU backend every kernel launch of a Pallas session runs the
    interpreter; the config has no knob that could say otherwise."""
    import jax
    from repro.kernels import resolve_interpret

    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert "interpret" not in {f.name for f in dataclasses.fields(CFG)}
    seen = _spy_pallas(monkeypatch)
    _q16_pallas_run(sworld, window_capacity=97)
    assert seen and all(i is True for i in seen)


def test_tpu_backend_compiles_kernels(sworld, monkeypatch):
    """With the platform query steered to a TPU, the same session asks
    Mosaic to compile every kernel (``interpret=False``)."""
    import jax
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    assert resolve_interpret(True) is True
    seen = _spy_pallas(monkeypatch)
    _q16_pallas_run(sworld, window_capacity=98)
    assert seen and all(i is False for i in seen)


def test_register_duplicate_name_raises_with_both_texts(sworld):
    """Registering a second query under an existing name is almost always a
    caller bug (silently dropping a standing query); the error carries both
    serializations so the collision is diagnosable, and ``replace=True``
    opts into substitution."""
    sess = sworld.session(CFG)
    first = sess.register(PQ.CQUERY1_RQ)
    with pytest.raises(ValueError, match="already registered") as ei:
        sess.register(PQ.CQUERY1_RQ)
    msg = str(ei.value)
    assert "existing:" in msg and "new:" in msg
    assert msg.count(first.text.strip().splitlines()[0]) >= 1
    assert "replace=True" in msg
    assert sess.queries["cquery1"] is first      # registration untouched
    second = sess.register(PQ.CQUERY1_RQ, replace=True)
    assert sess.queries["cquery1"] is second and second is not first
    outs_a, _ = first.run(sworld.chunks[:1])
    outs_b, _ = second.run(sworld.chunks[:1])
    assert_bit_identical(outs_a, outs_b, "replace")


def test_kb_required_for_kb_touching_query(sworld):
    sess = Session(CFG, vocab=sworld.vocab, kb=None)
    with pytest.raises(ValueError, match="no kb= attached"):
        sess.register(PQ.Q15_RQ)


def test_registered_query_text_round_trips(sworld):
    reg = sworld.session(CFG).register(PQ.CQUERY1_RQ)
    from repro.core.sparql import parse_query
    assert parse_query(reg.text, sworld.vocab) == reg.query


def test_window_geometry_reports_step_even_without_range_applied(sworld):
    """A registration carrying ``STEP`` reports it in window_geometry even
    when window_from_query=False leaves the config capacity in force —
    the geometry is what the query *declared*, not only what was applied."""
    reg = sworld.session(CFG).register(PQ.Q15_RQ)       # ... STEP 1]
    assert reg.window_geometry == (CFG.window_capacity, 1)
    # window_from_query=True applies both numbers from the clause
    applied = sworld.session(
        CFG.replace(window_from_query=True)).register(PQ.Q15_RQ)
    assert applied.window_geometry == (1000, 1)
    # a config-level step shows through for STEP-less query text
    stepped = sworld.session(CFG.replace(window_step=32))
    q = PQ.q15(sworld.vocab, sworld.tweets, sworld.kbd.schema)
    assert stepped.register(q).window_geometry == (CFG.window_capacity, 32)


def test_text_round_trips_step_without_effect(sworld):
    """serialize_query(info=) keeps the STEP clause verbatim even when the
    registration did not apply it (window_from_query=False)."""
    from repro.core.sparql import parse_query_info
    reg = sworld.session(CFG).register(PQ.Q15_RQ)
    assert "[RANGE TRIPLES 1000 STEP 1]" in reg.text
    q2, info2 = parse_query_info(reg.text, sworld.vocab)
    assert q2 == reg.query
    assert (info2.window_triples, info2.window_step) == (1000, 1)


# --------------------------------------------------------------------------
# deprecation shims
# --------------------------------------------------------------------------

def test_direct_runtime_construction_warns(sworld):
    from repro.core.pipeline import PipelinedRuntime
    from repro.core.planner import decompose
    from repro.core.runtime import (
        DSCEPRuntime, MonolithicRuntime, RuntimeConfig,
    )

    q = PQ.q15(sworld.vocab, sworld.tweets, sworld.kbd.schema)
    rcfg = RuntimeConfig(window_capacity=96, max_windows=4, bind_cap=512,
                         scan_cap=128, out_cap=512)
    dag = decompose(q, sworld.vocab)
    for ctor in (
        lambda: MonolithicRuntime(q, sworld.kbd.kb, rcfg),
        lambda: DSCEPRuntime(dag, sworld.kbd.kb, sworld.vocab, rcfg),
        lambda: PipelinedRuntime(dag, sworld.kbd.kb, sworld.vocab, rcfg),
    ):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ctor()
        assert any(issubclass(x.category, DeprecationWarning) for x in w)


def test_session_construction_does_not_warn(sworld):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for mode in MODES:
            sworld.session(CFG.replace(mode=mode)).register(PQ.Q15_RQ)
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)], (
        [str(x.message) for x in w])

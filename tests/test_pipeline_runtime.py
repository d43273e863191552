"""pipelined == single_program == monolithic (the dataflow layer).

The streaming runtime cuts the DAG at channel boundaries instead of fusing
it into one XLA program; results must stay **bit-identical** per chunk on
all three paper queries, with >= 2 chunks in flight, including when window
capacities overflow (flags must match too, never be dropped).  All modes
are constructed and driven through the unified Session API.
"""
import jax
import numpy as np
import pytest

from repro.core import paper_queries as PQ
from repro.core.faults import FaultEvent, FaultPlan
from repro.core.rdf import Vocab, to_host_rows
from repro.core.recovery import RecoveryConfig
from repro.core.session import ExecutionConfig, Session
from repro.data.dbpedia import KBConfig, generate_kb
from repro.data.tweets import (
    TweetSchema, TweetStreamConfig, generate_tweets, stream_chunks,
)
from repro.launch.mesh import place_operators

CFG = ExecutionConfig(window_capacity=96, max_windows=4, bind_cap=1024,
                      scan_cap=128, out_cap=1024, intermediate_cap=512)
QUERIES = {"q15": PQ.q15, "q16": PQ.q16, "cquery1": PQ.cquery1}


class PipeWorld:
    """Co-mention stream split into several chunks (multi-chunk pipelining)."""

    def __init__(self, num_tweets=36, seed=0):
        self.vocab = Vocab()
        self.kbd = generate_kb(
            self.vocab,
            KBConfig(num_artists=24, num_shows=12, filler_triples=80,
                     seed=seed),
        )
        self.tweets = TweetSchema.create(self.vocab)
        pool = np.concatenate([self.kbd.artist_ids, self.kbd.show_ids])
        self.rows = generate_tweets(
            self.vocab, self.tweets, pool,
            TweetStreamConfig(num_tweets=num_tweets, mentions_min=2,
                              mentions_max=3, seed=seed),
        )
        self.chunks = list(stream_chunks(self.rows, 96))


@pytest.fixture(scope="module")
def pworld():
    w = PipeWorld()
    assert len(w.chunks) >= 3, "need a multi-chunk stream to pipeline"
    return w


_RT_CACHE = {}


def runtimes(world, qname, cfg=CFG):
    """(single-program, pipelined) registrations for one query, built once."""
    key = (qname, cfg)     # ExecutionConfig is frozen, hence hashable
    if key not in _RT_CACHE:
        q = QUERIES[qname](world.vocab, world.tweets, world.kbd.schema)
        single = Session(cfg.replace(mode="single_program"),
                         vocab=world.vocab, kb=world.kbd.kb).register(q)
        piped = Session(cfg.replace(mode="pipelined"),
                        vocab=world.vocab, kb=world.kbd.kb).register(q)
        _RT_CACHE[key] = (q, single, piped)
    return _RT_CACHE[key]


def assert_bit_identical(outs_a, outs_b, tag=""):
    assert len(outs_a) == len(outs_b)
    for i, (a, b) in enumerate(zip(outs_a, outs_b)):
        for col_a, col_b in zip(a, b):
            assert bool(np.all(np.asarray(col_a) == np.asarray(col_b))), (
                f"{tag} chunk {i} diverges")


@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_pipelined_bit_identical_to_single_program(pworld, qname):
    q, single, piped = runtimes(pworld, qname)
    outs_s, ovf_s = single.run(pworld.chunks)
    outs_p, ovf_p = piped.run(pworld.chunks)
    assert_bit_identical(outs_s, outs_p, qname)
    # per-call overflow deltas match even on a reused (module-scoped) runtime
    assert ovf_p == ovf_s
    # and the paper's claim transitively: pipelined == monolithic result set
    mono = Session(CFG.replace(mode="monolithic"), vocab=pworld.vocab,
                   kb=pworld.kbd.kb).register(q)
    res_m, res_p = [], []
    for c, o in zip(pworld.chunks, outs_p):
        res_m += sorted(set((r[0], r[1], r[2])
                            for r in to_host_rows(mono.process_chunk(c)[0])))
        res_p += sorted(set((r[0], r[1], r[2]) for r in to_host_rows(o)))
    assert len(res_p) > 0
    assert sorted(res_m) == sorted(res_p)


# Q15 with an OPTIONAL stream pattern, which lands in the sink's plan
Q15_OPTIONAL_RQ = PQ.Q15_RQ.replace(
    "  ?tweet schema:mentions ?ent .\n",
    "  ?tweet schema:mentions ?ent .\n"
    "  OPTIONAL { ?tweet schema:likes ?likes . }\n")

# sink format -> (query text, config changes) that selects it
FORMATS = {
    # OPTIONAL is not delta-safe, so no slide tables: the upstream runs
    # delta on the slide view, the sink decodes its triples from windows
    "augmented": (Q15_OPTIONAL_RQ, dict(incremental=True, window_step=48)),
    "window_tables": (PQ.Q15_RQ, {}),
    "slide_tables": (PQ.Q15_RQ, dict(incremental=True, window_step=48)),
}


def sink_format(step):
    if step.split is None:
        return "augmented"
    return "slide_tables" if step.slides else "window_tables"


@pytest.mark.parametrize("fmt", sorted(FORMATS))
def test_dag_step_format_is_one_program_across_runtimes(pworld, fmt):
    """DagStep picks the sink's format from the plan and the config; the
    single program, the pipelined stages and the pipelined runtime's
    degraded fallback then publish the same bytes, and the fallback
    compiles the single program's very chunk step."""
    q, over = FORMATS[fmt]
    cfg = CFG.replace(**over)
    single = Session(cfg.replace(mode="single_program"), vocab=pworld.vocab,
                     kb=pworld.kbd.kb).register(q)
    # chunk 1 crashes the source stage and, with no restart allowed, is
    # evaluated by the fallback; the other chunks run through the channels
    piped = Session(
        cfg.replace(mode="pipelined",
                    faults=FaultPlan((FaultEvent("crash_stage", "source", 1),)),
                    recovery=RecoveryConfig(checkpoint_every=0,
                                            max_restarts=0)),
        vocab=pworld.vocab, kb=pworld.kbd.kb).register(q)
    rt_s, rt_p = single.runtime, piped.runtime
    assert sink_format(rt_s.step) == sink_format(rt_p.step) == fmt
    outs_s, ovf_s = single.run(pworld.chunks)
    outs_p, ovf_p = piped.run(pworld.chunks)
    assert piped.last_stats["recovery"]["degraded_chunks"] == [1]
    assert any(np.asarray(o.valid).any() for o in outs_s)
    assert_bit_identical(outs_s, outs_p, fmt)
    assert ovf_p == ovf_s
    kbs = {n: op.kb for n, op in rt_s.operators.items()}
    envs = {n: op.env for n, op in rt_s.operators.items()}

    def jp(fn):
        return str(jax.make_jaxpr(fn)(pworld.chunks[0], kbs, envs))

    assert jp(rt_p._fallback_step) == jp(rt_s._jit_chunk)


def test_schedule_keeps_two_chunks_in_flight(pworld):
    """Manual drive of the software-pipelined schedule: the sink consumes
    chunk t only after chunk t+1's producers were dispatched."""
    _, single, reg_p = runtimes(pworld, "q15")
    piped = reg_p.runtime
    outs_s, _ = single.run(pworld.chunks)
    outs_p = []
    max_in_flight = 0
    try:
        for c in pworld.chunks:
            if piped._in_flight >= 2:
                outs_p.append(piped.drain())
            piped.feed(c)
            max_in_flight = max(max_in_flight, piped._in_flight)
    finally:
        while piped._in_flight:       # never leave the cached runtime dirty
            outs_p.append(piped.drain())
    jax.block_until_ready(outs_p[-1])
    assert max_in_flight >= 2
    assert_bit_identical(outs_s, outs_p, "q15 manual schedule")


def test_overflow_case_flags_match_and_streams_stay_identical(pworld):
    """Capacities small enough to clip: both runtimes must report the same
    per-operator overflowed-window counts (observable, never dropped) and
    still publish bit-identical (clipped) streams."""
    tiny = ExecutionConfig(window_capacity=96, max_windows=4, bind_cap=1024,
                           scan_cap=128, out_cap=16, intermediate_cap=8)
    q, single, piped = runtimes(pworld, "cquery1", tiny)
    outs_s, ovf_s = single.run(pworld.chunks)
    outs_p, ovf_p = piped.run(pworld.chunks)
    assert sum(ovf_s.values()) > 0, "intended an overflowing configuration"
    assert ovf_p == ovf_s
    assert_bit_identical(outs_s, outs_p, "cquery1 overflow")


def test_channels_drained_and_lossless_after_stream(pworld):
    _, _, reg_p = runtimes(pworld, "q15")
    reg_p.run(pworld.chunks)
    for edge, st in reg_p.runtime.channel_stats().items():
        assert st["size"] == 0, edge
        assert st["overflows"] == 0, edge


def test_driver_misuse_raises_and_feed_queues_past_capacity(pworld):
    _, _, reg_p = runtimes(pworld, "q16")
    piped = reg_p.runtime
    cap = piped.channel_capacity
    with pytest.raises(RuntimeError):
        piped.drain()
    try:
        # feed never raises on a full pipeline: chunks beyond the channel
        # capacity wait in the host-side source queue instead
        for _ in range(cap + 2):
            piped.feed(pworld.chunks[0])
        assert piped._in_flight == cap
        assert len(piped._src_q) == 2
        with pytest.raises(RuntimeError):
            piped.process_stream(pworld.chunks)   # in-flight would leak in
        with pytest.raises(RuntimeError):
            piped.process_chunk(pworld.chunks[1])
        piped.drain()
        # draining freed a slot; the queue backfills it in the same call
        assert piped._in_flight == cap and len(piped._src_q) == 1
    finally:
        while piped._in_flight or piped._src_q:   # never leave it dirty
            piped.drain()


def test_pipeline_requires_double_buffering(pworld):
    q = QUERIES["q15"](pworld.vocab, pworld.tweets, pworld.kbd.schema)
    with pytest.raises(ValueError):
        Session(CFG.replace(mode="pipelined", channel_capacity=1),
                vocab=pworld.vocab, kb=pworld.kbd.kb).register(q)


def test_place_operators_policies():
    names = ["a_kb0", "b_kb1", "agg"]
    devs = ["d0", "d1", "d2"]
    single = place_operators(names, "agg", devices=devs, strategy="single")
    assert single == {"a_kb0": "d0", "b_kb1": "d0", "agg": "d0"}
    rr = place_operators(names, "agg", devices=devs)
    assert rr["agg"] == "d0"                       # sink on the host device
    assert rr["a_kb0"] == "d1" and rr["b_kb1"] == "d2"
    one = place_operators(names, "agg", devices=["d0"])
    assert set(one.values()) == {"d0"}
    with pytest.raises(ValueError):
        place_operators(names, "missing", devices=devs)
    with pytest.raises(ValueError):
        place_operators(names, "agg", devices=[])

"""Observability subsystem tests: tracer, metrics, uniform surfaces, and
the zero-overhead-off guarantee.

The hard acceptance bar of the observability PR is pinned here: with
``trace`` disabled the traced jaxpr of every operator step is *unchanged*
(no stats code executes on the off path at all), and enabling tracing
changes measured programs but never results — traced runs stay
bit-identical to untraced runs in all three execution modes.
"""
import json
import re
import time

import jax
import numpy as np
import pytest

from repro.core import paper_queries as PQ
from repro.core.rdf import Vocab, to_host_rows
from repro.core.session import ExecutionConfig, MODES, Session
from repro.data.dbpedia import KBConfig, generate_kb
from repro.data.tweets import (
    TweetSchema, TweetStreamConfig, generate_tweets, stream_chunks,
)
from repro.obs.metrics import (
    finalize_stats, merge_stats, reduce_stats, saturation, stat_add, stat_max,
)
from repro.obs.report import (
    attach_saturation, bottleneck_stage, format_explain, format_metrics_table,
    format_stage_table, to_json,
)
from repro.obs.trace import (
    LAYERS, TraceConfig, Tracer, in_layer, layer_scope, resolve_trace,
    span_or_null,
)

CFG = ExecutionConfig(window_capacity=96, max_windows=4, bind_cap=1024,
                      scan_cap=128, out_cap=1024, intermediate_cap=512)


class ObsWorld:
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
def oworld():
    w = ObsWorld()
    assert len(w.chunks) >= 3
    return w


def assert_bit_identical(outs_a, outs_b, tag=""):
    assert len(outs_a) == len(outs_b)
    for i, (a, b) in enumerate(zip(outs_a, outs_b)):
        for col, ca, cb in zip(a._fields, a, b):
            assert bool(np.all(np.asarray(ca) == np.asarray(cb))), (
                f"{tag} chunk {i} column {col} diverges")


# --------------------------------------------------------------------------
# tracer units: nesting, compile/steady split, config resolution
# --------------------------------------------------------------------------

def test_span_nesting_builds_paths():
    tr = Tracer(TraceConfig(fence=False))
    with tr.span("chunk"):
        with tr.span("stage:a"):
            pass
        with tr.span("stage:b"):
            with tr.span("probe"):
                pass
    with tr.span("chunk"):
        with tr.span("stage:a"):
            pass
    stats = tr.stats()
    assert set(stats) == {"chunk", "chunk/stage:a", "chunk/stage:b",
                          "chunk/stage:b/probe"}
    assert stats["chunk"]["count"] == 2
    assert stats["chunk/stage:a"]["count"] == 2
    assert stats["chunk/stage:b"]["count"] == 1


def test_first_sample_separated_from_steady():
    tr = Tracer(TraceConfig(fence=False))
    for _ in range(4):
        with tr.span("step"):
            time.sleep(0.001)
    s = tr.stats()["step"]
    assert s["count"] == 4
    assert s["steady"]["count"] == 3
    # the first (compile-inclusive) sample never enters the steady totals
    assert s["steady"]["total_s"] == pytest.approx(
        s["steady"]["mean_s"] * 3)
    assert s["first_s"] > 0.0
    tr.reset()
    assert tr.stats() == {}


def test_span_fence_blocks_on_device_value():
    tr = Tracer(TraceConfig())
    with tr.span("jit") as sp:
        out = sp.fence(jax.jit(lambda x: x * 2)(np.arange(8)))
    assert bool(np.all(np.asarray(out) == np.arange(8) * 2))
    assert tr.stats()["jit"]["count"] == 1


def test_resolve_trace_normalization():
    assert resolve_trace(None) is None
    assert resolve_trace(False) is None
    assert resolve_trace(True) == TraceConfig()
    cfg = TraceConfig(spans=False, metrics=True)
    assert resolve_trace(cfg) is cfg
    with pytest.raises(TypeError):
        resolve_trace("yes")


def test_spans_off_and_null_span_are_noop():
    tr = Tracer(TraceConfig(spans=False))
    with tr.span("ignored") as sp:
        assert sp.fence(123) == 123
    assert tr.stats() == {}
    with span_or_null(None, "also-ignored") as sp:
        assert sp.fence("v") == "v"


def test_spans_are_profiler_annotations(tmp_path):
    """Every span is a jax.profiler TraceAnnotation: a running profiler
    trace holds it on the host plane, with the span's metadata."""
    from jax.profiler import ProfileData

    tr = Tracer(TraceConfig(fence=False))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("dscep.chunk", seq=7):
            with tr.span("dscep.dispatch", seq=7):
                jax.block_until_ready(jax.numpy.ones(4))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dscep."):
                    found[ev.name] = (ev.start_ns, ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"dscep.chunk", "dscep.dispatch"}
    (c0, cdur, cmeta), (d0, ddur, dmeta) = (found["dscep.chunk"],
                                            found["dscep.dispatch"])
    assert cmeta == dmeta == {"seq": 7}
    assert c0 <= d0 and d0 + ddur <= c0 + cdur


def test_layer_scope_takes_only_engine_layers():
    with layer_scope("kb_join"):
        pass
    with pytest.raises(ValueError, match="unknown engine layer"):
        layer_scope("kb")
    with pytest.raises(ValueError, match="unknown engine layer"):
        in_layer("joins")


# --------------------------------------------------------------------------
# metric units: merge conventions encoded in the key names
# --------------------------------------------------------------------------

def test_stat_helpers_are_none_safe():
    stat_max(None, "hw_bind", 5)
    stat_add(None, "n_windows", 1)
    stats = {}
    stat_max(stats, "hw_bind", np.int32(3))
    stat_max(stats, "hw_bind", np.int32(7))
    stat_max(stats, "hw_bind", np.int32(2))
    stat_add(stats, "n_windows", np.int32(2))
    stat_add(stats, "n_windows", np.int32(3))
    assert int(stats["hw_bind"]) == 7
    assert int(stats["n_windows"]) == 5


def test_reduce_and_merge_follow_hw_vs_n_convention():
    # vmapped per-window stats: hw_* gauges reduce by max, n_* counters by sum
    per_window = {
        "hw_bind": np.array([3, 9, 4]),
        "n_retract": np.array([1, 0, 2]),
    }
    red = reduce_stats(per_window)
    assert int(red["hw_bind"]) == 9
    assert int(red["n_retract"]) == 3
    acc = {}
    merge_stats(acc, {"hw_bind": np.int32(5), "n_windows": np.int32(2)})
    merge_stats(acc, {"hw_bind": np.int32(3), "n_windows": np.int32(4)})
    fin = finalize_stats(acc)
    assert fin == {"hw_bind": 5, "n_windows": 6}
    assert all(isinstance(v, int) for v in fin.values())


def test_saturation_vs_caps():
    sat = saturation({"hw_bind": 512, "hw_probe_k": 8, "n_windows": 7},
                     {"bind_cap": 1024, "k_max": 8})
    assert sat["hw_bind"] == pytest.approx(0.5)
    assert sat["hw_probe_k"] == pytest.approx(1.0)
    assert "n_windows" not in sat      # counters have no capacity to saturate


# --------------------------------------------------------------------------
# report units
# --------------------------------------------------------------------------

def _span(first, steady):
    return {
        "count": 1 + len(steady), "first_s": first,
        "steady": {"count": len(steady), "total_s": sum(steady),
                   "mean_s": sum(steady) / len(steady) if steady else 0.0,
                   "min_s": min(steady) if steady else 0.0,
                   "max_s": max(steady) if steady else 0.0},
    }


def test_bottleneck_stage_prefix_and_compile_fallback():
    spans = {
        "chunk": _span(9.0, [5.0, 5.0]),            # enclosing span, excluded
        "chunk/stage:a": _span(8.0, [0.5, 0.4]),
        "chunk/stage:b": _span(1.0, [2.0, 2.1]),
    }
    # prefix matches the *last* path segment, skipping the chunk wrapper
    assert bottleneck_stage(spans, prefix="stage") == "chunk/stage:b"
    assert bottleneck_stage(spans) == "chunk"
    # single-pass traces (no steady samples) compete on the first sample
    only_first = {"chunk/stage:a": _span(8.0, []),
                  "chunk/stage:b": _span(1.0, [])}
    assert bottleneck_stage(only_first, prefix="stage") == "chunk/stage:a"
    assert bottleneck_stage({}, prefix="stage") is None


def test_tables_render():
    spans = {"stage:a": _span(0.5, [0.01, 0.02])}
    ops = {"op0": attach_saturation({"hw_bind": 10, "n_windows": 2},
                                    {"bind_cap": 100})}
    assert "stage:a" in format_stage_table(spans)
    table = format_metrics_table(ops)
    assert "hw_bind" in table and "10%" in table


# --------------------------------------------------------------------------
# uniform runtime surfaces: identical shape in all three modes
# --------------------------------------------------------------------------

def test_last_stats_uniform_across_modes_trace_off(oworld):
    for mode in MODES:
        reg = oworld.session(CFG.replace(mode=mode)).register(PQ.CQUERY1_RQ)
        reg.run(oworld.chunks)
        stats = reg.last_stats
        assert set(stats) == {"query", "mode", "overflow_totals", "channels",
                              "operators", "spans", "recovery", "degraded"}
        assert stats["mode"] == mode
        assert stats["recovery"]["enabled"] is False
        assert stats["degraded"] is False
        assert stats["operators"] == {}    # metrics need trace= enabled
        assert stats["spans"] == {}
        assert all(v == 0 for v in stats["overflow_totals"].values())
        if mode == "pipelined":
            assert stats["channels"]           # edges materialize here only
            for entry in stats["channels"].values():
                assert {"pushes", "pops", "depth_hw"} <= set(entry)
        else:
            assert stats["channels"] == {}
        json.dumps(stats)                  # surface is always serializable


def test_traced_metrics_agree_across_decomposed_modes(oworld):
    metrics = {}
    for mode in ("single_program", "pipelined"):
        reg = oworld.session(
            CFG.replace(mode=mode, trace=True)).register(PQ.CQUERY1_RQ)
        reg.run(oworld.chunks)
        stats = reg.last_stats
        assert stats["operators"], mode
        # pipelined mode adds each operator's channel traffic
        extra = {"channel"} if mode == "pipelined" else set()
        for entry in stats["operators"].values():
            assert {"counters", "caps", "saturation"} | extra == set(entry)
        metrics[mode] = {
            op: entry["counters"]
            for op, entry in stats["operators"].items()
        }
        assert stats["spans"], mode        # spans recorded too
    # both decomposed modes run the same per-operator programs over the same
    # stream — the device-side counters must agree exactly
    assert metrics["single_program"] == metrics["pipelined"]


def test_monolithic_hw_out_matches_published_rows(oworld):
    reg = oworld.session(
        CFG.replace(mode="monolithic", trace=True)).register(PQ.CQUERY1_RQ)
    outs, _ = reg.run(oworld.chunks)
    counters = reg.last_stats["operators"][reg.query.name]["counters"]
    # hand-computed cross-check: the constructed-output high-water of the
    # single monolithic operator is exactly the largest published chunk
    hand_hw_out = max(len(to_host_rows(o)) for o in outs)
    assert counters["hw_out"] == hand_hw_out
    assert counters["n_windows"] >= len(oworld.chunks)
    assert 0 < counters["hw_bind"] <= CFG.bind_cap
    assert 0 < counters["hw_scan"] <= CFG.scan_cap


def test_pipelined_stage_spans_cover_every_operator(oworld):
    reg = oworld.session(
        CFG.replace(mode="pipelined", trace=True)).register(PQ.CQUERY1_RQ)
    reg.run(oworld.chunks)
    reg.run(oworld.chunks)                 # second pass fills steady samples
    spans = reg.last_stats["spans"]
    rt = reg.runtime

    def named(prefix):
        return {p: s for p, s in spans.items()
                if p.split("/")[-1].startswith(prefix + "[")}

    stages = named("dscep.stage")
    assert {p.split("/")[-1] for p in stages} == {
        "dscep.stage[%s]" % name for name in ["source", *reg.operators]}
    for path, s in stages.items():
        assert s["count"] > 0 and s["steady"]["count"] > 0, path
        # one profiler name, the operator as metadata
        assert "dscep.stage[%s]" % s["meta"]["operator"] == \
            path.split("/")[-1]
    # the sink's step runs inside its drain; the others stand alone
    assert "dscep.drain/dscep.stage[%s]" % rt.final in stages
    assert spans["dscep.drain"]["count"] == 2 * len(oworld.chunks)
    transfers = {p.split("/")[-1] for p in named("dscep.transfer")}
    assert transfers == {"dscep.transfer[%s]" % e for e in
                         rt._edges() + ["source->%s" % n
                                        for n in rt.upstream]}
    assert bottleneck_stage(spans, prefix="dscep.stage") in stages


def test_tracer_key_splits_stats_not_the_span_name(monkeypatch):
    seen = []

    class Ann:
        def __init__(self, name, **meta):
            seen.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    tr = Tracer(TraceConfig(fence=False))
    for op in ("a", "b", "a"):
        with span_or_null(tr, "dscep.stage", key=op, operator=op):
            pass
    stats = tr.stats()
    assert set(stats) == {"dscep.stage[a]", "dscep.stage[b]"}
    assert stats["dscep.stage[a]"]["count"] == 2
    assert stats["dscep.stage[b]"]["meta"] == {"operator": "b"}
    assert [n for n, _ in seen] == ["dscep.stage"] * 3
    assert seen[1][1] == {"operator": "b"}


def test_pipelined_channel_counters_are_payload_bytes(oworld):
    """On the metrics path every operator's entry holds its channel
    traffic: the bytes of the payloads it receives and publishes per chunk,
    computed here from the configuration's shapes."""
    reg = oworld.session(CFG.replace(
        mode="pipelined", trace=TraceConfig(spans=False, metrics=True,
                                            fence=False))).register(
        PQ.CQUERY1_RQ)
    reg.run(oworld.chunks)
    rt = reg.runtime
    ops = reg.last_stats["operators"]
    assert set(ops) == set(reg.operators)
    W, C = CFG.max_windows, CFG.window_capacity
    row = 5 * 4 + 1                        # s, p, o, ts, graph u32; valid
    windows = W * C * row + W              # + window_valid
    pubs = {}
    for name in rt.upstream:
        spec = rt._split.pub[name]
        # table u32[W, rows, k], its row mask, and the overflow flags
        pubs[name] = W * spec.rows_cap * (4 * len(spec.cols) + 1) + W
        assert ops[name]["channel"] == {
            "in_bytes_per_chunk": windows, "out_bytes_per_chunk": pubs[name],
            "cross_device": 0,             # one CPU device
            "depth_hw": reg.channel_stats()[
                "%s->%s" % (name, rt.final)]["depth_hw"]}
    sink = ops[rt.final]["channel"]
    assert sink["in_bytes_per_chunk"] == windows + sum(pubs.values())
    assert sink["out_bytes_per_chunk"] == CFG.out_stream_cap * row
    assert sink["cross_device"] == 0 and sink["depth_hw"] >= 2
    assert "saturation" in ops[rt.final]


def test_pipelined_off_path_has_no_spans_counters_or_new_programs(oworld):
    """Tracing off: no span, no channel counter, and every stage traces
    the program a spans-and-metrics build traces on its plain path."""
    off = oworld.session(CFG.replace(mode="pipelined")).register(
        PQ.CQUERY1_RQ)
    on = oworld.session(CFG.replace(mode="pipelined", trace=True)).register(
        PQ.CQUERY1_RQ)
    off.run(oworld.chunks)
    on.run(oworld.chunks)
    stats = off.last_stats
    assert stats["spans"] == {} and stats["operators"] == {}
    assert off.runtime._traffic == {}
    assert all("channel" in e for e in on.last_stats["operators"].values())
    a, b = off.runtime, on.runtime
    chunk = oworld.chunks[0]

    def jp(fn, *args):
        return str(jax.make_jaxpr(fn)(*args))

    assert jp(a._windows_impl, chunk) == jp(b._windows_impl, chunk)
    _, shape = jax.eval_shape(a._windows_impl, chunk)
    payload = jax.tree.map(lambda x: jax.numpy.zeros(x.shape, x.dtype), shape)
    for name in a.upstream:
        oa, ob = a.operators[name], b.operators[name]
        assert jp(a._op_step[name], payload, oa.kb, oa.env) == \
            jp(b._op_step[name], payload, ob.kb, ob.env), name
    fa, fb = a.operators[a.final], b.operators[b.final]
    assert jp(a._sink_step, a._agg_win_ch, a._out_ch, fa.kb, fa.env) == \
        jp(b._sink_step, b._agg_win_ch, b._out_ch, fb.kb, fb.env)


# --------------------------------------------------------------------------
# the hard constraint: tracing off = zero overhead, tracing on = same bits
# --------------------------------------------------------------------------

def test_off_path_jaxpr_unchanged_and_stats_free(oworld, monkeypatch):
    """With tracing off the operator step must trace the *same program* as a
    build with no observability at all: no stats helper runs during trace
    (proved by poisoning them), and the stats twin traces a different
    program (the metrics really are new ops, not free)."""
    reg = oworld.session(CFG.replace(mode="single_program")).register(
        PQ.CQUERY1_RQ)
    op = next(iter(reg.operators.values()))
    args = (tuple(oworld.chunks[:1]), op.kb, op.env)
    jaxpr_off = jax.make_jaxpr(op._process_impl)(*args)

    def poisoned(*a, **k):
        raise AssertionError("stats helper executed on the trace-off path")

    import repro.core.algebra as algebra
    import repro.core.engine as engine
    import repro.obs.metrics as metrics
    for mod in (engine, algebra, metrics):
        for name in ("stat_max", "stat_add", "reduce_stats"):
            if hasattr(mod, name):
                monkeypatch.setattr(mod, name, poisoned)
    jaxpr_off_poisoned = jax.make_jaxpr(op._process_impl)(*args)
    assert str(jaxpr_off) == str(jaxpr_off_poisoned)
    monkeypatch.undo()

    import functools
    jaxpr_on = jax.make_jaxpr(
        functools.partial(op._process_impl, with_stats=True))(*args)
    assert str(jaxpr_on) != str(jaxpr_off)


def test_traced_outputs_bit_identical_to_untraced(oworld):
    for mode in MODES:
        off = oworld.session(CFG.replace(mode=mode)).register(PQ.CQUERY1_RQ)
        on = oworld.session(
            CFG.replace(mode=mode, trace=True)).register(PQ.CQUERY1_RQ)
        outs_off, ovf_off = off.run(oworld.chunks)
        outs_on, ovf_on = on.run(oworld.chunks)
        assert_bit_identical(outs_off, outs_on, mode)
        assert ovf_off == ovf_on


# --------------------------------------------------------------------------
# the program's own spans and scopes
# --------------------------------------------------------------------------

SPANS_ONLY = TraceConfig(spans=True, metrics=False, fence=False)
REGISTER_STEPS = ("dscep.prune", "dscep.closures", "dscep.kb_stats",
                  "dscep.plan", "dscep.env", "dscep.split_sink")


@pytest.mark.parametrize("mode", MODES)
def test_registration_spans_nest_under_register(oworld, mode):
    cfg = CFG.replace(mode=mode, kb_method="auto")
    reg = oworld.session(cfg.replace(trace=SPANS_ONLY)).register(
        PQ.CQUERY1_RQ)
    spans = reg.last_stats["spans"]
    top = spans["dscep.register"]
    assert top["count"] == 1 and top["meta"] == {"query": reg.query.name}
    children = {p.split("/", 1)[1]: s for p, s in spans.items()
                if p.startswith("dscep.register/")}
    ops = reg.operators
    if mode == "monolithic":
        expect = {"dscep.closures", "dscep.kb_stats", "dscep.plan",
                  "dscep.env"}
    else:
        expect = set(REGISTER_STEPS)
    assert set(children) == expect
    kb_ops = [n for n, op in ops.items() if op.kb is not None]
    for name, s in children.items():
        n = 1 if name == "dscep.split_sink" else (
            len(kb_ops) if name in ("dscep.prune", "dscep.closures",
                                    "dscep.kb_stats") and mode != "monolithic"
            else len(ops))
        assert s["count"] == n, name
        total = s["first_s"] + s["steady"]["total_s"]
        assert 0 <= total <= top["first_s"], name
        if name != "dscep.split_sink":
            assert s["meta"]["operator"] in ops
    # the same registration untraced: no tracer, no span
    off = oworld.session(cfg).register(PQ.CQUERY1_RQ, name="untraced")
    assert off.tracer is None
    assert off.last_stats["spans"] == {}


@pytest.mark.parametrize("mode", ("monolithic", "single_program"))
def test_chunk_spans_split_dispatch_and_account(oworld, mode):
    reg = oworld.session(CFG.replace(mode=mode, trace=SPANS_ONLY)).register(
        PQ.CQUERY1_RQ)
    reg.run(oworld.chunks)
    spans = reg.last_stats["spans"]
    n = len(oworld.chunks)
    chunk = spans["dscep.chunk"]
    assert chunk["count"] == n
    assert chunk["meta"] == {"seq": n - 1, "mode": mode}
    for child in ("dispatch", "account"):
        s = spans["dscep.chunk/dscep.%s" % child]
        assert s["count"] == n and s["meta"] == {"seq": n - 1}
        assert s["steady"]["total_s"] <= chunk["steady"]["total_s"]


# Q15 and Q16 on one mentioned entity, in 75%-overlap sliding windows
Q15Q16_RQ = """\
REGISTER QUERY q15q16 AS
PREFIX schema: <urn:dscep:schema>
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX rdfs: <http://www.w3.org/2000/01/rdf-schema#>
PREFIX dbo: <http://dbpedia.org/ontology/>
PREFIX out: <urn:dscep:out>
CONSTRUCT { ?tweet out:artistCode ?cc . }
FROM STREAM <stream> [RANGE TRIPLES 96 STEP 24]
FROM <kb>
WHERE {
  ?tweet schema:mentions ?ent .
  GRAPH <kb> {
    ?ent rdf:type/rdfs:subClassOf* dbo:MusicalArtist .
    ?ent dbo:birthPlace/dbo:country/dbo:countryCode ?cc .
  }
}
"""


def _hlo_layers(text):
    """The ``dscep.<layer>`` scopes named in a compiled program's op_names."""
    return set(re.findall(r'op_name="[^"]*?dscep\.(\w+)', text))


@pytest.mark.parametrize("query,cfg,expect", [
    (PQ.CQUERY1_RQ, CFG,
     {"pack", "scan", "stream_join", "kb_join", "filter", "finalize",
      "publish"}),
    (Q15Q16_RQ, CFG.replace(window_step=24, incremental=True),
     {"pack", "scan", "stream_join", "kb_join", "delta", "finalize",
      "publish"}),
], ids=["cquery1", "q15q16"])
def test_chunk_program_names_every_layer_it_runs(oworld, query, cfg, expect):
    """The single-program chunk step, compiled: each engine layer the plan
    runs shows as a dscep.<layer> scope in the ops' op_name metadata."""
    reg = oworld.session(cfg.replace(mode="single_program")).register(query)
    rt = reg.runtime
    kbs = {n: op.kb for n, op in rt.operators.items()}
    envs = {n: op.env for n, op in rt.operators.items()}
    text = rt._jit_chunk.lower(oworld.chunks[0], kbs, envs).compile().as_text()
    found = _hlo_layers(text)
    assert expect <= found <= set(LAYERS)


# --------------------------------------------------------------------------
# explain
# --------------------------------------------------------------------------

def test_explain_reports_planner_decisions(oworld):
    reg = oworld.session(
        CFG.replace(mode="single_program", kb_method="auto")).register(
        PQ.CQUERY1_RQ)
    art = reg.explain()
    assert art["query"] == reg.query.name
    assert art["kb_method"] == "auto"
    assert set(art["operators"]) == set(reg.operators)
    saw_kb_join = False
    for name, op_art in art["operators"].items():
        assert {"scan_cap", "bind_cap", "out_cap", "k_max"} <= set(
            op_art["caps"])
        assert isinstance(op_art["delta_capable"], bool)
        for step in op_art["steps"]:
            if step["step"] == "KBJoin":
                saw_kb_join = True
                assert step["method"] in ("scan", "probe")
                assert step.get("est_rows") is not None
                if step["method"] == "probe":
                    assert step["k_max"] >= 1
    assert saw_kb_join
    rendered = format_explain(art)
    assert reg.query.name in rendered and "KBJoin" in rendered
    json.dumps(art)


def test_to_json_bundles_stats_and_explain(oworld):
    reg = oworld.session(
        CFG.replace(mode="monolithic", trace=True)).register(PQ.CQUERY1_RQ)
    reg.run(oworld.chunks[:1])
    payload = to_json(reg.last_stats, explain=reg.explain())
    assert payload["query"] == reg.query.name
    assert "explain" in payload and "spans" in payload
    json.dumps(payload)

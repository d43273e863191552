"""Observability subsystem tests: tracer, metrics, uniform surfaces, and
the zero-overhead-off guarantee.

The hard acceptance bar of the observability PR is pinned here: with
``trace`` disabled the traced jaxpr of every operator step is *unchanged*
(no stats code executes on the off path at all), and enabling tracing
changes measured programs but never results — traced runs stay
bit-identical to untraced runs in all three execution modes.
"""
import json
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
from repro.obs.trace import TraceConfig, Tracer, resolve_trace, span_or_null

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


def test_profiler_that_cannot_start_raises(tmp_path, monkeypatch):
    """No profiler_dir: nothing to start.  A requested trace that fails to
    start raises instead of letting the run go on untraced."""
    assert Tracer(TraceConfig()).start_profiler() is False

    def refuse(_dir):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    tr = Tracer(TraceConfig(profiler_dir=str(tmp_path)))
    with pytest.raises(RuntimeError, match="profiler busy"):
        tr.start_profiler()
    tr.stop_profiler()                      # nothing was started: a no-op


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
        for entry in stats["operators"].values():
            assert {"counters", "caps", "saturation"} == set(entry)
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
    stages = {p.split("/")[-1] for p in spans
              if p.split("/")[-1].startswith("stage:")}
    expected = {"stage:source"} | {
        "stage:%s" % name for name in reg.operators}
    assert stages == expected
    for path, s in spans.items():
        if path.split("/")[-1].startswith("stage:"):
            assert s["count"] > 0 and s["steady"]["count"] > 0, path
    assert bottleneck_stage(spans, prefix="stage") in {
        p for p in spans if p.split("/")[-1].startswith("stage:")}


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

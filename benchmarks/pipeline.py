"""Sustained-throughput benchmark for the dataflow runtime -> BENCH_pipeline.json.

Compares the three ``ExecutionConfig.mode`` settings of the same CQuery1
over the same multi-chunk stream, all driven through one ``Session`` API:

* ``monolithic`` — one operator, full KB, chunk-at-a-time (paper Table 2
  baseline);
* ``single_program`` — the whole DAG fused into one XLA program, chunks
  pushed synchronously one at a time;
* ``pipelined`` — per-operator jitted steps over bounded device channels,
  software-pipelined schedule with up to ``channel_capacity`` chunks in
  flight, sink-only blocking.

Asserts (a) zero overflowed windows in every mode — capacity overruns would
silently clip results, so the satellite observability hook is exercised here
— (b) the pipelined final stream is **bit-identical** to the single-program
runtime per chunk, and (c) the pipelined schedule actually overlapped:
``depth_hw >= 2`` chunks in flight and (given >= 2 devices) the round_robin
placement spread operators over >= 2 distinct devices.

    PYTHONPATH=src python -m benchmarks.pipeline            # full shapes
    PYTHONPATH=src python -m benchmarks.pipeline --smoke    # CI tiny shapes
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

import jax
import numpy as np

from repro.core import paper_queries as PQ
from repro.core.session import ExecutionConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import ensure_host_devices

from .common import build_world, format_table, make_session

CHANNEL_CAPACITY = 4

# second workload: the expanded frontend surface — SELECT projection, a
# variable-length closure path (compiled through the fused closure kernel
# into one pair-relation join) and a boolean FILTER tree.  The shipped
# example file is the single source of truth so the benchmarked query can
# never drift from what a reader reproduces.
ARTIST_CLASSES_RQ_PATH = os.path.join(
    os.path.dirname(__file__), "..", "examples", "queries",
    "artist_classes.rq")


def _throughput(run_pass, num_chunks: int, iters: int) -> dict:
    """Median sustained chunks/sec of ``run_pass()``, with the first
    (compile-inclusive) pass timed separately as ``compile_s`` so the
    one-time cost the steady numbers exclude is still on record."""
    t0 = time.perf_counter()
    jax.block_until_ready(run_pass())          # warmup / compile
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run_pass())
        times.append(time.perf_counter() - t0)
    med = float(np.median(times))
    return {
        "median_s": med,
        "min_s": float(np.min(times)),
        "chunks_per_s": num_chunks / med,
        "compile_s": float(compile_s),
        "iters": iters,
    }


def _stage_breakdown(world, base, q, chunks, query: str,
                     passes: int = 2) -> dict:
    """Per-stage trace of the same workload on *separate* traced sessions.

    Tracing fences every stage boundary (``block_until_ready`` per span), so
    the headline throughput sessions above stay unfenced and these sessions
    exist only to answer *where* the time goes.  Two passes: the first is
    compile-inclusive (reported per span as ``first_s``), the second feeds
    the steady aggregates.
    """
    from repro.obs.report import bottleneck_stage, format_stage_table, to_json

    breakdown = {}
    for mode in ("monolithic", "single_program", "pipelined"):
        reg = make_session(world, base.replace(mode=mode, trace=True)).register(q)
        for _ in range(passes):
            reg.run(chunks)
        stats = reg.last_stats
        prefix = "stage" if mode == "pipelined" else "chunk"
        breakdown[mode] = {
            "spans": stats["spans"],
            "operators": stats["operators"],
            "channels": stats["channels"],
            "bottleneck_stage": bottleneck_stage(stats["spans"], prefix=prefix),
        }
        if mode == "pipelined":
            print(format_stage_table(
                stats["spans"],
                title="%s pipelined per-stage latency (traced sessions)" % query))
            print("[bench_pipeline] pipelined bottleneck stage: %s"
                  % breakdown[mode]["bottleneck_stage"])
        if mode == "pipelined":
            # full trace artifact (spans + metrics + channels + explain)
            trace_payload = to_json(stats, explain=reg.explain())
            path = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_trace_%s.json" % query)
            with open(path, "w") as f:
                json.dump(trace_payload, f, indent=2)
            print(f"[bench_pipeline] wrote {os.path.normpath(path)}")
    return breakdown


def _recovery_overhead(world, base, q, chunks, outs_single, iters,
                       plain_median_s: float) -> dict:
    """Cost of resilience: checkpoint-cadence overhead + time-to-recover.

    Sweeps ``RecoveryConfig.checkpoint_every`` over {0, 2, 8} on the same
    pipelined workload (0 = resilient bookkeeping but no mid-stream
    snapshots) and reports each cadence's throughput against the plain
    (recovery=None) pipelined baseline measured above.  Then injects one
    ``crash_stage`` on a mid-stream chunk and reports time-to-recover as
    the median faulted-pass minus median clean-pass wall time on the same
    warmed runtime — both steady-state, so the difference isolates
    checkpoint restore + replay.  Every pass is gated bit-exact against
    the single-program stream.
    """
    from repro.core.faults import FaultEvent, FaultInjector, FaultPlan
    from repro.core.recovery import RecoveryConfig

    def check(outs):
        assert len(outs) == len(outs_single)
        for i, (a, b) in enumerate(zip(outs_single, outs)):
            for col_a, col_b in zip(a, b):
                assert bool(np.all(np.asarray(col_a) == np.asarray(col_b))), (
                    "resilient chunk %d diverges from single-program" % i)

    cadence = {}
    for every in (0, 2, 8):
        reg = make_session(world, base.replace(
            mode="pipelined",
            recovery=RecoveryConfig(checkpoint_every=every))).register(q)
        outs, _ = reg.run(chunks)          # compile pass + correctness gate
        check(outs)
        ck_before = reg.last_stats["recovery"]["checkpoints"]
        r = _throughput(lambda s=reg: s.run(chunks)[0], len(chunks), iters)
        rec = reg.last_stats["recovery"]
        cadence[str(every)] = {
            **r,
            "overhead_vs_plain_pipelined":
                r["median_s"] / plain_median_s - 1.0,
            "checkpoints_per_pass":
                (rec["checkpoints"] - ck_before) / (iters + 1),
            "checkpoint_bytes": rec["checkpoint_bytes"],
        }
    rows = [
        [every, f"{r['median_s'] * 1e3:.1f} ms",
         f"{r['overhead_vs_plain_pipelined'] * 100:+.1f}%",
         f"{r['checkpoints_per_pass']:.1f}",
         f"{r['checkpoint_bytes'] / 1024:.0f} KiB"]
        for every, r in cadence.items()
    ]
    print(format_table(
        "resilient pipelined: checkpoint cadence overhead",
        ["checkpoint_every", "stream pass (median)", "vs plain piped",
         "ckpts/pass", "ckpt size"], rows))

    # -- time-to-recover from one injected mid-stream crash ------------------
    crash_chunk = max(1, len(chunks) // 2)
    plan = FaultPlan((FaultEvent("crash_stage", "source", crash_chunk),))
    reg = make_session(world, base.replace(
        mode="pipelined", faults=plan,
        recovery=RecoveryConfig(checkpoint_every=2))).register(q)
    check(reg.run(chunks)[0])              # compile pass (the crash fires here)
    n = max(2, iters)
    clean, faulted = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(reg.run(chunks)[0])
        clean.append(time.perf_counter() - t0)
    restarts_before = reg.last_stats["recovery"]["restarts"]
    for _ in range(n):
        # each scheduled fault fires at most once per injector, so re-arm
        # the schedule each pass — rebased onto this pass's seq window,
        # because events key on the lifetime chunk seq, which keeps rising
        # across passes on the warmed runtime
        rebased = FaultPlan((FaultEvent(
            "crash_stage", "source",
            reg.runtime._next_seq + crash_chunk),))
        reg.runtime._injector = FaultInjector(rebased)
        t0 = time.perf_counter()
        outs = reg.run(chunks)[0]
        jax.block_until_ready(outs)
        faulted.append(time.perf_counter() - t0)
        check(outs)
    rec = reg.last_stats["recovery"]
    restarts = rec["restarts"] - restarts_before
    assert restarts == n, (
        "expected one restart per faulted pass, got %d over %d passes"
        % (restarts, n))
    crash = {
        "crash_chunk": crash_chunk,
        "checkpoint_every": 2,
        "clean_pass_median_s": float(np.median(clean)),
        "faulted_pass_median_s": float(np.median(faulted)),
        "time_to_recover_s":
            float(np.median(faulted) - np.median(clean)),
        "restarts_per_faulted_pass": restarts / n,
        "replayed_total": rec["replayed"],
        "bit_exact_after_recovery": True,
    }
    print("[bench_pipeline] crash on chunk %d: clean pass %.1f ms, "
          "faulted pass %.1f ms, time-to-recover %.1f ms"
          % (crash_chunk, crash["clean_pass_median_s"] * 1e3,
             crash["faulted_pass_median_s"] * 1e3,
             crash["time_to_recover_s"] * 1e3))
    return {
        "what": "resilience cost on the same pipelined workload: throughput "
                "per checkpoint cadence (0 = no mid-stream snapshots) vs "
                "the plain recovery=None baseline, plus time-to-recover "
                "from one injected mid-stream crash_stage (steady-state "
                "faulted-pass minus clean-pass median); every pass gated "
                "bit-exact against the single-program stream",
        "checkpoint_cadence": cadence,
        "crash_recovery": crash,
    }


def run(iters: Optional[int] = None, smoke: bool = False,
        query: str = "cquery1", kb_method: str = "auto"):
    if iters is None:
        iters = 1 if smoke else 3
    if smoke:
        world = build_world(num_tweets=32, num_artists=16, num_shows=8,
                            filler=100, chunk_capacity=192)
        base = ExecutionConfig(window_capacity=64, max_windows=4, bind_cap=512,
                               scan_cap=128, out_cap=512, intermediate_cap=256,
                               kb_method=kb_method,
                               channel_capacity=CHANNEL_CAPACITY)
    else:
        # >= 8 chunks: the pipelined runtime needs a stream long enough to
        # amortize ramp-up/drain before its steady-state overlap shows
        world = build_world(num_tweets=1280, num_artists=64, num_shows=32,
                            filler=2000, chunk_capacity=1024)
        base = ExecutionConfig(window_capacity=256, max_windows=4,
                               bind_cap=2048, scan_cap=512, out_cap=2048,
                               intermediate_cap=1024, kb_method=kb_method,
                               channel_capacity=CHANNEL_CAPACITY)

    if query == "cquery1":
        q = PQ.cquery1(world.vocab, world.tweets, world.kbd.schema)
    else:
        from repro.core.sparql import parse_query
        with open(ARTIST_CLASSES_RQ_PATH) as f:
            q = parse_query(f.read(), world.vocab)
    chunks = world.chunks
    assert smoke or len(chunks) >= 8, (
        "non-smoke stream too short to pipeline: %d chunks" % len(chunks))
    num_devices = len(jax.devices())
    print(f"[bench_pipeline] {query}, {len(chunks)} chunks, "
          f"smoke={smoke}, iters={iters}, kb_method={kb_method}, "
          f"devices={num_devices}")

    # one Session per execution mode — the unified API this benchmark compares
    mono = make_session(world, base.replace(mode="monolithic")).register(q)
    single = make_session(world, base.replace(mode="single_program")).register(q)
    piped = make_session(world, base.replace(mode="pipelined")).register(q)

    # -- correctness gate: bit-identical streams, zero overflow -------------
    outs_single, ovf_single = single.run(chunks)
    outs_piped, ovf_piped = piped.run(chunks)
    outs_mono, ovf_mono = mono.run(chunks)
    assert len(outs_single) == len(outs_piped) == len(outs_mono)
    for i, (a, b, c) in enumerate(zip(outs_single, outs_piped, outs_mono)):
        for col_a, col_b, col_c in zip(a, b, c):
            assert bool(np.all(np.asarray(col_a) == np.asarray(col_b))), (
                "pipelined chunk %d diverges from single-program" % i)
            assert bool(np.all(np.asarray(col_a) == np.asarray(col_c))), (
                "monolithic chunk %d diverges from single-program" % i)
    for label, ovf in [("monolithic", ovf_mono),
                       ("single_program", ovf_single),
                       ("pipelined", ovf_piped)]:
        clipped = {n: c for n, c in ovf.items() if c}
        assert not clipped, (
            "%s overflowed windows %s — raise capacities, the benchmark "
            "would be comparing clipped result sets" % (label, clipped))
    dropped = {e: s["overflows"]
               for e, s in piped.runtime.channel_stats().items()
               if s["overflows"]}
    assert not dropped, "channel drops under the deterministic schedule: %s" % dropped
    print("[bench_pipeline] all three modes bit-exact over "
          f"{len(chunks)} chunks, zero overflow in all modes")

    # -- schedule tripwires: the pipeline must actually pipeline -------------
    depth_hw = piped.runtime.depth_hw
    assert depth_hw >= 2, (
        "pipelined schedule never overlapped (depth_hw=%d) — the benchmark "
        "would be timing a serial execution under a pipelined label"
        % depth_hw)
    placement = {name: str(dev)
                 for name, dev in (piped.runtime.placement or {}).items()}
    if num_devices >= 2:
        assert len(set(placement.values())) >= 2, (
            "round_robin placement collapsed onto one device with %d "
            "available: %s" % (num_devices, placement))
    print(f"[bench_pipeline] depth_hw={depth_hw}, placement={placement}")

    # -- throughput ----------------------------------------------------------
    def mono_pass():
        return mono.run(chunks)[0]

    def single_pass():
        return single.run(chunks)[0]

    def piped_pass():
        # same drive loop as the correctness gate above (sink-only blocking
        # lives inside process_stream; _throughput's block is then a no-op)
        return piped.run(chunks)[0]

    results = {
        "monolithic": _throughput(mono_pass, len(chunks), iters),
        "single_program": _throughput(single_pass, len(chunks), iters),
        "pipelined": _throughput(piped_pass, len(chunks), iters),
    }

    rows = [
        [mode, f"{r['median_s'] * 1e3:.1f} ms", f"{r['chunks_per_s']:.2f}"]
        for mode, r in results.items()
    ]
    print(format_table("%s sustained throughput" % query,
                       ["mode", "stream pass (median)", "chunks/s"], rows))

    # -- KB-access comparison: scan vs probe vs auto on one runtime ----------
    # (the trajectory record for the cost-based access-method work: same
    # query, same stream, only kb_method varies; the gate asserts the three
    # methods stay bit-identical and overflow-free.  Measured on the
    # *monolithic* runtime — the full KB is attached there, so the access
    # method dominates; decomposed modes already shrink each operator's
    # partition via used-KB pruning, the paper's alternative cure)
    kb_access = {}
    for method in ("scan", "probe", "auto"):
        sess_m = make_session(
            world, base.replace(mode="monolithic", kb_method=method)
        ).register(q)
        outs_m, ovf_m = sess_m.run(chunks)
        for i, (a, b) in enumerate(zip(outs_single, outs_m)):
            for col_a, col_b in zip(a, b):
                assert bool(np.all(np.asarray(col_a) == np.asarray(col_b))), (
                    "kb_method=%s chunk %d diverges" % (method, i))
        clipped = {n: c for n, c in ovf_m.items() if c}
        assert not clipped, (
            "kb_method=%s overflowed windows %s" % (method, clipped))
        kb_access[method] = _throughput(
            lambda s=sess_m: s.run(chunks)[0], len(chunks), iters)
    rows = [
        [method, f"{r['median_s'] * 1e3:.1f} ms", f"{r['chunks_per_s']:.2f}"]
        for method, r in kb_access.items()
    ]
    print(format_table("%s KB-access methods (monolithic, full KB)" % query,
                       ["kb_method", "stream pass (median)", "chunks/s"],
                       rows))

    # -- resilience cost: checkpoint cadence + time-to-recover ---------------
    recovery_overhead = _recovery_overhead(
        world, base, q, chunks, outs_single, iters,
        plain_median_s=results["pipelined"]["median_s"])

    # -- per-stage breakdown: where does each runtime spend its time? --------
    stage_breakdown = _stage_breakdown(world, base, q, chunks, query)

    payload = {
        "what": "sustained chunks/sec over one stream pass, one Session per "
                "ExecutionConfig mode: monolithic vs single-program DAG vs "
                "pipelined dataflow (up to channel_capacity chunks in "
                "flight, sink-only blocking)",
        "query": query,
        "kb_method": kb_method,
        "num_chunks": len(chunks),
        "channel_capacity": CHANNEL_CAPACITY,
        "num_devices": num_devices,
        "placement": placement,
        "depth_hw": depth_hw,
        "split_sink": piped.runtime._split is not None,
        "smoke": smoke,
        "bit_exact_vs_single_program": True,
        "overflowed_windows": 0,
        "results": results,
        "kb_access": {
            "what": "same query/stream on the monolithic (full-KB) runtime "
                    "with only ExecutionConfig.kb_method varying; all "
                    "methods bit-identical and overflow-free",
            "bit_exact_across_methods": True,
            "results": kb_access,
        },
        "recovery_overhead": recovery_overhead,
        "stage_breakdown": {
            "what": "per-stage span aggregates from separate traced "
                    "sessions (tracing fences each stage, so the headline "
                    "throughput above stays unfenced); first_s is the "
                    "compile-inclusive first pass, steady excludes it",
            **stage_breakdown,
        },
    }
    name = ("BENCH_pipeline.json" if query == "cquery1"
            else "BENCH_pipeline_%s.json" % query)
    path = os.path.join(os.path.dirname(__file__), "..", name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"[bench_pipeline] wrote {os.path.normpath(path)}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + 1 iter (CI artifact mode)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations (default: 3, or 1 with --smoke)")
    ap.add_argument("--query", default="cquery1",
                    choices=["cquery1", "artist_classes"],
                    help="workload: the paper's CQuery1, or the expanded "
                         "frontend surface (SELECT + closure path + boolean "
                         "FILTER)")
    ap.add_argument("--kb-method", default="auto",
                    choices=["scan", "probe", "auto"],
                    help="KB access method for the three benchmarked modes "
                         "(the kb_access section always compares all three "
                         "on the monolithic full-KB runtime)")
    args = ap.parse_args(argv)
    # on the CPU, a multi-device backend BEFORE jax initializes: round_robin
    # placement can only spread enrichment operators across devices when
    # the host platform exposes more than one
    ensure_host_devices(4)
    setup_compile_cache()
    run(iters=args.iters, smoke=args.smoke, query=args.query,
        kb_method=args.kb_method)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout: builds the cell's world from the seed (a
DBpedia-like KB made on the device, a TweetsKB-like stream), registers the
cell's query in the system's ``Session``, warms every program up, then
hands the stream to the system for ``--seconds`` in the cell's loop.
Afterwards the plain reference checks every window of a seeded sample of
the chunks, and the last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: every number compared, beside its limit.

It runs only on a TPU with the chips the cell asks for.  ``--rehearse``
runs the same path at the configuration's tiny rehearsal size on any
platform and prints no device number.  ``--control NAME`` applies the
configuration's control (a setting that breaks one of its guarantees) and
exists to show that the comparison fails it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

OUT_DIR = ROOT / ".bench_out"


class NoDevice(Exception):
    pass


@dataclasses.dataclass
class Run:
    """What a metric reader may read about one run."""

    cell: dict
    config: dict
    traffic: dict
    seconds: float
    setup_s: float
    kb_build_s: float
    plan_s: float
    compile_s: float
    window: object                    # bench.drive.Window
    peaks: Optional[dict] = None
    trace: Optional[dict] = None      # bench.trace_reduce.reduce output
    kernel_bytes: Optional[dict] = None
    counters: Optional[dict] = None   # per-operator engine counters


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny world on any platform; no device numbers")
    ap.add_argument("--control", default=None,
                    help="apply the configuration's named control")
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop: override the traffic's tweets per "
                         "second (for finding the knee)")
    ap.add_argument("--keep-trace", default=None,
                    help="also keep the raw trace and its events in this "
                         "directory")
    return ap.parse_args(argv)


def setup_cache() -> str:
    """JAX's persistent compilation cache in one fixed directory of the
    checkout (the path is part of an entry's key, so it never moves), so
    that only a cell's first run in a checkout compiles, and two checkouts
    share nothing."""
    import jax

    path = str(ROOT / ".bench_cache" / "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if rehearse:
        return devs
    if devs[0].platform != "tpu":
        raise NoDevice("JAX found no TPU (platform %r)" % devs[0].platform)
    if len(devs) < chips:
        raise NoDevice("the cell needs %d chips, JAX found %d"
                       % (chips, len(devs)))
    return devs


def apply_control(config: dict, name: Optional[str], rehearse: bool) -> dict:
    """The configuration with control ``name``'s execution settings (its
    ``rehearse`` settings at the rehearsal size)."""
    if name is None:
        return config
    controls = config.get("controls", {})
    if name not in controls:
        raise KeyError("configuration has no control %r (have: %s)"
                       % (name, ", ".join(controls)))
    ctl = controls[name]
    out = dict(config)
    out["execution"] = dict(config["execution"],
                            **ctl["rehearse" if rehearse else "execution"])
    return out


def open_rate(config: dict, traffic: dict) -> float:
    """Open loop: tweets per second offered, the traffic's share of the
    configuration's knee (the highest rate it sustains, found by a sweep
    on the chip)."""
    return float(traffic["rate_of_knee"]) \
        * float(config["load"]["knee_tweets_per_s"])


def counter_pass(cfg, query, make_batch, kb, gen, n: int) -> dict:
    """Engine counters: the same registration with the system's metrics
    on (a program of its own), over ``n`` chunks after the window."""
    import jax

    from repro.core.session import Session
    from repro.obs.trace import TraceConfig

    from bench import world as W

    reg = Session(cfg.replace(trace=TraceConfig(spans=False, metrics=True,
                                                fence=False)),
                  vocab=W.make_vocab(gen), kb=kb).register(query)
    for out in reg.stream([make_batch(k) for k in range(n)]):
        jax.device_get(out)
    return reg.last_stats["operators"]


def kernel_bytes(reduced: dict) -> dict:
    """Bytes of every KB-join kernel call in the traced window, from the
    shapes in the call's HLO text (``bench/roofline.hlo_bytes``)."""
    from bench import roofline, trace_reduce as TR

    out = {k: 0 for k in roofline.KB_JOIN_KERNELS}
    for calls in reduced["kernel_calls"].values():
        for name, n in calls.items():
            for k in out:
                if k in name:
                    out[k] += n * roofline.hlo_bytes(TR.hlo_call(name))
    return out


def trace_window(path: Path, keep: Optional[str]):
    import glob

    from bench import trace_reduce as TR
    from bench.roofline import KB_JOIN_KERNELS

    files = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        raise RuntimeError("the profiler wrote no trace under %s" % path)
    raw = max(files, key=os.path.getmtime)
    events = TR.load_xplane(raw)
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(raw, keep)
        TR.save(events, os.path.join(keep, "events.json.gz"))
    red = TR.reduce(events, KB_JOIN_KERNELS)
    return red, None if red is None else kernel_bytes(red)


def result_checks(checks: dict) -> None:
    from bench.check import LIMITS

    for name, value in checks.items():
        print("check %s = %s (limit %s)" % (name, value, LIMITS[name]),
              file=sys.stderr)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import spec as S

    try:
        bench = S.benchmark()
        cell = S.cell(bench, args.workload)
        config = S.config(bench, cell["config"])
        traffic = S.traffic(cell["traffic"])
    except (OSError, KeyError, ValueError) as err:
        print("bench: cannot find the cell: %s" % err, file=sys.stderr)
        return 2
    try:
        devices = devices_for(int(cell["chips"]), args.rehearse)
    except NoDevice as err:
        print("bench: %s; nothing was run" % err, file=sys.stderr)
        return 3
    if not args.rehearse:
        setup_cache()
    return run_cell(args, bench, cell, config, traffic, devices)


def run_cell(args, bench, cell, config, traffic, devices) -> int:
    import jax

    from repro.core.rdf import TripleBatch
    from repro.core.session import Session

    from bench import check as C
    from bench import drive as D
    from bench import spec as S
    from bench import world as W
    from bench.gen import kb as K

    config = apply_control(W.sized(config, args.rehearse), args.control,
                           args.rehearse)
    if args.rehearse:
        traffic = dict(traffic, **traffic.get("rehearse", {}))
    dev = devices[0]
    peaks = None if args.rehearse else S.peaks(dev.device_kind)
    counter = D.CompileCounter.get()

    t = time.perf_counter()
    world = W.build(config, traffic, args.seed)
    world_s = time.perf_counter() - t
    t = time.perf_counter()
    kb = K.build_device_kb(world.used, world.kb_shape, args.seed, dev)
    jax.block_until_ready(kb)
    kb_build_s = time.perf_counter() - t

    cfg = W.execution_config(config["execution"])
    query = S.query_text(config["query"])
    vocab = W.make_vocab(world.gen)
    t = time.perf_counter()
    reg = Session(cfg, vocab=vocab, kb=kb).register(query)
    plan_s = time.perf_counter() - t
    W.check_vocab(vocab, world.gen)

    def make_batch(k):
        return TripleBatch(*world.chunk_rows(k))

    def triples_of(k):
        # the stream triples chunk k adds (sliding chunks repeat the last
        # R - 1 slides of the one before)
        return int(world.chunks.new_rows[k % world.n_chunks])

    n_warm = int(traffic["warmup_chunks"])
    compiles0 = counter.count
    compile_s = D.warm_up(reg, make_batch,
                          [world.n_chunks - 1 - i for i in range(n_warm)])
    programs = counter.count - compiles0
    due = rate = None
    if traffic["loop"] == "open":
        rate = args.rate if args.rate is not None \
            else open_rate(config, traffic)
        due = D.arrivals(world, rate, W.rng(args.seed, 2))
    # what set-up left on the heap lives for the whole run: freeze it, so
    # no full collection rescans it inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START

    trace_dir = OUT_DIR / "trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    # a traced run traces a short window: the trace of every device op
    # grows with it, and reading it must stay within the run's time
    seconds = min(args.seconds, float(traffic["trace_seconds"])) \
        if args.trace else args.seconds
    window = D.run_window(reg, make_batch, triples_of, traffic, seconds,
                          due=due, tracing=bool(args.trace))
    if args.trace:
        jax.profiler.stop_trace()

    used = devices[:int(cell["chips"])]
    peak = 0
    for d in used:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    overflowed = sum(reg.overflow_totals().values())
    rec = reg.last_stats["recovery"]
    degraded = int(reg.last_stats["degraded"]) + rec["restarts"] \
        + len(rec["degraded_chunks"])

    run = Run(cell, config, traffic, seconds, setup_s, kb_build_s,
              plan_s, compile_s, window, peaks)
    if args.trace:
        run.trace, run.kernel_bytes = trace_window(trace_dir, args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.counters = counter_pass(cfg, query, make_batch, kb, world.gen,
                                    n=int(traffic["counter_chunks"]))
    del reg, kb

    recs = window.recs
    picks = C.sample(len(recs), int(traffic["check_chunks"]),
                     W.rng(args.seed, 3))
    ref = S.reference(config["query"])
    compared, mismatched = C.compare(recs, world, ref,
                                     world.reference_index(), picks)
    unanswered = sum(r.out is None for r in recs)
    checks = {
        "windows_mismatched": mismatched,
        "windows_overflowed": overflowed,
        "chunks_unanswered": unanswered,
        "compiles_in_window": window.compiles,
        "degraded_or_restarted": degraded,
    }
    correct = all(v <= C.LIMITS[k] for k, v in checks.items()) and compared > 0
    attempted = len(recs) * world.geometry.max_windows
    failed = mismatched + unanswered * world.geometry.max_windows
    print("bench: %s seed=%d chunks=%d windows_compared=%d setup_s=%.3f "
          "compile_s=%.3f programs=%d plan_s=%.3f kb_build_s=%.3f world_s=%.3f "
          "window_s=%.3f"
          % (args.workload, args.seed, len(recs), compared, setup_s,
             compile_s, programs, plan_s, kb_build_s, world_s,
             window.t_end - window.t0), file=sys.stderr)

    print("bench: stall watch %s" % window.stalls, file=sys.stderr)
    if due is not None:
        from bench.metrics._window import window_latencies_ms

        lat = window_latencies_ms(run)
        half = len(lat) // 2
        print("bench: open loop rate=%s tweets/s windows=%d latency_ms "
              "first_half_median=%.1f second_half_median=%.1f max=%.1f"
              % (rate, len(lat),
                 statistics.median(lat[:half] or lat),
                 statistics.median(lat[half:]), max(lat)), file=sys.stderr)
    if args.rehearse:
        result_checks(checks)
        print(json.dumps({"rehearsal": True, "correct": correct,
                          "attempted": attempted, "failed": failed,
                          "checks": _checks_out(checks)}))
        return 0

    metrics = {}
    for m in S.metrics(bench, args.workload, per_layer=bool(args.trace)):
        value = S.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        chips = int(cell["chips"])
        device["busy_s"] = sum(run.trace["busy_s"].values()) / chips
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    result_checks(checks)
    out["checks"] = _checks_out(checks)
    print(json.dumps(out))
    return 0


def _checks_out(checks: dict) -> dict:
    from bench.check import LIMITS

    return {k: {"value": v, "limit": LIMITS[k]} for k, v in checks.items()}


if __name__ == "__main__":
    sys.exit(main())

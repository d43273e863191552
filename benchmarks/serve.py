"""Serving throughput: queries/sec vs registered-query count.

The repo's first throughput-at-scale number (ROADMAP "multi-query,
multi-tenant serving"): one :class:`~repro.serve.engine.ServeEngine`
hosts N standing queries (a mix of exact duplicates, class variants
sharing a KB-join prefix, and filter-threshold variants — the population
:func:`repro.launch.dscep_run.serve_population` generates) and every
chunk streams through all of them.  Measured at N = 16 / 64 / 256 with
shared-plan dedup on vs off; at the smallest N the serving outputs are
additionally asserted bit-identical to N independent single-query
Sessions (and dedup-on vs dedup-off bit-identical at every N), so the
speedups compare equal result sets — ``"exact": true`` in the payload
records that the assertions ran.

    PYTHONPATH=src python benchmarks/serve.py [--smoke] [--iters K]

Writes BENCH_serve.json.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Optional

import numpy as np

from repro.core.session import ExecutionConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.dscep_run import serve_population

from .common import build_world, format_table, make_session
from .pipeline import _throughput

QUERY_COUNTS = (16, 64, 256)


def _assert_bit_identical(outs_a, outs_b, tag):
    assert len(outs_a) == len(outs_b), tag
    for i, (a, b) in enumerate(zip(outs_a, outs_b)):
        for col, ca, cb in zip(a._fields, a, b):
            assert bool(np.all(np.asarray(ca) == np.asarray(cb))), (
                "%s: chunk %d column %s diverges" % (tag, i, col))


def run(iters: Optional[int] = None, smoke: bool = False):
    if iters is None:
        iters = 1 if smoke else 2
    # smoke keeps the first two sweep points (the CI tripwire needs >= 2 and
    # the dedup-win claim is made at 64); the full run records all three
    counts = QUERY_COUNTS[:2] if smoke else QUERY_COUNTS
    if smoke:
        world = build_world(num_tweets=32, num_artists=16, num_shows=8,
                            filler=100, chunk_capacity=192)
        base = ExecutionConfig(mode="monolithic", window_capacity=64,
                               max_windows=4, bind_cap=1024, scan_cap=256,
                               out_cap=1024, out_stream_cap=2048)
    else:
        world = build_world(num_tweets=64, num_artists=32, num_shows=16,
                            filler=400, chunk_capacity=256)
        base = ExecutionConfig(mode="monolithic", window_capacity=96,
                               max_windows=4, bind_cap=1024, scan_cap=256,
                               out_cap=1024, out_stream_cap=2048)
    chunks = world.chunks
    print(f"[bench_serve] {len(chunks)} chunks of "
          f"{int(chunks[0].valid.shape[0])}, smoke={smoke}, iters={iters}, "
          f"N sweep={counts}")

    sweep = []
    for n in counts:
        texts = serve_population(n)
        outs_by = {}
        rates = {}
        stats_by = {}
        for dedup in (True, False):
            eng = make_session(world, base).serve(dedup=dedup)
            for t in texts:
                eng.register(t)
            outs, ovf = eng.run(chunks)
            outs_by[dedup] = (outs, ovf)
            r = _throughput(lambda e=eng: e.run(chunks)[0], len(chunks),
                            iters)
            r["queries_per_s"] = r["chunks_per_s"] * n
            rates[dedup] = r
            stats_by[dedup] = eng.last_stats

        # dedup on and off must publish identical streams at every N
        on_outs, on_ovf = outs_by[True]
        off_outs, off_ovf = outs_by[False]
        for qname in on_outs:
            _assert_bit_identical(on_outs[qname], off_outs[qname],
                                  "N=%d %s dedup-on vs off" % (n, qname))
        assert on_ovf == off_ovf, (n, on_ovf, off_ovf)

        independent = None
        if n == counts[0]:
            # the ground truth: every query in its own single-query Session
            regs = []
            for t in texts:
                reg = make_session(world, base).register(t)
                souts, sovf = reg.run(chunks)
                qname = reg.query.name
                _assert_bit_identical(on_outs[qname], souts,
                                      "N=%d %s serve vs single" % (n, qname))
                assert on_ovf[qname] == sovf[qname], (qname, on_ovf, sovf)
                regs.append(reg)
            r = _throughput(
                lambda: [reg.run(chunks)[0] for reg in regs],
                len(chunks), iters)
            r["queries_per_s"] = r["chunks_per_s"] * n
            independent = r

        st = stats_by[True]
        sweep.append({
            "queries": n,
            "dedup_on": rates[True],
            "dedup_off": rates[False],
            "independent_sessions": independent,
            "dedup_speedup": (rates[True]["queries_per_s"]
                              / rates[False]["queries_per_s"]),
            "distinct_plans": st["distinct_plans"],
            "cohort_batch_sizes": st["batch_sizes"],
            "prefix_groups": len(st["prefix_groups"]),
            "exact": True,
            "overflow_clipped": sum(on_ovf.values()),
        })
        print(f"[bench_serve] N={n}: dedup-on "
              f"{rates[True]['queries_per_s']:.1f} q/s, dedup-off "
              f"{rates[False]['queries_per_s']:.1f} q/s "
              f"({sweep[-1]['dedup_speedup']:.2f}x), "
              f"{st['distinct_plans']} distinct plans")

    rows = [
        [str(e["queries"]), e["distinct_plans"],
         f"{e['dedup_on']['queries_per_s']:.1f}",
         f"{e['dedup_off']['queries_per_s']:.1f}",
         f"{e['dedup_speedup']:.2f}x",
         (f"{e['independent_sessions']['queries_per_s']:.1f}"
          if e["independent_sessions"] else "--")]
        for e in sweep
    ]
    print(format_table(
        "serving throughput (query-evals/sec, steady state)",
        ["queries", "distinct plans", "dedup on", "dedup off",
         "dedup speedup", "independent"], rows))

    payload = {
        "what": "multi-query serving throughput: query-evaluations/sec of "
                "one ServeEngine hosting N standing queries (duplicates + "
                "class variants + filter variants) with shared-plan dedup "
                "on vs off; outputs asserted bit-identical to independent "
                "single-query Sessions at the smallest N and dedup-on == "
                "dedup-off at every N before timing",
        "population": "serve_population: 1/3 duplicates (plan dedup), 1/3 "
                      "class variants (shared KB-join prefix), 1/3 filter "
                      "thresholds (vmap cohort)",
        "num_chunks": len(chunks),
        "chunk_capacity": int(chunks[0].valid.shape[0]),
        "smoke": smoke,
        "exact": True,
        "sweep": sweep,
    }
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"[bench_serve] wrote {os.path.normpath(path)}")
    return sweep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + 1 iter (CI artifact mode)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations (default: 2, or 1 with --smoke)")
    args = ap.parse_args(argv)
    setup_compile_cache()
    run(iters=args.iters, smoke=args.smoke)


if __name__ == "__main__":
    main()

"""Sliding-window evaluation benchmark -> BENCH_window.json.

Sweeps the overlap ratio of STEP count windows (``step/capacity`` in
{1.0, 0.5, 0.25, 0.125} -> 0%/50%/75%/87.5% overlap) and compares, at each
geometry, full per-window recomputation (``incremental=False``) against the
delta evaluator (``incremental=True``) that runs the join chain once per
chunk over span-tagged bindings and only finalizes per window.

The workload is a deliberately join-heavy, OPTIONAL-free query (delta-safe:
``plan_supports_delta`` must hold, asserted below): tweets mentioning an
entity that is a MusicalArtist by subclass reasoning AND has a
birthPlace/country/countryCode path — one stream scan plus a closure join
plus a three-hop KB path on the same variable.

``max_windows`` scales with the overlap (enough windows to cover one chunk
at the given STEP), which is exactly the regime where recomputation pays
W times for the same join work the delta evaluator does once.

Correctness gate per sweep point: delta output is **bit-identical** to
recompute and both are overflow-free — the recorded speedups compare equal
result sets or the benchmark refuses to write.

    PYTHONPATH=src python -m benchmarks.window            # full shapes
    PYTHONPATH=src python -m benchmarks.window --smoke    # CI tiny shapes
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Optional

import numpy as np

from repro.core.planner import plan_supports_delta
from repro.core.session import ExecutionConfig
from repro.launch.compile_cache import setup_compile_cache

from .common import build_world, format_table, make_session
from .pipeline import _throughput

WINDOW_RQ = """\
REGISTER QUERY winbench AS
PREFIX schema: <urn:dscep:schema>
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX rdfs: <http://www.w3.org/2000/01/rdf-schema#>
PREFIX dbo: <http://dbpedia.org/ontology/>
PREFIX out: <urn:dscep:out>
CONSTRUCT {
  ?tweet out:artistCode ?cc .
}
FROM STREAM <stream> [RANGE TRIPLES 1000 STEP 1]
FROM <kb>
WHERE {
  ?tweet schema:mentions ?ent .
  GRAPH <kb> {
    ?ent rdf:type/rdfs:subClassOf* dbo:MusicalArtist .
    ?ent dbo:birthPlace/dbo:country/dbo:countryCode ?cc .
  }
}
"""

STEP_FRACTIONS = (1.0, 0.5, 0.25, 0.125)


def _assert_bit_identical(outs_a, outs_b, tag):
    assert len(outs_a) == len(outs_b), tag
    for i, (a, b) in enumerate(zip(outs_a, outs_b)):
        for col, ca, cb in zip(a._fields, a, b):
            assert bool(np.all(np.asarray(ca) == np.asarray(cb))), (
                "%s: chunk %d column %s diverges" % (tag, i, col))


def run(iters: Optional[int] = None, smoke: bool = False):
    if iters is None:
        iters = 1 if smoke else 3
    if smoke:
        world = build_world(num_tweets=32, num_artists=16, num_shows=8,
                            filler=100, chunk_capacity=192)
        capacity, max_cover = 64, 16
        base = ExecutionConfig(window_capacity=capacity, bind_cap=1024,
                               scan_cap=256, out_cap=1024,
                               intermediate_cap=512)
    else:
        # sized for the container's single CPU core: big enough that the
        # join chain dominates, small enough that the W-window recompute
        # baseline (the expensive side) finishes in minutes
        world = build_world(num_tweets=128, num_artists=48, num_shows=24,
                            filler=1000, chunk_capacity=512)
        capacity, max_cover = 128, 16
        base = ExecutionConfig(window_capacity=capacity, bind_cap=2048,
                               scan_cap=512, out_cap=2048,
                               intermediate_cap=1024)
    chunks = world.chunks
    chunk_cap = int(chunks[0].valid.shape[0])
    print(f"[bench_window] {len(chunks)} chunks of {chunk_cap}, "
          f"window capacity {capacity}, smoke={smoke}, iters={iters}")

    sweep = []
    for frac in STEP_FRACTIONS:
        step = max(1, int(capacity * frac))
        # enough windows to slide across one chunk at this STEP (bounded so
        # tiny steps don't explode compile time)
        max_windows = min(max_cover, max(1, -(-chunk_cap // step)))
        cfg = base.replace(mode="monolithic", window_step=step,
                           max_windows=max_windows)

        recomp = make_session(world, cfg).register(WINDOW_RQ)
        delta = make_session(world, cfg.replace(incremental=True)
                             ).register(WINDOW_RQ)
        assert plan_supports_delta(delta.runtime.operator.plan), (
            "benchmark query fell off the delta path — it would time the "
            "recompute fallback twice")

        outs_r, ovf_r = recomp.run(chunks)
        outs_d, ovf_d = delta.run(chunks)
        tag = "step=%d" % step
        _assert_bit_identical(outs_r, outs_d, tag)
        for label, ovf in (("recompute", ovf_r), ("delta", ovf_d)):
            clipped = {n: c for n, c in ovf.items() if c}
            assert not clipped, (
                "%s %s overflowed windows %s — raise capacities, the "
                "speedup would compare clipped result sets"
                % (tag, label, clipped))

        r_rec = _throughput(lambda: recomp.run(chunks)[0], len(chunks), iters)
        r_del = _throughput(lambda: delta.run(chunks)[0], len(chunks), iters)
        overlap = 1.0 - step / capacity
        sweep.append({
            "step": step,
            "overlap": overlap,
            "max_windows": max_windows,
            "recompute": r_rec,
            "delta": r_del,
            "speedup": r_del["chunks_per_s"] / r_rec["chunks_per_s"],
            "exact": True,
            "overflowed_windows": 0,
        })

    rows = [
        ["%d (%d%%)" % (e["step"], round(e["overlap"] * 100)),
         e["max_windows"],
         f"{e['recompute']['chunks_per_s']:.2f}",
         f"{e['delta']['chunks_per_s']:.2f}",
         f"{e['speedup']:.2f}x"]
        for e in sweep
    ]
    print(format_table(
        "winbench delta vs recompute (capacity %d, monolithic)" % capacity,
        ["STEP (overlap)", "windows", "recompute chunks/s",
         "delta chunks/s", "speedup"], rows))

    payload = {
        "what": "STEP-overlap sweep: per-chunk chunks/sec of incremental "
                "delta evaluation vs full per-window recomputation on one "
                "monolithic Session; each point bit-identical and "
                "overflow-free before timing",
        "query": "winbench (mentions + subclass closure + 3-hop path)",
        "window_capacity": capacity,
        "num_chunks": len(chunks),
        "chunk_capacity": chunk_cap,
        "smoke": smoke,
        "exact": True,
        "sweep": sweep,
    }
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_window.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"[bench_window] wrote {os.path.normpath(path)}")
    return sweep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + 1 iter (CI artifact mode)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations (default: 3, or 1 with --smoke)")
    args = ap.parse_args(argv)
    setup_compile_cache()
    run(iters=args.iters, smoke=args.smoke)


if __name__ == "__main__":
    main()

"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One benchmark per paper artifact (DESIGN.md §5):

* step1    — Table 1  (Q15/Q16 x two KB-access methods)
* step2    — Tables 2-3 (CQuery1 monolithic vs decomposed, both methods)
* step3    — Figs. 5-7 (used-KB and total-KB scaling)
* kernels  — Pallas kernel fidelity + shape sweeps
* join     — fused join->compaction before/after + scan-vs-probe KB-access
             microbenchmarks (also part of ``kernels``); records speedups
             to BENCH_join.json
* pipeline — sustained chunks/sec: monolithic vs single-program DAG vs
             pipelined dataflow runtime; records to BENCH_pipeline.json
* roofline — per-(arch x shape x mesh) roofline terms from the dry-run
             artifacts (run ``python -m repro.launch.dryrun`` first)
* serve    — multi-query serving throughput: queries/sec at 16/64/256
             registered queries, shared-plan dedup on vs off; records to
             BENCH_serve.json (not in the default set — run explicitly
             via ``--only serve``)

``--only step2,roofline`` selects a subset.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="step1,step2,step3,kernels,pipeline,roofline")
    p.add_argument("--iters", type=int, default=3)
    args = p.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache
    from repro.launch.mesh import ensure_host_devices

    ensure_host_devices(4)
    setup_compile_cache()
    want = [s.strip() for s in args.only.split(",") if s.strip()]

    failures = []
    t_start = time.time()
    for name in want:
        print(f"\n{'=' * 72}\n[benchmarks.run] {name}\n{'=' * 72}")
        t0 = time.time()
        try:
            if name == "step1":
                from . import step1
                step1.run(iters=args.iters)
            elif name == "step2":
                from . import step2
                step2.run(iters=args.iters)
            elif name == "step3":
                from . import step3
                step3.run(iters=args.iters)
            elif name == "kernels":
                from . import kernels
                kernels.run()
            elif name == "join":
                from . import kernels
                kernels.bench_join()
            elif name == "pipeline":
                from . import pipeline
                pipeline.run(iters=args.iters)
            elif name == "roofline":
                from . import roofline
                roofline.run()
            elif name == "serve":
                from . import serve
                serve.run(iters=args.iters)
            else:
                print(f"unknown benchmark {name!r}")
                failures.append(name)
                continue
            print(f"[benchmarks.run] {name} done in {time.time() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)

    print(f"\n[benchmarks.run] total {time.time() - t_start:.1f}s; "
          f"{'ALL OK' if not failures else 'FAILED: ' + ', '.join(failures)}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

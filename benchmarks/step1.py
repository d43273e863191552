"""Paper Table 1 (first step): Q15 / Q16 under both KB-access methods.

* ``scan``  ≙ "C-SPARQL KB access"  — engine scans an attached (pre-extracted)
  KB file per window; its store holds only the query-relevant slice, so
  *total = used* (paper: 103,075 for both).
* ``probe`` ≙ "SPARQL subquery" (SERVICE) — indexed endpoint lookups against
  the FULL knowledge base (paper total: 368,720,213), cost ~independent of
  unused triples.

Reported per (query × method): total KB size, used KB size, and steady-state
processing time per window (compile excluded), mirroring the paper's table.
"""
from __future__ import annotations

import numpy as np

from repro.core import paper_queries as PQ
from repro.core.planner import prune_kb_for
from repro.core.session import ExecutionConfig

from .common import (
    BenchWorld, build_world, format_table, make_session, ms, save_results,
    time_fn,
)

WINDOW_CAP = 256
MAX_WINDOWS = 4


def _exec_cfg(method: str) -> ExecutionConfig:
    return ExecutionConfig(
        mode="monolithic", window_capacity=WINDOW_CAP,
        max_windows=MAX_WINDOWS, bind_cap=2048, scan_cap=512, out_cap=2048,
        kb_method=method,
    )


def run(world: BenchWorld = None, iters: int = 5) -> dict:
    world = world or build_world(num_tweets=192, num_artists=96, num_shows=48,
                                 filler=4000, co_mention=False)
    kbs, ts, vocab = world.kbd.schema, world.tweets, world.vocab
    full_kb = world.kbd.kb
    total_full = int(np.asarray(full_kb.count()))

    results = {}
    rows = []
    for qname, builder in (("Q15", PQ.q15), ("Q16", PQ.q16)):
        q = builder(vocab, ts, kbs)
        used_kb = prune_kb_for(q, full_kb)
        used = int(np.asarray(used_kb.count()))
        for method in ("scan", "probe"):
            # scan ≙ engine-attached extracted KB slice (total == used);
            # probe ≙ endpoint holding the full KB (total == |full KB|).
            kb = used_kb if method == "scan" else full_kb
            total = used if method == "scan" else total_full
            reg = make_session(world, _exec_cfg(method), kb=kb).register(q)
            chunk = world.chunks[0]
            t = time_fn(lambda c: reg.process_chunk(c)[0], chunk, iters=iters)
            n_valid = int(np.asarray(chunk.valid.sum()))
            n_windows = min(MAX_WINDOWS, -(-n_valid // WINDOW_CAP))
            per_window = t["median_s"] / n_windows
            label = "C-SPARQL KB access" if method == "scan" else "SPARQL subquery"
            results[f"{qname}/{method}"] = {
                "total_kb": total, "used_kb": used,
                "per_window_s": per_window, **t,
            }
            rows.append([qname, label, total, used, ms(per_window)])

    table = format_table(
        "Table 1 — first step: Q15/Q16 x KB-access method",
        ["query", "KB access method", "total KB", "used KB", "time/window"],
        rows,
    )
    print(table)
    # the paper's qualitative claims for this table
    q15_scan = results["Q15/scan"]["per_window_s"]
    q15_probe = results["Q15/probe"]["per_window_s"]
    q16_scan = results["Q16/scan"]["per_window_s"]
    q16_probe = results["Q16/probe"]["per_window_s"]
    print(f"[check] Q15 probe beats scan (paper: 1.3s < 5s): "
          f"{q15_probe < q15_scan} ({ms(q15_probe)} vs {ms(q15_scan)})")
    print(f"[note]  Q16 here: probe {ms(q16_probe)} vs scan {ms(q16_scan)} — "
          f"the paper's Q16 scan-win (0.64s < 1.61s) came from per-window "
          f"SERVICE network round-trips to a 368M-triple endpoint; our probe "
          f"is an in-memory indexed lookup with no RTT, so it wins on both "
          f"queries (relationship documented, not asserted)")
    print(f"[check] probe cost ~independent of unused KB "
          f"(total {results['Q15/probe']['total_kb']} vs used "
          f"{results['Q15/probe']['used_kb']}): probe/scan ratio "
          f"{q15_probe / q15_scan:.2f}")
    save_results("step1_table1", {"results": results, "table": table})
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    run()

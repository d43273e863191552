"""Paper Figs. 5-7 (third step): how used-KB and total-KB size drive time.

* Fig. 5 (``--sweep used``): used == total, sweep the number of query-relevant
  triples; processing time should scale ~linearly (paper: 10x used -> ~10x
  time for QueryA; 7.5x -> ~6.5x for QueryB).
* Figs. 6/7 (``--sweep total``): fix the used slice, grow *unused* filler; the
  scan method's time grows with total size (paper: +30.2% for 10x unused on
  QueryA, +43.6% on QueryB), while the probe method stays ~flat — the paper's
  argument for partitioning the KB per sub-query.

QueryA/QueryB are the decomposition's artist/show operators from step 2.
"""
from __future__ import annotations

import numpy as np

from repro.core import paper_queries as PQ
from repro.core.planner import decompose, prune_kb_for
from repro.core.session import ExecutionConfig

from .common import (
    build_world, format_table, make_session, ms, save_results, time_fn,
)

WINDOW_CAP = 256
MAX_WINDOWS = 4


def _cfg(method: str) -> ExecutionConfig:
    return ExecutionConfig(
        mode="monolithic", window_capacity=WINDOW_CAP,
        max_windows=MAX_WINDOWS, bind_cap=2048, scan_cap=512, out_cap=2048,
        kb_method=method,
    )


def _subqueries(world):
    q = PQ.cquery1(world.vocab, world.tweets, world.kbd.schema)
    dag = decompose(q, world.vocab)
    subs = {}
    for name, sub in dag.subqueries.items():
        if sub.touches_kb:
            key = "QueryA" if "artist" in name else "QueryB"
            subs[key] = sub.query
    return subs


def sweep_used(iters: int = 3) -> dict:
    """Fig. 5: used == total; vary relevant-KB size via the entity universe.

    Sizes reach the scan-dominated regime (used-KB in the thousands) where
    the paper observes ~linear scaling; below that, fixed window-join work
    flattens the curve (visible in the first points).
    """
    sizes = [(64, 32), (192, 96), (512, 256), (1024, 512)]  # (artists, shows)
    out = {"QueryA": [], "QueryB": []}
    for n_art, n_show in sizes:
        world = build_world(num_tweets=96, num_artists=n_art, num_shows=n_show,
                            filler=0, co_mention=True, seed=7)
        chunk = world.chunks[0]
        for key, q in _subqueries(world).items():
            kb = prune_kb_for(q, world.kbd.kb)     # used == total
            reg = make_session(world, _cfg("scan"), kb=kb).register(q)
            t = time_fn(lambda c: reg.process_chunk(c)[0], chunk, iters=iters)
            out[key].append({
                "used_kb": int(np.asarray(kb.count())),
                "time_s": t["median_s"],
            })
    return out


def sweep_total(iters: int = 3) -> dict:
    """Figs. 6/7: fixed used slice, growing unused filler (both methods)."""
    fillers = [0, 1000, 4000, 16000]
    out = {"scan": {"QueryA": [], "QueryB": []},
           "probe": {"QueryA": [], "QueryB": []}}
    for filler in fillers:
        world = build_world(num_tweets=96, num_artists=64, num_shows=32,
                            filler=filler, co_mention=True, seed=7)
        chunk = world.chunks[0]
        for key, q in _subqueries(world).items():
            for method in ("scan", "probe"):
                reg = make_session(world, _cfg(method)).register(q)
                t = time_fn(lambda c: reg.process_chunk(c)[0], chunk, iters=iters)
                used = int(np.asarray(prune_kb_for(q, world.kbd.kb).count()))
                out[method][key].append({
                    "total_kb": int(np.asarray(world.kbd.kb.count())),
                    "used_kb": used,
                    "time_s": t["median_s"],
                })
    return out


def run(sweep: str = "both", iters: int = 3) -> dict:
    results = {}
    if sweep in ("used", "both"):
        used = sweep_used(iters)
        results["fig5_used"] = used
        rows = []
        for key, pts in used.items():
            base = pts[0]
            for p in pts:
                rows.append([
                    key, p["used_kb"], ms(p["time_s"]),
                    f"x{p['used_kb'] / max(1, base['used_kb']):.1f}",
                    f"x{p['time_s'] / base['time_s']:.1f}",
                ])
        print(format_table(
            "Fig. 5 — used-KB scaling (scan method, used == total)",
            ["query", "used KB", "time/chunk", "KB growth", "time growth"],
            rows,
        ))
        for key, pts in used.items():
            kb_ratio = pts[-1]["used_kb"] / max(1, pts[0]["used_kb"])
            t_ratio = pts[-1]["time_s"] / pts[0]["time_s"]
            print(f"[check] {key}: used-KB x{kb_ratio:.1f} -> time x{t_ratio:.1f} "
                  f"(paper: ~linear)")

    if sweep in ("total", "both"):
        total = sweep_total(iters)
        results["fig6_7_total"] = total
        rows = []
        for method in ("scan", "probe"):
            for key, pts in total[method].items():
                base = pts[0]
                for p in pts:
                    rows.append([
                        method, key, p["total_kb"], p["used_kb"], ms(p["time_s"]),
                        f"+{(p['time_s'] / base['time_s'] - 1) * 100:.0f}%",
                    ])
        print(format_table(
            "Figs. 6/7 — total-KB scaling (fixed used slice)",
            ["method", "query", "total KB", "used KB", "time/chunk", "vs no filler"],
            rows,
        ))
        for key in ("QueryA", "QueryB"):
            pts = total["scan"][key]
            grow = pts[-1]["time_s"] / pts[0]["time_s"] - 1
            kb_grow = pts[-1]["total_kb"] / pts[0]["total_kb"]
            ppts = total["probe"][key]
            pgrow = ppts[-1]["time_s"] / ppts[0]["time_s"] - 1
            print(f"[check] {key}: x{kb_grow:.0f} unused triples cost the scan "
                  f"method +{grow * 100:.0f}% (paper direction: unused KB costs "
                  f"scan, +30-44% at x10) while probe stays ~flat "
                  f"(+{pgrow * 100:.0f}%) — the partitioning argument")

    save_results("step3_figs5_7", results)
    return results


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    run(sys.argv[1] if len(sys.argv) > 1 else "both")

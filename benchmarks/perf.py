"""§Perf hillclimb comparer: roofline terms of tagged dry-run variants.

Workflow (one iteration of the hypothesis -> change -> measure loop):

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \\
        --shape train_4k --profile dp --tag dp
    PYTHONPATH=src python -m benchmarks.perf --cell olmo-1b/train_4k

prints baseline vs every tagged variant of that cell with the three roofline
terms, dominant-term delta, and per-collective byte breakdown — the numbers
that go into EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

from .common import format_table, save_results
from .roofline import ARTIFACTS, analyze, fmt_s


def load_cell_variants(arch: str, shape: str, mesh_tag: str = "pod1") -> Dict[str, dict]:
    out = {}
    base = os.path.join(ARTIFACTS, f"{arch}__{shape}__{mesh_tag}")
    for path in sorted(glob.glob(base + "*.json")):
        name = os.path.basename(path)[:-5]
        parts = name.split("__")
        tag = parts[3] if len(parts) > 3 else "baseline"
        with open(path) as f:
            out[tag] = json.load(f)
    return out


def compare(arch: str, shape: str, mesh_tag: str = "pod1") -> dict:
    variants = load_cell_variants(arch, shape, mesh_tag)
    if "baseline" not in variants:
        print(f"no baseline artifact for {arch}/{shape}")
        return {}
    rows, result = [], {}
    base = analyze(variants["baseline"])
    for tag in sorted(variants, key=lambda t: (t != "baseline", t)):
        a = analyze(variants[tag])
        if a is None:
            rows.append([tag, variants[tag].get("status", "?"),
                         "--", "--", "--", "--", "--"])
            continue
        dom_t = {"compute": a["t_compute_s"], "memory": a["t_memory_s"],
                 "collective": a["t_collective_s"]}[a["dominant"]]
        base_bound = max(base["t_compute_s"], base["t_memory_s"],
                         base["t_collective_s"])
        bound = max(dom_t, a["t_compute_s"])
        speedup = base_bound / bound if bound else float("inf")
        mem = variants[tag].get("memory", {})
        hbm = (mem.get("argument_bytes", 0) + mem.get("temp_bytes", 0)
               + mem.get("output_bytes", 0)) / 1e9
        result[tag] = {**a, "bound_s": bound, "speedup_vs_baseline": speedup,
                       "hbm_gb": hbm}
        rows.append([
            tag, a["dominant"], fmt_s(a["t_compute_s"]),
            fmt_s(a["t_memory_s"]), fmt_s(a["t_collective_s"]),
            f"{hbm:.1f}GB", f"x{speedup:.2f}",
        ])
    print(format_table(
        f"§Perf — {arch}/{shape} ({mesh_tag}) variants",
        ["variant", "bottleneck", "compute", "memory", "collective",
         "HBM/dev", "speedup"],
        rows,
    ))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", action="append", default=[],
                    help="arch/shape (repeatable)")
    ap.add_argument("--mesh", default="pod1")
    args = ap.parse_args(argv)
    cells = args.cell or []
    all_results = {}
    for cell in cells:
        arch, shape = cell.split("/")
        all_results[cell] = compare(arch, shape, args.mesh)
    if all_results:
        save_results("perf_variants", all_results)
    return all_results


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    main()

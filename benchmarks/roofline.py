"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape) on the single-pod 16x16 mesh, derive the three terms:

    compute    = HLO_FLOPs / (chips x 197e12 FLOP/s)      [bf16 peak / chip]
    memory     = HLO_bytes / (chips x 819e9 B/s)          [HBM bw / chip]
    collective = collective_bytes_per_device / 50e9 B/s   [ICI link bw]

``cost_analysis()`` on the CPU dry-run backend reports whole-program FLOPs
and bytes; we divide by chip count for per-chip terms.  Collective bytes are
parsed from the post-SPMD HLO (they are per-device already).  The dominant
term is the bottleneck the §Perf loop iterates on; MODEL_FLOPS/HLO_FLOPs
flags remat/redundancy waste.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from .common import format_table, save_results

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")

PEAK_FLOPS = 197e12          # TPU v5e bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link
CHIPS = {"16x16": 256, "2x16x16": 512}


def model_flops(rec: dict) -> float:
    """6·N_active·D for the step kind (train: x3 for fwd+bwd; decode: D=1·B)."""
    n = rec.get("params_active") or rec.get("params_total") or 0
    shape = rec["shape"]
    batch = {"train_4k": 256, "prefill_32k": 32, "decode_32k": 128,
             "long_500k": 1}[shape]
    seq = {"train_4k": 4096, "prefill_32k": 32768, "decode_32k": 1,
           "long_500k": 1}[shape]
    tokens = batch * seq
    mult = 6.0 if shape == "train_4k" else 2.0   # fwd+bwd vs fwd-only
    return mult * n * tokens


def analyze(rec: dict) -> Optional[dict]:
    if rec.get("status") != "OK":
        return None
    chips = CHIPS.get(rec["mesh"], 256)
    flops = rec["cost"].get("flops", 0.0)
    byts = rec["cost"].get("bytes accessed", 0.0)
    # cost_analysis flops on the dry-run backend are whole-program
    t_compute = flops / (chips * PEAK_FLOPS)
    t_memory = byts / (chips * HBM_BW)
    coll = rec.get("collectives", {})
    coll_bytes = sum(v for k, v in coll.items() if k != "count")
    t_coll = coll_bytes / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "hlo_flops": flops, "hlo_bytes": byts, "coll_bytes": coll_bytes,
        "model_flops": mf,
        "useful_ratio": (mf / (flops * chips)) if flops else 0.0,
        "roofline_frac": (
            min(1.0, terms["compute"] / max(terms.values()))
            if max(terms.values()) > 0 else 0.0
        ),
    }


def load_records(mesh_tag: str = "pod1") -> List[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(ARTIFACTS, f"*__{mesh_tag}.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.2f}ms"
    return f"{x * 1e6:.1f}us"


def run() -> dict:
    recs = load_records("pod1")
    if not recs:
        print("no dry-run artifacts found — run "
              "`PYTHONPATH=src python -m repro.launch.dryrun --both-meshes` first")
        return {}
    rows, out = [], {}
    skips = []
    for rec in recs:
        a = analyze(rec)
        key = f"{rec['arch']}/{rec['shape']}"
        if a is None:
            skips.append([rec["arch"], rec["shape"], rec.get("status", "?")])
            continue
        out[key] = a
        rows.append([
            rec["arch"], rec["shape"], fmt_s(a["t_compute_s"]),
            fmt_s(a["t_memory_s"]), fmt_s(a["t_collective_s"]),
            a["dominant"],
            f"{a['useful_ratio'] * 100:.0f}%",
            f"{a['roofline_frac'] * 100:.0f}%",
        ])
    print(format_table(
        "Roofline terms per (arch x shape), single-pod 16x16, v5e constants",
        ["arch", "shape", "compute", "memory", "collective", "bottleneck",
         "useful FLOPs", "roofline frac"],
        rows,
    ))
    if skips:
        print(format_table("Skipped cells", ["arch", "shape", "status"], skips))
    save_results("roofline", out)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    run()

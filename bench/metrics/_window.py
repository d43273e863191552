"""Shared arithmetic of the metric readers over one run's window."""
from __future__ import annotations

import statistics
from typing import List, Optional


def quantile(values: List[float], q: float) -> float:
    """The ``q`` quantile (0 < q < 1), by ``statistics.quantiles``'
    exclusive method over 100 cut points."""
    if len(values) == 1:
        return values[0]
    cuts = statistics.quantiles(values, n=100, method="exclusive")
    return cuts[round(q * 100) - 1]


def window_latencies_ms(run) -> Optional[List[float]]:
    """Per window of every chunk handed over: rows on the host minus the
    due time of the window's last tweet (open loop only)."""
    w = run.window
    if run.traffic["loop"] != "open":
        return None
    geo_r = run.config["execution"]
    r = 1
    step = geo_r.get("window_step")
    if step is not None and step < geo_r["window_capacity"]:
        r = -(-geo_r["window_capacity"] // step)
    out = []
    for rec in w.recs:
        for wi in range(int(geo_r["max_windows"])):
            due = w.t0 + float(rec.unit_due[wi + r - 1])
            out.append((rec.done - due) * 1e3)
    return out


def busiest(run):
    t = run.trace
    return None if t is None else t["busiest"]

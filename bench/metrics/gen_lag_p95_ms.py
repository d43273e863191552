"""How late the open-loop generator handed each chunk to the system, past
its due time: 95th percentile, in ms."""
from bench.metrics._window import quantile


def read(run):
    if run.traffic["loop"] != "open":
        return None
    w = run.window
    return quantile([(r.handed - (w.t0 + r.due)) * 1e3 for r in w.recs], 0.95)

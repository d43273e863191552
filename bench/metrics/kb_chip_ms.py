"""Device milliseconds per chunk on the busiest chip other than the
pipelined sink's: under round_robin the KB operators' chips, of which the
slower sets the upstream pace."""
from bench.metrics._chips import sink_plane


def read(run):
    if run.trace is None:
        return None
    sink = sink_plane()
    others = [s for plane, s in run.trace["busy_s"].items() if plane != sink]
    return 1e3 * max(others) / len(run.window.recs) if others else None

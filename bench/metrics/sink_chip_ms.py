"""Device milliseconds per chunk on the pipelined sink's chip (window
packing, the sink's scans and joins, finalize, publish): the busy time,
the union of its op intervals, of the plane ``_chips.sink_plane`` names."""
from bench.metrics._chips import sink_plane


def read(run):
    if run.trace is None:
        return None
    busy = run.trace["busy_s"].get(sink_plane())
    return None if busy is None else 1e3 * busy / len(run.window.recs)

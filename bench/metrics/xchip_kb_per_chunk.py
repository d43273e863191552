"""Kilobytes (1000 bytes) per chunk that cross chips between pipelined
stages: the inbound and outbound payload bytes of every operator the
system reports as placed on another device than the sink
(``last_stats["operators"][op]["channel"]``, counted on the metrics path
from the payloads' static shapes)."""


def read(run):
    if not run.counters:
        return None
    chans = [e["channel"] for e in run.counters.values() if "channel" in e]
    if not chans:
        return None
    return sum(c["in_bytes_per_chunk"] + c["out_bytes_per_chunk"]
               for c in chans if c["cross_device"]) / 1e3

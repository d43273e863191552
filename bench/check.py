"""The comparison that decides ``correct``.

Every window of the chunks in the sample (drawn from the seed among the
chunks the measured window handed to the system) is evaluated by the
plain reference over the same rows, and its row set compared with the rows
the system published for that window (published rows carry their window's
largest timestamp).  Exact comparison: a window whose sets differ in any
row is a mismatch.

The numbers compared, each with its limit:

* ``windows_mismatched`` (limit 0): sampled windows whose rows differ;
* ``windows_overflowed`` (limit 0): windows any operator clipped, over the
  whole run (the system's own overflow counters);
* ``chunks_unanswered`` (limit 0): chunks handed to the system with no
  published result;
* ``compiles_in_window`` (limit 0): compilations or compile-cache loads
  inside the measured window;
* ``degraded_or_restarted`` (limit 0): chunks the system degraded or
  restarted (pipelined recovery counters).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Set, Tuple

import numpy as np

from bench.reference.common import chunk_windows

LIMITS = {
    "windows_mismatched": 0,
    "windows_overflowed": 0,
    "chunks_unanswered": 0,
    "compiles_in_window": 0,
    "degraded_or_restarted": 0,
}


def published(out: tuple) -> Dict[int, Set[Tuple[int, int, int]]]:
    """The system's published rows of one chunk, by window timestamp."""
    s, p, o, ts, _, valid = out
    by_ts: Dict[int, Set[Tuple[int, int, int]]] = defaultdict(set)
    for a, b, c, t in zip(s[valid].tolist(), p[valid].tolist(),
                          o[valid].tolist(), ts[valid].tolist()):
        by_ts[t].add((a, b, c))
    return dict(by_ts)


def expected(rows, geometry, ref, kb_index) -> Dict[int, Set[Tuple[int, int, int]]]:
    """The reference's rows of one chunk, by window timestamp."""
    s, p, o, ts, graph, valid = rows
    host = list(zip(s[valid].tolist(), p[valid].tolist(), o[valid].tolist(),
                    ts[valid].tolist(), graph[valid].tolist()))
    out = {}
    for win in chunk_windows(host, geometry.window, geometry.max_windows,
                             geometry.step):
        got = ref.evaluate(win, kb_index)
        if got:
            out[max(r[3] for r in win)] = got
    return out


def sample(n: int, cap: int, rng: np.random.Generator) -> List[int]:
    if n <= cap:
        return list(range(n))
    return sorted(rng.choice(n, cap, replace=False).tolist())


def compare(recs, world, ref, kb_index, picks: List[int]) -> Tuple[int, int]:
    """``(windows compared, windows mismatched)`` over chunks ``picks``."""
    compared = mismatched = 0
    for i in picks:
        rec = recs[i]
        if rec.out is None:
            continue
        want = expected(world.chunk_rows(rec.k), world.geometry, ref, kb_index)
        got = published(rec.out)
        keys = set(want) | set(got)
        compared += world.geometry.max_windows
        mismatched += sum(want.get(t) != got.get(t) for t in keys)
    return compared, mismatched

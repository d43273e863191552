"""Plain reference: windows and the KB index, in straightforward Python.

Nothing here imports the system under test or reads anything it made: the
rows come from the benchmark's generators, the ids from ``bench/gen``.

Windowing follows the paper's count windows (§4.4): triples arrive in
timestamp order; an RDF-graph event (a tweet) is never split; a window
holds at most ``capacity`` triples.  Sliding windows (``STEP m``) pack the
stream graph by graph into slides of ``m`` triples, and window ``w`` is
slides ``w .. w+R-1`` with ``R = ceil(capacity / m)``.  A chunk is packed on
its own (no state crosses a chunk) into at most ``max_windows`` windows; the
benchmark's sliding chunks repeat the previous chunk's last ``R - 1``
slides, so every window of the stream lies in exactly one chunk.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Set, Tuple

Row = Tuple[int, int, int, int, int]   # (s, p, o, ts, graph)


def chunk_windows(rows: List[Row], capacity: int, max_windows: int,
                  step: Optional[int] = None) -> List[List[Row]]:
    """The non-empty windows of one chunk, in order."""
    if step is None or step >= capacity:
        unit_cap, r = capacity, 1
    else:
        unit_cap, r = step, -(-capacity // step)
    max_units = max_windows + r - 1
    rows = sorted(rows, key=lambda row: (row[3], row[4]))
    graphs: List[List[Row]] = []
    for row in rows:
        if graphs and graphs[-1][-1][4] == row[4]:
            graphs[-1].append(row)
        else:
            graphs.append([row])
    units: List[List[Row]] = [[]]
    fill = 0
    for g in graphs:
        g = g[:unit_cap]
        if fill + len(g) > unit_cap:
            units.append([])
            fill = 0
        units[-1].extend(g)
        fill += len(g)
    units = units[:max_units]
    units += [[] for _ in range(max_units - len(units))]
    windows = [sum(units[w:w + r], []) for w in range(max_windows)]
    return [w for w in windows if w]


class KBIndex:
    """The KB rows a query reads, indexed by ``(p, s)``, with the
    ``rdf:type / rdfs:subClassOf*`` test of hierarchy reasoning."""

    def __init__(self, rows, type_pred: int, subclass_pred: int) -> None:
        self.by_ps: Dict[Tuple[int, int], List[int]] = defaultdict(list)
        self.children: Dict[int, List[int]] = defaultdict(list)
        for s, p, o in rows:
            s, p, o = int(s), int(p), int(o)
            self.by_ps[(p, s)].append(o)
            if p == subclass_pred:
                self.children[o].append(s)
        self.type_pred = type_pred
        self._below: Dict[int, Set[int]] = {}

    def objects(self, p: int, s: int) -> List[int]:
        return self.by_ps.get((p, s), [])

    def descendants(self, root: int) -> Set[int]:
        """Classes ``c`` with ``c subClassOf* root``, the root included."""
        if root not in self._below:
            seen, frontier = {root}, [root]
            while frontier:
                nxt = []
                for c in frontier:
                    for ch in self.children.get(c, ()):
                        if ch not in seen:
                            seen.add(ch)
                            nxt.append(ch)
                frontier = nxt
            self._below[root] = seen
        return self._below[root]

    def is_a(self, entity: int, root: int) -> bool:
        """``entity rdf:type/rdfs:subClassOf* root``."""
        below = self.descendants(root)
        return any(c in below for c in self.objects(self.type_pred, entity))

    def path(self, start: int, preds) -> List[int]:
        """Every end of the property path ``start p1/p2/... ?end``."""
        ends = [start]
        for p in preds:
            ends = [o for e in ends for o in self.objects(p, e)]
        return ends


def by_predicate(window: List[Row]) -> Dict[int, List[Tuple[int, int]]]:
    """``p -> [(s, o)]`` over one window's triples."""
    out: Dict[int, List[Tuple[int, int]]] = defaultdict(list)
    for s, p, o, _, _ in window:
        out[p].append((s, o))
    return out

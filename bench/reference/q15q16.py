"""Plain reference of Q15 and Q16 on one entity (bench/queries/q15q16.rq).

For each tweet of a window and each entity it mentions that is a
``dbo:MusicalArtist`` by hierarchy reasoning (Q15), every country code at
the end of the entity's ``birthPlace/country/countryCode`` path (Q16)
gives the triple ``(tweet, out:artistCode, code)``.
"""
from __future__ import annotations

from typing import List, Set, Tuple

from bench.gen import layout as L

from .common import KBIndex, Row, by_predicate

P, T = L.PRED, L.TERM
CC_PATH = (P["dbo:birthPlace"], P["dbo:country"], P["dbo:countryCode"])


def evaluate(window: List[Row], kb: KBIndex) -> Set[Tuple[int, int, int]]:
    out = set()
    for tweet, ent in by_predicate(window).get(P["schema:mentions"], ()):
        if kb.is_a(ent, T["dbo:MusicalArtist"]):
            for cc in kb.path(ent, CC_PATH):
                out.add((tweet, P["out:artistCode"], cc))
    return out

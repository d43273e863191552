"""Plain reference of the paper's CQuery1 (bench/queries/cquery1.rq).

For each tweet of a window: an artist and a show it mentions (by hierarchy
reasoning), its positive and negative sentiment with ``?pos >= 0``, the
artist's ``birthPlace/country/countryCode``, and at least one of likes or
shares (the UNION; the OPTIONAL shares add no condition).  Each binding
constructs four triples about the artist; the result is their set.
"""
from __future__ import annotations

from typing import List, Set, Tuple

from bench.gen import layout as L

from .common import KBIndex, Row, by_predicate

P, T = L.PRED, L.TERM
CC_PATH = (P["dbo:birthPlace"], P["dbo:country"], P["dbo:countryCode"])


def evaluate(window: List[Row], kb: KBIndex) -> Set[Tuple[int, int, int]]:
    by = by_predicate(window)

    def values(pred):
        out = {}
        for s, o in by.get(pred, ()):
            out.setdefault(s, []).append(o)
        return out

    mentions = values(P["schema:mentions"])
    pos, neg = values(P["onyx:positiveEmotion"]), values(P["onyx:negativeEmotion"])
    likes, shares = values(P["schema:likes"]), values(P["schema:shares"])
    out = set()
    for tweet, ents in mentions.items():
        if not (likes.get(tweet) or shares.get(tweet)):
            continue
        shows = [e for e in ents if kb.is_a(e, T["dbo:TelevisionShow"])]
        for a in ents:
            if not shows or not kb.is_a(a, T["dbo:MusicalArtist"]):
                continue
            codes = kb.path(a, CC_PATH)
            for s in shows:
                for pv in pos.get(tweet, ()):
                    if pv < L.number(0):
                        continue
                    for nv in neg.get(tweet, ()):
                        for cc in codes:
                            out.update((
                                (a, P["out:coMentionedWith"], s),
                                (a, P["out:posSentiment"], pv),
                                (a, P["out:negSentiment"], nv),
                                (a, P["out:countryCode"], cc),
                            ))
    return out

"""The benchmark's own term-id table.

Every id the benchmark feeds the system is assigned here, from the seed's
data and this table alone, so the plain reference never reads an id the
system made.  The layout follows the system's wire format for terms
(``uint32``; predicates below 2**12, URIs and strings in the next 2**20,
numeric literals as fixed point above 2**30); ``bench/run.py`` interns the
named entries into the system's vocabulary in table order and refuses to
run if the system assigns any other id.

* named predicates: ``1 ..`` in :data:`PREDICATES` order (interned);
* annotation and filler predicates: raw ids in bands the vocabulary never
  reaches (it would have to intern two thousand predicates first);
* named terms: the query constants, interned from ``PRED_SPACE``;
* every other term: raw ids in ``[TERM_LO, TERM_HI)``, above a gap left for
  the terms the system interns itself (decomposition's row base).
"""
from __future__ import annotations

PRED_SPACE = 1 << 12
TERM_SPACE = 1 << 20
NUM_BASE = 1 << 30
NUM_OFFSET = 1 << 29          # fixed-point zero: value v -> NUM_BASE+NUM_OFFSET+round(100 v)
NUM_SCALE = 100

PREDICATES = (
    "schema:mentions",
    "onyx:positiveEmotion",
    "onyx:negativeEmotion",
    "schema:likes",
    "schema:shares",
    "rdf:type",
    "rdfs:subClassOf",
    "dbo:birthPlace",
    "dbo:country",
    "dbo:countryCode",
    "out:coMentionedWith",
    "out:posSentiment",
    "out:negSentiment",
    "out:countryCode",
    "out:artistCode",
)
PRED = {name: i + 1 for i, name in enumerate(PREDICATES)}

NAMED_TERMS = ("dbo:MusicalArtist", "dbo:TelevisionShow")
TERM = {name: PRED_SPACE + i for i, name in enumerate(NAMED_TERMS)}

FILLER_PRED_LO = 2048         # filler predicates: [2048, 2048 + n)
ANNOT_PRED_LO = 3072          # tweet annotation predicates: [3072, 3072 + n)
CLOSURE_PRED_LO = PRED_SPACE - 64   # reserved by the system for closure pairs

TERM_LO = PRED_SPACE + 4096
TERM_HI = PRED_SPACE + TERM_SPACE


def number(fixed: int) -> int:
    """Id of the numeric literal ``fixed / NUM_SCALE``."""
    return NUM_BASE + NUM_OFFSET + int(fixed)

"""The DBpedia-like KB (``kb.py``) and the TweetsKB-like stream
(``stream.py``) over the id table of ``layout.py``: the generator that a
configuration names with ``"generator": "dbpedia"``.

A generator is a module ``bench/gen/<name>.py``, found by that name, that
gives:

* ``PRED`` and ``TERM``: the named predicate and term ids, which the
  harness interns into the system's vocabulary in this order;
* ``TYPE_PRED`` and ``SUBCLASS_PRED``: the ids of the reference's
  ``rdf:type / rdfs:subClassOf*`` test;
* ``generate(config, traffic, rng) -> (kb_shape, used, stream)``: the KB's
  shape (``total_rows`` and ``filler_predicates`` for the device build),
  the queried KB rows ``[n, 3]`` uint32, and the event stream as a
  ``stream.Stream``; ``rng(i)`` is the seed's independent generator ``i``.
"""
from __future__ import annotations

from . import kb as K
from . import layout as L
from . import stream as G

PRED, TERM = L.PRED, L.TERM
TYPE_PRED, SUBCLASS_PRED = PRED["rdf:type"], PRED["rdfs:subClassOf"]


def generate(config: dict, traffic: dict, rng):
    kb_shape = K.KBShape.from_config(config["kb"])
    st_shape = G.StreamShape.from_config(config["stream"])
    alloc = K.allocate(kb_shape, st_shape.tweets, st_shape.hashtags,
                       st_shape.users)
    used = K.used_rows(kb_shape, alloc, rng(0))
    stream = G.generate(st_shape, alloc, rng(1),
                        float(traffic["mention_zipf"]))
    return kb_shape, used, stream

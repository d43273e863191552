"""DBpedia-like background KB, made from the seed.

Two parts:

* the used part, on the host (numpy): the class hierarchies under
  ``dbo:MusicalArtist`` and ``dbo:TelevisionShow`` (leaf -> mid -> root,
  as ``data/dbpedia.py`` shapes them), one ``rdf:type`` row per artist and
  show, one ``dbo:birthPlace`` per artist, ``dbo:country`` per place and
  ``dbo:countryCode`` per country.  This is what the queries read, and what
  the plain reference indexes;
* the filler, on the device: random ``(s, p, o)`` rows over many filler
  predicates, subjects and objects drawn from the whole term band, so the
  artists and shows carry unused properties as DBpedia entities do.  Its
  predicates are disjoint from every query predicate.

:func:`build_device_kb` lays both out in the system's ``KnowledgeBase``
format in one jitted call: composite keys and two stable sorts on the
device, equal to ``core/kb.build_kb`` of the same rows (a CPU test checks).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

from . import layout as L


@dataclasses.dataclass(frozen=True)
class KBShape:
    artist_leaf_classes: int
    show_leaf_classes: int
    artists: int
    shows: int
    places: int
    countries: int
    total_rows: int
    filler_predicates: int

    @staticmethod
    def from_config(block: Dict) -> "KBShape":
        return KBShape(**{f.name: int(block[f.name])
                          for f in dataclasses.fields(KBShape)})

    def classes(self, leaves: int) -> int:
        """Non-root classes of one hierarchy (mids plus leaves)."""
        return leaves + max(1, leaves // 3)


def allocate(kb: KBShape, tweets: int, hashtags: int, users: int
             ) -> Dict[str, Tuple[int, int]]:
    """``name -> (first id, count)`` of every raw term range, in order."""
    sizes = (
        ("artist_classes", kb.classes(kb.artist_leaf_classes)),
        ("show_classes", kb.classes(kb.show_leaf_classes)),
        ("artists", kb.artists), ("shows", kb.shows),
        ("places", kb.places), ("countries", kb.countries),
        ("codes", kb.countries), ("tweets", tweets),
        ("hashtags", hashtags), ("users", users),
    )
    out, nxt = {}, L.TERM_LO
    for name, n in sizes:
        out[name] = (nxt, int(n))
        nxt += int(n)
    if nxt > L.TERM_HI:
        raise ValueError("terms need %d ids; the term band holds %d"
                         % (nxt - L.TERM_LO, L.TERM_HI - L.TERM_LO))
    return out


def _ids(rng_: Tuple[int, int]) -> np.ndarray:
    lo, n = rng_
    return np.arange(lo, lo + n, dtype=np.uint32)


def _hierarchy(first: int, leaves: int, root: int) -> np.ndarray:
    """subClassOf rows: mids under the root, leaf i under mid i % mids."""
    mids = max(1, leaves // 3)
    mid_ids = np.arange(first, first + mids, dtype=np.uint32)
    leaf_ids = np.arange(first + mids, first + mids + leaves, dtype=np.uint32)
    sub = L.PRED["rdfs:subClassOf"]
    rows = [np.stack([mid_ids, np.full(mids, sub, np.uint32),
                      np.full(mids, root, np.uint32)], 1),
            np.stack([leaf_ids, np.full(leaves, sub, np.uint32),
                      mid_ids[np.arange(leaves) % mids]], 1)]
    return np.concatenate(rows)


def used_rows(kb: KBShape, alloc, rng: np.random.Generator) -> np.ndarray:
    """The queried part of the KB as ``[n, 3]`` uint32 ``(s, p, o)`` rows."""
    P = L.PRED
    a_cls, s_cls = _ids(alloc["artist_classes"]), _ids(alloc["show_classes"])
    artists, shows = _ids(alloc["artists"]), _ids(alloc["shows"])
    places, countries = _ids(alloc["places"]), _ids(alloc["countries"])
    codes = _ids(alloc["codes"])

    def rel(s, p, o):
        return np.stack([s, np.full(len(s), p, np.uint32), o], 1)

    parts = [
        _hierarchy(alloc["artist_classes"][0], kb.artist_leaf_classes,
                   L.TERM["dbo:MusicalArtist"]),
        _hierarchy(alloc["show_classes"][0], kb.show_leaf_classes,
                   L.TERM["dbo:TelevisionShow"]),
        rel(countries, P["dbo:countryCode"], codes),
        rel(places, P["dbo:country"],
            countries[rng.integers(0, len(countries), len(places))]),
        rel(artists, P["rdf:type"],
            a_cls[rng.integers(0, len(a_cls), len(artists))]),
        rel(artists, P["dbo:birthPlace"],
            places[rng.integers(0, len(places), len(artists))]),
        rel(shows, P["rdf:type"],
            s_cls[rng.integers(0, len(s_cls), len(shows))]),
    ]
    return np.concatenate(parts).astype(np.uint32)


def q15q16_used_rows(kb: KBShape) -> int:
    """Rows of the used KB that pruning keeps for the Q15-and-Q16 operator:
    every subClassOf row, the artists' rdf:type and birthPlace rows, and
    the country and countryCode rows."""
    return (kb.classes(kb.artist_leaf_classes)
            + kb.classes(kb.show_leaf_classes)
            + 2 * kb.artists + kb.places + kb.countries)


def jax_key(seed: int):
    """A JAX PRNG key from a seed of any size (jax.random takes 32 bits)."""
    import jax

    state = np.random.SeedSequence(abs(int(seed))).generate_state(1)
    return jax.random.key(int(state[0]))


def _rows(key, used_cols, kb: KBShape):
    """Unsorted ``(s, p, o)``: the used rows, then the seeded filler."""
    import jax
    import jax.numpy as jnp

    us, up, uo = used_cols
    n_fill = kb.total_rows - us.shape[0]
    if n_fill < 0:
        raise ValueError("total_rows %d is below the used KB's %d rows"
                         % (kb.total_rows, us.shape[0]))
    k_s, k_p, k_o = jax.random.split(key, 3)

    def draw(k, lo, hi):
        return jax.random.randint(k, (n_fill,), lo, hi,
                                  jnp.int32).astype(jnp.uint32)

    return (jnp.concatenate([us, draw(k_s, L.TERM_LO, L.TERM_HI)]),
            jnp.concatenate([up, draw(k_p, L.FILLER_PRED_LO,
                                      L.FILLER_PRED_LO + kb.filler_predicates)]),
            jnp.concatenate([uo, draw(k_o, L.TERM_LO, L.TERM_HI)]))


def _layout(s, p, o):
    """The two key-sorted views of ``core/kb.KnowledgeBase``."""
    import jax
    import jax.numpy as jnp

    from repro.core.kb import KnowledgeBase
    from repro.core.rdf import composite_key

    def view(anchor):
        return jax.lax.sort((composite_key(p, anchor), s, p, o),
                            num_keys=1, is_stable=True)

    key_ps, s_ps, p_ps, o_ps = view(s)
    key_po, s_po, p_po, o_po = view(o)
    return KnowledgeBase(s_ps=s_ps, p_ps=p_ps, o_ps=o_ps, key_ps=key_ps,
                         s_po=s_po, p_po=p_po, o_po=o_po, key_po=key_po,
                         valid=jnp.ones((s.shape[0],), bool))


def _device_inputs(used: np.ndarray, seed: int, device):
    import jax

    cols = tuple(jax.device_put(np.ascontiguousarray(used[:, i]), device)
                 for i in range(3))
    return jax.device_put(jax_key(seed), device), cols


def device_rows(used: np.ndarray, kb: KBShape, seed: int, device=None):
    """The KB's rows before layout, as three device columns (for tests)."""
    import jax

    key, cols = _device_inputs(used, seed, device)
    return jax.jit(_rows, static_argnums=2)(key, cols, kb)


def build_device_kb(used: np.ndarray, kb: KBShape, seed: int, device=None):
    """The whole KB as the system's ``KnowledgeBase`` on ``device``, made
    in one jitted call: the used rows, then ``total_rows - len(used)``
    filler rows, laid out as ``core/kb.build_kb`` lays out the same rows."""
    import jax

    key, cols = _device_inputs(used, seed, device)
    return jax.jit(lambda k, c: _layout(*_rows(k, c, kb)))(key, cols)

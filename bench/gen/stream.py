"""TweetsKB-like event stream, made from the seed, and its chunking.

Each tweet is one RDF-graph event whose triples share its timestamp.  The
part the queries read is shaped as ``data/tweets.py`` makes it: 2-4
distinct entity mentions, a positive and a negative sentiment score in
[0, 5], and likes and shares on 80% of tweets.  The rest of a tweet is the
TweetsKB annotation that no query reads (creation date, author, hashtags,
user mentions, entity-link confidences, interaction statistics), enough to
reach the paper's shape of about 38 triples per tweet (60k tweets, 2.3M
triples).

:func:`chunk_stream` packs tweets greedily into units of the engine's
window (tumbling) or slide (sliding) capacity, never splitting a tweet, and
groups whole units into chunks, so the engine's own packing of a chunk
finds exactly those units and drops nothing.  Sliding chunks overlap by the
slides a window spans beyond its first, so no window of the stream is lost
at a chunk boundary.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple

import numpy as np

from . import layout as L

# annotation predicates (raw ids from ANNOT_PRED_LO) and their object kinds
ANNOTATIONS = (
    ("dc:created", "number"),
    ("sioc:has_creator", "user"),
    ("sioc:id", "number"),
    ("sioc_t:Tag", "hashtag"),
    ("schema:mentionsUser", "user"),
    ("nee:hasMatchedURI", "term"),
    ("nee:confidence", "number"),
    ("schema:userInteractionCount", "number"),
)


@dataclasses.dataclass(frozen=True)
class StreamShape:
    tweets: int
    mentions_min: int
    mentions_max: int
    likes_shares_share: float
    annotations_min: int
    annotations_max: int
    hashtags: int
    users: int

    @staticmethod
    def from_config(block: Dict) -> "StreamShape":
        cast = {"float": float, "int": int}
        return StreamShape(**{f.name: cast[f.type](block[f.name])
                              for f in dataclasses.fields(StreamShape)})


class Stream(NamedTuple):
    s: np.ndarray          # [n] uint32, tweet order
    p: np.ndarray
    o: np.ndarray
    tweet_rows: np.ndarray  # [T] triples per tweet


def _mention_pool_draw(rng, pool: np.ndarray, shape, zipf: float):
    if zipf <= 0:
        return pool[rng.integers(0, len(pool), shape)]
    ranks = np.arange(1, len(pool) + 1, dtype=np.float64)
    prob = ranks ** -zipf
    hot = rng.permutation(pool)
    return hot[rng.choice(len(pool), size=shape, p=prob / prob.sum())]


def generate(sh: StreamShape, alloc, rng: np.random.Generator,
             mention_zipf: float = 0.0) -> Stream:
    P = L.PRED
    T = sh.tweets
    tweets = np.arange(alloc["tweets"][0], alloc["tweets"][0] + T,
                       dtype=np.uint32)
    pool = np.concatenate([np.arange(lo, lo + n, dtype=np.uint32)
                           for lo, n in (alloc["artists"], alloc["shows"])])
    m = rng.integers(sh.mentions_min, sh.mentions_max + 1, T)
    ment = _mention_pool_draw(rng, pool, (T, sh.mentions_max), mention_zipf)
    cols = np.arange(sh.mentions_max)[None, :]
    while True:                      # mentions within a tweet are distinct
        live = np.where(cols < m[:, None], ment, 0)
        srt = np.sort(live, axis=1)
        dup = np.any((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] != 0), axis=1)
        if not dup.any():
            break
        ment[dup] = _mention_pool_draw(rng, pool, (int(dup.sum()),
                                                   sh.mentions_max),
                                       mention_zipf)
    pos = rng.integers(0, 501, T)
    neg = rng.integers(0, 501, T)
    ls = rng.random(T) < sh.likes_shares_share
    likes = 100 * rng.integers(0, 1000, T)
    shares = 100 * rng.integers(0, 500, T)
    a = rng.integers(sh.annotations_min, sh.annotations_max + 1, T)
    amax = sh.annotations_max
    a_kind = rng.integers(0, len(ANNOTATIONS), (T, amax))
    objs = np.zeros((T, amax), np.uint32)
    for k, (_, kind) in enumerate(ANNOTATIONS):
        sel = a_kind == k
        n = int(sel.sum())
        if kind == "number":
            v = L.number(0) + rng.integers(0, 10 ** 6, n)
        elif kind == "term":
            v = rng.integers(L.TERM_LO, L.TERM_HI, n)
        else:
            lo, cnt = alloc[kind + "s"]
            v = lo + rng.integers(0, cnt, n)
        objs[sel] = v

    rows = m + 2 + 2 * ls + a
    width = sh.mentions_max + 4 + amax
    j = np.arange(width)[None, :]
    mj, lsj = m[:, None], (2 * ls)[:, None]
    Pm = np.zeros((T, width), np.uint32)
    Om = np.zeros((T, width), np.uint32)

    def put(mask, p, o):
        Pm[mask] = p[mask] if isinstance(p, np.ndarray) else p
        Om[mask] = o[mask]

    full = lambda v: np.broadcast_to(np.asarray(v, np.uint32)[:, None],
                                     (T, width))
    put(j < mj, P["schema:mentions"],
        np.pad(ment, ((0, 0), (0, width - sh.mentions_max))))
    put(j == mj, P["onyx:positiveEmotion"], full(L.number(0) + pos))
    put(j == mj + 1, P["onyx:negativeEmotion"], full(L.number(0) + neg))
    put((j == mj + 2) & ls[:, None], P["schema:likes"], full(L.number(0) + likes))
    put((j == mj + 3) & ls[:, None], P["schema:shares"],
        full(L.number(0) + shares))
    k = j - (mj + 2 + lsj)
    in_a = (k >= 0) & (k < a[:, None])
    kc = np.clip(k, 0, amax - 1)
    put(in_a, (L.ANNOT_PRED_LO + np.take_along_axis(a_kind, kc, 1)
               ).astype(np.uint32), np.take_along_axis(objs, kc, 1))
    live = j < rows[:, None]
    s = np.broadcast_to(tweets[:, None], (T, width))[live]
    return Stream(s=s.astype(np.uint32), p=Pm[live], o=Om[live],
                  tweet_rows=rows.astype(np.int64))


def pack_units(tweet_rows: np.ndarray, unit_cap: int) -> np.ndarray:
    """Greedy graph-preserving packing: the unit of every tweet."""
    unit = np.empty(len(tweet_rows), np.int64)
    u = fill = 0
    for i, n in enumerate(tweet_rows.tolist()):
        if n > unit_cap:
            raise ValueError("a tweet of %d triples exceeds the unit of %d"
                             % (n, unit_cap))
        if fill + n > unit_cap:
            u, fill = u + 1, 0
        fill += n
        unit[i] = u
    return unit


class Chunks(NamedTuple):
    s: np.ndarray          # [C, cap] uint32
    p: np.ndarray
    o: np.ndarray
    tweet: np.ndarray      # [C, cap] int64 tweet ordinal, -1 = pad
    valid: np.ndarray      # [C, cap] bool
    first_tweet: np.ndarray  # [C] first tweet ordinal of each chunk
    last_tweet: np.ndarray   # [C] last tweet ordinal
    unit_last_tweet: np.ndarray  # [C, U] last tweet ordinal of each unit
    new_rows: np.ndarray     # [C] triples of the chunk's last ``stride`` units


def chunk_stream(st: Stream, unit_cap: int, units_per_chunk: int,
                 stride: int) -> Chunks:
    """Chunks of ``units_per_chunk`` full units, chunk ``c`` starting at
    unit ``c * stride`` (a trailing partial chunk is left out).

    With sliding windows a chunk holds ``W + R - 1`` slides and yields the
    ``W`` windows that start in its first ``W`` slides; a stride of ``W``
    makes each chunk repeat the previous chunk's last ``R - 1`` slides, so
    every window of the stream is evaluated in exactly one chunk.  Each
    chunk's new triples (``new_rows``) are those of its last ``stride``
    units; tumbling windows have ``stride == units_per_chunk``."""
    if not 0 < stride <= units_per_chunk:
        raise ValueError("stride %d is not in [1, %d]"
                         % (stride, units_per_chunk))
    unit = pack_units(st.tweet_rows, unit_cap)
    n_chunks = (int(unit[-1] + 1) - units_per_chunk) // stride + 1
    cap = unit_cap * units_per_chunk
    starts = np.concatenate([[0], np.cumsum(st.tweet_rows)])
    tweet_of_row = np.repeat(np.arange(len(st.tweet_rows)), st.tweet_rows)
    out = {k: np.zeros((n_chunks, cap), np.uint32) for k in "spo"}
    tw = np.full((n_chunks, cap), -1, np.int64)
    first = np.zeros(n_chunks, np.int64)
    last = np.zeros(n_chunks, np.int64)
    ulast = np.zeros((n_chunks, units_per_chunk), np.int64)
    new = np.zeros(n_chunks, np.int64)
    for c in range(n_chunks):
        u0 = c * stride
        ts = np.nonzero((unit >= u0) & (unit < u0 + units_per_chunk))[0]
        first[c], last[c] = ts[0], ts[-1]
        r0, r1 = starts[ts[0]], starts[ts[-1] + 1]
        for k, col in zip("spo", (st.s, st.p, st.o)):
            out[k][c, :r1 - r0] = col[r0:r1]
        tw[c, :r1 - r0] = tweet_of_row[r0:r1]
        u = unit[ts] - u0
        ulast[c] = [ts[u == i][-1] for i in range(units_per_chunk)]
        new[c] = st.tweet_rows[ts[u >= units_per_chunk - stride]].sum()
    return Chunks(out["s"], out["p"], out["o"], tw, tw >= 0, first, last,
                  ulast, new)

"""The benchmark's generators: the device-built KB equals the system's
``build_kb`` of the same rows, the used KB has the paper's size, and the
chunking matches the plain reference's windowing."""
import numpy as np
import pytest

from bench import world as W
from bench.gen import kb as K
from bench.gen import layout as L
from bench.gen import stream as G
from bench.reference.common import chunk_windows
from bench import spec as S
from bench.spec import benchmark, config, query_text

SMALL = K.KBShape(artist_leaf_classes=6, show_leaf_classes=3, artists=50,
                  shows=20, places=10, countries=4, total_rows=4000,
                  filler_predicates=16)


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_device_kb_equals_build_kb(seed):
    from repro.core.kb import build_kb

    alloc = K.allocate(SMALL, 100, 10, 10)
    used = K.used_rows(SMALL, alloc, W.rng(seed, 0))
    s, p, o = (np.asarray(c) for c in K.device_rows(used, SMALL, seed))
    assert len(s) == SMALL.total_rows
    np.testing.assert_array_equal(np.stack([s, p, o], 1)[:len(used)], used)
    want = build_kb(s, p, o)
    got = K.build_device_kb(used, SMALL, seed)
    for field, a, b in zip(want._fields, want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), field)
    fill = p[len(used):]
    assert fill.min() >= L.FILLER_PRED_LO
    assert fill.max() < L.FILLER_PRED_LO + SMALL.filler_predicates


@pytest.mark.parametrize("name", ["cquery1-dbpedia", "q15q16-dbpedia-slide"])
def test_used_kb_is_the_papers_size(name):
    """Pruning keeps the paper's 103,075 rows for the Q15-and-Q16 operator
    (Table 1), at the configuration's own size, with no filler."""
    from repro.core.kb import build_kb
    from repro.core.planner import prune_kb_for
    from repro.core.sparql import parse_query

    cfg = config(benchmark(), name)
    shape = K.KBShape.from_config(cfg["kb"])
    assert K.q15q16_used_rows(shape) == 103075
    alloc = K.allocate(shape, 10, 10, 10)
    used = K.used_rows(shape, alloc, W.rng(1, 0))
    kb = build_kb(used[:, 0], used[:, 1], used[:, 2])
    q = parse_query(query_text("q15q16"), W.make_vocab(S.generator(cfg["generator"])))
    assert int(prune_kb_for(q, kb).count()) == 103075


@pytest.mark.parametrize("unit_cap,units,window,step", [
    (64, 4, 64, None), (16, 8, 64, 16)])
def test_chunks_pack_into_the_references_windows(unit_cap, units, window, step):
    sh = G.StreamShape(tweets=300, mentions_min=2, mentions_max=4,
                       likes_shares_share=0.8, annotations_min=2,
                       annotations_max=5, hashtags=20, users=20)
    alloc = K.allocate(SMALL, sh.tweets, sh.hashtags, sh.users)
    st = G.generate(sh, alloc, W.rng(3, 1))
    assert st.s.shape[0] == st.tweet_rows.sum()
    max_windows = units - (window // unit_cap) + 1
    ch = G.chunk_stream(st, unit_cap, units, max_windows)
    for c in range(ch.s.shape[0]):
        v = ch.valid[c]
        rows = list(zip(ch.s[c][v].tolist(), ch.p[c][v].tolist(),
                        ch.o[c][v].tolist(), ch.tweet[c][v].tolist(),
                        ch.tweet[c][v].tolist()))
        wins = chunk_windows(rows, window, max_windows, step)
        assert len(wins) == max_windows
        assert sum(len(w) for w in wins) >= v.sum()
        last = [w[-1][3] for w in wins]
        r = window // unit_cap
        assert last == ch.unit_last_tweet[c][r - 1:].tolist()


@pytest.mark.parametrize("unit_cap,units,r", [(64, 4, 1), (16, 8, 4),
                                              (250, 32, 4)])
def test_chunks_cover_every_window_of_the_stream_once(unit_cap, units, r):
    """Chunk by chunk, the windows evaluated are the stream's windows, each
    once: sliding chunks overlap by ``R - 1`` slides, and the triples each
    chunk adds sum to the stream's."""
    sh = G.StreamShape(tweets=2400, mentions_min=2, mentions_max=4,
                       likes_shares_share=0.8, annotations_min=2,
                       annotations_max=5, hashtags=20, users=20)
    alloc = K.allocate(SMALL, sh.tweets, sh.hashtags, sh.users)
    st = G.generate(sh, alloc, W.rng(5, 1))
    w = units - r + 1
    ch = G.chunk_stream(st, unit_cap, units, w)
    unit_last = [int(u[-1]) for u in np.split(
        np.arange(len(st.tweet_rows)),
        np.nonzero(np.diff(G.pack_units(st.tweet_rows, unit_cap)))[0] + 1)]
    # window g ends with unit g + r - 1; every window of whole chunks
    want = unit_last[r - 1:r - 1 + w * ch.s.shape[0]]
    got = [int(t) for c in range(ch.s.shape[0])
           for t in ch.unit_last_tweet[c][r - 1:]]
    assert got == want
    head = 0 if r == 1 else \
        st.tweet_rows[:ch.unit_last_tweet[0][r - 2] + 1].sum()
    assert ch.new_rows[0] == ch.valid[0].sum() - head
    assert head + ch.new_rows.sum() == \
        st.tweet_rows[:ch.last_tweet[-1] + 1].sum()


def test_stream_has_the_papers_shape():
    cfg = config(benchmark(), "cquery1-dbpedia")
    sh = G.StreamShape.from_config(cfg["stream"])
    assert sh.tweets == 60000
    mean = (sh.mentions_min + sh.mentions_max) / 2 + 2 \
        + 2 * sh.likes_shares_share \
        + (sh.annotations_min + sh.annotations_max) / 2
    assert 37 <= mean <= 39          # 2.3M triples / 60k tweets

"""The plain reference on hand-made windows: CQuery1's UNION, OPTIONAL,
FILTER and hierarchy reasoning, and Q15-and-Q16's path."""
from bench.gen import layout as L
from bench.reference import cquery1, q15q16
from bench.reference.common import KBIndex, chunk_windows

P, T = L.PRED, L.TERM
A_CLS, S_CLS, A_LEAF = 9001, 9002, 9003
ART, SHOW, OTHER, PLACE, CTRY, CODE = 9100, 9200, 9300, 9400, 9500, 9600
KB = KBIndex([
    (A_CLS, P["rdfs:subClassOf"], T["dbo:MusicalArtist"]),
    (A_LEAF, P["rdfs:subClassOf"], A_CLS),
    (S_CLS, P["rdfs:subClassOf"], T["dbo:TelevisionShow"]),
    (ART, P["rdf:type"], A_LEAF),
    (SHOW, P["rdf:type"], S_CLS),
    (OTHER, P["rdf:type"], 9999),
    (ART, P["dbo:birthPlace"], PLACE),
    (OTHER, P["dbo:birthPlace"], PLACE),
    (PLACE, P["dbo:country"], CTRY),
    (CTRY, P["dbo:countryCode"], CODE),
], P["rdf:type"], P["rdfs:subClassOf"])


def tweet(t, ts, mentions, pos=100, neg=50, likes=True, shares=False):
    rows = [(t, P["schema:mentions"], e, ts, ts) for e in mentions]
    rows += [(t, P["onyx:positiveEmotion"], L.number(pos), ts, ts),
             (t, P["onyx:negativeEmotion"], L.number(neg), ts, ts)]
    if likes:
        rows.append((t, P["schema:likes"], L.number(300), ts, ts))
    if shares:
        rows.append((t, P["schema:shares"], L.number(400), ts, ts))
    return rows


def test_cquery1_constructs_the_four_templates():
    got = cquery1.evaluate(tweet(1, 10, [ART, SHOW, OTHER]), KB)
    assert got == {
        (ART, P["out:coMentionedWith"], SHOW),
        (ART, P["out:posSentiment"], L.number(100)),
        (ART, P["out:negSentiment"], L.number(50)),
        (ART, P["out:countryCode"], CODE),
    }


def test_cquery1_needs_likes_or_shares_and_both_kinds():
    assert not cquery1.evaluate(tweet(1, 10, [ART, SHOW], likes=False), KB)
    assert cquery1.evaluate(
        tweet(1, 10, [ART, SHOW], likes=False, shares=True), KB)
    assert not cquery1.evaluate(tweet(1, 10, [ART, OTHER]), KB)
    assert not cquery1.evaluate(tweet(1, 10, [SHOW, OTHER]), KB)


def test_q15q16_keeps_artists_with_a_code():
    got = q15q16.evaluate(tweet(7, 10, [ART, SHOW, OTHER]), KB)
    assert got == {(7, P["out:artistCode"], CODE)}


def test_windows_never_split_a_tweet_and_slide():
    rows = [r for i in range(6) for r in tweet(i, i, [ART, SHOW])]   # 5 each
    tumble = chunk_windows(rows, 12, 8)
    assert [len(w) for w in tumble] == [10, 10, 10]
    slide = chunk_windows(rows, 10, 4, step=5)
    assert [len(w) for w in slide] == [10, 10, 10, 10]
    assert [w[-1][3] for w in slide] == [1, 2, 3, 4]

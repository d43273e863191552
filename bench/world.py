"""One cell's world: the KB, the stream and its chunks, the system's
vocabulary and execution config, all made from the configuration file and
the seed."""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from bench import spec as S
from bench.gen import kb as K
from bench.gen import layout as L
from bench.gen import stream as G
from bench.reference.common import KBIndex


def rng(seed: int, stream_id: int) -> np.random.Generator:
    """An independent numpy generator per use of the seed (any size)."""
    return np.random.default_rng([abs(int(seed)), stream_id])


def sized(config: dict, rehearse: bool) -> dict:
    """The configuration as run: the rehearsal block's overrides on top
    when rehearsing on the CPU at a tiny size."""
    if not rehearse:
        return config
    out = dict(config)
    for key, over in config.get("rehearse", {}).items():
        out[key] = dict(config[key], **over)
    return out


@dataclasses.dataclass
class Geometry:
    window: int
    step: Optional[int]
    max_windows: int

    @property
    def unit_cap(self) -> int:
        return self.window if self.step is None or self.step >= self.window \
            else self.step

    @property
    def slides_per_window(self) -> int:
        return 1 if self.unit_cap == self.window else -(-self.window // self.step)

    @property
    def units_per_chunk(self) -> int:
        return self.max_windows + self.slides_per_window - 1


@dataclasses.dataclass
class World:
    kb_shape: K.KBShape
    used: np.ndarray          # [n, 3] the queried part of the KB
    chunks: G.Chunks
    geometry: Geometry
    tweets: int
    gen: object               # the configuration's generator module

    @property
    def n_chunks(self) -> int:
        return int(self.chunks.s.shape[0])

    def chunk_rows(self, k: int):
        """Chunk ``k`` of the endless replay: base chunk ``k % C``; each
        replay cycle shifts timestamps and graph ids past the last cycle,
        so no two chunks of a run carry the same events."""
        c = self.chunks
        base, cycle = k % self.n_chunks, k // self.n_chunks
        tw = c.tweet[base]
        valid = c.valid[base]
        ts = np.where(valid, 1000 + tw + cycle * self.tweets, 0).astype(np.uint32)
        graph = np.where(valid, 1 + tw + cycle * self.tweets, 0).astype(np.uint32)
        return c.s[base], c.p[base], c.o[base], ts, graph, valid

    def reference_index(self) -> KBIndex:
        return KBIndex(self.used, self.gen.TYPE_PRED, self.gen.SUBCLASS_PRED)


def build(config: dict, traffic: dict, seed: int) -> World:
    """The world of the generator the configuration names
    (``bench/gen/<generator>.py``), chunked by its window geometry."""
    gen = S.generator(config["generator"])
    kb_shape, used, stream = gen.generate(config, traffic,
                                          lambda i: rng(seed, i))
    ex = config["execution"]
    geo = Geometry(int(ex["window_capacity"]), ex.get("window_step"),
                   int(ex["max_windows"]))
    chunks = G.chunk_stream(stream, geo.unit_cap, geo.units_per_chunk,
                            geo.max_windows)
    return World(kb_shape, used, chunks, geo, len(stream.tweet_rows), gen)


def make_vocab(gen):
    """The system's vocabulary with generator ``gen``'s named ids interned
    in table order; refuses a system that assigns any other id."""
    from repro.core.rdf import Vocab

    vocab = Vocab()
    for name, want in gen.PRED.items():
        got = vocab.pred(name)
        if got != want:
            raise RuntimeError("vocabulary gave predicate %s id %d, the "
                               "benchmark's table says %d" % (name, got, want))
    for name, want in gen.TERM.items():
        got = vocab.term(name)
        if got != want:
            raise RuntimeError("vocabulary gave term %s id %d, the "
                               "benchmark's table says %d" % (name, got, want))
    return vocab


def check_vocab(vocab, gen) -> None:
    """After registration: the query's names resolved to the table's ids
    and nothing the system interned reached the raw id bands."""
    for name, want in gen.PRED.items():
        if vocab.pred(name) != want:
            raise RuntimeError("predicate %s moved" % name)
    if vocab.num_preds >= L.FILLER_PRED_LO:
        raise RuntimeError("the system interned %d predicates, into the "
                           "filler band" % vocab.num_preds)
    if L.PRED_SPACE + vocab.num_terms >= L.TERM_LO:
        raise RuntimeError("the system interned %d terms, into the raw band"
                           % vocab.num_terms)


def execution_config(block: dict):
    """The configuration's ``execution`` block as the system's
    ``ExecutionConfig``; a key that is no longer a field fails loudly."""
    from repro.core.session import ExecutionConfig

    fields = {f.name for f in dataclasses.fields(ExecutionConfig)}
    unknown = sorted(set(block) - fields)
    if unknown:
        raise KeyError("execution keys %s are not fields of ExecutionConfig"
                       % unknown)
    return ExecutionConfig(**block)

"""RDF stream plumbing: the Aggregator's merge/order stage.

The paper's Aggregator "will merge all input RDF streams into one, order the
events on the new resulting stream, divide it into windows and send it to the
attached RSP engine" (§2).  Merging and ordering are jit-compiled here; window
division lives in :mod:`repro.core.window`.
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import in_layer

from .rdf import TripleBatch, concat_triples, sort_by_timestamp


@in_layer("pack")
def merge_streams(chunks: Sequence[TripleBatch]) -> TripleBatch:
    """Merge K stream chunks into one timestamp-ordered chunk.

    Each input is monotone in ``ts`` (paper assumption 3); the merged output is
    globally ordered, invalid rows compacted to the tail.  Implemented as
    concat + stable lexsort — an O(n log n) vectorized merge that XLA fuses
    well; per-stream monotonicity is *not* required for correctness, only for
    the paper's latency semantics.

    Two hot-path fast paths (K=1 is the per-chunk case in the runtimes):

    * a single input skips the concatenation entirely;
    * the lexsort runs under ``lax.cond`` on an O(n) already-ordered check,
      so an input that is already in merge order (valid-first, then
      non-decreasing ``(ts, graph)``) pays a scan instead of a sort.  The
      check is exact — when it passes, the stable lexsort is the identity —
      so results are bit-identical either way.
    """
    batch = chunks[0] if len(chunks) == 1 else concat_triples(list(chunks))
    big = jnp.uint32(0xFFFFFFFF)
    ts_key = jnp.where(batch.valid, batch.ts, big)
    ordered = jnp.all(
        (ts_key[1:] > ts_key[:-1])
        | ((ts_key[1:] == ts_key[:-1]) & (batch.graph[1:] >= batch.graph[:-1]))
    ) if batch.capacity > 1 else jnp.bool_(True)
    return jax.lax.cond(ordered, lambda b: b, sort_by_timestamp, batch)


merge_streams_jit = jax.jit(merge_streams)


class StreamSource:
    """Host-side pull source wrapping a chunk iterator (a *Stream Generator*).

    ``capacity`` is the static chunk width every pulled TripleBatch is padded
    to, so downstream jit programs see one shape.
    """

    def __init__(self, it: Iterator[TripleBatch], capacity: int):
        self._it = it
        self.capacity = capacity
        self._done = False

    def pull(self) -> TripleBatch | None:
        if self._done:
            return None
        try:
            chunk = next(self._it)
        except StopIteration:
            self._done = True
            return None
        cap = chunk.capacity
        if cap > self.capacity:
            raise ValueError("chunk capacity %d > source capacity %d" % (cap, self.capacity))
        if cap < self.capacity:
            pad = self.capacity - cap
            chunk = jax.tree.map(
                lambda col: jnp.pad(col, ((0, pad),)), chunk
            )
        return chunk


def round_robin_chunks(sources: List[StreamSource]) -> Iterator[TripleBatch]:
    """Interleave several sources into merged, ordered chunks (Aggregator in)."""
    while True:
        chunks = [c for c in (s.pull() for s in sources) if c is not None]
        if not chunks:
            return
        yield merge_streams_jit(chunks)

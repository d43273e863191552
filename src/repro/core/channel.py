"""Capacity-bounded device channels: the inter-operator transport.

The paper wires SCEP operators together with Kafka topics — bounded queues
of RDF events between independently scheduled processes.  This module is the
TPU/JAX analogue: a **fixed-shape ring buffer living in device memory** whose
push/pop are pure jittable ops.  An operator step embeds the pop of its
inbound edge in its own XLA program; pushes onto an edge run as their own
small program on the *consumer's* device (channels live with their
consumer, and one XLA program cannot span devices).  Channel state is
donated in either case — updated in place, never re-allocated.

A :class:`Channel` carries any fixed-shape pytree payload; in the DSCEP
pipeline the payloads are window-aligned batches — :class:`~repro.core.window.Windows`
on the source→aggregator edge and ``(TripleBatch[W, out_cap], overflow[W])``
on operator→aggregator edges (the Publisher→Aggregator edge made
first-class).

Semantics (all shapes static, all state device-resident):

* ``push`` into a **full** channel drops the *new* payload and increments the
  ``overflows`` counter — bounded-queue backpressure is observable, never
  silent (Kafka analogue: producer overrun on a size-capped topic).
* ``pop`` from an **empty** channel returns the zero payload with
  ``valid=False`` and leaves the state untouched.
* ``size``/``overflows`` are ``int32`` scalars on device; the host driver
  reads them only for monitoring/asserts, never to schedule (the schedule is
  deterministic, see :mod:`repro.core.pipeline`).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Channel(NamedTuple):
    """A bounded ring buffer over a pytree payload.

    ``slots`` holds ``capacity`` payloads stacked on a new leading axis;
    ``head`` indexes the oldest element; ``size`` is the occupancy.  The
    NamedTuple is itself a pytree, so channels pass through ``jax.jit``
    (including as donated arguments) and ``jax.device_put`` unchanged.
    """

    slots: Any            # payload pytree; every leaf is [capacity, ...]
    head: jax.Array       # int32 scalar — ring index of the oldest element
    size: jax.Array       # int32 scalar — occupancy in [0, capacity]
    overflows: jax.Array  # int32 scalar — pushes dropped because full

    @property
    def capacity(self) -> int:
        return int(jax.tree.leaves(self.slots)[0].shape[0])


def make_channel(payload_example: Any, capacity: int) -> Channel:
    """Allocate an empty channel shaped to hold ``capacity`` payloads.

    ``payload_example`` fixes the per-slot shapes/dtypes (its values are not
    stored); every slot starts zeroed so a pop-when-empty yields PAD rows.
    """
    if capacity < 1:
        raise ValueError("channel capacity must be >= 1, got %d" % capacity)
    slots = jax.tree.map(
        lambda leaf: jnp.zeros((capacity,) + jnp.shape(leaf), jnp.asarray(leaf).dtype),
        payload_example,
    )
    # three *distinct* zero buffers: the channel is donated as one pytree,
    # and XLA rejects donating one buffer through several arguments
    return Channel(
        slots=slots,
        head=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
        overflows=jnp.zeros((), jnp.int32),
    )


def push(ch: Channel, payload: Any) -> Channel:
    """Enqueue ``payload``; a full channel drops it and counts the overflow.

    The slot write is under ``lax.cond``: a push into a full channel — every
    backpressure event on the hot inter-operator path — must not pay the
    [capacity, ...]-sized scatter for a payload it is about to drop.  The
    drop-new semantics are unchanged (pinned by tests/test_channel.py).
    """
    cap = ch.capacity
    full = ch.size >= cap
    tail = jax.lax.rem(ch.head + ch.size, jnp.int32(cap))

    def write(slots):
        return jax.tree.map(lambda buf, x: buf.at[tail].set(x), slots, payload)

    slots = jax.lax.cond(full, lambda slots: slots, write, ch.slots)
    return Channel(
        slots=slots,
        head=ch.head,
        size=jnp.where(full, ch.size, ch.size + 1),
        overflows=ch.overflows + full.astype(jnp.int32),
    )


def pop(ch: Channel) -> Tuple[Channel, Any, jax.Array]:
    """Dequeue the oldest payload; returns ``(channel', payload, valid)``.

    An empty channel is left unchanged and yields the zero payload with
    ``valid=False`` (shape-stable: callers mask, they never branch).
    """
    cap = ch.capacity
    empty = ch.size <= 0
    payload = jax.tree.map(lambda buf: buf[ch.head], ch.slots)
    payload = jax.tree.map(
        lambda x: jnp.where(empty, jnp.zeros_like(x), x), payload
    )
    new = Channel(
        slots=ch.slots,
        head=jnp.where(empty, ch.head, jax.lax.rem(ch.head + 1, jnp.int32(cap))),
        size=jnp.maximum(ch.size - 1, 0),
        overflows=ch.overflows,
    )
    return new, payload, ~empty


def payload_bytes(tree: Any) -> int:
    """Bytes of a payload pytree, from its leaves' static shapes and dtypes
    (no device access)."""
    return sum(int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(tree))


def occupancy(ch: Channel) -> jax.Array:
    """Current number of queued payloads (int32 scalar, device-resident)."""
    return ch.size


def snapshot(ch: Channel) -> Channel:
    """Deep host copy of a channel's ring state (checkpoint ingredient).

    Channel buffers are *donated* to every push/pop step — holding a device
    reference across a step reads deleted buffers, so a checkpoint must
    materialize the ring on host.  ``device_get`` blocks until in-flight
    writes land, making the copy a consistent cut."""
    return jax.device_get(ch)


def restore(snap: Channel, device=None) -> Channel:
    """Re-materialize a :func:`snapshot` on device (the consumer's device
    under placement, mirroring :func:`make_channel` allocation)."""
    return jax.device_put(snap, device) if device is not None \
        else jax.device_put(snap)


# jitted conveniences with in-place (donated) channel state — an operator
# step embeds push/pop in its own program instead, but tests and host-side
# drivers use these directly.
push_jit = jax.jit(push, donate_argnums=0)
pop_jit = jax.jit(pop, donate_argnums=0)

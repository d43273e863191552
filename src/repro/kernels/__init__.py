"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package ships three files:

* ``kernel.py`` — ``pl.pallas_call`` + explicit ``BlockSpec`` VMEM tiling
  (TPU is the target; the Pallas interpreter runs them on CPU),
* ``ops.py``    — the jit'd public wrapper (padding, dtype policy, vmap),
* ``ref.py``    — the pure-jnp oracle every test sweeps against.

Kernels:

* ``hash_join``       — DSCEP's window-vs-KB match matrix (the scan-method
  hotspot: slot-mode equality compares tiled over the KB partition),
* ``closure``         — boolean-matmul transitive-closure step (RDFS
  subclass reasoning on the MXU),
* ``flash_attention`` — GQA flash attention fwd (causal / sliding-window),
* ``ssd``             — Mamba-2 state-space-duality chunked scan.

Interpret mode is a property of the platform, not a user option: the
kernels run through the Pallas interpreter exactly when JAX's default
backend is the CPU, and are compiled by Mosaic everywhere else.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else the platform rule: interpret on CPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"

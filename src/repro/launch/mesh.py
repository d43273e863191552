"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Mesh shapes (TPU v5e, 256 chips/pod):

* single pod:  (16, 16)      axes ("data", "model")
* multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") — DP across pods
  (the slow inter-pod links carry only gradient all-reduces, which overlap
  with backward compute and can run compressed, train/grad_compress.py).
"""
from __future__ import annotations

import os

import jax


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with Auto axes, so programs traced under the mesh
    may constrain shardings (``with_sharding_constraint``)."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def ensure_host_devices(n: int = 4) -> None:
    """On the CPU platform, make the backend expose ``n`` devices via
    ``--xla_force_host_platform_device_count``.

    The platform is the CPU when ``JAX_PLATFORMS`` names only ``cpu``;
    elsewhere the real devices are used as they are.  Must run before the
    jax backend initializes (importing jax is fine — XLA_FLAGS is read at
    first backend use).  A caller-provided count in ``XLA_FLAGS`` always
    wins.  Benchmark entry points call this so ``place_operators``
    round_robin has devices to spread enrichment operators over.
    """
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if {p.strip() for p in platforms.split(",")} != {"cpu"}:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % n
        ).strip()


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    return make_mesh((n // model, model), ("data", "model"))


# --------------------------------------------------------------------------
# operator placement (the dataflow runtime's device assignment policy)
# --------------------------------------------------------------------------

def place_operators(
    names, final, devices=None, strategy: str = "round_robin"
):
    """Assign each SCEP operator of a decomposed DAG to a device.

    The :class:`~repro.core.pipeline.PipelinedRuntime` places each operator's
    step (KB slice, env, inbound channels) on its assigned device; channel
    pushes across an edge become device-to-device copies — the mesh analogue
    of the paper's one-container-per-operator deployment.

    Strategies:

    * ``"single"``      — everything on ``devices[0]`` (the degenerate but
      always-valid placement; transport is a no-op).
    * ``"round_robin"`` — the aggregation operator (``final``) is pinned to
      ``devices[0]`` (it owns the sink the host blocks on); upstream
      enrichment operators cycle over the *remaining* devices so independent
      branches land on distinct hardware (falls back to ``devices[0]`` when
      only one device exists).

    Accepts a mesh-slice style device list (e.g. one row of a production
    mesh) via ``devices``; defaults to ``jax.devices()``.
    """
    devices = list(devices) if devices is not None else list(jax.devices())
    if not devices:
        raise ValueError("no devices to place operators on")
    names = list(names)
    if final not in names:
        raise ValueError("final operator %r not in %r" % (final, names))
    if strategy == "single":
        return {n: devices[0] for n in names}
    if strategy != "round_robin":
        raise ValueError("unknown placement strategy %r" % strategy)
    placement = {final: devices[0]}
    workers = devices[1:] or devices
    for i, name in enumerate(n for n in names if n != final):
        placement[name] = workers[i % len(workers)]
    return placement

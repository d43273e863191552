"""The trace plane of the pipelined sink's chip, shared by the per-chip
readers."""


def sink_plane() -> str:
    """The plane of ``jax.devices()[0]``, where ``place_operators``'
    round_robin pins the aggregation sink (the window source runs there
    too, on the default device).  The profiler names a chip's plane
    ``/device:TPU:<id>`` by the device's id."""
    import jax

    return "/device:TPU:%d" % jax.devices()[0].id

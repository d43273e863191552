"""The pipelined cell's per-chip and channel readers, on a synthetic
reduction of a four-chip trace and a synthetic counters dict."""
import types

import pytest

from bench.metrics import kb_chip_ms, sink_chip_ms, xchip_kb_per_chunk

# busy seconds per plane of a 3 s window; the sink's chip is jax.devices()[0]
# (id 0 on the CPU, as on a v5e host), chip 3 ran nothing and has no plane
BUSY = {"/device:TPU:1": 1.2, "/device:TPU:0": 1.5, "/device:TPU:2": 1.8}


def run(trace=None, counters=None, chunks=30):
    return types.SimpleNamespace(
        trace=trace, counters=counters,
        window=types.SimpleNamespace(recs=[None] * chunks))


def channel(in_b, out_b, cross):
    return {"saturation": {"hw_bind": 0.5},
            "channel": {"in_bytes_per_chunk": in_b,
                        "out_bytes_per_chunk": out_b,
                        "cross_device": cross, "depth_hw": 2}}


def test_sink_chip_is_the_plane_of_the_first_device():
    r = run({"busy_s": BUSY, "window_s": 3.0})
    assert sink_chip_ms.read(r) == pytest.approx(1e3 * 1.5 / 30)


def test_kb_chip_is_the_busiest_other_chip():
    r = run({"busy_s": BUSY, "window_s": 3.0})
    # chip 2 beats chip 1; the sink's plane is left out though chip 0 is
    # not the least busy
    assert kb_chip_ms.read(r) == pytest.approx(1e3 * 1.8 / 30)
    only_sink = run({"busy_s": {"/device:TPU:0": 1.5}, "window_s": 3.0})
    assert kb_chip_ms.read(only_sink) is None
    assert sink_chip_ms.read(run({"busy_s": {"/device:TPU:1": 1.0}})) is None


def test_only_crossing_bytes_are_counted():
    counters = {"agg": channel(500_000, 90_000, 0),
                "artist": channel(168_008, 688_136, 1),
                "show": channel(168_008, 557_064, 1)}
    got = xchip_kb_per_chunk.read(run(counters=counters))
    assert got == pytest.approx((168_008 + 688_136 + 168_008 + 557_064) / 1e3)
    one_chip = {k: channel(1, 1, 0) for k in ("agg", "artist")}
    assert xchip_kb_per_chunk.read(run(counters=one_chip)) == 0.0


def test_nothing_to_read_reads_none():
    for reader in (sink_chip_ms, kb_chip_ms, xchip_kb_per_chunk):
        assert reader.read(run()) is None
    # counters of a program that counts no channel (a one-program mode)
    plain = {"q": {"saturation": {"hw_bind": 0.5}}}
    assert xchip_kb_per_chunk.read(run(counters=plain)) is None

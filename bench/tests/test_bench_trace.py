"""The trace reduction: busy-interval union, kernel time by name, idle gaps
named by the host span open during them, on synthetic events and on a
small trace recorded on one v5e chip."""
import os

import pytest

from bench import trace_reduce as TR

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur):
    return TR.Ev(plane, line, name, float(start), float(dur))


EVENTS = [
    ev(HOST, "python", "bench.window", 0, 1000),
    ev(HOST, "python", "bench.system", 0, 300),
    ev(HOST, "python", "bench.fetch", 600, 300),
    ev(DEV, TR.OPS_LINE, "fusion.1", 100, 200),
    ev(DEV, TR.OPS_LINE, "%hash_join_count.2 = s32[8,1] custom-call(s32[8,3])",
       250, 150),
    ev(DEV, TR.OPS_LINE, "hash_join_emit.3", 500, 100),
    ev(DEV, TR.OPS_LINE, "copy.4", 950, 100),        # runs past the window
    ev(DEV, "XLA Modules", "jit_step", 100, 900),    # not an op line
]


def test_merged_union_clips_and_joins():
    assert TR.merged([(5, 9), (0, 3), (2, 4), (9, 12)], 1, 10) == [
        (1, 4), (5, 10)]


def test_reduce_synthetic():
    red = TR.reduce(EVENTS, ["hash_join_count", "hash_join_emit"])
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 400) + [500, 600) + [950, 1000)
    assert red["busy_s"][DEV] == pytest.approx(450e-9)
    ks = red["kernel_s"][DEV]
    assert ks["hash_join_count"] == pytest.approx(150e-9)
    assert ks["hash_join_emit"] == pytest.approx(100e-9)
    gaps = dict(red["idle_gaps"])
    # idle [0,100) in system, [400,500) none, [600,950) in fetch
    assert gaps["host:bench.system"] == pytest.approx(100e-9)
    assert gaps["host:none"] == pytest.approx(100e-9)
    assert gaps["host:bench.fetch"] == pytest.approx(300e-9 + 50e-9)
    assert red["device_ops"][0][0] == "fusion.1"


def test_no_device_op_reads_nothing():
    assert TR.reduce([e for e in EVENTS if e.plane == HOST]) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_cquery1_trace.json.gz")


def test_reduce_recorded_chip_trace():
    """16 ms of cquery1.tumble.sat on one v5e chip, around one call of
    the probe kernel (the profiler names device ops by their HLO text)."""
    from bench import roofline as R

    events = TR.load(RECORDED)
    red = TR.reduce(events, ["hash_join_probe"])
    assert red["busiest"] == DEV
    assert 0.9 * red["window_s"] < red["busy_s"][DEV] <= red["window_s"]
    probe_s = red["kernel_s"][DEV]["hash_join_probe"]
    assert 0 < probe_s < red["window_s"]
    assert sum(s for _, s in red["idle_gaps"]) <= red["window_s"]
    calls = list(red["kernel_calls"][DEV])
    # eight windows of 4096 binding rows, 3 variables, k_max 8, out_cap 4096
    assert R.hlo_bytes(TR.hlo_call(calls[0])) == \
        8 * R.hash_join_probe(4096, 3, 8, 4096)["bytes"]
    assert not any("{" in name for name, _ in red["device_ops"])

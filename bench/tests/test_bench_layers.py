"""Device self time by engine layer and idle gaps named by the program's
spans: on a synthetic nested trace, on a short trace recorded on one v5e
chip, and on the CPU trace of a rehearsal (which has no device op)."""
import os

import pytest

from bench import trace_layers as TL
from bench import trace_reduce as TR

from ._rehearse import rehearse

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def span(name, start, dur):
    return TR.Ev(HOST, "python3", name, float(start), float(dur))


def op(start, dur, layer, plane=DEV):
    return TL.Op(plane, float(start), float(dur), layer)


SPANS = [
    span("bench.window", 0, 1000),
    span("dscep.chunk", 40, 860),
    span("dscep.dispatch", 60, 30),
    span("bench.fetch", 860, 130),
]
OPS = [
    op(100, 400, "pack"),          # a while loop ...
    op(150, 150, ""),              # ... its unscoped body op inherits pack
    op(320, 100, "kb_join"),       # ... and a scoped body op keeps its own
    op(600, 100, ""),              # a top-level unscoped copy
    op(700, 100, "publish"),
    op(950, 150, "finalize"),      # runs past the window
    op(10, 20, "scan", plane="/device:TPU:1"),   # a less busy chip
]


def test_scope_layer_is_the_innermost_engine_layer():
    assert TL.scope_layer("jit(f)/dscep.stream_join/jit(g)/dscep.kb_join/"
                          "while/body/gather") == "kb_join"
    assert TL.scope_layer("jit(f)/dscep.pack/dscep.pack/scan") == "pack"
    assert TL.scope_layer("jit(f)/copy") == ""
    assert TL.scope_layer("jit(f)/dscep.notalayer/add") == ""


def test_self_time_inherits_and_adds_up_to_busy():
    red = TL.reduce(OPS, SPANS)
    assert red["busiest"] == DEV
    layers = dict(red["device_layers"])
    # the while's 400 ns less its two body ops, plus its unscoped body
    assert layers["pack"] == pytest.approx(300e-9)
    assert layers["kb_join"] == pytest.approx(100e-9)
    assert layers[TL.UNSCOPED] == pytest.approx(100e-9)
    assert layers["publish"] == pytest.approx(100e-9)
    assert layers["finalize"] == pytest.approx(50e-9)
    assert "scan" not in layers
    assert red["busy_s"] == pytest.approx(650e-9)
    assert sum(layers.values()) == pytest.approx(red["busy_s"])
    gaps = dict(red["idle_by_span"])
    # idle [0,100) and [500,600) in dscep.chunk, [800,950) in bench.fetch
    assert gaps == pytest.approx({"host:dscep.chunk": 200e-9,
                                  "host:bench.fetch": 150e-9})


def test_partly_overlapping_ops_still_add_up_to_busy():
    ops = [op(0, 100, "scan"), op(50, 100, "pack"), op(120, 10, "")]
    layers = TL.self_ns(ops, 0, 1000)
    assert layers == pytest.approx({"scan": 50, "pack": 100})
    assert sum(layers.values()) == TR.busy_ns(ops, 0, 1000)


def _xspace(path):
    """A TPU-like trace: one program on /device:TPU:0, its compiled module
    on the metadata plane, two host spans; times in ps from 1 us."""
    M = TL._message
    hlo = M("HloProto")()
    comp = hlo.hlo_module.computations.add()
    for name, op_name in (("while.1", "jit(f)/dscep.pack/while"),
                          ("fusion.2", "jit(f)/vmap(dscep.scan)/eq"),
                          ("copy.3", "")):
        ins = comp.instructions.add(name=name.encode())
        ins.metadata.op_name = op_name.encode()
    space = M("XSpace")()
    md = space.planes.add(name=TL.METADATA_PLANE)
    md.event_metadata.add(key=9).value.name = "jit_f(9)"
    md.event_metadata[0].value.stats.add(
        bytes_value=hlo.SerializeToString())
    dev = space.planes.add(name=DEV)
    for key, name in ((1, "jit_f(9)"), (2, "%while.1 = (s32[]) while()"),
                      (3, "%fusion.2 = s32[8] fusion()"),
                      (4, "%copy.3 = s32[8] copy(s32[8] %p)")):
        dev.event_metadata.add(key=key).value.name = name
    mods = dev.lines.add(name=TL.MODULES_LINE, timestamp_ns=1000)
    mods.events.add(metadata_id=1, offset_ps=0, duration_ps=900_000)
    line = dev.lines.add(name=TR.OPS_LINE, timestamp_ns=1000)
    for key, start, dur in ((2, 100, 400), (3, 150, 150), (4, 600, 100)):
        line.events.add(metadata_id=key, offset_ps=start * 1000,
                        duration_ps=dur * 1000)
    host = space.planes.add(name=HOST)
    for key, name in ((1, "bench.window"), (2, "dscep.chunk"),
                      (3, "jit_f")):
        host.event_metadata.add(key=key).value.name = name
    py = host.lines.add(name="python3", timestamp_ns=1000)
    for key, start, dur in ((1, 0, 1000), (2, 50, 800), (3, 60, 10)):
        py.events.add(metadata_id=key, offset_ps=start * 1000,
                      duration_ps=dur * 1000)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_load_xplane_maps_ops_to_layers_through_their_module(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    _xspace(path)
    ops, spans = TL.load_xplane(path)
    assert ops == [op(1100, 400, "pack"), op(1150, 150, "scan"),
                   op(1600, 100, "")]
    assert [(s.name, s.start, s.dur) for s in spans] == [
        ("bench.window", 1000, 1000), ("dscep.chunk", 1050, 800)]
    layers = dict(TL.reduce(ops, spans)["device_layers"])
    assert layers == pytest.approx({"pack": 250e-9, "scan": 150e-9,
                                    TL.UNSCOPED: 100e-9})


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_cquery1_layers.json.gz")


def test_reduce_recorded_chip_trace():
    """10 ms of cquery1.tumble.sat on one v5e chip, around a KB join:
    each op's start, duration and layer (ops that began before the window
    included, so their nested ops inherit), times from the window's start."""
    ops, spans = TL.load(RECORDED)
    red = TL.reduce(ops, spans)
    assert red["busiest"] == DEV
    assert 0.9 * red["window_s"] < red["busy_s"] <= red["window_s"]
    layers = dict(red["device_layers"])
    assert set(layers) <= set(TL.LAYERS) | {TL.UNSCOPED}
    assert sum(layers.values()) == pytest.approx(red["busy_s"], rel=1e-9)
    assert layers.get(TL.UNSCOPED, 0.0) <= 0.1 * red["busy_s"]
    assert layers["stream_join"] > 0 and layers["kb_join"] > 0
    assert sum(s for _, s in red["idle_by_span"]) \
        == pytest.approx(red["window_s"] - red["busy_s"])


def test_rehearsal_trace_has_no_device_layers(capsys, tmp_path):
    """A traced rehearsal runs on the CPU; its trace has no TPU plane, so
    the reduction reads nothing."""
    res = rehearse(capsys, "q15q16.slide75.sat", "--trace", "1",
                   "--keep-trace", str(tmp_path), seed=2**31 + 7)
    assert res["correct"] is True
    (raw,) = tmp_path.glob("*.xplane.pb")
    ops, spans = TL.load_xplane(str(raw))
    assert ops == []
    assert any(s.name == TR.WINDOW_SPAN for s in spans)
    assert TL.reduce(ops, spans) is None

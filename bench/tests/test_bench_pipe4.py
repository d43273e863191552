"""The four-chip pipelined deployment (``cquery1.pipe4.sat``) on four
virtual CPU devices, in a subprocess (the device count is fixed when the
backend starts): the cell rehearses correct, and the same configuration
registered through ``Session`` places its three operators on three
devices, publishes the plain reference's rows window by window, and counts
the bytes of its channels' payload shapes."""
import os
import subprocess
import sys

from bench import spec as S

SCRIPT = r"""
import contextlib, io, json, os, types
import jax
import numpy as np
assert len(jax.devices()) == 4, jax.devices()

from bench import check as C, run as bench_run, spec as S, world as W
from bench.gen import kb as K
from bench.metrics import xchip_kb_per_chunk
from repro.core.rdf import TripleBatch
from repro.core.session import Session
from repro.obs.trace import TraceConfig

CELL, SEED = "cquery1.pipe4.sat", 2**31 + 7
buf = io.StringIO()
with contextlib.redirect_stdout(buf):
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                         "--seconds", "1", "--trace", "0", "--rehearse"])
assert rc == 0, rc
res = json.loads(buf.getvalue().strip().splitlines()[-1])
assert res["correct"] is True and res["failed"] == 0, res

spec = S.benchmark()
config = W.sized(S.config(spec, S.cell(spec, CELL)["config"]), True)
world = W.build(config, S.traffic("sat"), SEED)
kb = K.build_device_kb(world.used, world.kb_shape, SEED, jax.devices()[0])
cfg = W.execution_config(config["execution"]).replace(
    trace=TraceConfig(spans=False, metrics=True, fence=False))
assert cfg.mode == "pipelined" and cfg.use_pallas
reg = Session(cfg, vocab=W.make_vocab(world.gen), kb=kb).register(
    S.query_text(config["query"]))
rt = reg.runtime
place = {n: rt.placement[n] for n in reg.operators}
assert len(place) == 3 and len(set(place.values())) == 3, place
assert place[rt.final] == jax.devices()[0], place

chunks = [TripleBatch(*world.chunk_rows(k)) for k in range(world.n_chunks)]
ref, index = S.reference(config["query"]), world.reference_index()
windows = rows = 0
for k, out in enumerate(reg.stream(chunks)):
    got = C.published(tuple(np.asarray(x) for x in jax.device_get(out)))
    want = C.expected(world.chunk_rows(k), world.geometry, ref, index)
    assert got == want, k
    windows += len(want)
    rows += sum(len(v) for v in want.values())
assert windows > 0 and rows > 0
assert not any(reg.overflow_totals().values()), reg.overflow_totals()

ops = reg.last_stats["operators"]
ex = config["execution"]
W_, row = ex["max_windows"], 5 * 4 + 1     # s, p, o, ts, graph u32; valid
win_bytes = W_ * ex["window_capacity"] * row + W_
pubs = {}
for name in rt.upstream:
    sp = rt._split.pub[name]
    pubs[name] = W_ * sp.rows_cap * (4 * len(sp.cols) + 1) + W_
    ch = ops[name]["channel"]
    assert ch["in_bytes_per_chunk"] == win_bytes, (name, ch)
    assert ch["out_bytes_per_chunk"] == pubs[name], (name, ch)
    assert ch["cross_device"] == 1 and ch["depth_hw"] == 2, (name, ch)
sink = ops[rt.final]["channel"]
assert sink["in_bytes_per_chunk"] == win_bytes + sum(pubs.values()), sink
assert sink["out_bytes_per_chunk"] == ex["out_stream_cap"] * row, sink
assert sink["cross_device"] == 0, sink
crossing = 2 * win_bytes + sum(pubs.values())
got = xchip_kb_per_chunk.read(types.SimpleNamespace(counters=ops))
assert got == crossing / 1e3, (got, crossing)
print("PIPE4_OK windows=%d rows=%d crossing_bytes=%d" % (windows, rows,
                                                          crossing))
"""


def test_pipe4_deployment_on_four_virtual_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(S.ROOT / "src"), str(S.ROOT)]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         cwd=str(S.ROOT), capture_output=True, text=True,
                         timeout=900)
    assert res.returncode == 0, res.stdout[-4000:] + "\n" + res.stderr[-8000:]
    assert "PIPE4_OK" in res.stdout, res.stdout[-4000:]

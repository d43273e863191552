"""The harness finds a configuration, its generator, a traffic mix or a
metric added as a new file by its name, and the roofline's byte functions agree with the shapes
of a kernel call's HLO text."""
import json

import pytest

from bench import roofline as R
from bench import spec as S


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "new-deploy.json").write_text(
        json.dumps({"query": "cquery1", "kb": {}}))
    (bench / "traffic" / "burst.json").write_text(
        json.dumps({"loop": "open", "inflight": 4}))
    (bench / "metrics" / "new_metric.x.py").write_text(
        "def read(run):\n    return 2 * run\n")
    (bench / "metrics" / "split.py").write_text(
        "def read(run):\n    return run + 1\n")
    (bench / "gen").mkdir()
    (bench / "gen" / "lubm.py").write_text(
        "from . import layout as L\nPRED = {'ub:subOrganizationOf': 1}\n"
        "def generate(config, traffic, rng):\n    return L.PRED_SPACE\n")
    spec = {"configs": [{"name": "new-deploy",
                         "file": "bench/configs/new-deploy.json"}],
            "workloads": [{"name": "new.cell", "config": "new-deploy",
                           "traffic": "burst", "chips": 1}],
            "end_to_end": [{"name": "setup_s"}],
            "per_layer": [{"name": "new_metric.x", "workloads": ["new.cell"]},
                          {"name": "other", "workloads": ["old.cell"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    loaded = S.benchmark(tmp_path)
    cell = S.cell(loaded, "new.cell")
    assert S.config(loaded, cell["config"], tmp_path)["query"] == "cquery1"
    assert S.traffic(cell["traffic"], bench)["inflight"] == 4
    names = [m["name"] for m in S.metrics(loaded, "new.cell", per_layer=True)]
    assert names == ["new_metric.x"]
    assert S.reader("new_metric.x", bench)(21) == 42
    # a metric split by the end-to-end metric it moves shares its reader
    assert S.reader("split.rate", bench)(1) == S.reader("split.sat", bench)(1) == 2
    with pytest.raises(KeyError):
        S.reader("absent.sat", bench)
    gen = S.generator("lubm", bench)
    assert gen.PRED == {"ub:subOrganizationOf": 1}
    assert gen.generate(None, None, None) == 1 << 12
    with pytest.raises(KeyError):
        S.cell(loaded, "missing.cell")


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = S.benchmark()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(S.reader(m["name"]))
    for w in spec["workloads"]:
        S.config(spec, w["config"])
        S.traffic(w["traffic"])


def test_unknown_device_kind_is_an_error():
    assert S.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        S.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("kernel,shapes,text", [
    ("hash_join_count", dict(m=256, nv=8, n=1024),
     "%hash_join_count.1 = s32[256,1]{1,0} custom-call(s32[256,8]{1,0} %a, "
     "s32[256,1]{1,0} %b, s32[1,1024]{1,0} %c, s32[1,1024]{1,0} %d, "
     "s32[1,1024]{1,0} %e, s32[1,1024]{1,0} %f)"),
    ("hash_join_emit", dict(m=256, nv=8, n=1024, out_cap=512),
     "(s32[1,513]{1,0}, s32[1,513]{1,0}) custom-call(s32[256,8]{1,0} %a, "
     "s32[256,1]{1,0} %b, s32[1,1024] %c, s32[1,1024] %d, s32[1,1024] %e, "
     "s32[1,1024] %f, s32[256,1]{1,0} %g)"),
    ("hash_join_match", dict(m=128, nv=4, n=256),
     "s8[128,256]{1,0} custom-call(s32[128,4] %a, s32[128,1] %b, "
     "s32[1,256] %c, s32[1,256] %d, s32[1,256] %e, s32[1,256] %f)"),
    ("hash_join_probe", dict(m=64, nv=3, k=8, out_cap=100),
     "(s32[1,101], s32[64,1]) custom-call(s32[64,3] %a, s32[64,1] %b, "
     "s32[64,8] %c, s32[64,8] %d, s32[64,8] %e, s32[64,8] %f)"),
    ("closure_step", dict(n=256),
     "f32[256,256] custom-call(f32[256,256] %a, f32[256,256] %b)"),
    ("closure_descendants", dict(n=128, out_cap=40),
     "(s32[41], s32[1]) custom-call(f32[128,128] %a, f32[1,128] %b)"),
])
def test_kernel_bytes_match_the_call_shapes(kernel, shapes, text):
    assert R.KERNELS[kernel](**shapes)["bytes"] == R.hlo_bytes(text)


def test_least_time_takes_the_larger_bound():
    peaks = S.peaks("TPU v5 lite")
    step = R.closure_step(4096)
    assert R.least_seconds(step, peaks) == step["flops"] / peaks["bf16_flops_per_s"]
    count = R.hash_join_count(4096, 8, 1 << 20)
    assert R.least_seconds(count, peaks) == count["bytes"] / peaks["hbm_bytes_per_s"]


def test_no_accelerator_no_result(capsys):
    """On a machine without a TPU the run stops before any work and prints
    no result line."""
    from bench import run as bench_run

    rc = bench_run.main(["--workload", "cquery1.tumble.sat", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no system to run: the run fails and prints no result."""
    import os
    import shutil
    import subprocess
    import sys

    root = S.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "bench/run.py", "--workload",
                          "cquery1.tumble.sat", "--seed", "1", "--seconds",
                          "1", "--trace", "0", "--rehearse"],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout == ""

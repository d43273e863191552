"""Shared helper of the rehearsal tests: one in-process run of a cell at
its tiny rehearsal size on the CPU, returning the result line."""
import json

from bench import run as bench_run


def rehearse(capsys, workload, *extra, seed=11, seconds=1):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0",
                         "--rehearse", *extra])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])

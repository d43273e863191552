"""Chaos smoke benchmark: seeded fault injection -> BENCH_chaos.json.

Runs the paper's CQuery1 through the pipelined runtime under a seeded
:class:`FaultPlan` (every fault kind aimed at the source stage, so the
plan needs no knowledge of the query DAG) and verifies the recovery
tripwires the CI chaos-smoke job asserts on:

* the recovered pipelined stream is **bit-identical** to a fault-free
  monolithic run — zero lost rows, zero duplicated rows;
* every scheduled fault fired exactly once (``injected == scheduled``)
  and at least one operator restart was actually exercised;
* the per-stage jaxprs of the chaotic runtime are byte-identical to a
  plain (recovery=None) pipelined runtime — all fault/recovery machinery
  lives on the host driver, never inside a traced program.

    PYTHONPATH=src python -m benchmarks.chaos              # default seed
    PYTHONPATH=src python -m benchmarks.chaos --seed 7
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import paper_queries as PQ
from repro.core.faults import FaultEvent, FaultPlan
from repro.core.recovery import RecoveryConfig
from repro.core.session import ExecutionConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import ensure_host_devices

from .common import build_world, format_table, make_session

DEFAULT_SEED = 1234


def _jaxpr_pin(plain, chaotic, chunk) -> bool:
    """True iff every per-stage traced program is byte-identical between a
    plain pipelined runtime and the fault-injected resilient one."""
    def jp(fn, *args):
        return str(jax.make_jaxpr(fn)(*args))

    if jp(plain._windows_impl, chunk) != jp(chaotic._windows_impl, chunk):
        return False
    _, opp_shape = jax.eval_shape(plain._windows_impl, chunk)
    op_payload = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                              opp_shape)
    for name in plain.upstream:
        pa, pb = plain.operators[name], chaotic.operators[name]
        if jp(functools.partial(plain._op_impl, name),
              op_payload, pa.kb, pa.env) != \
           jp(functools.partial(chaotic._op_impl, name),
              op_payload, pb.kb, pb.env):
            return False
    if plain._agg_win_ch is not None and chaotic._agg_win_ch is not None:
        fa = plain.operators[plain.final]
        fb = chaotic.operators[chaotic.final]
        if jp(plain._sink_impl, plain._agg_win_ch, plain._out_ch,
              fa.kb, fa.env) != \
           jp(chaotic._sink_impl, chaotic._agg_win_ch, chaotic._out_ch,
              fb.kb, fb.env):
            return False
    return True


def run(seed: int = DEFAULT_SEED):
    world = build_world(num_tweets=48, num_artists=16, num_shows=8,
                        filler=120, chunk_capacity=96)
    chunks = world.chunks
    assert len(chunks) >= 3, (
        "chaos stream too short for a mid-stream crash: %d chunks"
        % len(chunks))
    base = ExecutionConfig(window_capacity=64, max_windows=4, bind_cap=512,
                           scan_cap=128, out_cap=512, intermediate_cap=256,
                           channel_capacity=4)
    q = PQ.cquery1(world.vocab, world.tweets, world.kbd.schema)

    # a seeded schedule, hardened with one guaranteed mid-stream crash so
    # the restart tripwire below is exercised for every seed
    events = list(FaultPlan.seeded(seed, ("source",), len(chunks),
                                   n_events=4).events)
    if not any(ev.kind == "crash_stage" for ev in events):
        events.append(FaultEvent("crash_stage", "source",
                                 min(2, len(chunks) - 1)))
    plan = FaultPlan(tuple(events))
    print(f"[bench_chaos] seed={seed}, {len(chunks)} chunks, "
          f"plan={plan.counts()}")

    mono = make_session(world, base.replace(mode="monolithic")).register(q)
    outs_mono, ovf_mono = mono.run(chunks)

    # max_restarts sized above the worst case of the seeded plan (several
    # desync-triggering events can blame the same chunk), so the smoke
    # exercises full channel-path recovery rather than the degraded
    # fallback — degradation has its own coverage in tests/test_faults.py
    chaotic = make_session(world, base.replace(
        mode="pipelined", faults=plan,
        recovery=RecoveryConfig(checkpoint_every=2,
                                max_restarts=2 * len(plan.events)))).register(q)
    t0 = time.perf_counter()
    outs_chaos, ovf_chaos = chaotic.run(chunks)
    chaos_pass_s = time.perf_counter() - t0

    bit_exact = len(outs_chaos) == len(outs_mono)
    for a, b in zip(outs_mono, outs_chaos):
        for col_a, col_b in zip(a, b):
            bit_exact = bit_exact and bool(
                np.all(np.asarray(col_a) == np.asarray(col_b)))
    assert bit_exact, "recovered chaos stream diverges from fault-free run"
    clipped = {n: c for n, c in {**ovf_mono, **ovf_chaos}.items() if c}
    assert not clipped, "overflowed windows under chaos: %s" % clipped

    stats = chaotic.last_stats
    rec = stats["recovery"]
    assert rec["enabled"], "recovery surface missing from last_stats"
    assert rec["injected"] == plan.counts() == rec["scheduled"], (
        "injected %s != scheduled %s" % (rec["injected"], rec["scheduled"]))
    assert rec["restarts"] >= 1, "no restart exercised — tripwire dead"
    assert not stats["degraded"], (
        "chaos run degraded: %s" % rec["degraded_chunks"])

    plain = make_session(world, base.replace(mode="pipelined")).register(q)
    pin_ok = _jaxpr_pin(plain.runtime, chaotic.runtime, chunks[0])
    assert pin_ok, "fault machinery leaked into a traced stage program"

    rows = [[k, v] for k, v in sorted(rec["injected"].items()) if v]
    rows += [["restarts", rec["restarts"]], ["retries", rec["retries"]],
             ["replayed", rec["replayed"]], ["deduped", rec["deduped"]],
             ["checkpoints", rec["checkpoints"]]]
    print(format_table("chaos run (seed %d): injected faults + recovery"
                       % seed, ["event", "count"], rows))
    print("[bench_chaos] recovered bit-exact in %.1f ms "
          "(compile-inclusive first pass)" % (chaos_pass_s * 1e3))

    payload = {
        "what": "seeded chaos smoke: CQuery1 through the pipelined runtime "
                "under a FaultPlan covering every fault kind; recovered "
                "stream bit-identical to a fault-free monolithic run, all "
                "scheduled events fired, >=1 restart exercised, per-stage "
                "jaxprs pinned identical to a recovery-free runtime",
        "seed": seed,
        "num_chunks": len(chunks),
        "plan": [{"kind": ev.kind, "stage": ev.stage, "chunk": ev.chunk}
                 for ev in plan.events],
        "scheduled": rec["scheduled"],
        "injected": rec["injected"],
        "recovery": rec,
        "bit_exact_vs_fault_free": bool(bit_exact),
        "restart_exercised": rec["restarts"] >= 1,
        "jaxpr_pin_ok": bool(pin_ok),
        "degraded": stats["degraded"],
        "chaos_pass_s": chaos_pass_s,
    }
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_chaos.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"[bench_chaos] wrote {os.path.normpath(path)}")
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=DEFAULT_SEED,
                    help="FaultPlan seed (the CI job pins this)")
    args = ap.parse_args(argv)
    ensure_host_devices(4)
    setup_compile_cache()
    run(seed=args.seed)


if __name__ == "__main__":
    main()

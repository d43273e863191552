"""Pallas kernel micro-benchmarks: fidelity + shape sweeps vs ref oracles.

On the CPU backend the kernels run through the Pallas interpreter, so
wall-clock there is NOT TPU performance — the numbers that matter for the
kernels are the roofline terms in EXPERIMENTS.md §Roofline.  What this bench
certifies per kernel: (a) allclose vs the pure-jnp oracle at benchmark
shapes, (b) the jnp fallback's wall time (the path XLA actually runs on CPU),
(c) arithmetic-intensity bookkeeping used by the roofline analysis.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kb import kb_from_triples
from repro.core.pattern import Bindings, CompiledPattern, Slot

from repro.kernels.closure import ops as cl_ops
from repro.kernels.closure.ref import closure_ref
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.hash_join import ops as hj_ops
from repro.kernels.hash_join.ref import match_matrix_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd.ref import ssd_ref

from .common import format_table, ms, save_results, time_fn


def bench_flash_attention():
    rows, out = [], {}
    for (b, hq, hk, t, d), win in [((1, 4, 2, 256, 64), None),
                                   ((2, 8, 2, 512, 64), None),
                                   ((1, 4, 4, 512, 64), 128)]:
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(t), 3)
        q = jax.random.normal(k1, (b, hq, t, d), jnp.float32)
        k = jax.random.normal(k2, (b, hk, t, d), jnp.float32)
        v = jax.random.normal(k3, (b, hk, t, d), jnp.float32)
        got = fa_ops.flash_attention(q, k, v, causal=True, window=win)
        want = attention_ref(q, k, v, causal=True, window=win)
        err = float(jnp.max(jnp.abs(got - want)))
        ref_fn = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True,
                                                       window=win))
        tt = time_fn(ref_fn, q, k, v, iters=3)
        flops = 4 * b * hq * t * t * d   # qk + av
        key = f"b{b}h{hq}/{hk}t{t}d{d}" + (f"w{win}" if win else "")
        out[key] = {"max_err": err, "jnp_s": tt["median_s"], "flops": flops}
        rows.append(["flash_attention", key, f"{err:.2e}", ms(tt["median_s"])])
    return out, rows


def bench_decode_attention():
    rows, out = [], {}
    for b, hq, hk, s, d in [(4, 8, 2, 1024, 64), (8, 8, 8, 4096, 128)]:
        ks = jax.random.split(jax.random.PRNGKey(s), 3)
        q = jax.random.normal(ks[0], (b, hq, 1, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, hk, s, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, hk, s, d), jnp.float32)
        lengths = jnp.asarray(
            np.random.default_rng(s).integers(s // 2, s + 1, size=b), jnp.int32)
        got = da_ops.decode_attention(q, k, v, lengths)
        want = decode_attention_ref(q, k, v, lengths)
        err = float(jnp.max(jnp.abs(got - want)))
        ref_fn = jax.jit(decode_attention_ref)
        tt = time_fn(ref_fn, q, k, v, lengths, iters=3)
        key = f"b{b}h{hq}/{hk}s{s}d{d}"
        out[key] = {"max_err": err, "jnp_s": tt["median_s"]}
        rows.append(["decode_attention", key, f"{err:.2e}", ms(tt["median_s"])])
    return out, rows


def bench_ssd():
    rows, out = [], {}
    for b, t, nh, hd, s in [(1, 256, 4, 32, 32), (2, 512, 8, 32, 64)]:
        ks = jax.random.split(jax.random.PRNGKey(b + t), 5)
        x = jax.random.normal(ks[0], (b, t, nh, hd), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, nh), jnp.float32))
        A = -jnp.exp(jax.random.normal(ks[2], (nh,), jnp.float32) * 0.3)
        B = jax.random.normal(ks[3], (b, t, 1, s), jnp.float32)
        C = jax.random.normal(ks[4], (b, t, 1, s), jnp.float32)
        D = jnp.ones((nh,), jnp.float32)
        got = ssd_ops.ssd(x, dt, A, B, C, D, use_pallas=True)
        want, _ = ssd_ref(x, dt, A, B, C, D)
        err = float(jnp.max(jnp.abs(got - want)))
        ref_fn = jax.jit(lambda *a: ssd_ref(*a)[0])
        tt = time_fn(ref_fn, x, dt, A, B, C, D, iters=3)
        key = f"b{b}t{t}h{nh}p{hd}s{s}"
        out[key] = {"max_err": err, "jnp_s": tt["median_s"]}
        rows.append(["ssd_chunk_scan", key, f"{err:.2e}", ms(tt["median_s"])])
    return out, rows


def bench_closure():
    rows, out = [], {}
    for n in [128, 256, 512]:
        rng = np.random.default_rng(n)
        adj = jnp.asarray((rng.random((n, n)) < 0.02).astype(np.float32))
        got = cl_ops.transitive_closure(adj, max_depth=n, use_pallas=True)
        want = closure_ref(adj, steps=int(np.ceil(np.log2(n))))
        ok = bool(jnp.all(got == (want > 0.5)))
        ref_fn = jax.jit(lambda a: closure_ref(a, steps=int(np.ceil(np.log2(n)))))
        tt = time_fn(ref_fn, adj, iters=3)
        out[f"n{n}"] = {"exact": ok, "jnp_s": tt["median_s"]}
        rows.append(["closure", f"n{n}", "exact" if ok else "MISMATCH",
                     ms(tt["median_s"])])
    return out, rows


def _join_world(m, n, nv=3, seed=None):
    rng = np.random.default_rng(seed if seed is not None else m + n)
    base = 5000
    cols = rng.integers(base, base + 200, size=(m, nv)).astype(np.uint32)
    kb_rows = [
        (int(rng.integers(base, base + 200)), 1,
         int(rng.integers(base, base + 200)))
        for _ in range(n - 8)
    ]
    kb = kb_from_triples(kb_rows, capacity=n)
    bind = Bindings(jnp.asarray(cols), jnp.ones((m,), bool),
                    jnp.zeros((), bool))
    pat = CompiledPattern(Slot.bound(0), Slot.const_(1), Slot.free(1))
    return bind, kb, pat


def bench_join_fused():
    """Before/after for the fused join->compaction pipeline -> BENCH_join.json.

    *before* — the engine's unfused scan join: materialize the [M, N] match
    matrix, broadcast the [M, N, nv] row extension, compact M*N rows.
    *after* — the fused jnp path (the formulation XLA executes on this CPU
    host; it finds the same first ``out_cap`` matches as the Pallas
    kernel's count+emit phases).  On the CPU backend the Pallas fused
    kernel runs through the interpreter, so it is checked for
    bit-exactness but timed only as the jnp twin; on a TPU it is compiled,
    where the fusion targets the HBM-traffic ratio (O(M*N) -> O(M*N
    read-once + out_cap)).
    """
    from repro.core import algebra

    rows, out = [], {}
    for m, n, cap in [(128, 2048, 256), (256, 4096, 512), (256, 8192, 512)]:
        bind, kb, pat = _join_world(m, n)

        def run(c, v, fused):
            return algebra.kb_join_scan(
                Bindings(c, v, jnp.zeros((), bool)), kb, pat, cap,
                fuse_compaction=fused,
            )

        base_fn = jax.jit(lambda c, v: run(c, v, False))
        fused_fn = jax.jit(lambda c, v: run(c, v, True))
        want = base_fn(bind.cols, bind.valid)
        got = fused_fn(bind.cols, bind.valid)
        exact = bool(jnp.all(got.cols == want.cols)
                     & jnp.all(got.valid == want.valid))
        # Pallas fused kernel: parity only (interpret mode is not a timing)
        got_pl = algebra.kb_join_scan(bind, kb, pat, cap, use_pallas=True,
                                      fuse_compaction=True)
        exact &= bool(jnp.all(got_pl.cols == want.cols)
                      & jnp.all(got_pl.valid == want.valid))
        tb = time_fn(base_fn, bind.cols, bind.valid, iters=5)
        tf = time_fn(fused_fn, bind.cols, bind.valid, iters=5)
        speedup = tb["median_s"] / max(tf["median_s"], 1e-9)
        key = f"m{m}xn{n}cap{cap}"
        out[key] = {
            "exact": exact,
            "before_unfused_s": tb["median_s"],
            "after_fused_s": tf["median_s"],
            "speedup": speedup,
        }
        rows.append(["join_fused", f"{m}x{n}->cap{cap}",
                     "exact" if exact else "MISMATCH",
                     f"{ms(tb['median_s'])} -> {ms(tf['median_s'])} "
                     f"({speedup:.1f}x)"])
    return out, rows


def _probe_world(m, n, fanout, seed=None):
    """An anchored const-predicate join with controlled fan-out.

    ``n`` KB rows under one predicate, subjects drawn from a pool so every
    subject carries exactly ``fanout`` rows; binding rows anchor on pool
    subjects.  Subject-anchored probes and scans emit matches in the same
    (p,s)-view order, so scan-vs-probe results must be bit-identical.
    """
    rng = np.random.default_rng(seed if seed is not None else m + n)
    base = 5000
    pool = max(1, n // fanout)
    subs = base + np.repeat(np.arange(pool, dtype=np.int64), fanout)[: n - 8]
    kb_rows = [(int(s), 1, int(rng.integers(base, base + pool)))
               for s in subs]
    kb = kb_from_triples(kb_rows, capacity=n)
    cols = rng.integers(base, base + pool, size=(m, 3)).astype(np.uint32)
    bind = Bindings(jnp.asarray(cols), jnp.ones((m,), bool),
                    jnp.zeros((), bool))
    pat = CompiledPattern(Slot.bound(0), Slot.const_(1), Slot.free(1))
    return bind, kb, pat


def bench_probe_join():
    """Cost-based KB access: fused scan vs probe -> BENCH_join.json "probe".

    The paper's Figs. 5-7 relationship at kernel granularity: the scan pays
    the whole partition per join while the probe pays O(log N) + k_max
    gathers per binding row, so the gap widens linearly with KB size.  Each
    shape runs the planner's actual cost model
    (:func:`repro.core.planner._choose_kb_method` over
    :func:`repro.core.kb.collect_kb_stats`) to confirm "auto" picks the
    probe and to derive its ``k_max``; *exact* certifies the probe result
    (and the fused Pallas probe kernel in interpret mode) bit-identical to
    the fused scan — the CI tripwire asserts it stays true.
    """
    from repro.core import algebra
    from repro.core.kb import collect_kb_stats
    from repro.core.planner import _choose_kb_method

    rows, out = [], {}
    for m, n, fanout in [(256, 8192, 4), (256, 32768, 4), (256, 131072, 4)]:
        bind, kb, pat = _probe_world(m, n, fanout)
        cap = m * fanout
        stats = collect_kb_stats(kb)
        method, k_max = _choose_kb_method(pat, stats, 8)
        assert method == "probe", (method, stats.preds.get(1))

        def scan_run(c, v):
            return algebra.kb_join_scan(
                Bindings(c, v, jnp.zeros((), bool)), kb, pat, cap,
                fuse_compaction=True,
            )

        def probe_run(c, v, k=k_max):
            return algebra.kb_join_probe(
                Bindings(c, v, jnp.zeros((), bool)), kb, pat, cap, k)

        scan_fn = jax.jit(scan_run)
        probe_fn = jax.jit(probe_run)
        want = scan_fn(bind.cols, bind.valid)
        got = probe_fn(bind.cols, bind.valid)
        exact = bool(jnp.all(got.cols == want.cols)
                     & jnp.all(got.valid == want.valid)
                     & (got.overflow == want.overflow))
        # fused Pallas probe kernel: parity only (interpret mode, not timed)
        got_pl = algebra.kb_join_probe(bind, kb, pat, cap, k_max,
                                       use_pallas=True)
        exact &= bool(jnp.all(got_pl.cols == want.cols)
                      & jnp.all(got_pl.valid == want.valid)
                      & (got_pl.overflow == want.overflow))
        ts = time_fn(scan_fn, bind.cols, bind.valid, iters=5)
        tp = time_fn(probe_fn, bind.cols, bind.valid, iters=5)
        speedup = ts["median_s"] / max(tp["median_s"], 1e-9)
        key = f"m{m}xn{n}f{fanout}"
        out[key] = {
            "exact": exact,
            "auto_method": method,
            "derived_k_max": k_max,
            "fused_scan_s": ts["median_s"],
            "probe_s": tp["median_s"],
            "speedup": speedup,
        }
        rows.append(["probe_join", f"{m}x{n} fan{fanout} k{k_max}",
                     "exact" if exact else "MISMATCH",
                     f"{ms(ts['median_s'])} -> {ms(tp['median_s'])} "
                     f"({speedup:.1f}x)"])
    return out, rows


def write_bench_join(fused_out, probe_out):
    """Combine the scan-fusion and probe sections into BENCH_join.json."""
    import json

    payload = {
        "what": "scan-method KB join: unfused (materialize [M,N] + compact) "
                "vs fused join->compaction, jit on this host",
        "note": "Pallas fused kernels verified bit-exact in interpret mode; "
                "timings are the jnp paths XLA runs on CPU hosts.",
        "results": fused_out,
        "probe": {
            "what": "cost-based KB access: fused scan vs probe on an "
                    "anchored const-predicate join, k_max derived by the "
                    "planner's cost model from collect_kb_stats",
            "results": probe_out,
        },
    }
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_join.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"[bench_join] wrote {os.path.normpath(path)}")


def bench_join():
    """The ``--only join`` entry: both join sections + the combined file."""
    fused_out, fused_rows = bench_join_fused()
    probe_out, probe_rows = bench_probe_join()
    write_bench_join(fused_out, probe_out)
    return {"bench_join_fused": fused_out, "bench_probe_join": probe_out}, \
        fused_rows + probe_rows


def bench_hash_join():
    rows, out = [], {}
    for m, n in [(128, 1024), (256, 4096), (512, 8192)]:
        rng = np.random.default_rng(m + n)
        base = 5000
        cols = rng.integers(base, base + 200, size=(m, 2)).astype(np.uint32)
        kb_rows = [
            (int(rng.integers(base, base + 200)), 1,
             int(rng.integers(base, base + 200)))
            for _ in range(n - 8)
        ]
        kb = kb_from_triples(kb_rows, capacity=n)
        bind = Bindings(jnp.asarray(cols), jnp.ones((m,), bool),
                        jnp.zeros((), bool))
        pat = CompiledPattern(Slot.bound(0), Slot.const_(1), Slot.free(1))
        got = hj_ops.match_matrix(bind, kb, pat)
        want = match_matrix_ref(bind.cols, bind.valid, kb.s_ps, kb.p_ps,
                                kb.o_ps, kb.valid, pat)
        ok = bool(jnp.all(got == want))
        ref_fn = jax.jit(lambda c, v: match_matrix_ref(
            c, v, kb.s_ps, kb.p_ps, kb.o_ps, kb.valid, pat))
        tt = time_fn(ref_fn, bind.cols, bind.valid, iters=3)
        out[f"m{m}xn{n}"] = {"exact": ok, "jnp_s": tt["median_s"]}
        rows.append(["hash_join", f"{m}x{n}", "exact" if ok else "MISMATCH",
                     ms(tt["median_s"])])
    return out, rows


def run() -> dict:
    all_rows, results = [], {}
    for fn in (bench_hash_join, bench_join_fused, bench_probe_join,
               bench_closure, bench_flash_attention, bench_decode_attention,
               bench_ssd):
        out, rows = fn()
        results[fn.__name__] = out
        all_rows += rows
    write_bench_join(results["bench_join_fused"], results["bench_probe_join"])
    print(format_table(
        "Pallas kernels — fidelity sweeps (interpret mode) + jnp-path wall time",
        ["kernel", "shape", "vs ref", "jnp time"], all_rows,
    ))
    save_results("kernels", results)
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    run()

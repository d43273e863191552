#!/usr/bin/env python3
"""Bring-up smoke run of DSCEP on one TPU chip (or four, with --four-chips).

Drives the system's main path once, through the entry points a user calls,
over a world made from ``--seed``:

* a DBpedia-like KB of over 1M triples (``repro.data.dbpedia``: the used
  slice plus 1M unused filler triples, so used-KB pruning matters and the
  KB is far larger than any on-chip buffer);
* a TweetsKB-like stream (``repro.data.tweets``) cut into chunks of four
  1000-triple windows, the paper queries' ``RANGE TRIPLES 1000``;
* the launcher's caps: bind_cap 2048, scan_cap 512, out_cap 2048.

Phases on one chip:

* CQuery1 through a ``Session`` in the monolithic, single_program and
  pipelined modes on the default jnp path (``kb_method="auto"``);
* the same with the Pallas kernels on, once with every KB join a scan (the
  fused scan kernel) and once with probes (the fused probe kernel); the
  ``rdf:type/rdfs:subClassOf*`` closure runs through the closure kernels;
* ``artist_classes`` (a ``subClassOf+`` closure path) with the Pallas
  kernels on;
* a ``ServeEngine`` behind its admission front-end answering a small
  standing-query population.

Every CQuery1 stream must equal the monolithic jnp stream bit for bit, with
no overflowed window; artist_classes equals its own monolithic jnp stream;
each served query equals the same query run alone in its own Session.  Any
mismatch, overflow, tenant fault, quarantine, restart or degraded chunk
fails the run, as does a platform other than ``tpu``.  The last line of
standard output is ``{"ok": true, "device": {...}}`` and nothing else.

``--four-chips`` runs only what needs four chips — CQuery1 pipelined with
round_robin placement over the four devices, and single_program on a 4-way
``data`` mesh — each compared with monolithic on one chip.

    python3 chip_smoke.py
    python3 chip_smoke.py --four-chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse [--four-chips]

``--rehearse`` runs the same phases over a tiny world on any platform
(on the CPU, four virtual devices for ``--four-chips``) and prints no
result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the paper's window and the launcher's caps; the published chunk holds
# every window's out_cap rows, so publishing never clips (it has no
# overflow flag of its own)
WINDOW = 1000
WINDOWS_PER_CHUNK = 4
CAPS = dict(bind_cap=2048, scan_cap=512, out_cap=2048, intermediate_cap=2048,
            out_stream_cap=WINDOWS_PER_CHUNK * 2048)
SERVE_QUERIES = 6


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (pipelined over 4 "
                         "chips, single_program on a 4-way data mesh)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny world on any platform; prints no result line")
    return ap.parse_args(argv)


def build_world(seed: int, rehearse: bool):
    import numpy as np

    from repro.core.rdf import Vocab
    from repro.data.dbpedia import KBConfig, generate_kb
    from repro.data.tweets import (
        TweetSchema, TweetStreamConfig, generate_tweets, stream_chunks,
    )

    filler, chunks_wanted = (2_000, 3) if rehearse else (1_000_000, 4)
    vocab = Vocab()
    kbd = generate_kb(vocab, KBConfig(filler_triples=filler, seed=seed))
    tweets = TweetSchema.create(vocab)
    pool = np.concatenate([kbd.artist_ids, kbd.show_ids])
    chunk_triples = WINDOW * WINDOWS_PER_CHUNK
    # ~6.6 triples per tweet; generate enough for the wanted chunks
    num_tweets = chunks_wanted * chunk_triples // 6
    rows = generate_tweets(vocab, tweets, pool, TweetStreamConfig(
        num_tweets=num_tweets, mentions_min=2, mentions_max=4, seed=seed))
    chunks = list(stream_chunks(rows, chunk_triples))[:chunks_wanted]
    return vocab, kbd, chunks


def n_valid(batches) -> int:
    import numpy as np

    return int(sum(np.asarray(b.valid).sum() for b in batches))


def join_methods(explain) -> str:
    """``op: method,...`` for every KBJoin of every operator's plan."""

    def walk(steps):
        for s in steps:
            if s["step"] == "KBJoin":
                yield s["method"] + (
                    "(k=%d)" % s["k_max"] if "k_max" in s else "")
            for key in ("sub", "left", "right"):
                yield from walk(s.get(key, ()))

    return "; ".join(
        "%s: %s" % (op, ",".join(walk(e["steps"])) or "-")
        for op, e in explain["operators"].items())


def same_stream(got, want) -> bool:
    import numpy as np

    if len(got) != len(want):
        return False
    return all(
        np.array_equal(np.asarray(ca), np.asarray(cb))
        for a, b in zip(got, want) for ca, cb in zip(a, b))


def run_query(world, cfg, text, label, kind):
    """Register ``text`` in a fresh Session and stream every chunk through
    it: the first chunk alone (compile included), then the rest."""
    import jax

    from repro.core.session import Session

    vocab, kbd, chunks = world
    reg = Session(cfg, vocab=vocab, kb=kbd.kb).register(text)
    t0 = time.perf_counter()
    first, ovf1 = reg.run(chunks[:1])
    jax.block_until_ready(first)
    t1 = time.perf_counter()
    rest, ovf2 = reg.run(chunks[1:])
    jax.block_until_ready(rest)
    t2 = time.perf_counter()
    outs = first + rest
    overflow = sum(ovf1.values()) + sum(ovf2.values())
    stats = reg.last_stats
    rec = stats["recovery"]
    check(overflow == 0, "%s: %d overflowed windows" % (label, overflow))
    check(not stats["degraded"] and not rec["degraded_chunks"]
          and not rec["restarts"], "%s: degraded or restarted" % label)
    placement = getattr(reg.runtime, "placement", None) or {}
    print("[smoke] %s device_kind=%s mode=%s joins={%s} triples_in=%d "
          "rows_out=%d compile_s=%.3f steady_s=%.3f steady_chunks=%d%s"
          % (label, kind, cfg.mode, join_methods(reg.explain()),
             n_valid(chunks), n_valid(outs), t1 - t0, t2 - t1,
             len(chunks) - 1,
             " devices=%d" % len({str(d) for d in placement.values()})
             if placement else ""), flush=True)
    return outs, reg


def serve_phase(world, cfg, kind):
    """Serve a standing-query population behind the admission front-end and
    compare every query with the same query alone in its own Session."""
    from repro.core.session import Session
    from repro.launch.dscep_run import serve_population
    from repro.serve.batcher import QueryRequest

    vocab, kbd, chunks = world
    texts = serve_population(SERVE_QUERIES)
    eng = Session(cfg, vocab=vocab, kb=kbd.kb).serve()
    adm = eng.admission()
    for text in texts:
        check(adm.submit(QueryRequest(text)), "serve: registration refused")
    names = adm.active()
    outs = {n: [] for n in names}
    t0 = time.perf_counter()
    for c in chunks:
        check(adm.offer_chunk(c), "serve: chunk refused")
        for _, res in adm.drain():
            for n, o in res.items():
                outs[n].append(o)
    t_serve = time.perf_counter() - t0
    st = adm.stats()
    check(st["tenant_faults"] == 0 and st["quarantined_tenants"] == 0
          and st["chunks_invalid"] == 0
          and st["chunks_processed"] == len(chunks),
          "serve: admission counters %r" % {
              k: st[k] for k in ("tenant_faults", "quarantined_tenants",
                                 "chunks_invalid", "chunks_processed")})
    check(sum(eng.overflow_totals().values()) == 0, "serve: overflow")
    for name, text in zip(names, texts):
        alone, ovf = Session(cfg, vocab=vocab, kb=kbd.kb).register(
            text).run(chunks)
        check(sum(ovf.values()) == 0, "serve: %s alone overflowed" % name)
        check(same_stream(outs[name], alone),
              "serve: %s differs from its own Session" % name)
    sched = eng.last_stats
    print("[smoke] serve device_kind=%s mode=%s queries=%d distinct_plans=%d "
          "triples_in=%d rows_out=%d serve_s=%.3f (compile included) "
          "tenant_faults=0 quarantined=0"
          % (kind, cfg.mode, len(names), sched["distinct_plans"],
             n_valid(chunks), sum(n_valid(o) for o in outs.values()),
             t_serve), flush=True)


def one_chip_phases(world, base, kind):
    from repro.core import paper_queries as PQ
    from repro.core.session import MODES

    cq1 = PQ.CQUERY1_RQ
    ref, _ = run_query(world, base.replace(mode="monolithic"), cq1,
                       "cquery1/jnp/monolithic (reference)", kind)
    check(n_valid(ref) > 0, "reference stream is empty")
    for mode in MODES[1:]:
        label = "cquery1/jnp/%s" % mode
        got, _ = run_query(world, base.replace(mode=mode), cq1, label, kind)
        check(same_stream(got, ref), "%s differs from the reference" % label)
    pallas = base.replace(use_pallas=True, fuse_compaction=True)
    for method in ("scan", "probe"):
        for mode in MODES:
            label = "cquery1/pallas-%s/%s" % (method, mode)
            got, _ = run_query(world, pallas.replace(mode=mode,
                                                     kb_method=method),
                               cq1, label, kind)
            check(same_stream(got, ref),
                  "%s differs from the reference" % label)
    with open(os.path.join(ROOT, "examples", "queries",
                           "artist_classes.rq")) as f:
        ac = f.read()
    mono = base.replace(mode="monolithic")
    want, _ = run_query(world, mono, ac, "artist_classes/jnp/monolithic", kind)
    check(n_valid(want) > 0, "artist_classes stream is empty")
    got, _ = run_query(world, mono.replace(use_pallas=True,
                                           fuse_compaction=True),
                       ac, "artist_classes/pallas/monolithic", kind)
    check(same_stream(got, want), "artist_classes: pallas differs from jnp")
    serve_phase(world, mono, kind)


def four_chip_phases(world, base, kind):
    import jax

    from repro.core import paper_queries as PQ
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    check(len(devices) >= 4, "four-chip phases need 4 devices, found %d"
          % len(devices))
    cq1 = PQ.CQUERY1_RQ
    # the reference: the whole monolithic program on the first chip
    ref, _ = run_query(world, base.replace(mode="monolithic"), cq1,
                       "cquery1/jnp/monolithic@1chip (reference)", kind)
    got, reg = run_query(world, base.replace(mode="pipelined",
                                             placement="round_robin"),
                         cq1, "cquery1/jnp/pipelined@4chips", kind)
    used = {str(d) for d in reg.runtime.placement.values()}
    check(len(used) >= 2, "pipelined: operators on %d device(s)" % len(used))
    check(reg.runtime.depth_hw >= 2,
          "pipelined: depth_hw=%d < 2" % reg.runtime.depth_hw)
    check(same_stream(got, ref), "pipelined@4chips differs from reference")
    mesh = make_mesh((4,), ("data",))
    got, _ = run_query(world, base.replace(mode="single_program", mesh=mesh),
                       cq1, "cquery1/jnp/single_program@data4", kind)
    check(same_stream(got, ref), "single_program@data4 differs from reference")


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse and args.four_chips:
        from repro.launch.mesh import ensure_host_devices

        ensure_host_devices(4)
    import jax

    from repro.core.session import ExecutionConfig
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("chip_smoke: JAX found no TPU (platform %r); nothing was run"
              % dev.platform, file=sys.stderr)
        return 2
    print("[smoke] platform=%s device_kind=%s devices=%d"
          % (dev.platform, dev.device_kind, len(devices)), flush=True)
    t0 = time.perf_counter()
    world = build_world(args.seed, args.rehearse)
    print("[smoke] world: KB=%d triples, stream=%d triples in %d chunks, "
          "built in %.1f s" % (int(world[1].kb.count()), n_valid(world[2]),
                               len(world[2]), time.perf_counter() - t0),
          flush=True)
    base = ExecutionConfig(window_capacity=WINDOW,
                           max_windows=WINDOWS_PER_CHUNK, kb_method="auto",
                           **CAPS)
    try:
        if args.four_chips:
            four_chip_phases(world, base, dev.device_kind)
        else:
            one_chip_phases(world, base, dev.device_kind)
    except SmokeFailure as err:
        print("chip_smoke: FAILED: %s" % err, file=sys.stderr)
        return 1
    print("[smoke] all phases passed in %.1f s" % (time.perf_counter() - t0),
          flush=True)
    if args.rehearse:
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

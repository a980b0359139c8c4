"""On-chip smoke test of EE-Join's main path (a bring-up check, not a benchmark).

    python chip_smoke.py             # one TPU: batch phase + serving phase
    python chip_smoke.py --chips 4   # four TPUs: the multi-device paths only

One chip, through the entry points a user calls, on a sanctions-list
sized dictionary (``data/synth.make_corpus``, E = 16384 entities over a
32768-token vocabulary, 4096 x 256-token documents, Zipf mentions):

* batch — the §5 plan search through ``EEJoinOperator`` with
  ``use_kernel=True``; ``execute`` on the device and ``execute_corpus``
  over a file-backed ``MemmapCorpus`` streamed in >= 4 shards. Both must
  equal the ``use_kernel=False`` path bit for bit, and the brute-force
  ``oracle_extract`` on a document subset;
* serving — 64 variable-length requests through ``ExtractionService``
  (assembled as ``launch/serve_extract.py`` does), equal to
  ``one_shot_reference``.

``--chips 4`` runs only what needs several chips, each against its
one-chip reference, on the same dictionary and a quarter of the corpus:
``execute_sharded`` over a 4-device mesh vs ``execute``;
``ExtractionService`` on disjoint probe/verify pools vs
``one_shot_reference``; four replicas (one per chip, one process)
behind ``ClusterCoordinator`` vs ``one_shot_reference``.

Every candidate and result buffer is checked for overflow. Timings
printed on the way are smoke timings (wall clock, compilation
included), not benchmark numbers. The last line of stdout is
``{"ok": true, "device": {...}}``; any mismatch, overflow or missing TPU
exits non-zero without it. The phase functions take their sizes, so
``tests/test_chip_smoke.py`` runs them on the CPU at a tiny size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

GAMMA = 0.8
#: the one-chip deployment: a sanctions-list-sized dictionary
CORPUS = dict(num_entities=16384, vocab_size=32768, num_docs=4096,
              doc_len=256, mention_dist="zipf")
#: documents the brute-force host oracle checks (well under a minute)
ORACLE_DOCS = 2
SERVE_REQUESTS = 64
SERVE_BATCH_DOCS = 8
#: --chips 4 is charged four chips per second: it streams a quarter of
#: the corpus (the dictionary stays full size) and fewer requests
MULTICHIP_DOCS = 1024
MULTICHIP_REQUESTS = 8
#: the replica leg gives each replica one dictionary session it owns on
#: the hash ring, a disjoint slice of at most REPLICA_TENANT_ENTITIES
#: entities: every replica bootstraps every session and compiles on its
#: own chip, so the slices are kept small
REPLICA_TENANT_ENTITIES = 1024


class SmokeFailure(RuntimeError):
    """A parity, overflow or placement check failed."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


#: XLA backend compilations seen so far in this process: [count, seconds]
COMPILES = [0, 0.0]


def count_compiles() -> None:
    """Count backend compilations into ``COMPILES`` (progress lines
    report them: on a chip, compilation is most of a cold phase)."""
    import jax.monitoring

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILES[0] += 1
            COMPILES[1] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def progress(t0: float, c0: list) -> str:
    """Wall seconds and compilations since ``t0`` / snapshot ``c0``."""
    return (f"{time.perf_counter() - t0:.1f}s, {COMPILES[0] - c0[0]} "
            f"compiles ({COMPILES[1] - c0[1]:.1f}s)")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def make_smoke_corpus(seed: int, **sizes):
    from repro.data.synth import make_corpus

    return make_corpus(**{**CORPUS, **sizes}, seed=seed)


def window_grid(docs: int, doc_len: int, max_len: int) -> int:
    """Candidate capacity that holds every window: nothing can overflow."""
    return docs * doc_len * max_len


def _timed(fn):
    import jax

    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


def _no_overflow(m, capacity: int, what: str) -> int:
    n = int(m.count)
    check(n <= capacity, f"{what}: result buffer overflowed ({n} > {capacity})")
    return n


def batch_config(corpus, *, min_shards: int = 4,
                 result_capacity: int = 1 << 20):
    """The batch phase's ``EEJoinConfig``: window-grid candidate capacity
    and a device budget that splits the corpus into ``min_shards``."""
    from repro.core.eejoin import EEJoinConfig

    D, T = corpus.doc_tokens.shape
    L = corpus.dictionary.max_len
    return EEJoinConfig(
        gamma=GAMMA,
        use_kernel=True,
        max_candidates=window_grid(D, T, L),
        result_capacity=result_capacity,
        # spill sizing holds two staged shards: D*T*4*2 / min_shards
        device_budget_bytes=D * T * 8 // min_shards,
    )


def plan_and_prepare(corpus, cfg, stats_docs: int = 256):
    """§5 plan search + prepare through the public operator."""
    from repro.core.eejoin import EEJoinOperator

    op = EEJoinOperator(corpus.dictionary, cfg)
    D = corpus.doc_tokens.shape[0]
    stats = op.gather_statistics(corpus.doc_tokens[:stats_docs], total_docs=D)
    plan = op.choose_plan(stats)
    return op, plan, op.prepare(plan)


def oracle_truth(corpus, plan, docs: int):
    """Host-oracle truth on the first ``docs`` documents.

    Returns ``(truth, is_lsh)``: the variant scheme answers
    exact-variant semantics, every other scheme the configured
    similarity; ``is_lsh(entity)`` marks entities of LSH sides, which
    are approximate (their matches need only be a subset of the truth).
    """
    from repro.extraction.oracle import oracle_extract

    d = corpus.dictionary
    sub = corpus.doc_tokens[:docs]
    sims, truth, lsh = {}, set(), []
    E = d.num_entities
    for side, a, b in ((plan.head, 0, plan.split), (plan.tail, plan.split, E)):
        if a >= b:
            continue
        sim = "variant_exact" if side.scheme == "variant" else "extra"
        if sim not in sims:
            sims[sim] = oracle_extract(sub, d, GAMMA, sim)
        truth |= {x for x in sims[sim] if a <= x[3] < b}
        if side.scheme == "lsh":
            lsh.append((a, b))
    return truth, lambda e: any(a <= e < b for a, b in lsh)


def batch_phase(corpus, *, work_dir: Path, min_shards: int = 4,
                oracle_docs: int = ORACLE_DOCS, stats_docs: int = 256,
                tile_docs: int | None = None) -> dict:
    """``execute`` and ``execute_corpus`` on the device vs the XLA path
    and the host oracle. Returns the phase's report."""
    import jax.numpy as jnp

    from repro.core.eejoin import EEJoinOperator
    from repro.extraction import sharded
    from repro.kernels import ops

    cfg = batch_config(corpus, min_shards=min_shards)
    D, T = corpus.doc_tokens.shape
    t = time.perf_counter()
    op, plan, prepared = plan_and_prepare(corpus, cfg, stats_docs)
    E = corpus.dictionary.num_entities
    rep = {"plan": plan.describe(E), "plan_s": time.perf_counter() - t,
           "verify_impl": {f"{s.side.algo}:{s.side.scheme}":
                           ops.verify_impl(cfg.sim_name)
                           for s in prepared.sides}}
    log(f"batch: E={E} docs={D}x{T} plan {rep['plan']} "
        f"verify {rep['verify_impl']}")

    docs = jnp.asarray(corpus.doc_tokens)
    m, rep["execute_s"] = _timed(lambda: op.execute(prepared, docs))
    rep["matches"] = _no_overflow(m, cfg.result_capacity, "execute")
    got = m.to_set()

    ref_op = EEJoinOperator(corpus.dictionary,
                            dataclasses.replace(cfg, use_kernel=False))
    ref_prep = ref_op.prepare(plan)
    m_ref, rep["execute_xla_s"] = _timed(lambda: ref_op.execute(ref_prep, docs))
    _no_overflow(m_ref, cfg.result_capacity, "execute(use_kernel=False)")
    check(got == m_ref.to_set(),
          f"execute: kernel path {len(got)} matches != XLA path "
          f"{len(m_ref.to_set())}")

    work_dir.mkdir(parents=True, exist_ok=True)
    mc = sharded.MemmapCorpus.write(str(work_dir / "corpus"),
                                    corpus.doc_tokens)
    sd = sharded.shard_docs_for_budget(D, T, cfg.device_budget_bytes,
                                       tile_docs)
    rep["shards"] = -(-D // sd)
    check(rep["shards"] >= min_shards,
          f"execute_corpus streamed {rep['shards']} < {min_shards} shards")
    stream_stats: dict = {}
    mcorp, rep["execute_corpus_s"] = _timed(
        lambda: op.execute_corpus(prepared, mc, tile_docs=tile_docs,
                                  stream_stats=stream_stats))
    _no_overflow(mcorp, cfg.result_capacity, "execute_corpus")
    rep["streamed_launches"] = stream_stats.get("streamed_launches", 0)
    # the filter's true survivors among the window grid, over every side
    rep["survival_share"] = (100.0 * stream_stats["survivors"]
                             / stream_stats["windows"])
    check(mcorp.to_set() == got,
          f"execute_corpus {len(mcorp.to_set())} matches != execute "
          f"{len(got)}")

    t = time.perf_counter()
    truth, is_lsh = oracle_truth(corpus, plan, oracle_docs)
    rep["oracle_s"] = time.perf_counter() - t
    sub = {x for x in got if x[0] < oracle_docs}
    exact = {x for x in truth if not is_lsh(x[3])}
    check({x for x in sub if not is_lsh(x[3])} == exact and sub <= truth,
          f"oracle on {oracle_docs} docs: {len(sub)} matches vs truth "
          f"{len(truth)}")
    rep["oracle_docs"] = oracle_docs
    rep["oracle_matches"] = len(sub)
    log(f"batch: execute {rep['matches']} matches (kernel == XLA path), "
        f"execute_corpus over {rep['shards']} shards identical, oracle "
        f"{len(sub)} matches on {oracle_docs} docs identical; no overflow")
    return rep


def request_pool(corpus, requests: int, seed: int):
    """Seeded variable-length documents cut from the corpus (as
    ``launch/serve_extract.build_request_pool`` cuts them)."""
    import numpy as np

    D, T = corpus.doc_tokens.shape
    rng = np.random.default_rng(seed + 1)
    lens = rng.integers(T // 4, T + 1, size=requests)
    return [corpus.doc_tokens[i % D, :lens[i]] for i in range(requests)]


def serving_phase(corpus, *, requests: int = SERVE_REQUESTS,
                  batch_docs: int = SERVE_BATCH_DOCS, seed: int = 0,
                  devices=None) -> dict:
    """``requests`` documents through ``ExtractionService`` vs
    ``one_shot_reference``. ``devices`` picks the pool devices."""
    from repro.core.eejoin import EEJoinConfig
    from repro.serving import (
        BatcherConfig,
        ExtractionService,
        SessionCache,
        make_pools,
        one_shot_reference,
    )

    docs = request_pool(corpus, requests, seed)
    T = corpus.doc_tokens.shape[1]
    cfg = EEJoinConfig(
        gamma=GAMMA, use_kernel=True,
        max_candidates=window_grid(requests, T, corpus.dictionary.max_len),
        result_capacity=1 << 18,
    )
    cache = SessionCache()
    sess = cache.get_or_create(corpus.dictionary, cfg,
                               sample_docs=corpus.doc_tokens[:8])
    pools = make_pools(devices)
    svc = ExtractionService(
        cache, pools=pools,
        batcher_config=BatcherConfig(max_batch_docs=batch_docs,
                                     max_delay_s=0.02),
        queue_capacity=256, overlap=True,
    )
    t = time.perf_counter()
    with svc:
        for i, d in enumerate(docs):
            svc.submit(i, d, sess.key, block=True)
            svc.tick()
        svc.drain()
    serve_s = time.perf_counter() - t
    got = svc.results_set()
    want = one_shot_reference(sess, docs)
    s = svc.metrics.summary()
    check(s["completed"] == requests,
          f"served {s['completed']} of {requests} requests")
    check(len(want) < cfg.result_capacity,
          f"one-shot reference filled its result buffer ({len(want)})")
    check(got == want, f"served {len(got)} matches != one-shot "
          f"{len(want)}")
    rep = {"plan": sess.plan.describe(corpus.dictionary.num_entities),
           "pools": pools.describe(), "requests": requests,
           "batches": s["batches"], "matches": len(got), "serve_s": serve_s}
    log(f"serving: {requests} requests in {s['batches']} batches on "
        f"{rep['pools']}, plan {rep['plan']}: {len(got)} matches == "
        f"one_shot_reference")
    return rep


def sharded_phase(corpus, devices, *, stats_docs: int = 256) -> dict:
    """``execute_sharded`` over a mesh of ``devices`` vs one-device
    ``execute``, bit for bit, with the documents on one device and
    sharded over the mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    cfg = batch_config(corpus)
    op, plan, prepared = plan_and_prepare(corpus, cfg, stats_docs)
    docs = jnp.asarray(corpus.doc_tokens)
    m1, rep_s = _timed(lambda: op.execute(prepared, docs))
    rep = {"execute_s": rep_s}
    _no_overflow(m1, cfg.result_capacity, "execute (one device)")
    want = m1.to_set()
    mesh = Mesh(np.array(devices), ("workers",))
    placed = {"one_device": docs,
              "mesh": jax.device_put(
                  docs, NamedSharding(mesh, PartitionSpec("workers")))}
    for where, d in placed.items():
        t, c = time.perf_counter(), list(COMPILES)
        ms, rep[f"sharded_{where}_s"] = _timed(
            lambda: op.execute_sharded(prepared, d, mesh=mesh))
        _no_overflow(ms, cfg.result_capacity, f"execute_sharded ({where})")
        got = ms.to_set()
        check(got == want, f"execute_sharded over {len(devices)} devices, "
              f"documents on {where}: {len(got)} matches != execute "
              f"{len(want)}")
        log(f"sharded: documents on {where} ({progress(t, c)})")
    log(f"sharded: execute_sharded on a {len(devices)}-device mesh == "
        f"one-device execute ({len(want)} matches, plan "
        f"{plan.describe(corpus.dictionary.num_entities)})")
    return {"matches": len(want), **rep}


def replica_phase(corpus, devices, *, batch_docs: int = SERVE_BATCH_DOCS
                  ) -> dict:
    """One replica per device, all in this process, behind
    ``ClusterCoordinator``. Each replica owns (is the hash ring's
    primary for) one dictionary session, a disjoint slice of at most
    ``REPLICA_TENANT_ENTITIES`` entities, and answers one batch of it;
    every session is bootstrapped on every replica. Every response must
    equal ``one_shot_reference`` at its admitted epoch, each replica's
    session state must live on its own device, and no replica loop may
    end in an error."""
    from repro.core.eejoin import EEJoinConfig
    from repro.fabric.cluster import ClusterCoordinator, launch_local_cluster
    from repro.fabric.ring import HashRing
    from repro.serving import SessionCache, one_shot_reference
    from repro.serving.session import dictionary_fingerprint

    # full-length documents: every batch has one shape, so each chip
    # compiles the request path once per session plan rather than once
    # per batch shape (the serving phase covers variable lengths)
    D, T = corpus.doc_tokens.shape
    d = corpus.dictionary
    cfg = EEJoinConfig(
        gamma=GAMMA, use_kernel=True,
        max_candidates=window_grid(batch_docs, T, d.max_len),
        result_capacity=1 << 16,
    )
    t0, c0 = time.perf_counter(), list(COMPILES)
    names = [f"replica{i}" for i in range(len(devices))]
    ring = HashRing(names)  # the coordinator's ring over the same names
    step = min(REPLICA_TENANT_ENTITIES, d.num_entities // (2 * len(names)))
    owned: dict = {}  # replica -> first entity of its disjoint slice
    a = 0
    while len(owned) < len(names) and a + step <= d.num_entities:
        owner = ring.primary(dictionary_fingerprint(d.slice(a, a + step),
                                                    cfg))
        if owner in owned:
            a += 1
        else:
            owned[owner] = a
            a += step
    check(len(owned) == len(names),
          f"no disjoint {step}-entity slices with primaries {names}: "
          f"found {sorted(owned)}")
    cache = SessionCache()
    sessions = [cache.get_or_create(d.slice(owned[n], owned[n] + step), cfg,
                                    sample_docs=corpus.doc_tokens[:8])
                for n in names]
    log(f"replicas: one {step}-entity session per replica planned "
        f"({progress(t0, c0)})")
    threads, endpoints = launch_local_cluster(names, devices=devices,
                                              endpoint_timeout=900.0)
    coord = ClusterCoordinator(endpoints, hold_epochs=True)
    try:
        for k, sess in enumerate(sessions):
            t, c = time.perf_counter(), list(COMPILES)
            coord.add_session(sess)
            log(f"replicas: session {k} bootstrapped on {len(names)} "
                f"replicas ({progress(t, c)})")
        matches = 0
        for k, sess in enumerate(sessions):
            batch = [corpus.doc_tokens[(k * batch_docs + j) % D]
                     for j in range(batch_docs)]
            t, c = time.perf_counter(), list(COMPILES)
            epoch, m = coord.extract(sess.key, batch, timeout=900.0)
            served_s = progress(t, c)
            got = m.to_set()
            t, c = time.perf_counter(), list(COMPILES)
            want = one_shot_reference(sess, batch, epoch=epoch)
            log(f"replicas: {names[k]} answered session {k} ({served_s}); "
                f"one-shot reference ({progress(t, c)})")
            check(len(want) < cfg.result_capacity,
                  "replica reference filled its result buffer")
            check(got == want, f"{names[k]}'s response: {len(got)} "
                  f"matches != one-shot {len(want)}")
            matches += len(got)
        stats = coord.poll_stats()
    finally:
        coord.shutdown()
        for t in threads:
            t.join(timeout=30)
    for t in threads:
        check(t.error is None, f"{t.name} ended with {t.error!r}")
        check(not t.is_alive(), f"{t.name} still running after shutdown")
    served = {}
    for name, dev in zip(names, devices):
        remote = stats[name]["remote"]
        check(remote["devices"] == [str(dev)],
              f"{name}: session state on {remote['devices']}, expected "
              f"{dev}")
        served[name] = remote["requests_served"]
    check(all(n == 1 for n in served.values()),
          f"replicas served {served} batches, expected one each")
    log(f"replicas: {len(names)} replicas (one per device, one process) "
        f"served {served} batches for {len(sessions)} sessions; "
        f"{matches} matches == one_shot_reference ({progress(t0, c0)})")
    return {"served": served, "matches": matches}


def multichip_phase(corpus, devices, *, seed: int = 0,
                    stats_docs: int = 64) -> dict:
    """Everything that needs several chips, each against its one-chip
    reference."""
    t, c = time.perf_counter(), list(COMPILES)
    rep = {"sharded": sharded_phase(corpus, devices, stats_docs=stats_docs)}
    log(f"sharded leg done ({progress(t, c)})")
    t, c = time.perf_counter(), list(COMPILES)
    rep["pools"] = serving_phase(corpus, seed=seed, devices=devices,
                                 requests=MULTICHIP_REQUESTS)
    check("disjoint" in rep["pools"]["pools"],
          f"pools not disjoint: {rep['pools']['pools']}")
    log(f"pools leg done ({progress(t, c)})")
    rep["replicas"] = replica_phase(corpus, devices)
    return rep


def device_report() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = device_report()
    if dev["platform"] != "tpu":
        print(f"[chip_smoke] no TPU: JAX sees {dev}", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"[chip_smoke] --chips {args.chips} but JAX sees {dev}",
              file=sys.stderr)
        return 2
    from repro import compile_cache

    log(f"device {dev}; compile cache {compile_cache.enable()}")
    count_compiles()
    t = time.perf_counter()
    corpus = make_smoke_corpus(
        args.seed, **({} if args.chips == 1 else {"num_docs": MULTICHIP_DOCS}))
    log(f"corpus generated in {time.perf_counter() - t:.1f}s")
    try:
        if args.chips == 1:
            report = {
                "batch": batch_phase(corpus,
                                     work_dir=ROOT / ".smoke"),
                "serving": serving_phase(corpus, seed=args.seed),
            }
        else:
            report = multichip_phase(corpus, jax.devices()[:4],
                                     seed=args.seed)
        log("smoke timings (wall s, compilation included; not benchmark "
            "numbers) and counts: " + json.dumps(report, default=str))
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Multi-host serving fabric entrypoint (the distributed-tier demo).

    PYTHONPATH=src python -m repro.launch.serve_cluster \
        --replicas 2 --requests 16 --deltas 2 --check

Starts N replicas in this process, each on its own thread and device
(one chip per replica on a multi-chip host) and connected over TCP
sockets to the coordinator (the stand-in for N hosts — same socket
channels, same wire frames), bootstraps a session onto each from a
compacted base snapshot, then
serves a mixed workload through the cluster coordinator: requests are
consistent-hash routed to epoch-agreed replicas while serialized
``DictionaryDelta``s replicate live between batches. With ``--check``
every response is asserted bit-identical to the single-host
``one_shot_reference`` at the epoch the request was admitted under
(exit 1 on drift). ``--mode verify`` runs the same workload through
``ExtractionService`` with the verify pool behind the transport
(``remote_verify``) instead of direct request routing.

The report ends with the per-replica fabric section of
``ServingMetrics.summary``: lane/request bytes on the wire, frames
retried, replication lag, routed/shed per replica.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro import compile_cache
from repro.core.eejoin import EEJoinConfig
from repro.data.synth import make_corpus
from repro.fabric.cluster import ClusterCoordinator, launch_local_cluster
from repro.serving import (
    BatcherConfig,
    ExtractionService,
    ServingMetrics,
    SessionCache,
    one_shot_reference,
)
from repro.serving.session import pure_plan
from repro.updates.delta import random_delta


def build_workload(args):
    corpus = make_corpus(
        num_docs=max(args.requests, 8),
        doc_len=args.doc_len,
        vocab_size=512,
        num_entities=args.entities,
        seed=args.seed,
    )
    rng = np.random.default_rng(args.seed + 1)
    lens = rng.integers(args.doc_len // 4, args.doc_len + 1,
                        size=args.requests)
    docs = [corpus.doc_tokens[i % corpus.doc_tokens.shape[0], : lens[i]]
            for i in range(args.requests)]
    return corpus, docs, rng


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch-docs", type=int, default=4,
                    help="documents per routed request batch")
    ap.add_argument("--deltas", type=int, default=2,
                    help="live dictionary deltas replicated mid-stream")
    ap.add_argument("--doc-len", type=int, default=64)
    ap.add_argument("--entities", type=int, default=32)
    ap.add_argument("--scheme", default="prefix",
                    choices=("word", "prefix", "lsh", "variant"))
    ap.add_argument("--mode", default="route",
                    choices=("route", "verify"),
                    help="route: full requests to replicas; verify: "
                         "local probe + remote verify through "
                         "ExtractionService")
    ap.add_argument("--check", action="store_true",
                    help="assert per-request parity vs one_shot_reference "
                         "at the admitted epoch")
    ap.add_argument("--timeout", type=float, default=300.0,
                    help="per-RPC timeout (first request pays jit "
                         "compilation on the replica)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    corpus, docs, rng = build_workload(args)
    cfg = EEJoinConfig(
        gamma=0.8, max_candidates=4096, result_capacity=8192,
        use_kernel=True,
    )
    cache = SessionCache()
    sess = cache.get_or_create(corpus.dictionary, cfg,
                               plan=pure_plan(args.scheme))

    names = [f"replica{i}" for i in range(args.replicas)]
    t0 = time.perf_counter()
    threads, endpoints = launch_local_cluster(
        names, endpoint_timeout=args.timeout
    )
    print(f"[serve_cluster] started {len(threads)} replica(s) in "
          f"{time.perf_counter() - t0:.1f}s: {', '.join(names)}")
    metrics = ServingMetrics()
    coord = ClusterCoordinator(
        endpoints, metrics=metrics, hold_epochs=args.check
    )
    t0 = time.perf_counter()
    coord.add_session(sess)
    print(f"[serve_cluster] session {sess.key} (scheme {args.scheme}) "
          f"bootstrapped on {len(endpoints)} replicas in "
          f"{time.perf_counter() - t0:.1f}s")

    version = lambda: sess.current_state.version  # noqa: E731
    batches = [docs[i:i + args.batch_docs]
               for i in range(0, len(docs), args.batch_docs)]
    delta_every = max(len(batches) // (args.deltas + 1), 1)
    checked = 0
    failures = 0
    t0 = time.perf_counter()

    if args.mode == "route":
        admitted = []  # (epoch, batch_docs, served set)
        for bi, batch in enumerate(batches):
            if args.deltas and bi and bi % delta_every == 0 \
                    and len(sess.maintenance_log) < args.deltas:
                delta = random_delta(rng, version(), 512)
                coord.apply_delta(sess.key, delta)
                print(f"[serve_cluster] delta replicated before batch "
                      f"{bi}: +{delta.num_added}/-{delta.num_tombstoned} "
                      f"-> epoch {sess.epoch} "
                      f"({sess.maintenance_log[-1]['action']})")
            epoch, matches = coord.extract(sess.key, batch,
                                           timeout=args.timeout)
            admitted.append((epoch, batch, matches.to_set()))
        if args.check:
            for epoch, batch, got in admitted:
                want = one_shot_reference(sess, batch, epoch=epoch)
                checked += 1
                if got != want:
                    failures += 1
                    print(f"[serve_cluster] PARITY FAILED at epoch "
                          f"{epoch}: {len(got)} vs {len(want)} matches",
                          file=sys.stderr)
    else:  # verify mode: ExtractionService with the remote verify pool
        svc = ExtractionService(
            cache,
            batcher_config=BatcherConfig(max_batch_docs=args.batch_docs,
                                         max_delay_s=0.005),
            overlap=False,
            remote_verify=coord,
        )
        with svc:
            for bi, batch in enumerate(batches):
                if args.deltas and bi and bi % delta_every == 0 \
                        and len(sess.maintenance_log) < args.deltas:
                    svc.drain()  # route pending lanes at their epochs
                    delta = random_delta(rng, version(), 512)
                    coord.apply_delta(sess.key, delta)
                    print(f"[serve_cluster] delta replicated before "
                          f"batch {bi} -> epoch {sess.epoch}")
                for j, d in enumerate(batch):
                    svc.submit(bi * args.batch_docs + j, d, sess.key,
                               block=True)
                svc.tick()
            svc.drain()
        if args.check:
            got = svc.results_set()
            want = _verify_mode_reference(svc, sess, docs)
            checked = 1
            if got != want:
                failures = 1
                print(f"[serve_cluster] PARITY FAILED (verify mode): "
                      f"{len(got)} vs {len(want)} matches",
                      file=sys.stderr)
    elapsed = time.perf_counter() - t0

    print(f"[serve_cluster] served {len(batches)} batch(es) / "
          f"{len(docs)} doc(s) in {elapsed:.1f}s "
          f"({len(docs) / max(elapsed, 1e-9):.1f} docs/s), final epoch "
          f"{sess.epoch}, maintenance "
          f"{[m['action'] for m in sess.maintenance_log] or '[]'}")
    coord.poll_stats()
    s = metrics.summary()
    for name, row in s["replicas"].items():
        print(f"[serve_cluster] replica {name}: "
              f"{'alive' if row['alive'] else 'DEAD'}, routed "
              f"{row['routed']}, shed {row['shed']}, retried frames "
              f"{row['frames_retried']}, lag {row['replication_lag_epochs']}"
              f" epoch(s), lane bytes {row['lane_bytes']}, wire tx/rx "
              f"{row['bytes_sent']}/{row['bytes_received']} B")
    coord.shutdown()
    for t in threads:
        t.join(timeout=30)

    if args.check:
        if failures:
            return 1
        print(f"[serve_cluster] parity OK: {checked} response(s) "
              "bit-identical to one_shot_reference at their admitted "
              "epochs")
    return 0


def _verify_mode_reference(svc, sess, docs) -> set:
    """Exact reference for verify mode: replay each batch's docs at its
    admitted epoch (epochs recorded on the metrics batch rows)."""
    want = set()
    by_batch: dict[int, list] = {}
    for req in svc.completed:
        by_batch.setdefault(req.batch_id, []).append(req)
    epoch_of = {rec["batch_id"]: rec["epoch"]
                for rec in svc.metrics.batch_records}
    for bid, reqs in by_batch.items():
        bdocs = [docs[r.doc_id] for r in sorted(reqs, key=lambda r: r.doc_id)]
        ref = one_shot_reference(sess, bdocs, epoch=epoch_of[bid])
        id_map = {row: r.doc_id
                  for row, r in enumerate(sorted(reqs,
                                                 key=lambda r: r.doc_id))}
        want |= {(id_map[d], p, l, e) for (d, p, l, e) in ref}
    return want


if __name__ == "__main__":
    sys.exit(main())

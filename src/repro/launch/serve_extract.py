"""Online extraction serving entrypoint (the serving-subsystem demo).

    PYTHONPATH=src python -m repro.launch.serve_extract \
        --requests 32 --rate 200 --overlap --check

Builds a synthetic dictionary + request pool, creates a cached serving
session (statistics → cost-based plan choice, optionally calibrated to
this host), and drives the two-stage probe/verify service with a seeded
open-loop load generator in *real time* (arrivals realised with
``time.sleep``; the serving benches use a virtual clock instead — see
``benchmarks/bench_serving.py``). Prints the metrics summary and, with
``--check``, asserts bit-parity of the served matches against a
one-shot ``eejoin.execute`` over the same documents (exit 1 on drift).
"""
from __future__ import annotations

import argparse
import sys
import threading
import time

import numpy as np

from repro import compile_cache
from repro.core.eejoin import EEJoinConfig
from repro.data.synth import make_corpus
from repro.serving import (
    BatcherConfig,
    ExtractionService,
    ReplanConfig,
    SessionCache,
    make_pools,
    one_shot_reference,
    realized_gain,
    session_cache_summary,
)
from repro.serving.session import pure_plan


def build_request_pool(args):
    """Seeded variable-length documents cut from a synthetic corpus."""
    corpus = make_corpus(
        num_docs=max(args.requests, 8),
        doc_len=args.doc_len,
        vocab_size=2048,
        num_entities=args.entities,
        seed=args.seed,
    )
    rng = np.random.default_rng(args.seed + 1)
    lens = rng.integers(args.doc_len // 4, args.doc_len + 1, size=args.requests)
    docs = [corpus.doc_tokens[i % corpus.doc_tokens.shape[0], : lens[i]]
            for i in range(args.requests)]
    return corpus, docs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop arrival rate (docs/s, Poisson)")
    ap.add_argument("--doc-len", type=int, default=96)
    ap.add_argument("--entities", type=int, default=32)
    ap.add_argument("--batch-docs", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=20.0)
    ap.add_argument("--queue-capacity", type=int, default=256)
    ap.add_argument("--scheme", default="prefix",
                    choices=("word", "prefix", "lsh", "variant"))
    ap.add_argument("--plan", default="auto", choices=("auto", "forced"),
                    help="auto: stats + §5 plan search; forced: pure "
                         "ssjoin:<scheme>")
    ap.add_argument("--calibrate", action="store_true",
                    help="rescale cost constants to this host before the "
                         "plan search (implies --plan auto)")
    ap.add_argument("--overlap", dest="overlap", action="store_true",
                    default=True)
    ap.add_argument("--no-overlap", dest="overlap", action="store_false")
    ap.add_argument("--check", action="store_true",
                    help="assert parity vs one-shot eejoin.execute")
    ap.add_argument("--replan", action="store_true",
                    help="continuous calibration: background replanner "
                         "thread (drift-triggered §5 re-search + epoch "
                         "plan swap)")
    ap.add_argument("--drift-bound", type=float, default=0.3,
                    help="relative survivor/doc-length drift that "
                         "triggers a replan (with --replan)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    corpus, docs = build_request_pool(args)
    cfg = EEJoinConfig(
        gamma=0.8, max_candidates=8192, result_capacity=16384, use_kernel=True
    )
    cache = SessionCache()
    if args.plan == "forced" and not args.calibrate:
        sess = cache.get_or_create(corpus.dictionary, cfg,
                                   plan=pure_plan(args.scheme))
    else:
        sess = cache.get_or_create(
            corpus.dictionary, cfg,
            sample_docs=corpus.doc_tokens[:8],
            calibrate=args.calibrate,
            default_scheme=args.scheme,
        )
    pools = make_pools()
    print(f"[serve_extract] session {sess.key} "
          f"plan: {sess.plan.describe(corpus.dictionary.num_entities)}"
          f"{' (calibrated)' if sess.calibrated else ''}")
    print(f"[serve_extract] pools: {pools.describe()}; "
          f"overlap={'on' if args.overlap else 'off'}")

    replan = None
    if args.replan:
        replan = ReplanConfig(
            density_drift=args.drift_bound,
            doc_len_drift=args.drift_bound,
            thread=True,
        )
        print(f"[serve_extract] replan: on (drift bound "
              f"{args.drift_bound:.2f}, background thread)")
    svc = ExtractionService(
        cache,
        pools=pools,
        batcher_config=BatcherConfig(
            max_batch_docs=args.batch_docs,
            max_delay_s=args.max_delay_ms / 1e3,
        ),
        queue_capacity=args.queue_capacity,
        overlap=args.overlap,
        replan=replan,
    )

    rng = np.random.default_rng(args.seed + 2)
    gaps = rng.exponential(1.0 / max(args.rate, 1e-9), size=len(docs))

    def loadgen():
        # block=True: backpressure instead of shedding, so every doc is
        # served and the --check reference covers the full request set
        for i, d in enumerate(docs):
            time.sleep(gaps[i])
            svc.submit(i, d, sess.key, block=True)
            svc.tick()

    with svc:
        t = threading.Thread(target=loadgen)
        t.start()
        t.join()
        svc.drain()

    s = svc.metrics.summary()
    print(f"[serve_extract] {s['completed']}/{s['submitted']} requests in "
          f"{s['batches']} batches (rejected {s['rejected']}, occupancy "
          f"{s['occupancy_mean']:.2f}, depth max {s['queue_depth_max']})")
    print(f"[serve_extract] latency p50/p95/p99 = {s['latency_p50_s']:.4f}/"
          f"{s['latency_p95_s']:.4f}/{s['latency_p99_s']:.4f} s; "
          f"{s['docs_per_s']:.1f} docs/s, {s['lanes_per_s']:.1f} lanes/s")
    print(f"[serve_extract] streaming: {s['streamed_launches']} streamed "
          f"launches, {s['tiles_streamed']} tiles streamed, "
          f"{s['checkpoint_writes']} checkpoint writes "
          f"(sizing {s['lane_sizing'] or '{}'})")
    if args.replan:
        events = s["replan_events"]
        print(f"[serve_extract] replan: {s['replans']} trigger(s), "
              f"{s['replan_swaps']} swap(s)")
        for e in events:
            line = (f"[serve_extract]   [{e['reason']}] "
                    f"{e['old_plan']} -> {e.get('new_plan', '(kept)')}")
            if "predicted_gain" in e:
                line += f", predicted gain {e['predicted_gain']:+.1%}"
            rg = realized_gain(svc.metrics, e)
            if np.isfinite(rg):
                line += f", realized {rg:+.1%}"
            print(line)
    cs = session_cache_summary(cache)
    row = cs["per_session"][sess.key]
    print(f"[serve_extract] session cache: {cs['sessions']}/"
          f"{cs['max_sessions']} sessions, hits {cs['hits']}, misses "
          f"{cs['misses']}, evictions {cs['evictions']}")
    print(f"[serve_extract] session {sess.key}: epoch {row['epoch']}, "
          f"{row['open_segments']} open segment(s), "
          f"{row['live_entities']} live / {row['tombstoned']} tombstoned "
          f"entities, maintenance {row['maintenance'] or '[]'}")

    if args.check:
        want = one_shot_reference(sess, docs)
        got = svc.results_set()
        if got != want:
            print(f"[serve_extract] PARITY FAILED: served {len(got)} vs "
                  f"one-shot {len(want)} matches", file=sys.stderr)
            return 1
        print(f"[serve_extract] parity OK: {len(got)} matches identical to "
              "one-shot execute")
    return 0


if __name__ == "__main__":
    sys.exit(main())

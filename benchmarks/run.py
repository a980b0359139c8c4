"""Benchmark aggregator: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--smoke] [--only NAME]

``--smoke`` runs a seconds-long subset (tiny shapes, fused-vs-unfused
parity asserted) so CI catches benchmark drift without a full run.

Sections:
    algorithms   §6 main table (plans × mention distributions)
    cost_model   §4 fidelity (predicted vs measured + rank corr.)
    search       §5.2 plan search vs exhaustive oracle
    signatures   §3.3 signature study (shuffle bytes / skew / recall)
    scaling      §6 dictionary/corpus scaling + plan crossover
    kernels      Pallas kernels vs jnp oracle (interpret mode)
    corpus       corpus-scale streaming: DMA megakernel vs per-tile loop,
                 spill streaming + kill-then-resume checkpoint merges
    serving      async probe/verify serving: load vs latency percentiles
    replan       continuous calibration: replanner overhead + drift swap
    updates      live dictionary deltas: absorb vs rebuild + epoch swap
    fabric       multi-host serving fabric: lane transport throughput
                 (loopback vs socket) + delta-replication catch-up vs
                 snapshot bootstrap, parity asserted in-bench
    roofline     deliverable (g) reader over results/dryrun/
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (
    bench_algorithms,
    bench_corpus,
    bench_cost_model,
    bench_fabric,
    bench_hybrid,
    bench_kernels,
    bench_replan,
    bench_roofline,
    bench_scaling,
    bench_search,
    bench_serving,
    bench_signatures,
    bench_updates,
)
from repro import compile_cache

SECTIONS = [
    ("algorithms", bench_algorithms.main),
    ("hybrid", bench_hybrid.main),
    ("cost_model", bench_cost_model.main),
    ("search", bench_search.main),
    ("signatures", bench_signatures.main),
    ("scaling", bench_scaling.main),
    ("kernels", bench_kernels.main),
    ("corpus", bench_corpus.main),
    ("serving", bench_serving.main),
    ("replan", bench_replan.main),
    ("updates", bench_updates.main),
    ("fabric", bench_fabric.main),
    ("roofline", bench_roofline.main),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="seconds-long CI subset: kernel pipeline parity + timing only",
    )
    args = ap.parse_args()
    compile_cache.enable()
    if args.smoke and args.only:
        ap.error("--smoke runs a fixed subset; it cannot be combined with --only")
    if args.smoke:
        t0 = time.time()
        bench_kernels.main(smoke=True)
        print(f"# [kernels --smoke] done in {time.time() - t0:.1f}s", flush=True)
        t0 = time.time()
        bench_corpus.main(smoke=True)
        print(f"# [corpus --smoke] done in {time.time() - t0:.1f}s", flush=True)
        t0 = time.time()
        bench_serving.main(smoke=True)
        print(f"# [serving --smoke] done in {time.time() - t0:.1f}s", flush=True)
        t0 = time.time()
        bench_replan.main(smoke=True)
        print(f"# [replan --smoke] done in {time.time() - t0:.1f}s", flush=True)
        t0 = time.time()
        bench_updates.main(smoke=True)
        print(f"# [updates --smoke] done in {time.time() - t0:.1f}s", flush=True)
        t0 = time.time()
        bench_fabric.main(smoke=True)
        print(f"# [fabric --smoke] done in {time.time() - t0:.1f}s", flush=True)
        return
    failures = []
    for name, fn in SECTIONS:
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"# [{name}] done in {time.time() - t0:.1f}s\n", flush=True)
        except Exception:
            failures.append(name)
            print(f"# [{name}] FAILED\n{traceback.format_exc()}\n", flush=True)
    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()

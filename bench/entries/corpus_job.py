"""The MapReduce corpus job: back-to-back ``execute_corpus`` calls, one
per input split, each a file-backed ``MemmapCorpus`` streamed in shards
through the probe megakernel, then merged and verified.

Closed loop: the next split starts when the previous one's matches are
ready. ``docs_per_s`` is all documents of the splits completed in the
window over the window's whole length (the window closes at the end of
the first split that finishes after ``--seconds``).
"""
from __future__ import annotations

import tempfile
import time
from pathlib import Path

import numpy as np

from bench import gen
from bench.entries import Window, check_job, program_dictionary, reference
from bench.reference import Reference


class CorpusJob:
    def __init__(self, ctx):
        import jax
        from repro.core.eejoin import EEJoinConfig, EEJoinOperator

        self.jax = jax
        cfg = ctx.cell.config
        self.cfg, self.span = cfg, ctx.span
        docs_cfg = cfg["documents"]
        self.wl = wl = gen.make_watchlist(cfg)
        d = program_dictionary(wl, cfg)
        D, T, L = cfg["split_docs"], docs_cfg["doc_len"], wl.max_len
        self.D, self.T = D, T
        self.ecfg = EEJoinConfig(
            gamma=cfg["gamma"], sim_name=cfg["similarity"], use_kernel=True,
            max_candidates=D * T * L,  # the window grid: nothing overflows
            result_capacity=cfg["result_capacity"],
            # spill sizing holds two staged shards: D*T*4*2 / shards
            device_budget_bytes=D * T * 8 // cfg["shards_per_split"])
        sample = gen.make_docs(
            wl, gen.rng_for(cfg["dictionary_seed"], gen.STREAM_STATS),
            cfg["stats_docs"], T, docs_cfg["mentions_per_doc"],
            docs_cfg["noise"])
        t = time.monotonic()
        self.op = op = EEJoinOperator(d, self.ecfg)
        with self.span("bench.plan"):
            stats = op.gather_statistics(sample, total_docs=D)
            self.plan = op.choose_plan(stats)
            self.prepared = op.prepare(self.plan)
        self.plan_s = time.monotonic() - t
        self.plan_desc = self.plan.describe(wl.num_entities)

        self.docs_cfg = docs_cfg
        self.tmp = tempfile.TemporaryDirectory(prefix="bench_corpus_")
        self.make_traffic(ctx.seed, ctx.seconds)
        with self.span("bench.warmup"):
            self._split(0)  # every shape of the window: splits are alike

    def make_traffic(self, seed: int, seconds: float = 0.0,
                     rate: float | None = None) -> None:
        """The job's input splits, from ``seed``, as files (a closed loop
        has no rate; the window's length changes nothing here)."""
        from repro.extraction.sharded import MemmapCorpus

        rng = gen.rng_for(seed, gen.STREAM_DOCS)
        dc = self.docs_cfg
        self.splits_docs = [
            gen.make_docs(self.wl, rng, self.D, self.T,
                          dc["mentions_per_doc"], dc["noise"])
            for _ in range(self.cfg["corpus_splits"])]
        self.splits = [
            MemmapCorpus.write(str(Path(self.tmp.name) / f"split{i}"), docs)
            for i, docs in enumerate(self.splits_docs)]
        self.check_rng = gen.rng_for(seed, gen.STREAM_CHECK)
        self.last = {}  # split -> its newest Matches
        self.overflows = 0

    def _split(self, i: int):
        with self.span("bench.split"):
            m = self.op.execute_corpus(self.prepared,
                                       self.splits[i % len(self.splits)])
            self.jax.block_until_ready(m)
        if int(m.count) > self.ecfg.result_capacity:
            self.overflows += 1
        self.last[i % len(self.splits)] = m

    def window(self, seconds: float) -> Window:
        t0 = time.monotonic()
        n = 0
        while True:
            self._split(n)
            n += 1
            elapsed = time.monotonic() - t0
            if elapsed >= seconds:
                break
        docs = n * self.D
        return Window(
            elapsed_s=elapsed, attempted=n, failed=self.overflows,
            metrics={"docs_per_s": docs / elapsed},
            steady={"splits": n, "docs": docs, "window_s": elapsed},
            record={"docs": docs, "doc_len": self.T,
                    "sides": len(self.prepared.sides)})

    def sample(self) -> None:
        """The served matches of a seed-drawn sample of each split's
        documents, from the split's newest call in the window."""
        per = self.cfg["check_docs_per_split"]
        self.served, self.check_docs = [], []
        for i, m in sorted(self.last.items()):
            docs = np.sort(self.check_rng.choice(self.D, per, replace=False))
            cols = [np.asarray(a) for a in
                    (m.doc, m.pos, m.length, m.entity, m.score)]
            keep = np.isin(cols[0], docs)
            for d, p, n, e, s in zip(*(c[keep].tolist() for c in cols)):
                self.served.append(((i, d, p, n, e), s))
            self.check_docs += [((i, d), self.splits_docs[i][d])
                                for d in docs.tolist()]
        self.last.clear()

    def close(self) -> None:
        del self.prepared, self.op
        self.tmp.cleanup()

    def reference(self, precision: str = "float32") -> Reference:
        return reference(self.wl, self.cfg, precision)

    def check(self, ref: Reference | None = None) -> dict:
        return check_job(self, ref)


def setup(ctx) -> CorpusJob:
    return CorpusJob(ctx)

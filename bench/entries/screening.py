"""Real-time watch-list screening: transactions arrive on their own
schedule (open loop) and go through ``ExtractionService.submit`` /
``tick`` on one ``SessionCache`` session, micro-batched by length bucket,
probed and verified on one chip.

Each request is timed from its *due* time to its ``done_s`` on
``time.monotonic``, so a stall of the generator or the service counts
against every request it delays. A request refused by admission control
or failed by the pipeline is ``failed``. Requests due in the window that
finish after it still count, with their wait.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import gen
from bench.entries import Window, check_job, program_dictionary, reference
from bench.reference import Reference

#: the ingest loop wakes this long after a batch deadline falls due (s)
DEADLINE_SLACK_S = 5e-5


def nearest_rank(xs, q: float) -> float:
    """The ``q``-quantile of ``xs`` by nearest rank (a value of ``xs``)."""
    s = sorted(xs)
    return s[max(0, math.ceil(q * len(s)) - 1)]


class Screening:
    def __init__(self, ctx):
        from repro.core.eejoin import EEJoinConfig
        from repro.serving import (
            BatcherConfig,
            ExtractionService,
            SessionCache,
            make_pools,
        )

        cfg, self.traffic = ctx.cell.config, ctx.cell.traffic
        self.cfg, self.span = cfg, ctx.span
        self.docs_cfg = cfg["documents"]
        self.wl = wl = gen.make_watchlist(cfg)
        d = program_dictionary(wl, cfg)
        bc = cfg["batcher"]
        self.buckets, self.rows = tuple(bc["buckets"]), bc["max_batch_docs"]
        ecfg = EEJoinConfig(
            gamma=cfg["gamma"], sim_name=cfg["similarity"], use_kernel=True,
            # the window grid of the largest batch: nothing overflows
            max_candidates=self.rows * self.buckets[-1] * wl.max_len,
            result_capacity=cfg["result_capacity"])
        stats_rng = gen.rng_for(cfg["dictionary_seed"], gen.STREAM_STATS)
        sample = gen.pad_rows(
            self._txns(stats_rng, self._lengths(stats_rng, cfg["stats_docs"])),
            self.buckets[-1])
        t = time.monotonic()
        cache = SessionCache()
        with self.span("bench.plan"):
            self.sess = cache.get_or_create(d, ecfg, sample_docs=sample)
        self.plan_s = time.monotonic() - t
        self.plan = self.sess.plan
        self.plan_desc = self.plan.describe(wl.num_entities)
        self.svc = ExtractionService(
            cache, pools=make_pools(ctx.devices[:1]),
            batcher_config=BatcherConfig(max_batch_docs=self.rows,
                                         max_delay_s=bc["max_delay_s"],
                                         buckets=self.buckets),
            queue_capacity=cfg["queue_capacity"])
        self.svc.start()
        self.make_traffic(ctx.seed, ctx.seconds)
        with self.span("bench.warmup"):
            self.warm(stats_rng)

    def _lengths(self, rng, n: int) -> np.ndarray:
        dc = self.docs_cfg
        return gen.transaction_lengths(rng, n, dc["len_min"], dc["len_max"])

    def _txns(self, rng, lens) -> list[np.ndarray]:
        dc = self.docs_cfg
        return gen.make_transactions(self.wl, rng, lens, dc["mention_share"],
                                     dc["noise"])

    def make_traffic(self, seed: int, seconds: float,
                     rate: float | None = None) -> None:
        """The window's transactions, their tokens from ``seed``, and the
        mix's schedule (due times at the mix's rate unless ``rate`` is
        given, and lengths), the same for every seed."""
        rate = float(self.traffic["rate_per_s"] if rate is None else rate)
        n = int(rate * seconds * 1.2) + 64
        sched = self.traffic["schedule_seed"]
        lens = self._lengths(gen.rng_for(sched, gen.STREAM_LENGTHS), n)
        self.txns = self._txns(gen.rng_for(seed, gen.STREAM_DOCS), lens)
        self.due = gen.arrival_offsets(
            gen.rng_for(sched, gen.STREAM_ARRIVALS), n,
            {**self.traffic, "rate_per_s": rate})
        self.check_rng = gen.rng_for(seed, gen.STREAM_CHECK)

    def warm(self, rng) -> None:
        """Every ``(rows, bucket)`` batch shape the window can flush, once
        each through the service."""
        for bucket in self.buckets:
            docs = gen.make_docs(self.wl, rng, self.rows, bucket,
                                 self.docs_cfg["mention_share"],
                                 self.docs_cfg["noise"])
            for rows in range(1, self.rows + 1):
                for r in range(rows):
                    self.svc.submit(-1, docs[r], self.sess.key, block=True)
                self.svc.drain()  # one batch of ``rows`` rows

    def window(self, seconds: float) -> Window:
        from repro.serving import ServingMetrics

        svc, key, due, txns = self.svc, self.sess.key, self.due, self.txns
        svc.completed.clear()  # the service is idle: count this window only
        svc.metrics = ServingMetrics()
        n = len(due)
        reqs: list = [None] * n
        submit = np.full(n, np.nan)
        delay = self.svc.batcher.config.max_delay_s + DEADLINE_SLACK_S
        t0 = time.monotonic()
        end, quarter = t0 + seconds, t0 + seconds / 4
        backlog = {}
        flushes: list[float] = []  # when submitted requests' deadlines fall
        i = 0
        while True:
            now = time.monotonic()
            if now >= end:
                break
            while i < n and t0 + due[i] <= now:
                reqs[i] = svc.submit(i, txns[i], key, now=now)
                submit[i] = now
                flushes.append(now + delay)
                i += 1
                now = time.monotonic()
            svc.tick(now)
            flushes = [f for f in flushes if f > now]
            if "quarter" not in backlog and now >= quarter:
                backlog["quarter"] = i - len(svc.completed)
            # sleep to the next arrival, batch deadline or quarter mark
            wake = [end, t0 + due[i] if i < n else end] + flushes[:1]
            if "quarter" not in backlog:
                wake.append(quarter)
            time.sleep(max(0.0, min(wake) - time.monotonic()))
        backlog["end"] = i - len(svc.completed)
        if i == n:
            raise RuntimeError("the generator ran out of requests")
        with self.span("bench.drain"):
            svc.drain()
        self.reqs = reqs = reqs[:i]
        due_abs = t0 + due[:i]
        ok = [r is not None and r.error is None for r in reqs]
        lat = [(r.done_s - due_abs[k]) * 1e3 if ok[k] else math.inf
               for k, r in enumerate(reqs)]
        failed = i - sum(ok)  # their latency is infinite: past any limit
        done = [r for r in reqs if r is not None and r.error is None]
        lag_ms = (submit[:i] - due_abs) * 1e3
        return Window(
            elapsed_s=seconds, attempted=i, failed=failed,
            metrics={"p50_ms": nearest_rank(lat, 0.50),
                     "p95_ms": nearest_rank(lat, 0.95)},
            steady={"requests": i, "completed": len(done),
                    "backlog_quarter": backlog.get("quarter", 0),
                    "backlog_end": backlog["end"],
                    "gen_lag_p95_ms": nearest_rank(lag_ms, 0.95),
                    "batches": svc.metrics.batches},
            record={"lag_ms": lag_ms.tolist(),
                    "batch_wait_ms": [(r.flush_s - r.arrival_s) * 1e3
                                      for r in done],
                    "probe_ms": [x * 1e3 for x in svc.metrics.probe_s],
                    "verify_ms": [x * 1e3 for x in svc.metrics.verify_s]})

    def sample(self) -> None:
        """The served answers of a seed-drawn sample of the window's
        completed requests, the longest among them. A match is keyed by
        the document id the service reported it under."""
        done = [k for k, r in enumerate(self.reqs)
                if r is not None and r.error is None]
        n = min(self.cfg["check_requests"], len(done))
        longest = max(done, key=lambda k: len(self.txns[k]))
        pick = self.check_rng.choice(done, n, replace=False).tolist()
        self.served, self.check_docs = [], []
        for k in sorted(set(pick) | {longest}):
            self.served += [((d, p, m, e), s)
                            for d, p, m, e, s in self.reqs[k].matches]
            self.check_docs.append(((k,), self.txns[k]))

    def close(self) -> None:
        self.svc.stop()
        del self.svc, self.sess

    def reference(self, precision: str = "float32") -> Reference:
        return reference(self.wl, self.cfg, precision)

    def check(self, ref: Reference | None = None) -> dict:
        return check_job(self, ref)


def setup(ctx) -> Screening:
    return Screening(ctx)

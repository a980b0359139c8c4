"""Two-stage async extraction service: probe pool -> lanes -> verify pool.

The pipeline splits one request's work exactly where the sharded driver
splits a shard's: the *probe* stage streams a micro-batch's ``[D, T]``
tile through ``fused_probe`` (with the in-kernel compaction epilogue)
and reduces it to one ``[1, NC]`` candidate lane per plan side
(``extraction.sharded.shard_lane`` — the wire unit, ``(1 + NC) * 4``
bytes, plus a ``[1, NC, 2]`` variant-key payload when the fused
variant scheme is on); the *verify* stage re-expands the lane into
compacted candidate windows (attaching the shipped variant keys, so
set hashes are never recomputed) and runs the plan's probe+verify join
(``EEJoinOperator.side_matches``). The stages run on **disjoint device
pools** connected by a **double-buffered handoff queue** (depth 2):
while the verify pool joins batch i, the probe pool is already
streaming batch i+1 — the serving-time analogue of the driver's
per-tile DMA overlap.

Results are bit-identical to a one-shot ``eejoin.execute`` over the
same documents (windows never span documents and lane merging is exact,
so micro-batching cannot change any match) — asserted per scheme and
geometry in ``tests/test_serving.py``.

Threading model: the caller's thread owns ingest (``submit`` → admission
queue → ``tick`` → micro-batcher); a probe worker and a verify worker
own the two stages (one combined worker when ``overlap=False``). All
queues are bounded, so a slow verify pool backpressures probe, a slow
probe backpressures the flush queue, and the admission queue sheds or
blocks producers — nothing in the pipeline can grow without limit.
"""
from __future__ import annotations

import queue as _pyqueue
import threading
import time
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.extraction import engine
from repro.extraction.results import (
    Matches,
    gather_from_tiles,
    merge_matches,
    select_from_tiles,
)
from repro.extraction.sharded import shard_lane_steady
from repro.serving.batcher import BatcherConfig, MicroBatch, MicroBatcher
from repro.serving.metrics import ServingMetrics
from repro.serving.pools import DevicePools, make_pools
from repro.serving.queue import AdmissionQueue, ExtractRequest
from repro.serving.session import SessionCache
from repro.tracing import span

#: probe->verify handoff queue depth: 2 slots double-buffer the pools
#: (verify drains batch i while probe fills batch i+1).
HANDOFF_DEPTH = 2


def one_shot_reference(session, docs, epoch: int | None = None
                       ) -> set[tuple[int, int, int, int]]:
    """The serving parity target: one-shot ``execute`` over ``docs``.

    Pads the variable-length documents into a single [N, T] array (row
    i = doc_id i) and runs the session's current (or a pinned past)
    epoch in one batch call — for an epoch-0 session this is exactly
    the frozen-dictionary ``execute`` of the prepared plan; with live
    deltas applied it probes base + open segments and masks tombstones,
    identically to the served pipeline. ``ExtractionService.
    results_set()`` over the same documents must equal this set — the
    single reference implementation used by tests, the serving bench,
    and ``serve_extract --check``.
    """
    from repro.core.dictionary import PAD
    from repro.updates.builders import execute_epoch

    docs = [np.asarray(d, dtype=np.int32).reshape(-1) for d in docs]
    T = max((len(d) for d in docs), default=1)
    padded = np.full((len(docs), max(T, 1)), PAD, dtype=np.int32)
    for i, d in enumerate(docs):
        padded[i, : len(d)] = d
    state = session.state_for(epoch if epoch is not None else session.epoch)
    return execute_epoch(
        state, jnp.asarray(padded), session.config
    ).to_set()


class _Handoff:
    """One probed batch in flight between the pools."""

    __slots__ = ("batch", "lanes", "probe_s", "probe_start_s", "probe_end_s",
                 "windows", "survivors")

    def __init__(self, batch: MicroBatch, lanes: list, probe_s: float,
                 probe_start_s: float, probe_end_s: float,
                 windows: int = 0, survivors: int = 0):
        self.batch = batch
        # per plan side: (count [1] i32, cand [1, NC] i32,
        #                 keys [1, NC, 2] u32 | None  — fused variant)
        self.lanes = lanes
        self.probe_s = probe_s
        # the probe stage on the service's clock (batch timeline)
        self.probe_start_s = probe_start_s
        self.probe_end_s = probe_end_s
        # telemetry for the continuous-calibration loop: enumerated
        # candidate windows and true filter survivors of this batch
        self.windows = windows
        self.survivors = survivors


class ExtractionService:
    """Online micro-batched EE-Join extraction over device pools."""

    def __init__(
        self,
        sessions: SessionCache,
        pools: DevicePools | None = None,
        batcher_config: BatcherConfig | None = None,
        queue_capacity: int = 256,
        overlap: bool = True,
        clock: Callable[[], float] = time.monotonic,
        session_quota: int | None = None,
        replan=None,
        remote_verify=None,
    ):
        self.sessions = sessions
        self.pools = pools or make_pools()
        self.batcher = MicroBatcher(batcher_config or BatcherConfig())
        self.queue = AdmissionQueue(queue_capacity, session_quota=session_quota)
        self.overlap = overlap
        self.clock = clock
        self.metrics = ServingMetrics()
        self.completed: list[ExtractRequest] = []
        # fail at config time, not deep inside the kernel: the largest
        # possible batch must keep flat lane indices inside int32
        engine.check_flat_index_space(
            self.batcher.config.max_batch_docs,
            self.batcher.config.buckets[-1],
            32,
        )
        self._flush_q: _pyqueue.Queue = _pyqueue.Queue()
        self._handoff_q: _pyqueue.Queue = _pyqueue.Queue(maxsize=HANDOFF_DEPTH)
        self._workers: list[threading.Thread] = []
        self._started = False
        self._lock = threading.Lock()  # completed-list + metrics writes
        self._ingest_lock = threading.Lock()  # batcher is not thread-safe
        self.errors: list[tuple[int, Exception]] = []  # (batch_id, exc)
        # continuous calibration: ``replan`` is a serving.replan.
        # ReplanConfig (None = off). With replan.thread the loop polls
        # in the background; otherwise it steps inline from tick() —
        # deterministic on a virtual clock.
        self.replanner = None
        if replan is not None:
            from repro.serving.replan import Replanner

            self.replanner = Replanner(
                sessions, replan, metrics=self.metrics, clock=clock
            )
        # multi-host fabric: when set, the verify pool sits behind a
        # transport channel — probed lanes are framed and shipped to an
        # epoch-agreed replica instead of joined on the local verify
        # device (``fabric.cluster.ClusterCoordinator.verify_lanes`` or
        # anything duck-typed like it). The probe stage, batching,
        # epoch pinning and result fan-out are unchanged.
        self.remote_verify = remote_verify

    # ------------------------------------------------------------- lifecycle
    def start(self) -> None:
        """Spawn the stage workers (idempotent)."""
        if self._started:
            return
        self._started = True
        if self.overlap:
            targets = [self._probe_worker, self._verify_worker]
        else:
            targets = [self._serial_worker]
        for fn in targets:
            t = threading.Thread(target=fn, daemon=True, name=fn.__name__)
            t.start()
            self._workers.append(t)
        if self.replanner is not None:
            self.replanner.start()  # no-op unless ReplanConfig.thread

    def stop(self) -> None:
        """Drain and terminate the workers.

        The shutdown sentinel and joins run even when ``drain``
        re-raises a batch failure — workers never outlive the service.
        """
        if not self._started:
            return
        try:
            self.drain()
        finally:
            if self.replanner is not None:
                self.replanner.stop()
            self._flush_q.put(None)
            for t in self._workers:
                t.join()
            self._workers.clear()
            self._started = False

    def __enter__(self) -> "ExtractionService":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---------------------------------------------------------------- ingest
    def submit(self, doc_id: int, tokens, session_key: str,
               now: float | None = None,
               block: bool = False) -> ExtractRequest | None:
        """Admit one document; None when shed by admission control.

        ``block=True`` switches to backpressure mode: instead of being
        rejected, the producer itself drains the admission queue into
        the batcher (``tick``) until space frees — the calling thread
        owns ingest, so backpressure is "do the flushing work", not
        "wait for someone else to". Raises ValueError on caller errors —
        unknown session, document longer than the largest length
        bucket — rather than shedding them silently.
        """
        try:
            sess = self.sessions.get(session_key)
        except KeyError:
            raise ValueError(
                f"submit: unknown session {session_key!r}; create it first "
                "with SessionCache.get_or_create(dictionary, ...)"
            ) from None
        self.batcher.config.bucket_for(len(np.asarray(tokens).reshape(-1)))
        now = self.clock() if now is None else now

        def _quota_limited() -> bool:
            return (self.queue.session_quota is not None
                    and sess.inflight >= self.queue.session_quota)

        req = self.queue.try_submit(doc_id, tokens, session_key, now,
                                    session_inflight=sess.inflight)
        while req is None and block:
            # one tick always empties the admission queue into the bins,
            # so a single pass frees *queue* space. The tick reads a
            # fresh clock: deadline flushes must keep firing while the
            # producer spins here, or a quota-limited session whose
            # last batch sits in an unflushed bin would never complete
            # and the loop would never exit.
            self.tick()
            if _quota_limited():
                # quota frees only when the stage workers complete this
                # session's batches — yield so they can, and do not
                # re-attempt (each attempt would count another
                # rejection in the queue's admission stats)
                time.sleep(1e-4)
                continue
            req = self.queue.try_submit(doc_id, tokens, session_key, now,
                                        session_inflight=sess.inflight)
        if req is not None:
            with self._lock:  # vs the -= in _complete/_fail_batch
                sess.inflight += 1  # pins the session against LRU eviction
        quota = req is None and _quota_limited()
        self.metrics.record_submit(
            req is not None, self.queue.depth(), now,
            quota=quota, session_key=session_key if quota else None,
        )
        return req

    def tick(self, now: float | None = None) -> int:
        """Move admitted requests into bins and flush due batches.

        Returns the number of batches handed to the probe stage. The
        ingest loop (or the load generator) calls this between submits;
        ``drain`` calls it with a forced flush.
        """
        now = self.clock() if now is None else now
        with self._ingest_lock:  # concurrent producers may tick via submit
            for req in self.queue.take():
                self.batcher.add(req)
            n = self._dispatch(self.batcher.poll(now))
        if self.replanner is not None and not self.replanner.config.thread:
            # inline replan mode: the loop steps on the ingest thread
            # (outside the ingest lock — a swap only takes session locks)
            self.replanner.step(now)
        return n

    def drain(self) -> None:
        """Force-flush everything pending and wait until it completes.

        Re-raises the first stage-worker failure (with its batch id)
        after the queues empty: a failed batch marks its requests
        ``error`` and never hangs the join (see ``_fail_batch``).
        """
        if not self._started:
            self.start()
        now = self.clock()
        with self._ingest_lock:
            for req in self.queue.take():
                self.batcher.add(req)
            self._dispatch(self.batcher.flush_all(now))
        self._flush_q.join()
        if self.overlap:
            self._handoff_q.join()
        if self.errors:
            errs, self.errors = self.errors, []  # report once, then reset
            batch_id, exc = errs[0]
            raise RuntimeError(
                f"{len(errs)} batch(es) failed in the serving pipeline; "
                f"first failure on batch {batch_id} (per-request details "
                "on ExtractRequest.error)"
            ) from exc

    def _dispatch(self, batches: list[MicroBatch]) -> int:
        for b in batches:
            sess = self.sessions.get(b.session_key)
            sess.requests += b.rows
            sess.batches += 1
            if self.replanner is not None:
                self.replanner.attach(sess)  # lazy ObservedStats
            # epoch stamp + pin: the batch executes on the dictionary
            # epoch current at dispatch, even if apply_delta hot-swaps
            # the session before its probe/verify runs (the swap
            # protocol: in-flight work finishes on its admitted epoch).
            # Read-and-pin is one atomic step under the session lock —
            # a separate read could see an epoch that a concurrent
            # apply_delta garbage-collects before the pin lands.
            b.epoch = sess.pin_current()
            self._flush_q.put(b)
        return len(batches)

    # ---------------------------------------------------------- stage bodies
    def _probe_batch(self, batch: MicroBatch) -> _Handoff:
        """Probe stage: stream the batch's tiles, reduce to [1, NC] lanes.

        Versioned: each plan side probes with its epoch's (possibly
        delta-unioned) Bloom filter. Adaptive lane widths are sized
        steady-state — the previous batch's measured per-tile survivor
        max for the same (side, bucket, epoch) skips the count pass
        (``shard_lane_steady``; sizing decisions land in metrics).
        """
        start_s = self.clock()
        sess = self.sessions.get(batch.session_key)
        state = sess.state_for(batch.epoch)
        dev = self.pools.probe_device(batch.batch_id)
        t0 = time.perf_counter()
        with span("eejoin.serve.probe"):
            docs = jax.device_put(jnp.asarray(batch.docs), dev)
            lanes = []
            for i, eside in enumerate(state.sides):
                stream_stats: dict = {}
                lane, count, keys, tile_max, sizing = shard_lane_steady(
                    docs, 0, state.max_len, eside.flt, eside.params,
                    batch.spec.tile_docs,
                    width_hint=sess.lane_hint(i, batch.bucket, batch.epoch),
                    stream_stats=stream_stats,
                )
                sess.update_lane_hint(i, batch.bucket, batch.epoch, tile_max)
                with self._lock:
                    self.metrics.record_sizing(sizing)
                    self.metrics.record_stream(stream_stats,
                                               observed=sess.observed)
                lanes.append((count, lane, keys))
            jax.block_until_ready(lanes)
        probe_s = time.perf_counter() - t0
        windows = survivors = 0
        if sess.observed is not None:
            # telemetry for the replan loop: enumerated-window count
            # (drift denominator) + true survivor totals per side, and
            # the raw rows into the recent-document ring the next
            # replan gathers statistics from. Host-side numpy; skipped
            # entirely when replanning is off.
            from repro.serving.replan import batch_windows

            windows = batch_windows(batch.docs, state.max_len)
            survivors = sum(
                int(np.asarray(count).sum()) for count, _, _ in lanes
            )
            sess.observed.observe_docs(batch.docs)
        return _Handoff(batch, lanes, probe_s, start_s, self.clock(),
                        windows, survivors)

    def _verify_batch(self, handoff: _Handoff) -> None:
        """Verify stage: lanes -> candidate windows -> probe+verify join.

        Versioned: every side verifies against its epoch's base
        structures plus each open delta segment (same candidate dict,
        matches merged), then tombstoned entities are masked before
        results fan back out.
        """
        from repro.extraction.results import filter_matches
        from repro.updates.builders import epoch_side_matches

        start_s = self.clock()
        batch = handoff.batch
        sess = self.sessions.get(batch.session_key)
        if self.remote_verify is not None:
            self._verify_batch_remote(handoff, sess, start_s)
            return
        state = sess.state_for(batch.epoch)
        dev = self.pools.verify_device(batch.batch_id)
        capacity = sess.config.result_capacity
        t0 = time.perf_counter()
        with span("eejoin.serve.verify"):
            # the handoff traffic: per side one (1 + NC)-int lane, plus
            # the raw [D, T] tokens the verify pool gathers windows from
            docs = jax.device_put(jnp.asarray(batch.docs), dev)
            out: Matches | None = None
            overflow = 0
            for eside, (count, lane, keys) in zip(state.sides, handoff.lanes):
                count, lane = jax.device_put((count, lane), dev)
                NC = eside.params.max_candidates
                with span("eejoin.lanes.merge"):
                    sel, ok, n = select_from_tiles(count, lane, NC)
                with span("eejoin.windows.gather"):
                    cands = engine.candidates_from_flat(
                        docs, sel, ok, n, state.max_len, NC
                    )
                if keys is not None:
                    # fused variant keys rode the handoff lane: the verify
                    # pool attaches them instead of recomputing set hashes
                    with span("eejoin.lanes.merge"):
                        keys = gather_from_tiles(
                            count, jax.device_put(keys, dev), NC)
                    cands = engine.attach_variant_keys(cands, keys)
                overflow += int(cands["overflow"])
                with span("eejoin.verify"):
                    m = epoch_side_matches(cands, eside, capacity)
                if out is None:
                    out = m
                else:
                    with span("eejoin.matches.merge"):
                        out = merge_matches(out, m, capacity)
            if state.has_tombstones:
                with span("eejoin.matches.merge"):
                    out = filter_matches(out, state.live, capacity)
            jax.block_until_ready(out)
        verify_s = time.perf_counter() - t0
        self._complete(batch, out, handoff, verify_s, overflow, start_s)

    def _verify_batch_remote(self, handoff: _Handoff, sess,
                             start_s: float) -> None:
        """Remote verify: frame the lanes, ship, complete on the reply.

        The lanes come back to host memory once (they are a few KB —
        the whole point of the compaction), get framed by
        ``sharded.lanes_to_wire`` and routed to a replica that has
        acked the batch's epoch; the replica runs the identical verify
        sequence over its replicated epoch state
        (``fabric.replica.verify_lanes_on_state``), so the reply is
        bit-identical to the local join.
        """
        batch = handoff.batch
        t0 = time.perf_counter()
        with span("eejoin.serve.verify_remote"):
            lanes = [
                (np.asarray(count), np.asarray(lane),
                 None if keys is None else np.asarray(keys))
                for count, lane, keys in handoff.lanes
            ]
            matches, overflow = self.remote_verify.verify_lanes(
                batch.session_key, batch.epoch, batch.docs, lanes
            )
        verify_s = time.perf_counter() - t0
        self._complete(batch, matches, handoff, verify_s, overflow, start_s)

    def _complete(self, batch: MicroBatch, matches: Matches,
                  handoff: _Handoff, verify_s: float, overflow: int,
                  verify_start_s: float) -> None:
        """Fan the batch's Matches back out to its requests (host side).

        ``handoff`` carries the batch's probe timing and telemetry; its
        verify stage ends on the service's clock when its requests are
        done (``ServingMetrics.record_batch``)."""
        now = self.clock()
        doc = np.asarray(matches.doc)
        pos = np.asarray(matches.pos)
        length = np.asarray(matches.length)
        ent = np.asarray(matches.entity)
        score = np.asarray(matches.score)
        keep = doc >= 0
        by_row: dict[int, list] = {}
        for d, p, l, e, s in zip(doc[keep], pos[keep], length[keep],
                                 ent[keep], score[keep]):
            by_row.setdefault(int(d), []).append(
                (int(p), int(l), int(e), float(s))
            )
        with self._lock:
            sess = self.sessions.get(batch.session_key)
            sess.inflight -= batch.rows
            n_lanes = len(sess.state_for(batch.epoch).sides)
            sess.unpin_epoch(batch.epoch)
            for row, req in enumerate(batch.reqs):
                req.matches = [
                    (req.doc_id, p, l, e, s)
                    for (p, l, e, s) in sorted(by_row.get(row, []))
                ]
                req.done = True
                req.done_s = now
                req.batch_id = batch.batch_id
                self.completed.append(req)
                self.metrics.record_done(req.done_s - req.arrival_s, now)
            self.metrics.record_batch(
                batch_id=batch.batch_id,
                rows=batch.rows,
                occupancy=batch.occupancy,
                n_lanes=n_lanes,
                flush_s=batch.flush_s,
                probe_s=handoff.probe_s,
                verify_s=verify_s,
                overflow=overflow,
                epoch=batch.epoch,
                windows=handoff.windows,
                survivors=handoff.survivors,
                observed=sess.observed,
                probe_start_s=handoff.probe_start_s,
                probe_end_s=handoff.probe_end_s,
                verify_start_s=verify_start_s,
                verify_end_s=now,
            )

    def _fail_batch(self, batch: MicroBatch, exc: Exception) -> None:
        """A stage raised: surface the error, never wedge the pipeline.

        The batch's requests complete with ``error`` set (empty
        matches), the exception is parked on ``self.errors`` for
        ``drain`` to re-raise, and the worker loop stays alive so the
        queue joins always terminate.
        """
        now = self.clock()
        with self._lock:
            self.errors.append((batch.batch_id, exc))
            try:
                sess = self.sessions.get(batch.session_key)
                sess.inflight -= batch.rows
                if batch.epoch >= 0:
                    sess.unpin_epoch(batch.epoch)
            except KeyError:
                pass  # session evicted while busy is itself the failure
            for req in batch.reqs:
                req.error = f"{type(exc).__name__}: {exc}"
                req.done = True
                req.done_s = now
                req.batch_id = batch.batch_id
                self.completed.append(req)

    # -------------------------------------------------------------- workers
    def _probe_worker(self) -> None:
        while True:
            batch = self._flush_q.get()
            if batch is None:
                self._flush_q.task_done()
                self._handoff_q.put(None)
                return
            try:
                handoff = self._probe_batch(batch)
            except Exception as exc:  # noqa: BLE001 — parked for drain()
                self._fail_batch(batch, exc)
            else:
                self._handoff_q.put(handoff)
            finally:
                self._flush_q.task_done()

    def _verify_worker(self) -> None:
        while True:
            handoff = self._handoff_q.get()
            if handoff is None:
                self._handoff_q.task_done()
                return
            try:
                self._verify_batch(handoff)
            except Exception as exc:  # noqa: BLE001 — parked for drain()
                self._fail_batch(handoff.batch, exc)
            finally:
                self._handoff_q.task_done()

    def _serial_worker(self) -> None:
        """overlap=False: one worker runs both stages back-to-back."""
        while True:
            batch = self._flush_q.get()
            if batch is None:
                self._flush_q.task_done()
                return
            try:
                self._verify_batch(self._probe_batch(batch))
            except Exception as exc:  # noqa: BLE001 — parked for drain()
                self._fail_batch(batch, exc)
            finally:
                self._flush_q.task_done()

    # ------------------------------------------------------------ inspection
    def results_set(self) -> set[tuple[int, int, int, int]]:
        """Dedup'd (doc_id, pos, length, entity) across completed requests
        — directly comparable with ``Matches.to_set()`` of a one-shot
        batch run over the same documents."""
        with self._lock:
            return {
                (d, p, l, e)
                for req in self.completed
                for (d, p, l, e, _s) in req.matches
            }

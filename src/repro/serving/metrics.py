"""Serving metrics: queue depth, batch occupancy, latency percentiles,
lane throughput — plus the two-stage pipeline schedule model the benches
use to account latency under overlap.

Everything here is host-side bookkeeping (plain floats/ints, numpy for
percentiles): recording a sample never touches a device or a jit cache,
so metrics cannot perturb the pipeline they observe.
"""
from __future__ import annotations

import dataclasses

import numpy as np

PERCENTILES = (50, 95, 99)


def percentiles(xs, ps=PERCENTILES) -> dict[str, float]:
    """{'p50': ..., 'p95': ..., 'p99': ...} (NaN on empty input)."""
    if len(xs) == 0:
        return {f"p{p}": float("nan") for p in ps}
    arr = np.asarray(xs, dtype=np.float64)
    vals = np.percentile(arr, ps)
    return {f"p{p}": float(v) for p, v in zip(ps, vals)}


def pipeline_schedule(
    ready_s,
    probe_s,
    verify_s,
    overlap: bool,
    buffer_depth: int = 2,
):
    """Completion times of batches through the two-stage pipeline.

    ``ready_s[i]`` is when batch i is flushed (available to probe);
    ``probe_s``/``verify_s`` its measured stage service times. With
    ``overlap`` the stages run on disjoint pools connected by a
    ``buffer_depth``-slot handoff queue: probe i starts once probe i-1
    finished AND verify has started draining batch i-buffer_depth (the
    double-buffer backpressure), verify i once probe i finished AND
    verify i-1 finished. Without overlap one worker runs both stages
    back-to-back. Returns (probe_done, verify_done) float arrays —
    request latency is ``verify_done[batch] - arrival``.
    """
    n = len(ready_s)
    assert len(probe_s) == n and len(verify_s) == n
    probe_done = np.zeros(n)
    verify_done = np.zeros(n)
    verify_start = np.zeros(n)
    for i in range(n):
        if overlap:
            start_p = max(ready_s[i], probe_done[i - 1] if i else 0.0)
            if i >= buffer_depth:
                # handoff queue full until verify pulls batch i - depth
                start_p = max(start_p, verify_start[i - buffer_depth])
            probe_done[i] = start_p + probe_s[i]
            verify_start[i] = max(probe_done[i],
                                  verify_done[i - 1] if i else 0.0)
            verify_done[i] = verify_start[i] + verify_s[i]
        else:
            start = max(ready_s[i], verify_done[i - 1] if i else 0.0)
            probe_done[i] = start + probe_s[i]
            verify_start[i] = probe_done[i]
            verify_done[i] = verify_start[i] + verify_s[i]
    return probe_done, verify_done


@dataclasses.dataclass
class ServingMetrics:
    """Mutable counters + samples for one service run."""

    submitted: int = 0
    rejected: int = 0
    rejected_quota: int = 0  # of which: per-session admission quota
    rejected_by_session: dict = dataclasses.field(default_factory=dict)
    completed: int = 0
    batches: int = 0
    # steady-state lane sizing: how each probed side sized its emit
    # lanes (fixed | count_pass | hint | refit) — 'hint' is the
    # amortised steady state, 'count_pass' the cold/stale fallback,
    # 'refit' the undersized-hint recovery (one extra emit pass)
    lane_sizing: dict = dataclasses.field(default_factory=dict)
    lanes: int = 0  # [1, NC] probe->verify handoffs (one per batch per side)
    # streamed probe path (single-launch DMA megakernel): launches taken,
    # in-kernel tiles consumed (one DMA wait each), and checkpoint
    # writes/hits when a probed side persists lanes — mirrors
    # sharded.stream_probe_tiles' stream_stats keys, so the streamed
    # path is observable like lane sizing already is.
    streamed_launches: int = 0
    tiles_streamed: int = 0
    checkpoint_writes: int = 0
    checkpoint_hits: int = 0
    docs: int = 0
    overflow_windows: int = 0  # candidate-buffer overflow, summed over batches
    depth_samples: list = dataclasses.field(default_factory=list)
    occupancy_samples: list = dataclasses.field(default_factory=list)
    # per-batch rows; their ``*_s`` stamps share the service's clock:
    # flush <= probe_start <= probe_end <= verify_start <= verify_end
    batch_records: list = dataclasses.field(default_factory=list)
    latencies_s: list = dataclasses.field(default_factory=list)
    probe_s: list = dataclasses.field(default_factory=list)
    verify_s: list = dataclasses.field(default_factory=list)
    # continuous calibration: replanner triggers (events carry trigger
    # reason, drift values, old→new plan and predicted gain; swaps are
    # the subset that actually installed a new plan epoch)
    replans: int = 0
    replan_swaps: int = 0
    replan_events: list = dataclasses.field(default_factory=list)
    # multi-host fabric (fabric.cluster): per-replica transport and
    # routing counters — lane bytes on the wire, frames retried,
    # replication lag in epochs, routed/shed — folded in by
    # ClusterCoordinator.poll_stats and surfaced in the serve_cluster
    # report
    replicas: dict = dataclasses.field(default_factory=dict)
    first_arrival_s: float = float("nan")
    last_done_s: float = float("nan")

    def record_submit(self, accepted: bool, depth: int, now: float,
                      quota: bool = False,
                      session_key: str | None = None) -> None:
        self.submitted += 1
        if accepted:
            if np.isnan(self.first_arrival_s):
                self.first_arrival_s = now
        else:
            self.rejected += 1
            if quota:
                self.rejected_quota += 1
                if session_key is not None:
                    self.rejected_by_session[session_key] = (
                        self.rejected_by_session.get(session_key, 0) + 1
                    )
        self.depth_samples.append(depth)

    def record_sizing(self, sizing: str) -> None:
        """One probed side sized its lanes via ``sizing`` (see field doc)."""
        self.lane_sizing[sizing] = self.lane_sizing.get(sizing, 0) + 1

    def record_stream(self, stream_stats: dict,
                      observed=None) -> None:
        """Fold one probe call's ``stream_stats`` dict into the counters.

        The dict is the mutable accumulator the streaming drivers fill
        (``sharded.stream_probe_tiles`` / ``LaneCheckpointStore``);
        empty when the per-tile launch loop ran instead — recording it
        is then a no-op, so the counters directly read "how much of the
        probe traffic took the streamed path". Partial dicts are fine
        (every key defaults to 0). ``observed`` — a per-session
        ``serving.replan.ObservedStats`` — receives the same dict when
        the continuous-calibration loop is on.
        """
        self.streamed_launches += stream_stats.get("streamed_launches", 0)
        self.tiles_streamed += stream_stats.get("tiles_streamed", 0)
        self.checkpoint_writes += stream_stats.get("checkpoint_writes", 0)
        self.checkpoint_hits += stream_stats.get("checkpoint_hits", 0)
        if observed is not None:
            observed.record_stream(stream_stats)

    def record_batch(self, batch_id: int, rows: int, occupancy: float,
                     n_lanes: int, flush_s: float, probe_s: float,
                     verify_s: float, overflow: int = 0,
                     epoch: int = 0, windows: int = 0,
                     survivors: int = 0, observed=None, *,
                     probe_start_s: float, probe_end_s: float,
                     verify_start_s: float, verify_end_s: float) -> None:
        """One completed batch. ``probe_s``/``verify_s`` are the stage
        bodies' ``perf_counter`` durations; the ``*_s`` stamps are the
        batch's timeline on the service's clock, so a request's
        ``done_s - arrival_s`` splits into batch wait, probe queue,
        probe, handoff wait and verify."""
        self.batches += 1
        self.docs += rows
        self.lanes += n_lanes
        self.occupancy_samples.append(occupancy)
        self.probe_s.append(probe_s)
        self.verify_s.append(verify_s)
        self.overflow_windows += overflow
        self.batch_records.append({
            "batch_id": batch_id,
            "rows": rows,
            "occupancy": occupancy,
            "flush_s": flush_s,
            "probe_start_s": probe_start_s,
            "probe_end_s": probe_end_s,
            "verify_start_s": verify_start_s,
            "verify_end_s": verify_end_s,
            "probe_s": probe_s,
            "verify_s": verify_s,
            "epoch": epoch,
            "windows": windows,
            "survivors": survivors,
        })
        if observed is not None:
            # the telemetry feedback path: the session's ObservedStats
            # (serving.replan) folds the same sample into its EWMAs
            observed.record_batch(
                rows=rows, windows=windows, survivors=survivors,
                probe_s=probe_s, verify_s=verify_s,
            )

    def record_replan(self, event: dict) -> None:
        """One replanner trigger (swapped or not) — see serving.replan."""
        self.replans += 1
        if event.get("swapped"):
            self.replan_swaps += 1
        self.replan_events.append(dict(event))

    def record_replica(self, name: str, row: dict) -> None:
        """Latest per-replica fabric counters (overwrites the old row —
        these are cumulative gauges, not samples)."""
        self.replicas[name] = dict(row)

    def record_done(self, latency_s: float, done_s: float) -> None:
        self.completed += 1
        self.latencies_s.append(latency_s)
        if np.isnan(self.last_done_s) or done_s > self.last_done_s:
            self.last_done_s = done_s

    @property
    def elapsed_s(self) -> float:
        return self.last_done_s - self.first_arrival_s

    def summary(self) -> dict:
        """Flat dict: the serving bench row / entrypoint report."""
        lat = percentiles(self.latencies_s)
        elapsed = self.elapsed_s
        rate = (lambda x: x / elapsed) if elapsed and elapsed > 0 else (
            lambda x: float("nan"))
        return {
            "submitted": self.submitted,
            "rejected": self.rejected,
            "completed": self.completed,
            "batches": self.batches,
            "queue_depth_mean": float(np.mean(self.depth_samples))
            if self.depth_samples else 0.0,
            "queue_depth_max": int(max(self.depth_samples))
            if self.depth_samples else 0,
            "occupancy_mean": float(np.mean(self.occupancy_samples))
            if self.occupancy_samples else 0.0,
            "latency_p50_s": lat["p50"],
            "latency_p95_s": lat["p95"],
            "latency_p99_s": lat["p99"],
            "probe_s_mean": float(np.mean(self.probe_s))
            if self.probe_s else 0.0,
            "verify_s_mean": float(np.mean(self.verify_s))
            if self.verify_s else 0.0,
            "docs_per_s": rate(self.docs),
            "lanes_per_s": rate(self.lanes),
            "overflow_windows": self.overflow_windows,
            "rejected_quota": self.rejected_quota,
            "lane_sizing": dict(self.lane_sizing),
            "streamed_launches": self.streamed_launches,
            "tiles_streamed": self.tiles_streamed,
            "checkpoint_writes": self.checkpoint_writes,
            "checkpoint_hits": self.checkpoint_hits,
            "replans": self.replans,
            "replan_swaps": self.replan_swaps,
            "replan_events": [dict(e) for e in self.replan_events],
            "replicas": {
                name: {
                    "alive": row.get("alive", True),
                    "routed": row.get("routed", 0),
                    "shed": row.get("shed", 0),
                    "failures": row.get("failures", 0),
                    "frames_retried": row.get("frames_retried", 0),
                    "lane_bytes": row.get("lane_bytes", 0),
                    "bytes_sent": row.get("bytes_sent", 0),
                    "bytes_received": row.get("bytes_received", 0),
                    "replication_lag_epochs": row.get(
                        "replication_lag_epochs", 0
                    ),
                }
                for name, row in self.replicas.items()
            },
        }


def session_cache_summary(cache) -> dict:
    """SessionCache + per-session serving state, one flat report dict.

    The cache-level counters (hit/miss/eviction) say whether dictionary
    churn is thrashing the LRU; the per-session rows surface what the
    live-updates subsystem is doing — current epoch, open delta
    segments, live/tombstoned entity counts and the maintenance actions
    taken — next to the serving counters. Consumed by the
    ``serve_extract --check`` report and the updates bench.
    """
    sessions = {}
    for key, s in cache._sessions.items():
        state = s.current_state
        sessions[key] = {
            "epoch": s.epoch,
            "requests": s.requests,
            "batches": s.batches,
            "inflight": s.inflight,
            "open_segments": state.open_segments,
            "live_entities": state.version.num_live,
            "tombstoned": int(state.version.tombstones.sum()),
            "pinned_epochs": sorted(s.epochs),
            "calibrated": s.calibrated,
            "maintenance": [m["action"] for m in s.maintenance_log],
        }
    return {
        "sessions": len(cache),
        "max_sessions": cache.max_sessions,
        "hits": cache.hits,
        "misses": cache.misses,
        "evictions": cache.evictions,
        "per_session": sessions,
    }

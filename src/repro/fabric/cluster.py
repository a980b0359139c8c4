"""Cluster coordinator: epoch-agreed routing over replicated sessions.

The coordinator owns the authoritative ``DictionarySession`` mirrors
(deltas apply here first), replicates every change to the replicas as
serialized deltas with the chosen maintenance action
(``session.delta_log`` is the replication source of truth), and routes
requests by three rules, in order:

1. **ring placement** — ``HashRing.owners(session_key)`` gives the
   deterministic preference order of replicas for a session;
2. **epoch agreement** — a request admitted (pinned) at epoch E is
   only sent to replicas whose last ack for that session is >= E; a
   lagging replica is skipped, never asked and never wrong;
3. **admission accounting** — per-replica inflight is capped, dead
   replicas (transport failures) are shed with bounded retry + backoff
   over the remaining candidates; if every candidate is shed the
   request errors — shed loudly, never silently dropped.

Epoch release: the coordinator refcounts outstanding requests per
(session, epoch); when an epoch older than current drains to zero it
broadcasts RELEASE so replicas drop their retention pins —
``hold_epochs=True`` keeps the coordinator-side pins (and skips local
GC) so tests and ``serve_cluster --check`` can still compute
``one_shot_reference`` at any admitted epoch after the run.
"""
from __future__ import annotations

import dataclasses
import json
import socket
import threading
import time

import numpy as np

from repro.fabric.replica import (
    encode_delta_ship,
    encode_replan_ship,
    encode_request,
    replica_main,
    snapshot_session,
)
from repro.fabric.ring import HashRing
from repro.fabric.transport import (
    ChannelClosed,
    Endpoint,
    SocketChannel,
    TransportTimeout,
)
from repro.fabric.wire import (
    FT_DELTA,
    FT_LANES,
    FT_RELEASE,
    FT_REQUEST,
    FT_SHUTDOWN,
    FT_SNAPSHOT,
    FT_STATS,
    decode_frame,
    matches_from_wire,
)


class ClusterShed(RuntimeError):
    """Every candidate replica was shed (dead, lagging, or saturated)."""


@dataclasses.dataclass
class ReplicaHandle:
    """Coordinator-side view of one replica."""

    name: str
    endpoint: Endpoint
    alive: bool = True
    inflight: int = 0
    routed: int = 0
    shed: int = 0
    failures: int = 0
    lane_bytes: int = 0
    # session key -> last acked epoch (-1 = not bootstrapped)
    acked: dict = dataclasses.field(default_factory=dict)
    # session key -> how many delta_log entries have been shipped
    log_pos: dict = dataclasses.field(default_factory=dict)


def pad_docs(docs) -> np.ndarray:
    """Variable-length docs -> one [N, T] PAD-padded int32 array.

    Row i = doc i, exactly like ``serving.service.one_shot_reference``
    pads — the wire request must describe the same batch the reference
    executes.
    """
    from repro.core.dictionary import PAD

    rows = [np.asarray(d, dtype=np.int32).reshape(-1) for d in docs]
    T = max((len(r) for r in rows), default=1)
    out = np.full((len(rows), max(T, 1)), PAD, dtype=np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


class ClusterCoordinator:
    """Routes extraction over replicated sessions with epoch agreement."""

    def __init__(self, replicas: dict[str, Endpoint], *,
                 metrics=None, max_inflight_per_replica: int = 8,
                 route_retries: int = 2, retry_backoff_s: float = 0.05,
                 hold_epochs: bool = False):
        if not replicas:
            raise ValueError("ClusterCoordinator needs >= 1 replica")
        self.handles = {
            name: ReplicaHandle(name=name, endpoint=ep)
            for name, ep in replicas.items()
        }
        self.ring = HashRing(list(replicas))
        self.metrics = metrics
        self.max_inflight_per_replica = max_inflight_per_replica
        self.route_retries = route_retries
        self.retry_backoff_s = retry_backoff_s
        self.hold_epochs = hold_epochs
        self.sessions: dict = {}  # key -> coordinator-local session
        # (session, epoch) -> outstanding request count (release protocol)
        self._outstanding: dict = {}
        # key -> epochs the replicas still hold retention pins for
        self._retained: dict = {}
        self.released: list = []  # (session, epoch) broadcast log

    # --------------------------------------------------------- replication
    def add_session(self, sess) -> None:
        """Register + bootstrap ``sess`` on every replica (snapshot)."""
        self.sessions[sess.key] = sess
        payload = snapshot_session(sess)
        for h in self.handles.values():
            ack = json.loads(
                h.endpoint.call(FT_SNAPSHOT, payload).payload.decode()
            )
            if int(ack["epoch"]) != sess.epoch:
                raise RuntimeError(
                    f"replica {h.name} bootstrapped session {sess.key} "
                    f"at epoch {ack['epoch']}, coordinator is at "
                    f"{sess.epoch}"
                )
            h.acked[sess.key] = int(ack["epoch"])
            h.log_pos[sess.key] = len(sess.delta_log)
        self._retained[sess.key] = {int(sess.epoch)}
        if self.hold_epochs:
            sess.pin_current()

    def sync_session(self, key: str) -> None:
        """Ship un-replicated ``delta_log`` entries; collect epoch acks.

        The log replays in order with the coordinator's *actual*
        maintenance action forced, so every replica walks the identical
        epoch chain (same numbers, same id renumbering on compaction).
        Divergent ack epochs are a protocol failure and raise.
        """
        sess = self.sessions[key]
        log = list(sess.delta_log)
        for h in self.handles.values():
            if not h.alive:
                continue
            pos = h.log_pos.get(key, 0)
            for entry in log[pos:]:
                if entry["action"] == "replan":
                    payload = encode_replan_ship(
                        key, entry["parent_epoch"], entry["plan"],
                        entry["cost_params"],
                    )
                else:
                    payload = encode_delta_ship(
                        key, entry["parent_epoch"], entry["action"],
                        entry["delta"], entry.get("sample_docs"),
                    )
                try:
                    ack = json.loads(
                        h.endpoint.call(FT_DELTA, payload).payload.decode()
                    )
                except (TransportTimeout, ChannelClosed):
                    h.alive = False
                    h.failures += 1
                    break
                if int(ack["epoch"]) != entry["epoch"]:
                    raise RuntimeError(
                        f"replication diverged: replica {h.name} acked "
                        f"epoch {ack['epoch']} for session {key}, "
                        f"coordinator log says {entry['epoch']}"
                    )
                h.acked[key] = int(ack["epoch"])
                h.log_pos[key] = pos = pos + 1
                self._retained.setdefault(key, set()).add(
                    int(entry["epoch"])
                )
        self._sweep_drained(key)

    def _sweep_drained(self, key: str) -> None:
        """Release retained epochs that predate current and have no
        outstanding requests — an epoch that drained *before* the next
        delta landed would otherwise stay pinned on every replica
        forever (``_finish`` only fires for requests still in flight
        across the flip)."""
        sess = self.sessions[key]
        for epoch in sorted(self._retained.get(key, ())):
            if epoch != sess.epoch \
                    and self._outstanding.get((key, epoch), 0) <= 0:
                self.release_epoch(key, epoch)

    def apply_delta(self, key: str, delta, sample_docs=None, **kw):
        """Apply on the coordinator mirror, then replicate the log."""
        sess = self.sessions[key]
        if self.hold_epochs:
            # keep the parent epoch for post-run reference checks
            sess.pin_current()
        state = sess.apply_delta(delta, sample_docs=sample_docs, **kw)
        self.sync_session(key)
        return state

    # ------------------------------------------------------------- routing
    def _candidates(self, key: str, epoch: int):
        """Ring-ordered eligible replicas for a request at ``epoch``."""
        out = []
        for name in self.ring.owners(key, n=len(self.handles)):
            h = self.handles[name]
            if not h.alive:
                h.shed += 1
                continue
            if h.acked.get(key, -1) < epoch:
                h.shed += 1  # lagging: epoch agreement forbids routing
                continue
            if h.inflight >= self.max_inflight_per_replica:
                h.shed += 1
                continue
            out.append(h)
        return out

    def _route(self, key: str, epoch: int, ftype: int, payload: bytes,
               timeout: float | None = None):
        """Send to the first healthy candidate; fail over with backoff."""
        last_exc = None
        for attempt in range(self.route_retries + 1):
            for h in self._candidates(key, epoch):
                h.inflight += 1
                try:
                    frame = h.endpoint.call(ftype, payload,
                                            timeout=timeout)
                except (TransportTimeout, ChannelClosed) as exc:
                    # dead or wedged replica: mark and fail over — the
                    # endpoint already burned its own frame-level
                    # retries before giving up
                    h.alive = False
                    h.failures += 1
                    last_exc = exc
                    continue
                finally:
                    h.inflight -= 1
                h.routed += 1
                if ftype == FT_LANES:
                    h.lane_bytes += len(payload)
                return h, frame
            if attempt < self.route_retries:
                time.sleep(self.retry_backoff_s * (2 ** attempt))
        raise ClusterShed(
            f"no replica could serve session {key} at epoch {epoch}: "
            f"members {self.ring.members}, acks "
            f"{ {n: h.acked.get(key, -1) for n, h in self.handles.items()} }, "
            f"alive { {n: h.alive for n, h in self.handles.items()} }"
            + (f"; last transport error: {last_exc}" if last_exc else "")
        )

    def _admit(self, key: str, epoch: int) -> None:
        self._outstanding[(key, epoch)] = (
            self._outstanding.get((key, epoch), 0) + 1
        )

    def _finish(self, key: str, epoch: int) -> None:
        left = self._outstanding.get((key, epoch), 0) - 1
        self._outstanding[(key, epoch)] = max(left, 0)
        sess = self.sessions[key]
        if left <= 0 and epoch != sess.epoch:
            self.release_epoch(key, epoch)

    def release_epoch(self, key: str, epoch: int) -> None:
        """Broadcast RELEASE: the cluster drained epoch ``epoch``."""
        if (key, epoch) in self.released:
            return  # a second broadcast would double-unpin on replicas
        self._retained.get(key, set()).discard(epoch)
        body = json.dumps({"session": key, "epoch": epoch}).encode()
        for h in self.handles.values():
            if not h.alive:
                continue
            try:
                h.endpoint.call(FT_RELEASE, body)
            except (TransportTimeout, ChannelClosed):
                h.alive = False
                h.failures += 1
        self.released.append((key, epoch))
        if not self.hold_epochs:
            sess = self.sessions[key]
            if epoch in sess.epochs and epoch != sess.epoch:
                sess.unpin_epoch(epoch)

    def extract(self, key: str, docs, timeout: float | None = None):
        """Serve one request: pin epoch, route, decode, release.

        Returns ``(epoch, Matches)`` — the admitted epoch is part of
        the result because the caller's parity reference is
        ``one_shot_reference(sess, docs, epoch=epoch)``.
        """
        sess = self.sessions[key]
        epoch = sess.pin_current()
        self._admit(key, epoch)
        try:
            payload = encode_request(key, epoch, pad_docs(docs))
            _h, frame = self._route(key, epoch, FT_REQUEST, payload,
                                    timeout=timeout)
            meta, matches = matches_from_wire(frame.payload)
            if int(meta["epoch"]) != epoch:
                raise RuntimeError(
                    f"replica {meta.get('replica')} answered for epoch "
                    f"{meta['epoch']}, request was pinned at {epoch}"
                )
            return epoch, matches
        finally:
            sess.unpin_epoch(epoch)
            self._finish(key, epoch)

    def verify_lanes(self, session_key: str, epoch: int, docs, lanes):
        """Remote verify: ship probed lanes, get Matches back.

        The ``ExtractionService.remote_verify`` hook — the service's
        probe stage already pinned ``epoch`` for the batch, so there is
        no pin here, only epoch-agreed routing. Returns
        ``(Matches, overflow)`` with host arrays.
        """
        from repro.extraction.sharded import lanes_to_wire

        self._admit(session_key, epoch)
        try:
            payload = lanes_to_wire(
                docs, lanes, {"session": session_key, "epoch": int(epoch)}
            )
            _h, frame = self._route(session_key, epoch, FT_LANES, payload)
            meta, matches = matches_from_wire(frame.payload)
            return matches, int(meta.get("overflow", 0))
        finally:
            self._finish(session_key, epoch)

    # ----------------------------------------------------------- lifecycle
    def poll_stats(self) -> dict:
        """Collect replica stats; fold per-replica rows into metrics."""
        out = {}
        for name, h in self.handles.items():
            remote = {}
            if h.alive:
                try:
                    remote = json.loads(
                        h.endpoint.call(FT_STATS, b"").payload.decode()
                    )
                except (TransportTimeout, ChannelClosed):
                    h.alive = False
                    h.failures += 1
            ch = h.endpoint.channel
            lag = {
                key: int(self.sessions[key].epoch) - int(e)
                for key, e in h.acked.items()
                if key in self.sessions
            }
            row = {
                "alive": h.alive,
                "routed": h.routed,
                "shed": h.shed,
                "failures": h.failures,
                "frames_sent": h.endpoint.frames_sent,
                "frames_retried": h.endpoint.frames_retried,
                "frames_damaged": h.endpoint.frames_damaged,
                "lane_bytes": h.lane_bytes,
                "bytes_sent": getattr(ch, "bytes_sent", 0),
                "bytes_received": getattr(ch, "bytes_received", 0),
                "replication_lag_epochs": max(lag.values(), default=0),
                "remote": remote,
            }
            out[name] = row
            if self.metrics is not None:
                self.metrics.record_replica(name, row)
        return out

    def shutdown(self) -> None:
        for h in self.handles.values():
            try:
                # no reply: the handler returning None ends the serve
                # loop without sending, so fire-and-forget
                from repro.fabric.wire import encode_frame

                h.endpoint.channel.send(
                    encode_frame(FT_SHUTDOWN, h.endpoint.next_seq(), b"")
                )
            except (ChannelClosed, OSError):
                pass
            try:
                h.endpoint.close()
            except (ChannelClosed, OSError):
                pass


# ------------------------------------------------- one-process topology


class ReplicaThread(threading.Thread):
    """``replica_main`` on a daemon thread; ``error`` keeps the exception
    that ended the loop (``None`` after a clean shutdown)."""

    def __init__(self, host: str, port: int, name: str, device):
        super().__init__(name=f"replica-{name}", daemon=True)
        self._args = (host, port, name, device)
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            replica_main(*self._args)
        except BaseException as exc:
            self.error = exc
            raise


def launch_local_cluster(names, *, devices=None, timeout: float = 120.0,
                         endpoint_timeout: float = 60.0,
                         retries: int = 3):
    """Start one replica per name on a ``ReplicaThread``; return
    (threads, endpoints).

    Replicas serve from this process — one that has touched JAX holds
    the chip, so it never starts children that need it — each pinned
    to its own device: replica i gets ``devices[i % len(devices)]``
    (default ``jax.devices()``; one chip per replica on a multi-chip
    host, all on the one device of a single-device host). The
    coordinator listens on an ephemeral 127.0.0.1 port and each replica
    connects back over TCP and announces its name in a hello frame, so
    frames take the same wire path as between hosts.
    ``endpoint_timeout`` is generous by default: a replica's first
    request pays jit compilation.
    """
    import jax

    devs = list(devices if devices is not None else jax.devices())
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(len(names))
    host, port = srv.getsockname()
    threads = []
    for i, name in enumerate(names):
        t = ReplicaThread(host, port, name, devs[i % len(devs)])
        t.start()
        threads.append(t)
    endpoints: dict[str, Endpoint] = {}
    srv.settimeout(timeout)
    try:
        for _ in names:
            conn, _addr = srv.accept()
            channel = SocketChannel(conn)
            hello = decode_frame(channel.recv(timeout=timeout))
            name = json.loads(hello.payload.decode())["replica"]
            endpoints[name] = Endpoint(
                channel, timeout=endpoint_timeout, retries=retries
            )
    finally:
        srv.close()
    if set(endpoints) != set(names):
        raise RuntimeError(
            f"cluster launch: expected replicas {sorted(names)}, "
            f"got {sorted(endpoints)}"
        )
    return threads, endpoints

"""Pluggable channels + seq-matched RPC for the serving fabric.

Channel layer
-------------
A channel moves whole frames (opaque byte strings) with an outer
little-endian u32 length prefix. Two real implementations:

* ``LoopbackChannel`` — an in-process queue pair (tests, benches).
* ``SocketChannel`` — a TCP stream (``launch_local_cluster`` replicas and
  the real multi-host deployment).

Both expose the same three methods (``send``, ``recv``, ``close``), so
everything above the channel — fault handling, RPC, replication — is
transport-agnostic.

Fault injection
---------------
``FaultyChannel`` wraps any channel and perturbs *whole frames* on
send: drop, duplicate, reorder (hold one frame, emit it after the
next), truncate (cut the frame short — the outer length prefix stays
consistent with the shortened bytes, so damage is only detectable by
the frame header's redundant length + crc), and corrupt (flip one
payload byte). This models a lossy transport above a reliable stream:
the outer framing survives, the frame codec must catch the rest.

RPC layer
---------
``Endpoint`` turns a channel into a call/response port. Every call
stamps a fresh seq; the caller waits for a frame echoing that seq,
discarding strays (stale duplicates, reordered leftovers). Timeouts
and damaged frames trigger bounded retries with exponential backoff —
resending the *same seq*, so the server side can deduplicate: replica
servers cache the last response per seq and replay it instead of
re-executing, which makes retries safe even for non-idempotent
operations (applying a delta twice would corrupt the epoch chain).
"""
from __future__ import annotations

import dataclasses
import queue
import socket
import struct
import threading
import time

from repro.fabric.wire import (
    FT_ERROR,
    Frame,
    FrameError,
    decode_frame,
    encode_frame,
)

_LEN = struct.Struct("<I")


class TransportTimeout(TimeoutError):
    """No (valid) response arrived within the deadline + retry budget."""


class RemoteError(RuntimeError):
    """The remote handler failed; message carried back in an ERROR frame."""


class ChannelClosed(ConnectionError):
    """The peer closed the channel."""


class LoopbackChannel:
    """In-process channel half: one send queue, one recv queue."""

    def __init__(self, tx: "queue.Queue[bytes | None]",
                 rx: "queue.Queue[bytes | None]"):
        self._tx = tx
        self._rx = rx
        self._closed = False

    def send(self, frame: bytes) -> None:
        if self._closed:
            raise ChannelClosed("loopback channel is closed")
        # length prefix kept for symmetry with SocketChannel so fault
        # injection and byte accounting behave identically on both
        self._tx.put(_LEN.pack(len(frame)) + frame)

    def recv(self, timeout: float | None = None) -> bytes:
        if self._closed:
            raise ChannelClosed("loopback channel is closed")
        try:
            data = self._rx.get(timeout=timeout)
        except queue.Empty:
            raise TransportTimeout(
                f"loopback recv timed out after {timeout}s"
            ) from None
        if data is None:
            raise ChannelClosed("loopback peer closed")
        (n,) = _LEN.unpack_from(data)
        body = data[_LEN.size:]
        if n != len(body):
            raise FrameError(
                f"outer length prefix says {n} bytes, got {len(body)}"
            )
        return body

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._tx.put(None)


def loopback_pair() -> tuple[LoopbackChannel, LoopbackChannel]:
    """Two connected in-process channel halves."""
    a: "queue.Queue[bytes | None]" = queue.Queue()
    b: "queue.Queue[bytes | None]" = queue.Queue()
    return LoopbackChannel(a, b), LoopbackChannel(b, a)


class SocketChannel:
    """Frame channel over a connected TCP (or AF_UNIX) stream socket."""

    def __init__(self, sock: socket.socket):
        self._sock = sock
        if sock.family in (socket.AF_INET, socket.AF_INET6):
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.bytes_sent = 0
        self.bytes_received = 0

    def send(self, frame: bytes) -> None:
        data = _LEN.pack(len(frame)) + frame
        try:
            self._sock.sendall(data)
        except (BrokenPipeError, ConnectionResetError, OSError) as exc:
            raise ChannelClosed(f"socket send failed: {exc}") from exc
        self.bytes_sent += len(data)

    def _recv_exact(self, n: int, deadline: float | None) -> bytes:
        chunks = []
        got = 0
        while got < n:
            if deadline is not None:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TransportTimeout("socket recv timed out")
                self._sock.settimeout(left)
            else:
                self._sock.settimeout(None)
            try:
                chunk = self._sock.recv(n - got)
            except socket.timeout:
                raise TransportTimeout("socket recv timed out") from None
            except (ConnectionResetError, OSError) as exc:
                raise ChannelClosed(f"socket recv failed: {exc}") from exc
            if not chunk:
                raise ChannelClosed("socket peer closed")
            chunks.append(chunk)
            got += len(chunk)
        return b"".join(chunks)

    def recv(self, timeout: float | None = None) -> bytes:
        deadline = None if timeout is None else time.monotonic() + timeout
        head = self._recv_exact(_LEN.size, deadline)
        (n,) = _LEN.unpack(head)
        body = self._recv_exact(n, deadline)
        self.bytes_received += _LEN.size + n
        return body

    def close(self) -> None:
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._sock.close()


def socket_pair() -> tuple[SocketChannel, SocketChannel]:
    """Two connected TCP channel halves over 127.0.0.1 (tests/benches)."""
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    cli = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    cli.connect(srv.getsockname())
    acc, _ = srv.accept()
    srv.close()
    return SocketChannel(cli), SocketChannel(acc)


@dataclasses.dataclass
class FaultPlan:
    """Which outgoing frames to damage, and how.

    ``action`` in {"drop", "dup", "reorder", "truncate", "corrupt"};
    ``frames`` is the set of 0-based send indices to hit (every send
    through the wrapper increments the index, damaged or not).
    """

    action: str
    frames: frozenset[int] = frozenset()

    _ACTIONS = ("drop", "dup", "reorder", "truncate", "corrupt")

    def __post_init__(self):
        if self.action not in self._ACTIONS:
            raise ValueError(
                f"FaultPlan action {self.action!r} not in {self._ACTIONS}"
            )
        self.frames = frozenset(int(i) for i in self.frames)


class FaultyChannel:
    """Wrap a channel; perturb whole frames on send per ``FaultPlan``s."""

    def __init__(self, inner, plans: list[FaultPlan] | None = None):
        self._inner = inner
        self.plans = list(plans or [])
        self.sends = 0
        self.faults_injected = 0
        self._held: bytes | None = None  # reorder buffer

    def _plan_for(self, idx: int) -> FaultPlan | None:
        for p in self.plans:
            if idx in p.frames:
                return p
        return None

    def send(self, frame: bytes) -> None:
        idx = self.sends
        self.sends += 1
        plan = self._plan_for(idx)
        if plan is None:
            self._inner.send(frame)
            if self._held is not None:
                held, self._held = self._held, None
                self._inner.send(held)
            return
        self.faults_injected += 1
        if plan.action == "drop":
            return
        if plan.action == "dup":
            self._inner.send(frame)
            self._inner.send(frame)
            return
        if plan.action == "reorder":
            # hold this frame; it goes out right after the next send
            if self._held is not None:
                self._inner.send(self._held)
            self._held = frame
            return
        if plan.action == "truncate":
            # outer prefix stays consistent with the shortened bytes:
            # only the frame header's redundant length/crc can tell
            cut = max(len(frame) // 2, 1)
            self._inner.send(frame[:cut])
            return
        if plan.action == "corrupt":
            pos = len(frame) // 2
            damaged = bytearray(frame)
            damaged[pos] ^= 0xFF
            self._inner.send(bytes(damaged))
            return

    def recv(self, timeout: float | None = None) -> bytes:
        return self._inner.recv(timeout=timeout)

    def close(self) -> None:
        self._inner.close()


class Endpoint:
    """Seq-matched RPC port over a frame channel (client side).

    ``call`` retries on timeout and on damaged/unmatched responses,
    re-sending the same seq each time; pair with a server that dedupes
    by seq (``replica.ReplicaServer``) and retries become safe for
    non-idempotent operations too.
    """

    def __init__(self, channel, *, timeout: float = 10.0,
                 retries: int = 3, backoff: float = 0.05):
        self.channel = channel
        self.timeout = timeout
        self.retries = retries
        self.backoff = backoff
        self._seq = 0
        self.frames_sent = 0
        self.frames_retried = 0
        self.frames_damaged = 0
        # one in-flight call per endpoint: the channel is a single
        # stream and responses are matched by seq, so concurrent
        # callers (service verify worker + coordinator control plane)
        # must serialize here
        self._lock = threading.Lock()

    def next_seq(self) -> int:
        self._seq = (self._seq + 1) % 2**32
        return self._seq

    def call(self, ftype: int, payload: bytes,
             timeout: float | None = None) -> Frame:
        with self._lock:
            return self._call_locked(ftype, payload, timeout)

    def _call_locked(self, ftype: int, payload: bytes,
                     timeout: float | None = None) -> Frame:
        seq = self.next_seq()
        wire = encode_frame(ftype, seq, payload)
        deadline_each = self.timeout if timeout is None else timeout
        last_err: Exception | None = None
        for attempt in range(self.retries + 1):
            if attempt:
                self.frames_retried += 1
                time.sleep(self.backoff * (2 ** (attempt - 1)))
            try:
                self.channel.send(wire)
                self.frames_sent += 1
                resp = self._await(seq, deadline_each)
            except TransportTimeout as exc:
                last_err = exc
                continue
            if resp.ftype == FT_ERROR:
                raise RemoteError(resp.payload.decode("utf-8", "replace"))
            return resp
        raise TransportTimeout(
            f"no response for seq={seq} after {self.retries + 1} "
            f"attempts ({last_err})"
        )

    def _await(self, seq: int, timeout: float) -> Frame:
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise TransportTimeout(f"seq={seq} timed out")
            raw = self.channel.recv(timeout=left)
            try:
                frame = decode_frame(raw)
            except FrameError:
                # damaged response: keep waiting; the send-side retry
                # loop re-asks if nothing clean arrives in time
                self.frames_damaged += 1
                continue
            if frame.seq != seq:
                # stale duplicate or reordered leftover — not ours
                continue
            return frame

    def close(self) -> None:
        self.channel.close()


def serve_frames(channel, handler, *, idle_timeout: float | None = None,
                 dedupe_depth: int = 128) -> None:
    """Server loop: decode, dedupe by seq, dispatch, reply.

    ``handler(frame) -> (ftype, payload) | None`` — ``None`` ends the
    loop (after any reply is sent the handler arranged itself).
    Damaged inbound frames are dropped silently: the client's retry
    re-sends them. Responses are cached per seq (bounded LRU of
    ``dedupe_depth``) and replayed on duplicate seqs, so a retried
    non-idempotent request executes exactly once.
    """
    seen: dict[int, tuple[int, bytes]] = {}
    order: list[int] = []
    while True:
        try:
            raw = channel.recv(timeout=idle_timeout)
        except (ChannelClosed, TransportTimeout):
            return
        try:
            frame = decode_frame(raw)
        except FrameError:
            continue
        if frame.seq in seen:
            ftype, payload = seen[frame.seq]
            channel.send(encode_frame(ftype, frame.seq, payload))
            continue
        try:
            result = handler(frame)
        except Exception as exc:  # noqa: BLE001 — surfaced to the peer
            msg = f"{type(exc).__name__}: {exc}".encode()
            channel.send(encode_frame(FT_ERROR, frame.seq, msg))
            continue
        if result is None:
            return
        ftype, payload = result
        seen[frame.seq] = (ftype, payload)
        order.append(frame.seq)
        if len(order) > dedupe_depth:
            seen.pop(order.pop(0), None)
        channel.send(encode_frame(ftype, frame.seq, payload))

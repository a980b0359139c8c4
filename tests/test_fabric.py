"""Multi-host serving fabric: wire codec, transport fault tolerance,
delta replication with epoch agreement, and cluster-routed parity.

The two contracts everything below drills into:

* **Bit-exactness across the wire.** Lane frames, match frames and
  dictionary snapshots/deltas round-trip byte-for-byte, so a remote
  ``select_from_tiles`` merge — and therefore every routed response —
  is bit-identical to the single-host ``one_shot_reference`` at the
  request's admitted epoch.
* **No silent corruption.** A dropped, duplicated, reordered,
  truncated or bit-flipped frame is either detected (crc / redundant
  length / sha256 container fingerprint) and retried, or decodes to
  the identical payload. Faults may cost retries; they may never
  change matches. Retried non-idempotent frames (delta application)
  execute exactly once via the server's seq-dedupe cache.

The multi-process test at the bottom is the CI stand-in for multiple
hosts: real ``spawn`` processes, real TCP sockets, live replicated
deltas mid-stream.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np
import pytest

from repro.core.eejoin import EEJoinConfig
from repro.data.synth import make_corpus
from repro.extraction.sharded import lanes_from_wire, lanes_to_wire
from repro.fabric.cluster import (
    ClusterCoordinator,
    ClusterShed,
    launch_local_cluster,
)
from repro.fabric.replica import ReplicaServer, encode_request
from repro.fabric.ring import HashRing
from repro.fabric.transport import (
    Endpoint,
    FaultPlan,
    FaultyChannel,
    LoopbackChannel,
    RemoteError,
    SocketChannel,
    TransportTimeout,
    loopback_pair,
    serve_frames,
    socket_pair,
)
from repro.fabric.wire import (
    FRAME_TYPES,
    FT_ACK,
    FT_REQUEST,
    FT_SHUTDOWN,
    FrameError,
    decode_frame,
    encode_frame,
    matches_from_wire,
)
from repro.serving import SessionCache, one_shot_reference
from repro.serving.metrics import ServingMetrics
from repro.serving.session import pure_plan
from repro.updates.delta import (
    DictionaryDelta,
    DictionaryVersion,
    pack_arrays,
    random_delta,
    unpack_arrays,
)

GAMMA = 0.8
SCHEMES = ("word", "prefix", "lsh", "variant")


def _config(**kw):
    kw.setdefault("gamma", GAMMA)
    kw.setdefault("max_candidates", 4096)
    kw.setdefault("result_capacity", 8192)
    kw.setdefault("use_kernel", True)
    return EEJoinConfig(**kw)


def _dense_corpus(seed=7, num_entities=24):
    # small vocab → real matches; a parity check over zero matches is
    # vacuous and every e2e test below asserts non-vacuity
    return make_corpus(num_docs=8, doc_len=48, vocab_size=48,
                      num_entities=num_entities, seed=seed)


def _session(corpus, scheme="word", **cfg):
    cache = SessionCache()
    return cache, cache.get_or_create(corpus.dictionary, _config(**cfg),
                                      plan=pure_plan(scheme))


def _var_docs(corpus, seed, n=6, min_len=8):
    rng = np.random.default_rng(seed)
    D, T = corpus.doc_tokens.shape
    lens = rng.integers(min_len, T + 1, size=n)
    return [np.asarray(corpus.doc_tokens[i % D, : lens[i]])
            for i in range(n)]


@contextlib.contextmanager
def _thread_cluster(n=2, fault_plans=None, ep_timeout=60.0, ep_retries=3,
                    **coord_kw):
    """In-process cluster: ReplicaServers on loopback serve threads."""
    endpoints, servers, threads = {}, {}, []
    for i in range(n):
        a, b = loopback_pair()
        if fault_plans and i in fault_plans:
            a = FaultyChannel(a, fault_plans[i])
        srv = ReplicaServer(f"t{i}")
        th = threading.Thread(target=serve_frames, args=(b, srv.handle),
                              kwargs={"idle_timeout": 600.0}, daemon=True)
        th.start()
        endpoints[f"t{i}"] = Endpoint(a, timeout=ep_timeout,
                                      retries=ep_retries, backoff=0.01)
        servers[f"t{i}"] = srv
        threads.append(th)
    coord = ClusterCoordinator(endpoints, **coord_kw)
    try:
        yield coord, servers
    finally:
        coord.shutdown()
        for th in threads:
            th.join(timeout=10)


# ------------------------------------------------------------ frame codec
def test_frame_roundtrip_all_types():
    payload = bytes(range(64))
    for ftype in FRAME_TYPES:
        f = decode_frame(encode_frame(ftype, 12345, payload))
        assert (f.ftype, f.seq, f.payload) == (ftype, 12345, payload)
    f = decode_frame(encode_frame(FT_ACK, 0, b""))
    assert (f.ftype, f.seq, f.payload) == (FT_ACK, 0, b"")


def test_frame_every_single_byte_flip_is_detected():
    wire = encode_frame(FT_REQUEST, 7, b"lane payload bytes")
    for i in range(len(wire)):
        for bit in (0x01, 0x80):
            bad = bytearray(wire)
            bad[i] ^= bit
            with pytest.raises(FrameError):
                decode_frame(bytes(bad))


def test_frame_every_truncation_is_detected():
    wire = encode_frame(FT_REQUEST, 9, b"0123456789abcdef")
    for cut in range(len(wire)):
        with pytest.raises(FrameError):
            decode_frame(wire[:cut])
    with pytest.raises(FrameError):
        decode_frame(wire + b"\x00")  # trailing garbage


def test_frame_rejects_unknown_type_and_version():
    with pytest.raises(FrameError):
        encode_frame(200, 1, b"")
    wire = bytearray(encode_frame(FT_ACK, 1, b""))
    wire[4] = 99  # version byte
    with pytest.raises(FrameError):
        decode_frame(bytes(wire))


def test_frame_roundtrip_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(
        ftype=st.sampled_from(sorted(FRAME_TYPES)),
        seq=st.integers(min_value=0, max_value=2**32 - 1),
        payload=st.binary(max_size=512),
    )
    @hyp.settings(deadline=None, max_examples=60)
    def run(ftype, seq, payload):
        f = decode_frame(encode_frame(ftype, seq, payload))
        assert (f.ftype, f.seq, f.payload) == (ftype, seq, payload)

    run()


# ------------------------------------------------------------- lane frames
def _lane_geometry(rng, n_sides, G, NC, with_keys):
    lanes = []
    for s in range(n_sides):
        count = rng.integers(0, 2 * NC, size=G).astype(np.int32)
        cand = np.full((G, NC), -1, np.int32)
        for g in range(G):
            n = min(int(count[g]), NC)
            if n:
                vals = np.sort(rng.choice(10_000, size=n, replace=False))
                cand[g, :n] = vals
        keys = (rng.integers(0, 2**32, size=(G, NC, 2), dtype=np.uint64)
                .astype(np.uint32) if with_keys[s] else None)
        lanes.append((count, cand, keys))
    return lanes


def test_lane_wire_roundtrip_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(
        seed=st.integers(min_value=0, max_value=2**31),
        n_sides=st.integers(min_value=1, max_value=3),
        G=st.integers(min_value=1, max_value=3),
        NC=st.integers(min_value=1, max_value=16),
        D=st.integers(min_value=1, max_value=4),
        T=st.integers(min_value=1, max_value=12),
        keys=st.lists(st.booleans(), min_size=3, max_size=3),
    )
    @hyp.settings(deadline=None, max_examples=40)
    def run(seed, n_sides, G, NC, D, T, keys):
        rng = np.random.default_rng(seed)
        docs = rng.integers(0, 40, size=(D, T)).astype(np.int32)
        docs[rng.random(size=(D, T)) < 0.2] = 0  # PAD holes + PAD rows
        lanes = _lane_geometry(rng, n_sides, G, NC, keys)
        meta, docs2, lanes2 = lanes_from_wire(
            lanes_to_wire(docs, lanes, {"session": "s", "epoch": 3})
        )
        assert meta["epoch"] == 3 and meta["n_sides"] == n_sides
        np.testing.assert_array_equal(docs2, docs)
        assert docs2.dtype == docs.dtype
        for (c1, l1, k1), (c2, l2, k2) in zip(lanes, lanes2):
            np.testing.assert_array_equal(c2, c1)
            np.testing.assert_array_equal(l2, l1)
            assert l2.dtype == np.int32
            if k1 is None:
                assert k2 is None
            else:
                np.testing.assert_array_equal(k2, k1)
                assert k2.dtype == np.uint32

    run()


def test_lane_wire_zero_survivor_and_pad_only():
    docs = np.zeros((2, 6), np.int32)  # PAD-only batch
    lanes = [(np.zeros(1, np.int32), np.full((1, 8), -1, np.int32), None)]
    meta, docs2, lanes2 = lanes_from_wire(lanes_to_wire(docs, lanes))
    np.testing.assert_array_equal(docs2, docs)
    assert int(lanes2[0][0][0]) == 0
    assert (lanes2[0][1] == -1).all() and lanes2[0][2] is None


def test_lane_wire_corruption_never_silently_wrong():
    rng = np.random.default_rng(0)
    docs = rng.integers(0, 40, size=(2, 8)).astype(np.int32)
    lanes = _lane_geometry(rng, 2, 2, 8, [True, False])
    wire = lanes_to_wire(docs, lanes)
    for off in range(0, len(wire), max(len(wire) // 200, 1)):
        bad = bytearray(wire)
        bad[off] ^= 0xFF
        try:
            _meta, docs2, lanes2 = lanes_from_wire(bytes(bad))
        except ValueError:
            continue  # detected — the required outcome for real damage
        # decode succeeded: the flip must have been in dead container
        # space and the arrays must be bit-identical
        np.testing.assert_array_equal(docs2, docs)
        for (c1, l1, k1), (c2, l2, k2) in zip(lanes, lanes2):
            np.testing.assert_array_equal(c2, c1)
            np.testing.assert_array_equal(l2, l1)
            if k1 is not None:
                np.testing.assert_array_equal(k2, k1)


def test_pack_arrays_fingerprint_guards_truncation():
    meta, arrays = {"kind": "x"}, {"a": np.arange(7, dtype=np.int32)}
    data = pack_arrays(meta, arrays)
    m2, a2 = unpack_arrays(data)
    assert m2["kind"] == "x"
    np.testing.assert_array_equal(a2["a"], arrays["a"])
    for cut in (0, 10, len(data) // 2, len(data) - 1):
        with pytest.raises(ValueError):
            unpack_arrays(data[:cut])


# ------------------------------------------- delta/version serialization
def test_delta_roundtrip():
    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    rng = np.random.default_rng(11)
    for _ in range(4):
        d = random_delta(rng, sess.current_state.version, 48)
        d2 = DictionaryDelta.from_bytes(d.to_bytes())
        assert d2.added == d.added
        assert sorted(d2.tombstones) == sorted(d.tombstones)
        if d.added_freq is None:
            assert d2.added_freq is None
        else:
            np.testing.assert_array_equal(d2.added_freq, d.added_freq)


def test_version_roundtrip_with_segments_and_tombstones():
    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    rng = np.random.default_rng(12)
    v = sess.current_state.version
    v = v.apply(random_delta(rng, v, 48))  # open segment + tombstones
    v2 = DictionaryVersion.from_bytes(v.to_bytes())
    assert v2.epoch == v.epoch
    assert v2.num_segments == v.num_segments
    np.testing.assert_array_equal(v2.tombstones, v.tombstones)
    d1, ids1 = v.effective_dictionary()
    d2, ids2 = v2.effective_dictionary()
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(d1.tokens, d2.tokens)
    np.testing.assert_array_equal(d1.lengths, d2.lengths)
    np.testing.assert_array_equal(d1.token_weight, d2.token_weight)


# -------------------------------------------------------------------- ring
def test_ring_deterministic_and_distinct():
    r1 = HashRing(["a", "b", "c"])
    r2 = HashRing(["c", "a", "b"])  # member order must not matter
    for key in ("s1", "s2", "deadbeef", ""):
        owners = r1.owners(key, n=3)
        assert owners == r2.owners(key, n=3)
        assert sorted(owners) == ["a", "b", "c"]  # distinct, all members
        assert r1.primary(key) == owners[0]


def test_ring_minimal_movement_on_membership_change():
    keys = [f"k{i}" for i in range(200)]
    r = HashRing(["a", "b", "c"])
    before = {k: r.primary(k) for k in keys}
    r.add("d")
    moved = sum(1 for k in keys if r.primary(k) != before[k])
    # consistent hashing: only ~1/4 of keys should move to the newcomer,
    # and every moved key must have moved *to* d
    assert 0 < moved < len(keys) // 2
    assert all(r.primary(k) == "d" for k in keys
               if r.primary(k) != before[k])
    r.remove("d")
    assert {k: r.primary(k) for k in keys} == before


# --------------------------------------------------------------- transport
def _echo_server(channel, fail_seqs=(), calls=None):
    def handler(frame):
        if calls is not None:
            calls.append(frame.seq)
        if frame.seq in fail_seqs:
            raise RuntimeError("handler exploded")
        if frame.ftype == FT_SHUTDOWN:
            return None
        return FT_ACK, frame.payload[::-1]

    th = threading.Thread(target=serve_frames, args=(channel, handler),
                          kwargs={"idle_timeout": 30.0}, daemon=True)
    th.start()
    return th


@pytest.mark.parametrize("make_pair", [loopback_pair, socket_pair],
                         ids=["loopback", "socket"])
def test_endpoint_roundtrip_both_channels(make_pair):
    a, b = make_pair()
    th = _echo_server(b)
    ep = Endpoint(a, timeout=10.0)
    for i in range(5):
        body = f"payload-{i}".encode()
        resp = ep.call(FT_REQUEST, body)
        assert resp.ftype == FT_ACK and resp.payload == body[::-1]
    ep.channel.send(encode_frame(FT_SHUTDOWN, ep.next_seq(), b""))
    th.join(timeout=10)
    ep.close()


def test_endpoint_surfaces_remote_errors():
    a, b = loopback_pair()
    calls = []
    th = _echo_server(b, fail_seqs={1}, calls=calls)
    ep = Endpoint(a, timeout=5.0)
    with pytest.raises(RemoteError, match="handler exploded"):
        ep.call(FT_REQUEST, b"boom")
    assert ep.call(FT_REQUEST, b"ok").payload == b"ko"
    ep.channel.send(encode_frame(FT_SHUTDOWN, ep.next_seq(), b""))
    th.join(timeout=10)


@pytest.mark.parametrize("action", ["drop", "dup", "reorder", "truncate",
                                    "corrupt"])
@pytest.mark.parametrize("ftype", [FT_REQUEST, FT_ACK],
                         ids=["request", "ack"])
def test_fault_matrix_exactly_once_and_correct(action, ftype):
    """Every fault on every frame type: the call still returns the
    right payload, and the handler ran exactly once per seq."""
    a, b = loopback_pair()
    faulty = FaultyChannel(a, [FaultPlan(action, frames=frozenset({1, 3}))])
    calls = []
    th = _echo_server(b, calls=calls)
    ep = Endpoint(faulty, timeout=2.0, retries=4, backoff=0.01)
    for i in range(5):
        body = f"m{i}".encode()
        assert ep.call(ftype, body).payload == body[::-1]
    if action in ("drop", "truncate", "corrupt"):
        assert ep.frames_retried > 0  # fault cost retries, not answers
    assert faulty.faults_injected > 0
    # dedupe cache: retried/duplicated seqs executed exactly once
    assert sorted(calls) == sorted(set(calls))
    ep.channel.send(encode_frame(FT_SHUTDOWN, ep.next_seq(), b""))
    th.join(timeout=10)


def test_endpoint_times_out_on_dead_server():
    a, _b = loopback_pair()  # nobody serving
    ep = Endpoint(a, timeout=0.05, retries=1, backoff=0.01)
    with pytest.raises(TransportTimeout):
        ep.call(FT_REQUEST, b"anyone home?")
    assert ep.frames_retried == 1


def test_socket_channel_counts_bytes():
    a, b = socket_pair()
    wire = encode_frame(FT_ACK, 1, b"x" * 100)
    a.send(wire)
    assert b.recv(timeout=5.0) == wire
    assert a.bytes_sent == len(wire) + 4  # outer length prefix
    assert b.bytes_received == len(wire) + 4
    a.close()
    b.close()
    assert isinstance(a, SocketChannel) and isinstance(b, SocketChannel)


# -------------------------------------------- replication / epoch agreement
def test_replica_rejects_request_ahead_of_ack():
    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    with _thread_cluster(n=1) as (coord, servers):
        coord.add_session(sess)
        docs = np.asarray([corpus.doc_tokens[0]])
        ep = coord.handles["t0"].endpoint
        with pytest.raises(RemoteError, match="lags"):
            ep.call(FT_REQUEST,
                    encode_request(sess.key, sess.epoch + 1, docs))
        # at the acked epoch the same request serves fine
        frame = ep.call(FT_REQUEST,
                        encode_request(sess.key, sess.epoch, docs))
        meta, _m = matches_from_wire(frame.payload)
        assert int(meta["epoch"]) == sess.epoch


def test_replica_refuses_lane_frame_that_drops_survivors():
    """A decoded lane narrower than the merge capacity must hold all of
    its survivors; one that does not is refused, not merged."""
    from repro.fabric.replica import verify_lanes_on_state

    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    docs = np.asarray(corpus.doc_tokens[:1])
    lane = np.full((1, 4), -1, np.int32)
    lane[0] = np.arange(4)
    lanes = [(np.array([5], np.int32), lane, None)] * len(
        sess.current_state.sides)
    with pytest.raises(ValueError, match="holds only 4"):
        verify_lanes_on_state(sess.current_state, sess.config, docs, lanes)


def test_coordinator_never_routes_to_lagging_replica():
    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    rng = np.random.default_rng(21)
    docs = _var_docs(corpus, 22, n=4)
    with _thread_cluster(n=2, hold_epochs=True) as (coord, servers):
        coord.add_session(sess)
        # replicate a delta to t0 only: t1 is marked dead during sync,
        # then comes back — alive but lagging
        coord.handles["t1"].alive = False
        coord.apply_delta(sess.key, random_delta(rng, sess.current_state.version, 48))
        coord.handles["t1"].alive = True
        assert coord.handles["t1"].acked[sess.key] < sess.epoch
        shed_before = coord.handles["t1"].shed
        epoch, matches = coord.extract(sess.key, docs)
        assert epoch == sess.epoch
        # epoch agreement: only t0 may have served it
        assert coord.handles["t0"].routed == 1
        assert coord.handles["t1"].routed == 0
        assert matches.to_set() == one_shot_reference(sess, docs,
                                                      epoch=epoch)
        # ...and if t1 was ring-preferred it was shed, not routed
        if coord.ring.primary(sess.key) == "t1":
            assert coord.handles["t1"].shed > shed_before
        # catch-up resync makes t1 eligible again
        coord.sync_session(sess.key)
        assert coord.handles["t1"].acked[sess.key] == sess.epoch


def test_all_replicas_lagging_sheds_cleanly():
    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    rng = np.random.default_rng(31)
    with _thread_cluster(n=2, route_retries=0) as (coord, servers):
        coord.add_session(sess)
        # local-only delta (bypasses coordinator replication): every
        # replica now lags the coordinator epoch
        sess.apply_delta(random_delta(rng, sess.current_state.version, 48))
        with pytest.raises(ClusterShed):
            coord.extract(sess.key, [corpus.doc_tokens[0]])
        coord.sync_session(sess.key)
        epoch, _m = coord.extract(sess.key, [corpus.doc_tokens[0]])
        assert epoch == sess.epoch


def test_replicated_compaction_is_identical():
    """Force a compaction (id renumbering!) through replication and
    check replicas land on the same epoch + identical results."""
    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    rng = np.random.default_rng(41)
    docs = _var_docs(corpus, 42, n=4)
    with _thread_cluster(n=2, hold_epochs=True) as (coord, servers):
        coord.add_session(sess)
        coord.apply_delta(sess.key,
                          random_delta(rng, sess.current_state.version, 48),
                          force_action="compact")
        for srv in servers.values():
            assert srv.sessions[sess.key].epoch == sess.epoch
        total = 0
        for name in coord.handles:  # pin each replica's answer directly
            ep = coord.handles[name].endpoint
            frame = ep.call(FT_REQUEST, encode_request(
                sess.key, sess.epoch, np.asarray(
                    [np.pad(d, (0, max(len(x) for x in docs) - len(d)))
                     for d in docs])))
            _meta, matches = matches_from_wire(frame.payload)
            got = matches.to_set()
            assert got == one_shot_reference(sess, docs, epoch=sess.epoch)
            total += len(got)
        assert total > 0, "compaction parity check is vacuous"


def test_epoch_release_protocol():
    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    rng = np.random.default_rng(51)
    with _thread_cluster(n=2) as (coord, servers):
        coord.add_session(sess)
        e0 = sess.epoch
        coord.extract(sess.key, [corpus.doc_tokens[0]])
        coord.apply_delta(sess.key,
                          random_delta(rng, sess.current_state.version, 48))
        # e0 drained before the delta: next request admits the new
        # epoch, and the old one is released everywhere
        epoch, _m = coord.extract(sess.key, [corpus.doc_tokens[1]])
        assert epoch == sess.epoch != e0
        for srv in servers.values():
            retained = srv.stats()["retained_epochs"][sess.key]
            assert e0 not in retained, (
                f"epoch {e0} still pinned on {srv.name}: {retained}"
            )
        assert (sess.key, e0) in coord.released


# ------------------------------------------------- e2e parity (in-process)
@pytest.mark.parametrize("scheme", SCHEMES)
def test_cluster_parity_with_live_deltas(scheme):
    corpus = _dense_corpus(seed=60 + SCHEMES.index(scheme))
    _cache, sess = _session(corpus, scheme)
    rng = np.random.default_rng(61)
    with _thread_cluster(n=2, hold_epochs=True) as (coord, servers):
        coord.add_session(sess)
        total = 0
        for round_i in range(3):
            docs = _var_docs(corpus, 62 + round_i, n=4)
            epoch, matches = coord.extract(sess.key, docs)
            got = matches.to_set()
            assert got == one_shot_reference(sess, docs, epoch=epoch)
            total += len(got)
            if round_i < 2:
                coord.apply_delta(
                    sess.key,
                    random_delta(rng, sess.current_state.version, 48),
                )
        assert total > 0, f"{scheme}: cluster parity check is vacuous"
        assert sum(h.routed for h in coord.handles.values()) == 3


def test_cluster_parity_under_fault_injection():
    """Bit-flips, drops and truncations on the wire to the replica:
    responses stay bit-identical, damage shows up only as retries.

    Single replica so every request must survive its fault (no quiet
    failover hiding a broken retry path); faults are armed only after
    a warm-up request so the retry timeout never races jit compilation,
    and fault indices are spaced so each faulted send's retry is clean
    (a retry re-sends through the same faulty channel and bumps the
    send index)."""
    corpus = _dense_corpus()
    _cache, sess = _session(corpus)
    # fixed-shape docs: one compiled executable serves every request
    docs = [np.asarray(corpus.doc_tokens[i]) for i in range(3)]
    with _thread_cluster(n=1, fault_plans={0: []}, ep_timeout=20.0,
                         hold_epochs=True) as (coord, servers):
        faulty = coord.handles["t0"].endpoint.channel
        assert isinstance(faulty, FaultyChannel)
        coord.add_session(sess)                      # send 0
        epoch, matches = coord.extract(sess.key, docs)   # send 1: warm
        want = one_shot_reference(sess, docs, epoch=epoch)
        assert matches.to_set() == want
        assert len(want) > 0, "fault-injection parity check is vacuous"
        # sends 2..: one fault per request, clean retry in between
        faulty.plans.extend([
            FaultPlan("corrupt", frames=frozenset({2})),
            FaultPlan("drop", frames=frozenset({4})),
            FaultPlan("truncate", frames=frozenset({6})),
            FaultPlan("dup", frames=frozenset({8})),
            FaultPlan("reorder", frames=frozenset({9})),
        ])
        for _ in range(5):
            epoch, matches = coord.extract(sess.key, docs)
            assert matches.to_set() == want, "faults changed matches"
        assert faulty.faults_injected >= 4, "faults did not fire"
        # corrupt/drop/truncate are invisible to the server (damaged
        # inbound frames are dropped) — only the client retry recovers
        assert coord.handles["t0"].endpoint.frames_retried >= 3
        assert coord.handles["t0"].alive
        # the dedupe cache kept every retried request exactly-once
        assert servers["t0"].requests_served == 6


def test_remote_verify_through_service():
    """ExtractionService with the verify pool behind the transport."""
    from repro.serving import BatcherConfig, ExtractionService

    corpus = _dense_corpus()
    cache, sess = _session(corpus, "prefix")
    docs = _var_docs(corpus, 80, n=6)
    with _thread_cluster(n=2) as (coord, servers):
        coord.add_session(sess)
        svc = ExtractionService(
            cache,
            batcher_config=BatcherConfig(max_batch_docs=3,
                                         max_delay_s=0.0),
            overlap=False,
            remote_verify=coord,
        )
        with svc:
            for i, d in enumerate(docs):
                assert svc.submit(i, d, sess.key) is not None
            svc.drain()
        got = svc.results_set()
        assert got == one_shot_reference(sess, docs)
        assert len(got) > 0, "remote-verify parity check is vacuous"
        assert sum(s.lane_batches_served for s in servers.values()) > 0
        assert all(s.requests_served == 0 for s in servers.values())


# ------------------------------------------------- e2e parity (TCP replicas)
@pytest.mark.slow
def test_multiprocess_cluster_parity_all_schemes():
    """The acceptance gate: >= 2 replicas over TCP sockets (one thread
    and device each, in this process — a JAX process never starts
    children that need its chip), one session per scheme, live
    replicated deltas mid-stream, every response bit-identical to
    ``one_shot_reference`` at its admitted epoch."""
    threads, endpoints = launch_local_cluster(
        ["p0", "p1"], endpoint_timeout=300.0
    )
    try:
        metrics = ServingMetrics()
        coord = ClusterCoordinator(endpoints, metrics=metrics,
                                   hold_epochs=True)
        total = 0
        for si, scheme in enumerate(SCHEMES):
            corpus = _dense_corpus(seed=90 + si, num_entities=16)
            _cache, sess = _session(corpus, scheme)
            rng = np.random.default_rng(91 + si)
            coord.add_session(sess)
            for round_i in range(2):
                docs = _var_docs(corpus, 92 + round_i, n=3)
                epoch, matches = coord.extract(sess.key, docs,
                                               timeout=300.0)
                got = matches.to_set()
                assert got == one_shot_reference(sess, docs, epoch=epoch), \
                    f"{scheme}: drift at epoch {epoch}"
                total += len(got)
                if round_i == 0:
                    coord.apply_delta(
                        sess.key,
                        random_delta(rng, sess.current_state.version, 48),
                    )
        assert total > 0, "multi-process parity check is vacuous"
        stats = coord.poll_stats()
        assert sum(r["remote"].get("requests_served", 0)
                   for r in stats.values() if r["remote"]) == 8
        assert "replicas" in metrics.summary()
    finally:
        coord.shutdown()
        for t in threads:
            t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert [t.error for t in threads] == [None] * len(threads)

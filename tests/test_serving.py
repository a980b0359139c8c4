"""Serving subsystem: parity with one-shot execute + component contracts.

The central contract: the micro-batched probe/verify service must
produce results **bit-identical** to a one-shot ``eejoin.execute`` over
the same documents — for every supported scheme, at every geometry
(uneven lengths, PAD-only docs, zero-survivor batches, multiple live
dictionary sessions), with overlap on and off. Windows never span
documents and lane merging is exact, so micro-batching must be
invisible in the results.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.cost_model import OBJ_JOB, SideCost
from repro.core.eejoin import EEJoinConfig
from repro.core.plan import Plan, PlanSide
from repro.data.synth import make_corpus
from repro.extraction import engine as E
from repro.serving import (
    AdmissionQueue,
    BatcherConfig,
    ExtractionService,
    MicroBatcher,
    SessionCache,
    make_pools,
    one_shot_reference,
    pipeline_schedule,
)
from repro.serving.queue import ExtractRequest
from repro.serving.session import pure_plan, dictionary_fingerprint

GAMMA = 0.8


def _config(**kw):
    kw.setdefault("gamma", GAMMA)
    kw.setdefault("max_candidates", 4096)
    kw.setdefault("result_capacity", 8192)
    kw.setdefault("use_kernel", True)
    return EEJoinConfig(**kw)


def _var_docs(corpus, seed, n=None, min_len=8):
    """Uneven-length documents cut from corpus rows (seeded)."""
    rng = np.random.default_rng(seed)
    D, T = corpus.doc_tokens.shape
    n = n or D
    lens = rng.integers(min_len, T + 1, size=n)
    return [np.asarray(corpus.doc_tokens[i % D, : lens[i]]) for i in range(n)]


def _one_shot(sess, docs):
    """Reference: one-shot execute over the same docs (row i = doc_id i)."""
    return one_shot_reference(sess, docs)


def _serve(cache, sess, docs, overlap, batch_docs=3, session_keys=None):
    svc = ExtractionService(
        cache,
        pools=make_pools(),
        batcher_config=BatcherConfig(max_batch_docs=batch_docs,
                                     max_delay_s=0.0),
        overlap=overlap,
    )
    with svc:
        for i, d in enumerate(docs):
            key = session_keys[i] if session_keys else sess.key
            assert svc.submit(i, d, key) is not None
        svc.drain()
    return svc


# ------------------------------------------------------ scheme x overlap
@pytest.mark.parametrize("scheme", ["word", "prefix", "lsh", "variant"])
@pytest.mark.parametrize("overlap", [True, False])
def test_serving_parity_all_schemes(small_corpus, scheme, overlap):
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan(scheme))
    docs = _var_docs(small_corpus, seed=5)
    svc = _serve(cache, sess, docs, overlap)
    want = _one_shot(sess, docs)
    assert svc.results_set() == want
    assert len(want) > 0, "vacuous parity"
    assert svc.metrics.completed == len(docs)


@pytest.mark.parametrize("overlap", [True, False])
def test_batch_timeline_stamps_are_ordered(small_corpus, overlap):
    """Every batch record is stamped on the service's clock in pipeline
    order, and its requests are done after its verify ends."""
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    svc = _serve(cache, sess, _var_docs(small_corpus, seed=7), overlap)
    done = {}
    for req in svc.completed:
        done.setdefault(req.batch_id, []).append(req)
    assert len(svc.metrics.batch_records) == len(done) > 1
    for r in svc.metrics.batch_records:
        stamps = [r["flush_s"], r["probe_start_s"], r["probe_end_s"],
                  r["verify_start_s"], r["verify_end_s"]]
        stamps += [req.done_s for req in done[r["batch_id"]]]
        assert stamps == sorted(stamps), stamps
        for req in done[r["batch_id"]]:
            assert req.arrival_s <= r["flush_s"]


def test_serving_parity_hybrid_plan(small_corpus):
    """A split plan (index head + ssjoin tail) served batch by batch."""
    z = SideCost(0, 0, 0, 0, 0, 0, 0, 0, 0)
    plan = Plan(12, PlanSide("index", "prefix"), PlanSide("ssjoin", "prefix"),
                OBJ_JOB, 0.0, z, z, 0)
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(), plan=plan)
    docs = _var_docs(small_corpus, seed=6)
    svc = _serve(cache, sess, docs, overlap=True)
    assert svc.results_set() == _one_shot(sess, docs)


# ------------------------------------------------------------ geometries
def test_serving_parity_pad_only_docs(small_corpus):
    """All-PAD documents flow through and contribute nothing."""
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    docs = _var_docs(small_corpus, seed=7)
    docs[1] = np.zeros(17, np.int32)  # PAD-only rows of differing lengths
    docs[4] = np.zeros(40, np.int32)
    svc = _serve(cache, sess, docs, overlap=True)
    got = svc.results_set()
    assert got == _one_shot(sess, docs)
    assert not any(d in (1, 4) for (d, _p, _l, _e) in got)


def test_serving_zero_survivor_batches(small_corpus):
    """An impossible gamma prunes everything: served stream stays empty
    (and every request still completes)."""
    cache = SessionCache()
    # gamma=1.0 + an unrelated vocabulary region: no candidate verifies
    rng = np.random.default_rng(8)
    docs = [rng.integers(400, 512, size=rng.integers(8, 33)).astype(np.int32)
            for _ in range(6)]
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    svc = _serve(cache, sess, docs, overlap=True, batch_docs=2)
    assert svc.results_set() == _one_shot(sess, docs)
    assert svc.metrics.completed == len(docs)


@pytest.mark.parametrize("overlap", [True, False])
def test_serving_multi_dictionary_sessions(small_corpus, zipf_corpus, overlap):
    """Two dictionaries live in one cache; interleaved requests route to
    their own session and each stream matches its own one-shot run."""
    cache = SessionCache()
    s1 = cache.get_or_create(small_corpus.dictionary, _config(),
                             plan=pure_plan("prefix"))
    s2 = cache.get_or_create(zipf_corpus.dictionary, _config(),
                             plan=pure_plan("word"))
    assert s1.key != s2.key and len(cache) == 2
    docs = _var_docs(small_corpus, seed=9, n=10)
    keys = [s1.key if i % 2 == 0 else s2.key for i in range(len(docs))]
    svc = _serve(cache, s1, docs, overlap, session_keys=keys)
    for sess in (s1, s2):
        mine = [i for i, k in enumerate(keys) if k == sess.key]
        want = {
            (mine[r], p, l, e)
            for (r, p, l, e) in _one_shot(sess, [docs[i] for i in mine])
        }
        got = {
            m for req in svc.completed if req.session_key == sess.key
            for m in ((d, p, l, e) for (d, p, l, e, _s) in req.matches)
        }
        assert got == want


# ---------------------------------------------------------------- batcher
def test_batcher_deterministic_flush_ordering():
    """Same admission stream -> identical batch composition run-to-run."""
    def run():
        b = MicroBatcher(BatcherConfig(max_batch_docs=2, max_delay_s=0.01,
                                       buckets=(16, 32)))
        rng = np.random.default_rng(3)
        out = []
        for i in range(9):
            tokens = rng.integers(1, 99, size=rng.integers(4, 33))
            b.add(ExtractRequest(req_id=i, doc_id=i,
                                 tokens=tokens.astype(np.int32),
                                 session_key="s", arrival_s=0.001 * i))
            out.extend(b.poll(now=0.001 * i))
        out.extend(b.flush_all(now=1.0))
        assert b.pending() == 0
        return [(x.bucket, [r.req_id for r in x.reqs]) for x in out]

    first, second = run(), run()
    assert first == second
    assert sorted(r for _, rs in first for r in rs) == list(range(9))


def test_batcher_full_bin_flushes_before_deadline():
    b = MicroBatcher(BatcherConfig(max_batch_docs=2, max_delay_s=100.0,
                                   buckets=(8,)))
    for i in range(2):
        b.add(ExtractRequest(req_id=i, doc_id=i,
                             tokens=np.ones(4, np.int32),
                             session_key="s", arrival_s=0.0))
    out = b.poll(now=0.0)  # full, despite an unexpired deadline
    assert len(out) == 1 and out[0].rows == 2
    assert out[0].occupancy == 1.0


def test_batcher_deadline_flush_partial_bin():
    b = MicroBatcher(BatcherConfig(max_batch_docs=8, max_delay_s=0.01,
                                   buckets=(8,)))
    b.add(ExtractRequest(req_id=0, doc_id=0, tokens=np.ones(3, np.int32),
                         session_key="s", arrival_s=0.0))
    assert b.poll(now=0.005) == []  # deadline not reached
    out = b.poll(now=0.02)
    assert len(out) == 1 and out[0].rows == 1


def test_batcher_rejects_oversized_docs():
    cfg = BatcherConfig(buckets=(16, 32))
    with pytest.raises(ValueError, match="largest length bucket"):
        cfg.bucket_for(33)


def test_batch_geometry_reuses_plan_shards():
    from repro.extraction.sharded import plan_shards

    b = MicroBatcher(BatcherConfig(max_batch_docs=4, max_delay_s=0.0,
                                   buckets=(8,), tile_docs=2))
    for i in range(3):
        b.add(ExtractRequest(req_id=i, doc_id=i, tokens=np.ones(5, np.int32),
                             session_key="s", arrival_s=0.0))
    (batch,) = b.poll(now=0.0)
    assert batch.spec == plan_shards(3, 1, shard_docs=3, tile_docs=2)
    assert batch.spec.tiles_per_shard == 2


# ------------------------------------------------------------------ queue
def test_admission_queue_sheds_when_full():
    q = AdmissionQueue(capacity=2)
    assert q.try_submit(0, [1, 2], "s", 0.0) is not None
    assert q.try_submit(1, [1, 2], "s", 0.0) is not None
    assert q.try_submit(2, [1, 2], "s", 0.0) is None  # admission control
    assert (q.accepted, q.rejected, q.depth()) == (2, 1, 2)
    taken = q.take()
    assert [r.req_id for r in taken] == [0, 1]  # FIFO, ids in admission order
    assert q.try_submit(3, [1, 2], "s", 0.0) is not None


def test_service_blocking_submit_backpressure(small_corpus):
    """block=True: the producer drains the queue itself (inline tick)
    instead of being rejected, so every doc lands despite a tiny
    admission queue."""
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    docs = _var_docs(small_corpus, seed=10, n=6)
    svc = ExtractionService(
        cache,
        batcher_config=BatcherConfig(max_batch_docs=2, max_delay_s=0.0),
        queue_capacity=2,
        overlap=False,
    )
    with svc:
        for i, d in enumerate(docs):
            assert svc.submit(i, d, sess.key, block=True) is not None
        svc.drain()
    assert svc.metrics.rejected == 0  # backpressure, not shedding
    assert svc.results_set() == _one_shot(sess, docs)


def test_service_worker_failure_surfaces_not_hangs(small_corpus, monkeypatch):
    """A raising stage must fail the batch's requests and re-raise from
    drain() — never wedge the queue joins."""
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    svc = ExtractionService(cache, overlap=True)
    monkeypatch.setattr(
        ExtractionService, "_probe_batch",
        lambda self, batch: (_ for _ in ()).throw(RuntimeError("boom")),
    )
    svc.start()
    for i in range(3):
        svc.submit(i, np.ones(8, np.int32), sess.key)
    with pytest.raises(RuntimeError, match="failed in the serving"):
        svc.drain()
    svc.stop()  # errors were reported once; stop must not hang or re-raise
    assert all(r.done and r.error and "boom" in r.error for r in svc.completed)
    assert len(svc.completed) == 3
    assert sess.inflight == 0  # failure path still unpins the session


def test_session_cache_never_evicts_busy_sessions(small_corpus, zipf_corpus):
    cache = SessionCache(max_sessions=1)
    busy = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    busy.inflight = 2  # admitted work in flight
    with pytest.raises(RuntimeError, match="in-flight"):
        cache.get_or_create(zipf_corpus.dictionary, _config(),
                            plan=pure_plan("prefix"))
    busy.inflight = 0
    cache.get_or_create(zipf_corpus.dictionary, _config(),
                        plan=pure_plan("prefix"))  # idle -> evictable
    assert cache.evictions == 1


def test_service_rejects_unknown_session(small_corpus):
    cache = SessionCache()
    cache.get_or_create(small_corpus.dictionary, _config(),
                        plan=pure_plan("prefix"))
    svc = ExtractionService(cache)
    with pytest.raises(ValueError, match="unknown session"):
        svc.submit(0, np.ones(4, np.int32), "nope")


# ---------------------------------------------------------------- session
def test_session_cache_hits_and_lru_eviction(small_corpus, zipf_corpus):
    cache = SessionCache(max_sessions=1)
    s1 = cache.get_or_create(small_corpus.dictionary, _config(),
                             plan=pure_plan("prefix"))
    again = cache.get_or_create(small_corpus.dictionary, _config(),
                                plan=pure_plan("prefix"))
    assert again is s1 and cache.hits == 1
    cache.get_or_create(zipf_corpus.dictionary, _config(),
                        plan=pure_plan("prefix"))
    assert cache.evictions == 1 and len(cache) == 1
    with pytest.raises(KeyError):
        cache.get(s1.key)


def test_session_fingerprint_covers_dictionary_and_config(small_corpus):
    d = small_corpus.dictionary
    base = dictionary_fingerprint(d, _config())
    assert base == dictionary_fingerprint(d, _config())
    assert base != dictionary_fingerprint(d, _config(gamma=0.9))
    import dataclasses as dc

    mutated = dc.replace(d, tokens=d.tokens.copy())
    mutated.tokens[0, 0] += 1
    assert base != dictionary_fingerprint(mutated, _config())


def test_session_plan_choice_from_stats(small_corpus):
    """sample_docs -> statistics -> §5 plan search (no forced plan)."""
    cache = SessionCache()
    sess = cache.get_or_create(
        small_corpus.dictionary, _config(),
        sample_docs=small_corpus.doc_tokens[:4],
    )
    assert sess.plan.evaluations > 0  # came out of the search
    docs = _var_docs(small_corpus, seed=11, n=6)
    svc = _serve(cache, sess, docs, overlap=True)
    assert svc.results_set() == _one_shot(sess, docs)


def test_session_requires_kernel_path(small_corpus):
    with pytest.raises(ValueError, match="use_kernel=True"):
        SessionCache().get_or_create(
            small_corpus.dictionary, _config(use_kernel=False)
        )


# ------------------------------------------------------- params validation
def test_extract_params_kernel_compact_requires_kernel():
    with pytest.raises(ValueError, match="requires use_kernel=True"):
        E.ExtractParams(gamma=GAMMA, scheme="prefix", kernel_compact=True)


def test_extract_params_kernel_compact_tracks_use_kernel():
    assert E.ExtractParams(gamma=GAMMA, scheme="prefix").kernel_compact is False
    assert E.ExtractParams(
        gamma=GAMMA, scheme="prefix", use_kernel=True
    ).kernel_compact is True
    p = E.ExtractParams(gamma=GAMMA, scheme="prefix", use_kernel=True,
                        kernel_compact=False)
    assert p.kernel_compact is False  # explicit opt-out stays honoured


@pytest.mark.parametrize("kw,match", [
    (dict(scheme="bogus"), "not a known"),
    (dict(gamma=0.0), "must be in"),
    (dict(gamma=1.5), "must be in"),
    (dict(max_candidates=0), "must be positive"),
    (dict(result_capacity=-1), "must be positive"),
])
def test_extract_params_validation_messages(kw, match):
    base = dict(gamma=GAMMA, scheme="prefix")
    base.update(kw)
    with pytest.raises(ValueError, match=match):
        E.ExtractParams(**base)


def test_fused_probe_compact_rejects_bad_args():
    from repro.kernels import ops

    docs = jnp.ones((2, 8), jnp.int32)
    with pytest.raises(ValueError, match="positive"):
        ops.fused_probe_compact(docs, None, 4, 0)
    with pytest.raises(ValueError, match="max_len <= 32"):
        ops.fused_probe_compact(docs, None, 33, 16)


def test_check_flat_index_space_message():
    with pytest.raises(ValueError, match="overflows int32"):
        E.check_flat_index_space(1 << 20, 1 << 10, 32)


# ------------------------------------------------------- shard_lane format
def test_shard_lane_public_wire_format(small_corpus):
    """shard_lane is the public wire unit: [1, NC] int32 ascending flat
    indices, -1 sentinel, count may exceed NC; the variant-key payload
    slot is None for schemes without fused keys."""
    from repro.core.filter import build_ish_filter
    from repro.extraction.sharded import shard_lane

    d = small_corpus.dictionary
    f = build_ish_filter(d, GAMMA)
    flt = (jnp.asarray(f.bits), f.num_bits, f.num_hashes)
    params = E.ExtractParams(gamma=GAMMA, scheme="prefix", use_kernel=True,
                             max_candidates=64)
    docs = jnp.asarray(small_corpus.doc_tokens)
    lane, count, keys = shard_lane(docs, 0, d.max_len, flt, params)
    assert keys is None, "prefix scheme ships no key payload"
    lane, count = np.asarray(lane), np.asarray(count)
    assert lane.shape == (1, 64) and lane.dtype == np.int32
    assert count.shape == (1,) and count.dtype == np.int32
    valid = lane[0][lane[0] >= 0]
    assert (np.diff(valid) > 0).all(), "lane indices must ascend"
    assert (lane[0][len(valid):] == -1).all(), "-1 sentinel pads the tail"
    assert int(count[0]) >= len(valid)


def test_shard_lane_variant_key_payload(small_corpus):
    """Fused variant keys ride the wire as a [1, NC, 2] uint32 payload,
    0 in padded slots, bit-identical to window_variant_key over the
    lane's decoded windows."""
    from repro.core.filter import build_ish_filter
    from repro.core.variants import window_variant_key
    from repro.extraction.sharded import shard_lane

    d = small_corpus.dictionary
    f = build_ish_filter(d, GAMMA)
    flt = (jnp.asarray(f.bits), f.num_bits, f.num_hashes)
    params = E.ExtractParams(gamma=GAMMA, scheme="variant", use_kernel=True,
                             max_candidates=64)
    docs = jnp.asarray(small_corpus.doc_tokens)
    lane, count, keys = shard_lane(docs, 0, d.max_len, flt, params)
    assert keys is not None and keys.shape == (1, 64, 2)
    assert np.asarray(keys).dtype == np.uint32
    lane, keys = np.asarray(lane)[0], np.asarray(keys)[0]
    T, L = docs.shape[1], d.max_len
    docs_np = np.asarray(docs)
    for j, flat in enumerate(lane):
        if flat < 0:
            assert keys[j, 0] == 0 and keys[j, 1] == 0
            continue
        dd, rem = divmod(flat, T * L)
        p, l = divmod(rem, L)
        win = np.zeros((1, L), np.int32)
        n = min(l + 1, T - p)
        win[0, :n] = docs_np[dd, p:p + n]
        k1, k2 = window_variant_key(win, win != 0, xp=np)
        assert keys[j, 0] == k1[0] and keys[j, 1] == k2[0]


@pytest.mark.parametrize("overlap", [True, False])
def test_serving_parity_variant_adaptive_lanes(small_corpus, overlap):
    """Serving with adaptive two-pass lane sizing (probe stage is eager,
    so the per-batch count pass runs live) must stay bit-identical to
    the one-shot reference for the fused variant scheme."""
    cache = SessionCache()
    sess = cache.get_or_create(
        small_corpus.dictionary,
        _config(adaptive_lanes=True),
        plan=pure_plan("variant"),
    )
    docs = _var_docs(small_corpus, seed=41, n=7)
    svc = _serve(cache, sess, docs, overlap)
    assert svc.results_set() == _one_shot(sess, docs)


# ---------------------------------------------------------------- metrics
def test_pipeline_schedule_overlap_beats_serial():
    ready = [0.0, 0.0, 0.0, 0.0]
    probe = [1.0] * 4
    verify = [1.0] * 4
    _, over = pipeline_schedule(ready, probe, verify, overlap=True)
    _, serial = pipeline_schedule(ready, probe, verify, overlap=False)
    assert over[-1] == pytest.approx(5.0)  # 1 fill + 4 drains
    assert serial[-1] == pytest.approx(8.0)  # 4 * (probe + verify)
    assert (np.asarray(over) <= np.asarray(serial)).all()


def test_pipeline_schedule_double_buffer_backpressure():
    """A slow verify stage must stall probe once both buffers fill."""
    ready = [0.0] * 4
    probe = [0.1] * 4
    verify = [10.0] * 4
    pd, _ = pipeline_schedule(ready, probe, verify, overlap=True,
                              buffer_depth=2)
    # probe 2 can run ahead, probe 3 waits for verify to start batch 1
    assert pd[2] < 1.0 and pd[3] > 10.0


def test_metrics_percentiles_and_summary():
    from repro.serving.metrics import ServingMetrics, percentiles

    p = percentiles(np.arange(1, 101))
    assert p["p50"] == pytest.approx(50.5) and p["p99"] == pytest.approx(99.01)
    m = ServingMetrics()
    m.record_submit(True, depth=3, now=0.0)
    m.record_submit(False, depth=4, now=0.1)
    m.record_batch(batch_id=0, rows=2, occupancy=0.5, n_lanes=1,
                   flush_s=0.0, probe_s=0.01, verify_s=0.02,
                   probe_start_s=0.0, probe_end_s=0.01,
                   verify_start_s=0.01, verify_end_s=0.03)
    m.record_done(latency_s=0.5, done_s=1.0)
    s = m.summary()
    assert s["submitted"] == 2 and s["rejected"] == 1
    assert s["queue_depth_max"] == 4 and s["occupancy_mean"] == 0.5
    assert s["docs_per_s"] == pytest.approx(2.0)


# ------------------------------------------------- live updates (epochs)
def _delta_from(corpus, rows, tombstones=()):
    """Delta whose adds copy dictionary rows (so they match documents)."""
    from repro.updates import DictionaryDelta

    d = corpus.dictionary
    added = tuple(
        tuple(int(t) for t in d.tokens[i, : int(d.lengths[i])]) for i in rows
    )
    return DictionaryDelta(added=added, tombstones=tuple(tombstones))


def test_apply_delta_hot_swap_parity(small_corpus):
    """Serve, hot-swap a delta, serve again: each stream matches its own
    epoch's one-shot reference; the session was never evicted."""
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    docs = _var_docs(small_corpus, seed=31, n=6)
    svc = _serve(cache, sess, docs, overlap=True)
    ref0 = _one_shot(sess, docs)
    assert svc.results_set() == ref0

    e0 = sess.epoch
    sess.apply_delta(_delta_from(small_corpus, rows=(2, 3), tombstones=(0,)),
                     force_action="absorb")
    assert sess.epoch == e0 + 1
    assert cache.misses == 1 and len(cache) == 1  # same session object
    svc2 = _serve(cache, sess, docs, overlap=True)
    ref1 = _one_shot(sess, docs)
    assert svc2.results_set() == ref1
    assert ref1 != ref0  # the tombstone (a matching entity) changed results
    recs = svc2.metrics.batch_records
    assert all(r["epoch"] == e0 + 1 for r in recs)


@pytest.mark.parametrize("action", ["absorb", "compact"])
def test_epoch_swap_under_inflight_load(small_corpus, action):
    """The no-drain swap contract: batches dispatched before apply_delta
    finish on the old epoch, later ones on the new — and every request's
    results equal a single-epoch run of its own batch's epoch."""
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    docs = _var_docs(small_corpus, seed=32, n=10)
    svc = ExtractionService(
        cache, pools=make_pools(),
        batcher_config=BatcherConfig(max_batch_docs=2, max_delay_s=0.0),
        overlap=True,
    )
    e0 = sess.epoch
    with svc:
        for i in range(5):
            assert svc.submit(i, docs[i], sess.key) is not None
        svc.tick()  # dispatch: these batches are pinned to epoch e0
        ref0 = one_shot_reference(sess, docs, epoch=e0)
        state = sess.apply_delta(
            _delta_from(small_corpus, rows=(1, 2), tombstones=(0, 4)),
            force_action=action,
        )
        e1 = sess.epoch
        assert e1 > e0 and state is sess.current_state
        ref1 = one_shot_reference(sess, docs, epoch=e1)
        for i in range(5, 10):
            assert svc.submit(i, docs[i], sess.key) is not None
        svc.drain()
    epoch_of = {r["batch_id"]: r["epoch"] for r in svc.metrics.batch_records}
    seen = set()
    for req in svc.completed:
        ep = epoch_of[req.batch_id]
        seen.add(ep)
        ref = ref0 if ep == e0 else ref1
        want = {(d, p, l, e) for (d, p, l, e) in ref if d == req.doc_id}
        got = {(d, p, l, e) for (d, p, l, e, _s) in req.matches}
        assert got == want, (req.doc_id, ep)
    assert seen == {e0, e1}  # the swap really straddled in-flight work
    assert ref0 != ref1
    # old epoch state was garbage-collected once its last batch finished
    assert sorted(sess.epochs) == [e1]


def test_session_cache_summary_counters(small_corpus, zipf_corpus):
    from repro.serving import session_cache_summary
    from repro.serving.session import dictionary_fingerprint as fp

    cache = SessionCache()
    s1 = cache.get_or_create(small_corpus.dictionary, _config(),
                             plan=pure_plan("prefix"))
    cache.get_or_create(small_corpus.dictionary, _config(),
                        plan=pure_plan("prefix"))  # hit
    s2 = cache.get_or_create(zipf_corpus.dictionary, _config(),
                             plan=pure_plan("word"))
    s1.apply_delta(_delta_from(small_corpus, rows=(1,)),
                   force_action="absorb")
    cs = session_cache_summary(cache)
    assert cs["sessions"] == 2
    assert cs["hits"] == 1 and cs["misses"] == 2 and cs["evictions"] == 0
    row = cs["per_session"][s1.key]
    assert row["epoch"] == 1 and row["open_segments"] == 1
    assert row["maintenance"] == ["absorb"]
    assert cs["per_session"][s2.key]["epoch"] == 0


# ------------------------------------------------- per-session quotas
def test_session_quota_sheds_and_counts(small_corpus):
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    docs = _var_docs(small_corpus, seed=33, n=6)
    svc = ExtractionService(
        cache, pools=make_pools(),
        batcher_config=BatcherConfig(max_batch_docs=8, max_delay_s=0.0),
        session_quota=2,
    )
    with svc:
        got = [svc.submit(i, d, sess.key) for i, d in enumerate(docs)]
        assert sum(r is not None for r in got) == 2  # quota, not capacity
        assert svc.queue.rejected_quota == 4
        assert svc.queue.rejected_by_session[sess.key] == 4
        assert svc.metrics.rejected_quota == 4
        assert svc.metrics.rejected_by_session[sess.key] == 4
        svc.drain()
        # quota frees as batches complete: admission works again
        assert svc.submit(99, docs[0], sess.key) is not None
        svc.drain()
    assert svc.metrics.completed == 3


def test_session_quota_block_backpressures(small_corpus):
    """block=True at the quota: the producer waits for completions
    instead of shedding, and every request is eventually served. The
    nonzero flush deadline is load-bearing: quota-limited requests sit
    in a *non-full* bin, so the retry loop's ticks must read a fresh
    clock for the deadline flush to ever fire (the livelock regression
    this test pins down)."""
    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    docs = _var_docs(small_corpus, seed=34, n=8)
    svc = ExtractionService(
        cache, pools=make_pools(),
        batcher_config=BatcherConfig(max_batch_docs=3, max_delay_s=0.002),
        session_quota=2,
    )
    with svc:
        for i, d in enumerate(docs):
            assert svc.submit(i, d, sess.key, block=True) is not None
        svc.drain()
    assert svc.metrics.completed == len(docs)
    assert svc.results_set() == _one_shot(sess, docs)


def test_quota_validation():
    with pytest.raises(ValueError, match="session_quota"):
        AdmissionQueue(4, session_quota=0)


# ----------------------------------------- steady-state lane sizing
def test_steady_state_lane_sizing_amortises_count_pass(small_corpus):
    """Same (session, bucket) batches: exactly one count pass per plan
    side, every later batch sizes off the previous batch's counts —
    with results identical to the one-shot reference."""
    cache = SessionCache()
    sess = cache.get_or_create(
        small_corpus.dictionary,
        _config(adaptive_lanes=True),
        plan=pure_plan("prefix"),
    )
    T = small_corpus.doc_tokens.shape[1]
    # equal-length docs -> one length bucket -> one (side, bucket) hint
    docs = [np.asarray(small_corpus.doc_tokens[i % 8, :T])
            for i in range(12)]
    svc = ExtractionService(
        cache, pools=make_pools(),
        batcher_config=BatcherConfig(max_batch_docs=3, max_delay_s=0.0),
    )
    with svc:
        for i, d in enumerate(docs):
            assert svc.submit(i, d, sess.key) is not None
            svc.tick()
        svc.drain()
    assert svc.results_set() == _one_shot(sess, docs)
    sizing = svc.metrics.lane_sizing
    n_sides = len(sess.current_state.sides)
    n_batches = svc.metrics.batches
    assert sizing.get("count_pass", 0) == n_sides  # first batch only
    assert sizing.get("fixed", 0) == 0
    total = sum(sizing.values())
    assert total == n_batches * n_sides
    assert sizing.get("hint", 0) + sizing.get("refit", 0) == total - n_sides
    # the hint cache holds the measured per-tile max for this epoch
    (key, (epoch, tile_max)), *_ = sess.lane_hints.items()
    assert epoch == sess.epoch and tile_max >= 0


def test_shard_lane_steady_matches_shard_lane(small_corpus):
    """Hint, count-pass, undersized-hint (refit) and fixed sizing all
    produce the wire lane of the reference shard_lane."""
    from repro.extraction.sharded import shard_lane, shard_lane_steady

    docs = jnp.asarray(small_corpus.doc_tokens[:8])
    d = small_corpus.dictionary
    from repro.core.filter import build_ish_filter

    f = build_ish_filter(d, GAMMA)
    flt = (jnp.asarray(f.bits), f.num_bits, f.num_hashes)
    base = E.ExtractParams(gamma=GAMMA, scheme="prefix", use_kernel=True,
                           max_candidates=1024)
    ref_lane, ref_n, _ = shard_lane(docs, 0, d.max_len, flt, base, 4)

    adaptive = E.ExtractParams(gamma=GAMMA, scheme="prefix", use_kernel=True,
                               max_candidates=1024, adaptive_lanes=True)
    lane, n, _k, tile_max, sizing = shard_lane_steady(
        docs, 0, d.max_len, flt, adaptive, 4)
    assert sizing == "count_pass" and tile_max >= 0
    np.testing.assert_array_equal(np.asarray(lane), np.asarray(ref_lane))
    assert int(n[0]) == int(ref_n[0])

    lane, n, _k, tm2, sizing = shard_lane_steady(
        docs, 0, d.max_len, flt, adaptive, 4, width_hint=tile_max)
    assert sizing == "hint" and tm2 == tile_max
    np.testing.assert_array_equal(np.asarray(lane), np.asarray(ref_lane))

    if tile_max > 1:  # an undersized hint must refit, never truncate
        lane, n, _k, tm3, sizing = shard_lane_steady(
            docs, 0, d.max_len, flt, adaptive, 4, width_hint=1)
        assert sizing in ("refit", "hint")  # hint iff rounding covered it
        np.testing.assert_array_equal(np.asarray(lane), np.asarray(ref_lane))
        assert int(n[0]) == int(ref_n[0])

    lane, n, _k, tm, sizing = shard_lane_steady(
        docs, 0, d.max_len, flt, base, 4)
    assert sizing == "fixed" and tm == -1
    np.testing.assert_array_equal(np.asarray(lane), np.asarray(ref_lane))


def test_rebuild_resets_drift_baseline(small_corpus):
    """A drift-triggered rebuild must re-anchor the density baseline:
    otherwise every later delta re-measures against the stale value and
    pays a full re-plan per update."""
    import dataclasses

    cache = SessionCache()
    sess = cache.get_or_create(small_corpus.dictionary, _config(),
                               plan=pure_plan("prefix"))
    # plant a far-off baseline so the first sampled delta drifts
    sess.cost_params = dataclasses.replace(
        sess.cost_params, lane_density=1e-6
    )
    sample = small_corpus.doc_tokens[:8]
    sess.apply_delta(_delta_from(small_corpus, rows=(1,)),
                     sample_docs=sample)
    assert sess.maintenance_log[-1]["action"] == "rebuild"
    assert sess.cost_params.lane_density > 1e-6  # baseline re-anchored
    # same sample again: density unchanged vs the new baseline -> no
    # drift, no second rebuild
    sess.apply_delta(_delta_from(small_corpus, rows=(2,)),
                     sample_docs=sample)
    assert sess.maintenance_log[-1]["action"] != "rebuild"


# --------------------------------------------- metrics edge cases (PR 7)
def test_metrics_percentiles_empty_and_single_sample():
    import math

    from repro.serving.metrics import percentiles

    empty = percentiles([])
    assert set(empty) == {"p50", "p95", "p99"}
    assert all(math.isnan(v) for v in empty.values())
    single = percentiles([0.25])
    assert all(v == pytest.approx(0.25) for v in single.values())


def test_metrics_summary_before_any_batch():
    """summary() on a fresh collector: zero counters, NaN-not-crash
    for every rate and percentile, and empty replan telemetry."""
    import math

    from repro.serving.metrics import ServingMetrics

    s = ServingMetrics().summary()
    assert s["submitted"] == s["rejected"] == s["completed"] == 0
    assert s["batches"] == 0 and s["queue_depth_max"] == 0
    assert s["occupancy_mean"] == 0.0 and s["probe_s_mean"] == 0.0
    assert math.isnan(s["latency_p50_s"]) and math.isnan(s["docs_per_s"])
    assert s["replans"] == 0 and s["replan_events"] == []
    # and the whole report stays JSON-serializable
    import json

    json.dumps(s)


def test_metrics_record_stream_partial_dicts():
    """Partial / empty / unknown-keyed stream dicts fold cleanly, and
    the same dict fans out to an attached ObservedStats."""
    from repro.serving import ObservedStats
    from repro.serving.metrics import ServingMetrics

    m = ServingMetrics()
    obs = ObservedStats(capacity=4)
    m.record_stream({})
    m.record_stream({"tiles_streamed": 3}, observed=obs)
    m.record_stream({"streamed_launches": 1,
                     "some_future_counter": 9}, observed=obs)
    assert m.tiles_streamed == 3
    assert m.streamed_launches == 1 and m.checkpoint_writes == 0
    assert obs.stream_counters["tiles_streamed"] == 3
    assert obs.stream_counters["some_future_counter"] == 9


def test_metrics_record_replan_counters():
    from repro.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.record_replan({"reason": "doc_len", "swapped": False})
    m.record_replan({"reason": "lane_density", "swapped": True, "epoch": 1})
    s = m.summary()
    assert s["replans"] == 2 and s["replan_swaps"] == 1
    assert [e["reason"] for e in s["replan_events"]] == [
        "doc_len", "lane_density"]
    # summary deep-copies events: mutating the report must not leak back
    s["replan_events"][0]["reason"] = "mutated"
    assert m.replan_events[0]["reason"] == "doc_len"

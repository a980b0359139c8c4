"""Corpus-scale streaming: the single-launch DMA megakernel, host spill
streaming, and resumable shard merges.

Bit-parity contracts: the streamed launch (``ExtractParams(streamed=True)``
— in-kernel tile loop over a double-buffered DMA pipeline) must reproduce
the per-tile launch loop (``streamed=False``) bit for bit at every
geometry and scheme, ``spill_filter_compact`` over a file-backed corpus
must match the resident drivers field for field, and a killed-then-resumed
checkpointed run must merge to identical results. The HBM model's
``streamed=`` term and the checkpoint-manifest guard are pinned here too.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.dictionary import PAD
from repro.extraction import engine as E
from repro.extraction import sharded as SH

GAMMA = 0.8
CAND_KEYS = ("win_tokens", "win_valid", "doc", "pos", "length",
             "n_survive", "overflow")


def _docs(rng, D, T, vocab=2048, pad_frac=0.15):
    d = rng.integers(1, vocab, size=(D, T)).astype(np.int32)
    d[rng.random((D, T)) < pad_frac] = PAD
    return jnp.asarray(d)


def _filter(rng, num_bits=1 << 14, density=0.3):
    w = (rng.random((num_bits // 32, 32)) < density).astype(np.uint32)
    bits = (w << np.arange(32, dtype=np.uint32)).sum(axis=1).astype(np.uint32)
    return (jnp.asarray(bits), num_bits, 3)


def _params(**kw):
    kw.setdefault("gamma", GAMMA)
    kw.setdefault("scheme", "prefix")
    kw.setdefault("use_kernel", True)
    return E.ExtractParams(**kw)


def _assert_cands_equal(got, want):
    for k in CAND_KEYS:
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(want[k]), err_msg=k
        )
    if "variant_keys" in want:
        assert "variant_keys" in got
        for a, b in zip(got["variant_keys"], want["variant_keys"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------- streamed vs per-tile parity
@pytest.mark.parametrize("scheme", ["word", "prefix", "lsh", "variant"])
def test_streamed_parity_schemes(scheme):
    """Every scheme, uneven geometry: streamed launch == per-tile loop.

    D=13 with tile_docs=3 forces a PAD-padded ragged tail AND a tile
    height that is not a multiple of the NC-derived sub-tile height, so
    the streamed buffer layout must replay the per-tile padding exactly.
    """
    rng = np.random.default_rng(21)
    docs = _docs(rng, 13, 96)
    flt = _filter(rng)
    per_tile = _params(scheme=scheme, max_candidates=256, streamed=False)
    streamed = _params(scheme=scheme, max_candidates=256, streamed=True)
    want = SH.stream_filter_compact(docs, 7, flt, per_tile, tile_docs=3)
    got = SH.stream_filter_compact(docs, 7, flt, streamed, tile_docs=3)
    _assert_cands_equal(got, want)
    assert int(want["n_survive"]) > 0  # non-vacuous
    # and both match the unsharded single-call fast path
    _assert_cands_equal(got, E.fused_filter_compact(
        docs, 7, flt, _params(scheme=scheme, max_candidates=256)))


def test_streamed_parity_raw_lanes():
    """stream_probe_tiles: raw counts/cands lanes identical bit for bit."""
    rng = np.random.default_rng(22)
    docs = _docs(rng, 16, 64)
    flt = _filter(rng)
    base = dict(max_candidates=128)
    c0, x0, _ = SH.stream_probe_tiles(
        docs, 6, flt, _params(streamed=False, **base), tile_docs=4)
    c1, x1, _ = SH.stream_probe_tiles(
        docs, 6, flt, _params(streamed=True, **base), tile_docs=4)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(x0), np.asarray(x1))


def test_streamed_parity_pad_only_tiles():
    """Tiles made entirely of PAD rows stream to empty lanes."""
    rng = np.random.default_rng(23)
    d = np.array(_docs(rng, 16, 64))
    d[4:12] = PAD  # tiles 1 and 2 (tile_docs=4) are PAD-only
    docs = jnp.asarray(d)
    flt = _filter(rng)
    want = SH.stream_filter_compact(
        docs, 6, flt, _params(max_candidates=256, streamed=False), tile_docs=4)
    got = SH.stream_filter_compact(
        docs, 6, flt, _params(max_candidates=256, streamed=True), tile_docs=4)
    _assert_cands_equal(got, want)
    assert not np.isin(np.asarray(got["doc"]), np.arange(4, 12)).any()


def test_streamed_parity_zero_survivors():
    """Empty filter: every chunk streams through, none emits."""
    rng = np.random.default_rng(24)
    docs = _docs(rng, 12, 64, pad_frac=0.0)
    flt = (jnp.zeros(((1 << 12) // 32,), jnp.uint32), 1 << 12, 3)
    want = SH.stream_filter_compact(
        docs, 6, flt, _params(max_candidates=128, streamed=False), tile_docs=4)
    got = SH.stream_filter_compact(
        docs, 6, flt, _params(max_candidates=128, streamed=True), tile_docs=4)
    _assert_cands_equal(got, want)
    assert int(got["n_survive"]) == 0


def test_streamed_count_only_parity():
    """The count-only sizing pass streams to identical per-tile counts."""
    rng = np.random.default_rng(25)
    docs = _docs(rng, 13, 96)
    flt = _filter(rng)
    want = SH.stream_tile_counts(
        docs, 7, flt, _params(max_candidates=128, streamed=False), tile_docs=3)
    got = SH.stream_tile_counts(
        docs, 7, flt, _params(max_candidates=128, streamed=True), tile_docs=3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_streamed_adaptive_lanes_parity():
    """Two-pass adaptive sizing composes with the streamed launch."""
    rng = np.random.default_rng(26)
    docs = _docs(rng, 13, 96)
    flt = _filter(rng)
    want = SH.stream_filter_compact(
        docs, 7, flt,
        _params(max_candidates=256, adaptive_lanes=True, streamed=False),
        tile_docs=3)
    got = SH.stream_filter_compact(
        docs, 7, flt,
        _params(max_candidates=256, adaptive_lanes=True, streamed=True),
        tile_docs=3)
    _assert_cands_equal(got, want)


def test_resolve_streamed_auto_and_override():
    assert SH.resolve_streamed(_params(), 1) is False  # 1 tile: nothing to overlap
    assert SH.resolve_streamed(_params(), 2) is True
    assert SH.resolve_streamed(_params(streamed=True), 1) is True
    assert SH.resolve_streamed(_params(streamed=False), 8) is False


def test_streamed_requires_kernel_compact():
    with pytest.raises(ValueError, match="kernel_compact"):
        _params(streamed=True, use_kernel=False)
    with pytest.raises(ValueError, match="kernel_compact"):
        _params(streamed=True, kernel_compact=False)


# ------------------------------------------------- shard-geometry planning
def test_plan_shards_clamps_tiny_corpus():
    """Requested shard/tile heights larger than the corpus clamp down:
    a 3-doc corpus with shard_docs=64 must not pad every tile to 64."""
    spec = SH.plan_shards(3, n_workers=1, shard_docs=64, tile_docs=64)
    assert spec.shard_docs == 3
    assert spec.tile_docs == 3
    assert spec.num_shards == 1
    assert spec.tiles_per_shard == 1


def test_plan_shards_clamped_parity():
    """The clamped tiny-corpus geometry still merges bit-identically."""
    rng = np.random.default_rng(27)
    docs = _docs(rng, 3, 64)
    flt = _filter(rng)
    params = _params(max_candidates=128)
    want = E.fused_filter_compact(docs, 6, flt, params)
    got = SH.sharded_filter_compact(
        docs, 6, flt, params, shard_docs=64, tile_docs=64
    )
    _assert_cands_equal(got, want)


def test_shard_docs_for_budget_rule():
    """budget // (T * 4 * 2) rows, tile-aligned, floored at one tile."""
    T, td = 128, 64
    budget = 512 * T * 4 * 2  # exactly 512 rows of double-buffer headroom
    assert SH.shard_docs_for_budget(10_000, T, budget, td) == 512
    # non-tile-aligned budget rounds down to whole tiles
    assert SH.shard_docs_for_budget(10_000, T, budget - 1, td) == 512 - td
    # a budget below one tile still streams tile-sized shards
    assert SH.shard_docs_for_budget(10_000, T, 1, td) == td
    # and clamps to the corpus
    assert SH.shard_docs_for_budget(100, T, budget, td) == 100


# --------------------------------------------------- HBM model direction
def test_hbm_model_streamed_direction():
    from repro.kernels.fused_probe import hbm_bytes_fused, hbm_bytes_unfused

    kw = dict(kernel_compact=True)
    per_tile = hbm_bytes_fused(4096, 128, 8, 256, 4, False, **kw)
    streamed = hbm_bytes_fused(4096, 128, 8, 256, 4, False, streamed=True,
                               **kw)
    # streaming elides exactly the packed-bitmap write (D * T * 4 bytes)
    assert per_tile - streamed == 4096 * 128 * 4
    # the unfused pipeline has no term to elide (documented no-op)
    assert (hbm_bytes_unfused(4096, 128, 8, 256, 1, streamed=True)
            == hbm_bytes_unfused(4096, 128, 8, 256, 1))
    # streamed modeling without the lane epilogue is a contradiction
    with pytest.raises(ValueError, match="kernel_compact"):
        hbm_bytes_fused(4096, 128, 8, 256, 4, False, streamed=True)


def test_lane_plan_streamed_delta():
    from repro.core.cost_model import lane_plan

    plan = lane_plan(4096, 128, 8, 256, density=0.01, streamed=True)
    base = lane_plan(4096, 128, 8, 256, density=0.01, streamed=False)
    assert plan["streamed"] is True and base["streamed"] is False
    assert base["bytes_streamed_delta"] == 0  # per-tile plan: nothing elided
    best = min(plan["bytes_fixed"], plan["bytes_two_pass"])
    best_base = min(base["bytes_fixed"], base["bytes_two_pass"])
    assert plan["bytes_streamed_delta"] == best_base - best > 0


# ------------------------------------------------ checkpoints: resumable
def test_sharded_checkpoint_roundtrip(tmp_path):
    """Full run writes per-shard lanes; rerun loads them (no re-probe)."""
    rng = np.random.default_rng(31)
    docs = _docs(rng, 13, 96)
    flt = _filter(rng)
    params = _params(max_candidates=256)
    ckpt = str(tmp_path / "lanes")
    want = E.fused_filter_compact(docs, 7, flt, params)
    s1: dict = {}
    got = SH.sharded_filter_compact(
        docs, 7, flt, params, shard_docs=4, tile_docs=2,
        checkpoint_dir=ckpt, stream_stats=s1,
    )
    _assert_cands_equal(got, want)
    assert s1["checkpoint_writes"] == 4 and s1.get("checkpoint_hits", 0) == 0
    s2: dict = {}
    again = SH.sharded_filter_compact(
        docs, 7, flt, params, shard_docs=4, tile_docs=2,
        checkpoint_dir=ckpt, stream_stats=s2,
    )
    _assert_cands_equal(again, want)
    assert s2["checkpoint_hits"] == 4 and s2.get("checkpoint_writes", 0) == 0
    assert s2.get("streamed_launches", 0) == 0  # nothing re-probed


def test_spill_kill_then_resume(tmp_path):
    """Interrupted corpus job resumes from the last finished shard to
    bit-identical merged results."""
    rng = np.random.default_rng(32)
    docs = np.array(_docs(rng, 24, 64))
    flt = _filter(rng)
    params = _params(max_candidates=256)
    corpus = SH.MemmapCorpus.write(str(tmp_path / "corpus"), docs)
    ckpt = str(tmp_path / "lanes")
    want = E.fused_filter_compact(jnp.asarray(docs), 6, flt, params)

    with pytest.raises(RuntimeError, match="simulated interruption"):
        SH.spill_filter_compact(
            corpus, 6, flt, params, shard_docs=4, tile_docs=2,
            checkpoint_dir=ckpt, fail_after_shards=2,
        )
    # the kill left exactly 2 whole shard checkpoints (atomic writes)
    done = sorted(p.name for p in (tmp_path / "lanes").glob("shard_*.npz"))
    assert done == ["shard_000000.npz", "shard_000001.npz"]

    stats: dict = {}
    got = SH.spill_filter_compact(
        corpus, 6, flt, params, shard_docs=4, tile_docs=2,
        checkpoint_dir=ckpt, stream_stats=stats,
    )
    _assert_cands_equal(got, want)
    assert stats["checkpoint_hits"] == 2  # resumed, not re-probed
    assert stats["checkpoint_writes"] == 4  # only the remaining shards


def test_spill_kill_then_resume_variant(tmp_path):
    """Variant key payloads survive the checkpoint round trip."""
    rng = np.random.default_rng(33)
    docs = np.array(_docs(rng, 16, 64))
    flt = _filter(rng)
    params = _params(scheme="variant", max_candidates=256)
    corpus = SH.MemmapCorpus.write(str(tmp_path / "corpus"), docs)
    ckpt = str(tmp_path / "lanes")
    want = E.fused_filter_compact(jnp.asarray(docs), 6, flt, params)
    with pytest.raises(RuntimeError, match="simulated interruption"):
        SH.spill_filter_compact(
            corpus, 6, flt, params, shard_docs=4, tile_docs=2,
            checkpoint_dir=ckpt, fail_after_shards=1,
        )
    got = SH.spill_filter_compact(
        corpus, 6, flt, params, shard_docs=4, tile_docs=2,
        checkpoint_dir=ckpt,
    )
    _assert_cands_equal(got, want)
    assert "variant_keys" in got


def test_checkpoint_manifest_mismatch(tmp_path):
    """Resuming against a different filter/geometry raises, never merges."""
    rng = np.random.default_rng(34)
    docs = _docs(rng, 12, 64)
    flt = _filter(rng)
    params = _params(max_candidates=128)
    ckpt = str(tmp_path / "lanes")
    SH.sharded_filter_compact(docs, 6, flt, params, shard_docs=4,
                              tile_docs=2, checkpoint_dir=ckpt)
    other = _filter(np.random.default_rng(99))
    with pytest.raises(ValueError, match="manifest mismatch"):
        SH.sharded_filter_compact(docs, 6, other, params, shard_docs=4,
                                  tile_docs=2, checkpoint_dir=ckpt)
    with pytest.raises(ValueError, match="manifest mismatch"):
        SH.sharded_filter_compact(docs, 6, flt, params, shard_docs=6,
                                  tile_docs=2, checkpoint_dir=ckpt)
    # reset=True wipes the stale lanes and starts the new job over
    corpus = SH.MemmapCorpus(tokens=np.array(docs))
    got = SH.spill_filter_compact(
        corpus, 6, other, params, shard_docs=4, tile_docs=2,
        checkpoint_dir=ckpt, reset_checkpoints=True,
    )
    _assert_cands_equal(got, E.fused_filter_compact(docs, 6, other, params))


# -------------------------------------------------- spill streaming
def test_spill_over_budget_parity(tmp_path):
    """A corpus over the device budget completes via spill streaming and
    matches the resident path field for field."""
    rng = np.random.default_rng(35)
    docs = np.array(_docs(rng, 32, 64))
    flt = _filter(rng)
    params = _params(max_candidates=256)
    corpus = SH.MemmapCorpus.write(str(tmp_path / "corpus"), docs)
    # budget holds 4 docs of double-buffered staging: 8 shards of 4
    budget = 4 * 64 * 4 * 2
    stats: dict = {}
    got = SH.spill_filter_compact(
        corpus, 6, flt, params, device_budget_bytes=budget, tile_docs=2,
        stream_stats=stats,
    )
    _assert_cands_equal(got, E.fused_filter_compact(
        jnp.asarray(docs), 6, flt, params))
    # 8 staged shard regions of 4x64 int32 each crossed the host buffer
    assert stats["spill_bytes_staged"] == 8 * 4 * 64 * 4
    assert stats["streamed_launches"] == 8  # one launch per shard
    assert stats["tiles_streamed"] > 8


def test_spill_accepts_host_arrays(tmp_path):
    """Plain in-memory [D, T] arrays duck-type as a MemmapCorpus."""
    rng = np.random.default_rng(36)
    docs = np.array(_docs(rng, 10, 64))
    flt = _filter(rng)
    params = _params(max_candidates=128)
    got = SH.spill_filter_compact(docs, 6, flt, params, shard_docs=3,
                                  tile_docs=2)
    _assert_cands_equal(got, E.fused_filter_compact(
        jnp.asarray(docs), 6, flt, params))


def test_memmap_corpus_roundtrip(tmp_path):
    rng = np.random.default_rng(37)
    docs = np.array(_docs(rng, 6, 32))
    c = SH.MemmapCorpus.write(str(tmp_path / "c"), docs)
    assert (c.rows, c.seq_len) == (6, 32)
    np.testing.assert_array_equal(np.asarray(c.tokens), docs)
    reopened = SH.MemmapCorpus.open(str(tmp_path / "c"))
    np.testing.assert_array_equal(np.asarray(reopened.tokens), docs)


def test_spill_requires_epilogue():
    rng = np.random.default_rng(38)
    docs = np.array(_docs(rng, 8, 64))
    with pytest.raises(ValueError, match="in-kernel compaction"):
        SH.spill_filter_compact(
            docs, 6, _filter(rng), _params(use_kernel=False),
        )


# ------------------------------------------------ serving observability
def test_shard_lane_steady_stream_stats():
    """Multi-tile serving probes report their streamed-launch counters."""
    rng = np.random.default_rng(39)
    docs = _docs(rng, 12, 64)
    flt = _filter(rng)
    stats: dict = {}
    lane, n, keys, tile_max, sizing = SH.shard_lane_steady(
        docs, 0, 6, flt, _params(max_candidates=128), tile_docs=4,
        stream_stats=stats,
    )
    assert sizing == "fixed" and keys is None
    assert stats["streamed_launches"] == 1
    assert stats["tiles_streamed"] >= 3
    # pinning streamed=False leaves the counters untouched
    stats2: dict = {}
    lane2, n2, *_ = SH.shard_lane_steady(
        docs, 0, 6, flt, _params(max_candidates=128, streamed=False),
        tile_docs=4, stream_stats=stats2,
    )
    assert stats2 == {}
    np.testing.assert_array_equal(np.asarray(lane), np.asarray(lane2))
    assert int(n[0]) == int(n2[0])


def test_serving_metrics_record_stream():
    from repro.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.record_stream({"streamed_launches": 2, "tiles_streamed": 8,
                     "checkpoint_writes": 1})
    m.record_stream({})  # per-tile probe: a no-op
    m.record_stream({"streamed_launches": 1, "tiles_streamed": 4,
                     "checkpoint_hits": 3})
    s = m.summary()
    assert s["streamed_launches"] == 3
    assert s["tiles_streamed"] == 12
    assert s["checkpoint_writes"] == 1
    assert s["checkpoint_hits"] == 3


# ------------------------------------------------ end-to-end: eejoin
def test_execute_corpus_equals_execute(small_corpus, tmp_path):
    from repro.core.cost_model import OBJ_JOB, SideCost
    from repro.core.eejoin import EEJoinConfig, EEJoinOperator
    from repro.core.plan import Plan, PlanSide

    c = small_corpus
    op = EEJoinOperator(
        c.dictionary,
        EEJoinConfig(gamma=GAMMA, max_candidates=4096, result_capacity=8192,
                     use_kernel=True,
                     device_budget_bytes=3 * c.doc_tokens.shape[1] * 4 * 2),
    )
    z = SideCost(0, 0, 0, 0, 0, 0, 0, 0, 0)
    plan = Plan(0, PlanSide("index", "prefix"), PlanSide("ssjoin", "prefix"),
                OBJ_JOB, 0.0, z, z, 0)
    prepared = op.prepare(plan)
    docs = jnp.asarray(c.doc_tokens)
    want = op.execute(prepared, docs).to_set()
    corpus = SH.MemmapCorpus.write(str(tmp_path / "corpus"), c.doc_tokens)
    stats: dict = {}
    got = op.execute_corpus(
        prepared, corpus, tile_docs=2,
        checkpoint_dir=str(tmp_path / "ckpt"), stream_stats=stats,
    ).to_set()
    assert got == want
    assert stats["checkpoint_writes"] > 0
    # resume path: a second call consumes the checkpoints, same matches
    again = op.execute_corpus(
        prepared, corpus, tile_docs=2, checkpoint_dir=str(tmp_path / "ckpt"),
    ).to_set()
    assert again == want


def test_execute_corpus_kill_then_resume(small_corpus, tmp_path):
    from repro.core.cost_model import OBJ_JOB, SideCost
    from repro.core.eejoin import EEJoinConfig, EEJoinOperator
    from repro.core.plan import Plan, PlanSide

    c = small_corpus
    op = EEJoinOperator(
        c.dictionary,
        EEJoinConfig(gamma=GAMMA, max_candidates=4096, result_capacity=8192,
                     use_kernel=True),
    )
    z = SideCost(0, 0, 0, 0, 0, 0, 0, 0, 0)
    plan = Plan(0, PlanSide("ssjoin", "prefix"), PlanSide("ssjoin", "variant"),
                OBJ_JOB, 0.0, z, z, 0)
    prepared = op.prepare(plan)
    want = op.execute(prepared, jnp.asarray(c.doc_tokens)).to_set()
    corpus = SH.MemmapCorpus.write(str(tmp_path / "corpus"), c.doc_tokens)
    ckpt = str(tmp_path / "ckpt")
    with pytest.raises(RuntimeError, match="simulated interruption"):
        op.execute_corpus(prepared, corpus, shard_docs=2, tile_docs=2,
                          checkpoint_dir=ckpt, fail_after_shards=2)
    got = op.execute_corpus(prepared, corpus, shard_docs=2, tile_docs=2,
                            checkpoint_dir=ckpt).to_set()
    assert got == want


@pytest.mark.parametrize("head,tail", [
    (("index", "prefix"), ("ssjoin", "prefix")),
    (("ssjoin", "prefix"), ("ssjoin", "variant")),
])
def test_execute_corpus_counts_survivors_and_windows(small_corpus, tmp_path,
                                                     head, tail):
    """``stream_stats`` gets each side's true filter survivors (the set
    entries of ``survival_mask``) and its D x T x L window grid."""
    from repro.core.cost_model import OBJ_JOB, SideCost
    from repro.core.eejoin import EEJoinConfig, EEJoinOperator
    from repro.core.plan import Plan, PlanSide

    c = small_corpus
    op = EEJoinOperator(
        c.dictionary,
        EEJoinConfig(gamma=GAMMA, max_candidates=4096, result_capacity=8192,
                     use_kernel=True),
    )
    z = SideCost(0, 0, 0, 0, 0, 0, 0, 0, 0)
    plan = Plan(6, PlanSide(*head), PlanSide(*tail), OBJ_JOB, 0.0, z, z, 0)
    prepared = op.prepare(plan)
    assert len(prepared.sides) == 2
    corpus = SH.MemmapCorpus.write(str(tmp_path / "corpus"), c.doc_tokens)
    stats: dict = {}
    op.execute_corpus(prepared, corpus, shard_docs=4, tile_docs=2,
                      stream_stats=stats)
    D, T = c.doc_tokens.shape
    L = prepared.max_entity_len
    docs = jnp.asarray(c.doc_tokens)
    want = sum(int(np.asarray(E.survival_mask(docs, L, side.flt)[1]).sum())
               for side in prepared.sides)
    assert 0 < stats["survivors"] == want
    assert stats["windows"] == 2 * D * T * L

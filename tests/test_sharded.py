"""Sharded streaming driver vs the unsharded fused fast path.

Bit-parity contracts: ``sharded_filter_compact`` (and the single-device
``stream_filter_compact``) must reproduce ``engine.fused_filter_compact``
field for field at every shard geometry — uneven shard sizes, PAD-only
shards, zero-survivor shards, more shards than devices — and the
in-kernel compaction epilogue must agree with both the legacy XLA
bitmap compaction (``kernel_compact=False``) and the fully unfused
``compact_candidates`` reference, so neither fallback can rot.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.dictionary import PAD
from repro.extraction import engine as E
from repro.extraction import sharded as SH
from repro.extraction.results import select_from_tiles, select_nonzero
from repro.launch.mesh import make_extraction_mesh

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


# ------------------------------------------------------- shard geometries
@pytest.mark.parametrize("shard_docs,tile_docs", [(4, 2), (5, 3), (13, 2), (3, 1)])
def test_sharded_parity_uneven_shards(shard_docs, tile_docs):
    """D=13 never divides evenly: ragged tails at every geometry."""
    rng = np.random.default_rng(11)
    docs = _docs(rng, 13, 96)
    flt = _filter(rng)
    params = _params(max_candidates=256)
    want = E.fused_filter_compact(docs, 7, flt, params)
    got = SH.sharded_filter_compact(
        docs, 7, flt, params, shard_docs=shard_docs, tile_docs=tile_docs
    )
    _assert_cands_equal(got, want)
    assert int(want["n_survive"]) > 0  # non-vacuous


def test_sharded_parity_pad_only_shards():
    """A shard made entirely of PAD rows must contribute nothing."""
    rng = np.random.default_rng(12)
    d = np.array(_docs(rng, 16, 64))
    d[4:8] = PAD  # shard 1 (shard_docs=4) is PAD-only
    docs = jnp.asarray(d)
    flt = _filter(rng)
    params = _params(max_candidates=256)
    want = E.fused_filter_compact(docs, 6, flt, params)
    got = SH.sharded_filter_compact(docs, 6, flt, params, shard_docs=4, tile_docs=2)
    _assert_cands_equal(got, want)
    assert not np.isin(np.asarray(got["doc"]), [4, 5, 6, 7]).any()


def test_sharded_parity_zero_survivor_shards():
    """Empty Bloom filter: every shard streams, none emits candidates."""
    rng = np.random.default_rng(13)
    docs = _docs(rng, 10, 64, pad_frac=0.0)
    flt = (jnp.zeros(((1 << 12) // 32,), jnp.uint32), 1 << 12, 3)
    params = _params(max_candidates=128)
    want = E.fused_filter_compact(docs, 6, flt, params)
    got = SH.sharded_filter_compact(docs, 6, flt, params, shard_docs=3, tile_docs=2)
    _assert_cands_equal(got, want)
    assert int(got["n_survive"]) == 0
    assert not bool(np.asarray(got["win_valid"]).any())


def test_sharded_parity_more_shards_than_devices():
    """shard count > device count: the wave loop must round-robin."""
    rng = np.random.default_rng(14)
    docs = _docs(rng, 12, 64)
    flt = _filter(rng)
    params = _params(max_candidates=256)
    mesh = make_extraction_mesh(1)  # 1 CPU device, 6 shards -> 6 waves
    want = E.fused_filter_compact(docs, 6, flt, params)
    got = SH.sharded_filter_compact(
        docs, 6, flt, params, mesh=mesh, shard_docs=2, tile_docs=2
    )
    _assert_cands_equal(got, want)


def test_sharded_overflow_surfaced():
    """Saturated filter + tiny capacity: overflow counts must agree."""
    rng = np.random.default_rng(15)
    docs = _docs(rng, 8, 48, pad_frac=0.0)
    flt = (jnp.full(((1 << 12) // 32,), 0xFFFFFFFF, jnp.uint32), 1 << 12, 3)
    params = _params(max_candidates=64)
    want = E.fused_filter_compact(docs, 5, flt, params)
    got = SH.sharded_filter_compact(docs, 5, flt, params, shard_docs=3, tile_docs=1)
    _assert_cands_equal(got, want)
    assert int(got["overflow"]) > 0


# ------------------------------------------------------- tile streaming
@pytest.mark.parametrize("tile_docs", [1, 3, 64])
def test_stream_filter_compact_parity(tile_docs):
    rng = np.random.default_rng(16)
    docs = _docs(rng, 11, 80)
    flt = _filter(rng)
    params = _params(max_candidates=256)
    want = E.fused_filter_compact(docs, 6, flt, params)
    got = SH.stream_filter_compact(docs, 6, flt, params, tile_docs=tile_docs)
    _assert_cands_equal(got, want)


# ------------------------------------------------------- compaction paths
def test_kernel_epilogue_vs_legacy_xla_compaction():
    """The in-kernel epilogue (kernel_compact=True), the legacy XLA
    bitmap compaction (False) and the fully unfused reference must all
    agree — the fallback paths stay exercised and correct."""
    rng = np.random.default_rng(17)
    docs = _docs(rng, 12, 96)
    flt = _filter(rng)
    epi = E.fused_filter_compact(docs, 7, flt, _params(max_candidates=512))
    legacy = E.fused_filter_compact(
        docs, 7, flt, _params(max_candidates=512, kernel_compact=False)
    )
    base, surv = E.survival_mask(docs, 7, flt, use_kernel=False)
    unfused = E.compact_candidates(base, surv, 512)
    _assert_cands_equal(epi, legacy)
    _assert_cands_equal(epi, unfused)


def test_sharded_delegates_legacy_compaction():
    """kernel_compact=False has no lanes to shard over: the driver must
    fall back to the (legacy) single-call path with identical output."""
    rng = np.random.default_rng(18)
    docs = _docs(rng, 9, 64)
    flt = _filter(rng)
    params = _params(max_candidates=128, kernel_compact=False)
    want = E.fused_filter_compact(docs, 6, flt, params)
    got = SH.sharded_filter_compact(docs, 6, flt, params, shard_docs=4)
    _assert_cands_equal(got, want)


def _assert_variant_equal(got, want):
    np.testing.assert_array_equal(np.asarray(got["sigs"]),
                                  np.asarray(want["sigs"]), err_msg="sigs")
    for i, (a, b) in enumerate(zip(got["variant_keys"],
                                   want["variant_keys"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"variant_keys[{i}]")


# ------------------------------------------- variant keys + adaptive lanes
@pytest.mark.parametrize("shard_docs,tile_docs", [(4, 2), (5, 3), (13, 2), (3, 1)])
@pytest.mark.parametrize("adaptive", [False, True])
def test_sharded_variant_keys_parity(shard_docs, tile_docs, adaptive):
    """Fused variant keys must ride the shard/tile lanes bit-identically
    to the unsharded fused path, one-pass and adaptive two-pass alike."""
    rng = np.random.default_rng(31)
    docs = _docs(rng, 13, 96)
    flt = _filter(rng)
    want = E.fused_filter_compact(
        docs, 7, flt, _params(scheme="variant", max_candidates=256)
    )
    params = _params(scheme="variant", max_candidates=256,
                     adaptive_lanes=adaptive)
    got = SH.sharded_filter_compact(
        docs, 7, flt, params, shard_docs=shard_docs, tile_docs=tile_docs
    )
    _assert_cands_equal(got, want)
    _assert_variant_equal(got, want)
    assert int(want["n_survive"]) > 0  # non-vacuous


@pytest.mark.parametrize("shard_docs,tile_docs", [(4, 2), (5, 3), (3, 1)])
@pytest.mark.parametrize("scheme", ["prefix", "variant"])
def test_sharded_adaptive_two_pass_parity(shard_docs, tile_docs, scheme):
    """Two-pass (count wave -> narrow emit) vs one-pass lane bit-identity
    at every shard geometry, sequential and mesh paths."""
    rng = np.random.default_rng(32)
    docs = _docs(rng, 11, 80)
    flt = _filter(rng)
    one = SH.sharded_filter_compact(
        docs, 6, flt, _params(scheme=scheme, max_candidates=256),
        shard_docs=shard_docs, tile_docs=tile_docs,
    )
    adaptive = _params(scheme=scheme, max_candidates=256, adaptive_lanes=True)
    two = SH.sharded_filter_compact(
        docs, 6, flt, adaptive, shard_docs=shard_docs, tile_docs=tile_docs
    )
    _assert_cands_equal(two, one)
    mesh = make_extraction_mesh(1)
    two_mesh = SH.sharded_filter_compact(
        docs, 6, flt, adaptive, mesh=mesh,
        shard_docs=shard_docs, tile_docs=tile_docs,
    )
    _assert_cands_equal(two_mesh, one)
    if scheme == "variant":
        _assert_variant_equal(two, one)
        _assert_variant_equal(two_mesh, one)


def test_stream_tile_counts_matches_emit_counts():
    """The count-only sizing pass must reproduce the emit pass's
    per-sub-tile counts exactly (same grid, same SMEM accumulation)."""
    rng = np.random.default_rng(33)
    docs = _docs(rng, 10, 64)
    flt = _filter(rng)
    params = _params(max_candidates=128)
    counts = SH.stream_tile_counts(docs, 6, flt, params, tile_docs=3)
    emitted, _, _ = SH.stream_probe_tiles(docs, 6, flt, params, tile_docs=3)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(emitted))


def test_streaming_rejects_forced_lsh_kernel_sigs():
    """kernel_sigs=True + lsh cannot be honored on the streaming path
    (dense band sigs have no lane to ride): it must raise, not silently
    store-and-discard the kernel's sig tensor."""
    rng = np.random.default_rng(36)
    docs = _docs(rng, 8, 48)
    flt = _filter(rng)
    params = _params(scheme="lsh", max_candidates=64, kernel_sigs=True)
    with pytest.raises(ValueError, match="streaming path"):
        SH.stream_filter_compact(docs, 5, flt, params, tile_docs=4)
    with pytest.raises(ValueError, match="streaming path"):
        SH.sharded_filter_compact(docs, 5, flt, params, shard_docs=4)
    # unforced lsh streams fine: band sigs recomputed post-compaction
    ok = SH.stream_filter_compact(
        docs, 5, flt, _params(scheme="lsh", max_candidates=64), tile_docs=4
    )
    assert "sigs" not in ok


def test_shard_lane_adaptive_traced_requires_width():
    """Tracing shard_lane with adaptive_lanes and no explicit width must
    raise (the sizing host sync cannot run inside a trace), never fall
    back silently to worst-case lanes."""
    import jax

    rng = np.random.default_rng(34)
    docs = _docs(rng, 8, 48)
    flt = _filter(rng)
    params = _params(max_candidates=64, adaptive_lanes=True)
    with pytest.raises(ValueError, match="lane_width"):
        jax.jit(
            lambda d: SH.shard_lane(d, 0, 5, flt, params)
        )(docs)


@pytest.mark.parametrize("G,C,capacity", [(1, 8, 8), (4, 16, 16), (7, 32, 16)])
def test_select_from_tiles_matches_select_nonzero(G, C, capacity):
    """Lane merge == flat select_nonzero over the concatenated bitmap
    whenever lane width >= capacity (the driver's invariant)."""
    rng = np.random.default_rng(G * C + capacity)
    span = C  # elements per tile
    mask = rng.random(G * span) < 0.4
    counts = np.array([mask[g * span:(g + 1) * span].sum() for g in range(G)],
                      dtype=np.int32)
    cands = np.full((G, C), -1, dtype=np.int32)
    for g in range(G):
        idx = np.nonzero(mask[g * span:(g + 1) * span])[0] + g * span
        cands[g, :min(len(idx), C)] = idx[:C]
    got_idx, got_ok, got_n = select_from_tiles(
        jnp.asarray(counts), jnp.asarray(cands), capacity
    )
    want_idx, want_ok = select_nonzero(jnp.asarray(mask), capacity)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    assert int(got_n) == int(mask.sum())


def test_check_lanes_refuses_incomplete_narrow_lanes():
    from repro.extraction.results import check_lanes

    check_lanes(np.array([4, 2], np.int32), 4, 16)   # complete
    check_lanes(np.array([40, 2], np.int32), 16, 16)  # full width
    with pytest.raises(ValueError, match="holds only 4 of a tile's 5"):
        check_lanes(np.array([5, 2], np.int32), 4, 16)


def test_select_from_tiles_complete_tiles_narrow_lanes():
    """With complete tiles (every tile's survivors fit its lane), a
    narrow C < capacity merge must equal the full-width merge."""
    from repro.extraction.results import gather_from_tiles

    rng = np.random.default_rng(35)
    G, C, capacity = 5, 4, 16
    counts = rng.integers(0, C + 1, size=G).astype(np.int32)  # <= C each
    wide = np.full((G, capacity), -1, dtype=np.int32)
    payload = np.zeros((G, capacity, 2), dtype=np.uint32)
    base = 0
    for g in range(G):
        idx = base + np.sort(rng.choice(100, size=counts[g], replace=False))
        wide[g, :counts[g]] = idx
        payload[g, :counts[g]] = rng.integers(
            1, 2**32, size=(counts[g], 2), dtype=np.uint32
        )
        base += 100
    narrow = wide[:, :C]
    want_idx, want_ok, want_n = select_from_tiles(
        jnp.asarray(counts), jnp.asarray(wide), capacity
    )
    got_idx, got_ok, got_n = select_from_tiles(
        jnp.asarray(counts), jnp.asarray(narrow), capacity
    )
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    assert int(got_n) == int(want_n)
    # payload gather picks the same survivors as the index merge
    pay = gather_from_tiles(
        jnp.asarray(counts), jnp.asarray(payload[:, :C]), capacity
    )
    want_pay = gather_from_tiles(
        jnp.asarray(counts), jnp.asarray(payload), capacity
    )
    np.testing.assert_array_equal(np.asarray(pay), np.asarray(want_pay))


# ------------------------------------------------------- end-to-end
@pytest.mark.parametrize("scheme", ["prefix", "lsh", "variant"])
def test_execute_sharded_equals_execute(small_corpus, scheme):
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
    plan = Plan(0, PlanSide("index", "prefix"), PlanSide("ssjoin", scheme),
                OBJ_JOB, 0.0, z, z, 0)
    prepared = op.prepare(plan)
    docs = jnp.asarray(c.doc_tokens)
    want = op.execute(prepared, docs).to_set()
    got = op.execute_sharded(prepared, docs, shard_docs=3, tile_docs=2).to_set()
    assert got == want and len(want) > 0


def test_execute_adaptive_config_equals_fixed(small_corpus):
    """EEJoinConfig(adaptive_lanes=True) must flow through prepare into
    every side's ExtractParams and change nothing in the results."""
    from repro.core.cost_model import OBJ_JOB, SideCost
    from repro.core.eejoin import EEJoinConfig, EEJoinOperator
    from repro.core.plan import Plan, PlanSide

    c = small_corpus
    z = SideCost(0, 0, 0, 0, 0, 0, 0, 0, 0)
    plan = Plan(0, PlanSide("ssjoin", "variant"), PlanSide("ssjoin", "variant"),
                OBJ_JOB, 0.0, z, z, 0)
    docs = jnp.asarray(c.doc_tokens)
    outs = {}
    for adaptive in (False, True):
        op = EEJoinOperator(
            c.dictionary,
            EEJoinConfig(gamma=GAMMA, max_candidates=4096,
                         result_capacity=8192, use_kernel=True,
                         adaptive_lanes=adaptive),
        )
        prepared = op.prepare(plan)
        assert prepared.sides[0].params.adaptive_lanes is adaptive
        outs[adaptive] = op.execute(prepared, docs).to_set()
    assert outs[True] == outs[False] and len(outs[True]) > 0

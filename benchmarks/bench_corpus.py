"""Corpus-scale streaming bench: the single-launch DMA megakernel vs the
per-tile launch loop, plus host spill streaming with resumable shard
merges.

Methodology: both launch modes run the identical sub-tile grid and
epilogue (parity is asserted field-for-field before any timing, CI
fails on drift), so the interpret-mode wall-clock difference measures
the launch restructuring — one ``pallas_call`` whose in-kernel tile
loop replaces ``tiles_per_shard`` separate kernel dispatches. On a real
TPU the same structure additionally overlaps tile i+1's HBM->VMEM DMA
with tile i's recurrence; that claim is carried by the analytic HBM
model (``hbm_bytes_fused(streamed=True)`` — the packed-bitmap round
trip disappears) whose *direction* is asserted against the measured
direction in-bench, and by the guarded real-device leg that records the
first non-interpret validation when a TPU backend is present.

Row schema (see docs/benchmarks.md):
    corpus_streamed — per geometry: per_tile_s / streamed_s / speedup,
        tiles, tiles_per_s, modeled HBM bytes both ways + bytes_saved.
    corpus_spill — over-budget corpus through ``spill_filter_compact``:
        shards, bytes_staged, checkpoint writes/hits for the
        kill-then-resume leg, tiles_per_s end to end.
"""
from __future__ import annotations

import tempfile

import numpy as np

import jax
import jax.numpy as jnp

from repro.extraction import engine as E
from repro.extraction import sharded as SH
from repro.kernels import fused_probe as fp

from benchmarks.common import emit, timeit

GAMMA = 0.8
L = 8
PARITY_KEYS = ("win_tokens", "win_valid", "doc", "pos", "length",
               "n_survive", "overflow")

#: wall-clock floor the streamed launch must clear over the per-tile
#: loop at >= MIN_TILES tiles per shard (the PR's perf acceptance bar)
MIN_SPEEDUP = 1.3
MIN_TILES = 4


def _filter(rng, num_bits=1 << 18, density=0.1):
    w = (rng.random((num_bits // 32, 32)) < density).astype(np.uint32)
    bits = (w << np.arange(32, dtype=np.uint32)).sum(axis=1).astype(np.uint32)
    return (jnp.asarray(bits), num_bits, 3)


def _params(streamed, NC, **kw):
    return E.ExtractParams(gamma=GAMMA, scheme="prefix", max_candidates=NC,
                           use_kernel=True, streamed=streamed, **kw)


def run_streamed(smoke: bool = False) -> list[dict]:
    """Single-launch streamed megakernel vs the per-tile launch loop."""
    rows = []
    rng = np.random.default_rng(41)
    flt = _filter(rng)
    scales = (
        ((32, 128, 8, 256),)
        if smoke
        else ((32, 128, 8, 256), (64, 128, 8, 256), (64, 256, 16, 1024))
    )
    for D, T, td, NC in scales:
        docs = jnp.asarray(rng.integers(1, 65536, size=(D, T)), jnp.int32)
        n_tiles = -(-D // td)
        per_tile = _params(False, NC)
        streamed = _params(True, NC)

        # parity: the full compacted dicts agree field for field (and
        # match the unsharded single call), so the timed probe stage
        # below compares two bit-identical computations
        c_pt = SH.stream_filter_compact(docs, L, flt, per_tile, tile_docs=td)
        c_st = SH.stream_filter_compact(docs, L, flt, streamed, tile_docs=td)
        c_ref = E.fused_filter_compact(docs, L, flt, _params(None, NC))
        for k in PARITY_KEYS:
            assert (np.asarray(c_pt[k]) == np.asarray(c_st[k])).all(), (
                f"streamed parity drift: {k}"
            )
            assert (np.asarray(c_ref[k]) == np.asarray(c_st[k])).all(), (
                f"unsharded parity drift: {k}"
            )
        assert int(c_st["n_survive"]) > 0, "parity must cover real survivors"
        # timing: the probe stage — the launch loop the streamed mode
        # restructures (n_tiles dispatches -> one); the lane merge and
        # window gather after it are identical code either way
        f_pt = lambda: SH.stream_probe_tiles(docs, L, flt, per_tile,
                                             tile_docs=td)[:2]
        f_st = lambda: SH.stream_probe_tiles(docs, L, flt, streamed,
                                             tile_docs=td)[:2]
        t_pt, t_st = timeit(f_pt, iters=7), timeit(f_st, iters=7)
        speedup = t_pt / t_st
        bytes_pt = fp.hbm_bytes_fused(D, T, L, NC, 4, False, sig_width=L,
                                      kernel_compact=True)
        bytes_st = fp.hbm_bytes_fused(D, T, L, NC, 4, False, sig_width=L,
                                      kernel_compact=True, streamed=True)
        # model-vs-measured direction: the model says streaming moves
        # strictly fewer bytes; the measurement must agree on direction
        assert bytes_st < bytes_pt, "HBM model must favor streaming"
        assert speedup > 1.0, (
            f"measured direction contradicts the HBM model at D{D}xT{T}: "
            f"streamed {t_st:.4f}s vs per-tile {t_pt:.4f}s"
        )
        if n_tiles >= MIN_TILES:
            assert speedup >= MIN_SPEEDUP, (
                f"streamed launch must beat the per-tile loop by "
                f">= {MIN_SPEEDUP}x at {n_tiles} tiles/shard, got "
                f"{speedup:.2f}x (D{D}xT{T}/td{td})"
            )
        rows.append({
            "kernel": "corpus_streamed", "shape": f"D{D}xT{T}/td{td}",
            "tiles": n_tiles,
            "per_tile_s": t_pt, "streamed_s": t_st, "speedup": speedup,
            "tiles_per_s": n_tiles / t_st,
            "hbm_bytes_per_tile": bytes_pt, "hbm_bytes_streamed": bytes_st,
            "bytes_saved": bytes_pt - bytes_st,
        })
    return rows


def run_spill(smoke: bool = False) -> list[dict]:
    """Over-budget corpus through spill streaming + kill-then-resume.

    The corpus is a file (``MemmapCorpus``) several times larger than
    the device budget; shards are file regions staged through one host
    buffer. The resume leg kills the job after 2 fresh shards
    (``fail_after_shards``) and restarts it against the checkpoints —
    merged results are asserted bit-identical to the uninterrupted run.
    """
    rows = []
    rng = np.random.default_rng(42)
    flt = _filter(rng)
    D, T, td, NC = (96, 128, 4, 256) if smoke else (384, 256, 16, 1024)
    docs = rng.integers(1, 65536, size=(D, T)).astype(np.int32)
    # budget holds one 4-tile shard double-buffered -> 6-shard corpus,
    # 3x over the device budget
    shard_rows = 4 * td
    budget = shard_rows * T * 4 * 2
    params = _params(True, NC)
    with tempfile.TemporaryDirectory() as tmp:
        corpus = SH.MemmapCorpus.write(f"{tmp}/corpus", docs)
        stats: dict = {}
        f_spill = lambda: SH.spill_filter_compact(
            corpus, L, flt, params, device_budget_bytes=budget,
            tile_docs=td, stream_stats=stats,
        )
        c_spill = f_spill()
        c_ref = E.fused_filter_compact(jnp.asarray(docs), L, flt,
                                       _params(None, NC))
        for k in PARITY_KEYS:
            assert (np.asarray(c_ref[k]) == np.asarray(c_spill[k])).all(), (
                f"spill parity drift: {k}"
            )
        n_shards = -(-D // shard_rows)
        # single-run counters (timeit below re-runs and re-accumulates)
        bytes_staged = stats["spill_bytes_staged"]
        n_tiles = stats["tiles_streamed"]
        assert bytes_staged == n_shards * shard_rows * T * 4
        t_spill = timeit(lambda: f_spill()["n_survive"], iters=3)

        # kill-then-resume: interrupt after 2 fresh shards, restart
        ck: dict = {}
        try:
            SH.spill_filter_compact(
                corpus, L, flt, params, device_budget_bytes=budget,
                tile_docs=td, checkpoint_dir=f"{tmp}/ckpt",
                fail_after_shards=2,
            )
            raise AssertionError("fail_after_shards hook did not fire")
        except RuntimeError:
            pass
        c_resumed = SH.spill_filter_compact(
            corpus, L, flt, params, device_budget_bytes=budget,
            tile_docs=td, checkpoint_dir=f"{tmp}/ckpt", stream_stats=ck,
        )
        for k in PARITY_KEYS:
            assert (np.asarray(c_spill[k]) == np.asarray(c_resumed[k])).all(), (
                f"resume parity drift: {k}"
            )
        assert ck["checkpoint_hits"] == 2, "resume must consume the 2 lanes"
        rows.append({
            "kernel": "corpus_spill", "shape": f"D{D}xT{T}/s{shard_rows}t{td}",
            "shards": n_shards,
            "budget_bytes": budget,
            "corpus_bytes": docs.nbytes,
            "bytes_staged": bytes_staged,
            "spill_s": t_spill,
            "tiles_per_s": n_tiles / t_spill,
            "resume_checkpoint_hits": ck["checkpoint_hits"],
            "resume_checkpoint_writes": ck["checkpoint_writes"],
        })
    return rows


def run_device() -> list[dict]:
    """Real-device leg: the streamed comparison compiled (not
    interpreted) on the TPU. Raises without one — a device leg never
    falls back to the CPU."""
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "corpus_device needs a TPU backend; found "
            f"{jax.default_backend()!r}")
    return run_streamed(smoke=False)


def main(smoke: bool = False, device: bool = False) -> None:
    emit("corpus_smoke" if smoke else "corpus_streamed",
         run_streamed(smoke=smoke))
    emit("corpus_spill_smoke" if smoke else "corpus_spill",
         run_spill(smoke=smoke))
    if device:
        emit("corpus_device", run_device())


if __name__ == "__main__":
    import sys

    main(device="--device" in sys.argv[1:])

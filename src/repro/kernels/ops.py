"""Jit'd public wrappers around the Pallas kernels.

These are the dispatch points the extraction engine calls when
``use_kernel=True``: they adapt engine-level arguments (entity-id lists,
weight tables) to the dense tile layout the kernels consume, and choose
the execution mode from the platform: compiled Mosaic on TPU, Pallas
interpret mode on the CPU backend (the test path), and an error anywhere
else — a kernel never falls back to the interpreter on an accelerator.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import fused_probe as _fp
from repro.kernels import jaccard_verify as _jv
from repro.kernels import minhash as _mh
from repro.kernels import window_filter as _wf


def _interpret() -> bool:
    """Interpret mode on the CPU backend, compiled on TPU, else an error."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels target TPU (compiled) or CPU (interpret mode); "
        f"backend {backend!r} is neither — run with use_kernel=False"
    )


def jaccard_verify(win_tokens, ent_ids, dict_tokens, token_weight, sim_name: str):
    """Engine-facing verify: gathers entity rows/weights, runs the kernel.

    win_tokens [N, L]; ent_ids [N, K] (-1 invalid); dict_tokens [E, L];
    token_weight [V]. Returns scores [N, K] f32 (0 for invalid ids).
    Similarities the kernel does not fuse (anything but "extra" and
    "missing") take the jnp path of ``core.semantics.similarity``; see
    ``verify_impl``.
    """
    if verify_impl(sim_name) == "jnp":
        from repro.core.semantics import similarity

        safe = jnp.maximum(ent_ids, 0)
        return similarity(
            sim_name, dict_tokens[safe], win_tokens[:, None, :], token_weight, xp=jnp
        )

    from repro.core.semantics import first_occurrence_mask

    safe = jnp.maximum(ent_ids, 0)
    ent_toks = dict_tokens[safe]  # [N, K, L]
    ent_w = token_weight[ent_toks] * (ent_toks != 0)
    first = first_occurrence_mask(win_tokens, xp=jnp)
    win_w = token_weight[win_tokens] * first
    scores = jaccard_scores(win_tokens, win_w, ent_toks, ent_w, sim_name)
    return jnp.where(ent_ids >= 0, scores, 0.0)


def verify_impl(sim_name: str) -> str:
    """Which verify implementation ``jaccard_verify`` runs for a
    similarity: ``"pallas"`` (fused kernel) or ``"jnp"``."""
    return "pallas" if sim_name in ("extra", "missing") else "jnp"


def jaccard_scores(win_tokens, win_w, ent_tokens, ent_w, mode: str):
    """Kernel-layout verify: pre-gathered [N, L] / [N, K, L] rows and
    weights -> [N, K] f32 scores (``jaccard_verify.jaccard_verify_pallas``)."""
    return _jv.jaccard_verify_pallas(
        win_tokens,
        win_w.astype(jnp.float32),
        ent_tokens,
        ent_w.astype(jnp.float32),
        mode=mode,
        interpret=_interpret(),
    )


def minhash(tokens, valid, bands: int, rows: int):
    """[N, L] tokens -> [N, bands] uint32 banded minhash signatures."""
    return _mh.minhash_pallas(
        tokens, valid, bands=bands, rows=rows, interpret=_interpret()
    )


def window_filter(doc_tokens, bits, num_bits: int, num_hashes: int, max_len: int):
    """[D, T] docs -> [D, T, L] bool window-survival mask (Bloom probe)."""
    return _wf.window_filter_pallas(
        doc_tokens,
        bits,
        num_bits=num_bits,
        num_hashes=num_hashes,
        max_len=max_len,
        interpret=_interpret(),
    )


def fused_probe(
    doc_tokens,
    flt: tuple | None,
    max_len: int,
    sig_mode: str = _fp.SIG_MODE_NONE,
    bands: int = 4,
    rows: int = 2,
):
    """One-pass filter+signature megakernel (the use_kernel fast path).

    ``flt`` is (bits, num_bits, num_hashes) or None (validity only).
    Returns (packed [D, T] uint32 survival bitmap, sigs or None) — see
    ``fused_probe.fused_probe_pallas``; ``sigs`` holds [.., bands]
    MinHash band sigs (``sig_mode="lsh"``) or [.., 2] variant key
    pairs (``sig_mode="variant"``, dense mode).
    """
    packed, sigs, _, _, _ = _probe(
        doc_tokens, flt, max_len, sig_mode, bands, rows, 0
    )
    return packed, sigs


def fused_probe_compact(
    doc_tokens,
    flt: tuple | None,
    max_len: int,
    candidates: int,
    sig_mode: str = _fp.SIG_MODE_NONE,
    bands: int = 4,
    rows: int = 2,
    lane_width: int | None = None,
):
    """``fused_probe`` plus the in-kernel compaction epilogue.

    Returns (packed, sigs, counts [G] int32, cands [G, W] int32, vkeys):
    per grid tile, the true survivor count and the tile's first ``W``
    survivors as ascending global flat window indices (-1 pad), where
    ``W = lane_width or candidates``, capped at the tile's window count
    (``fused_probe.lane_width_for`` — a capped lane is complete). With
    ``sig_mode="variant"`` the survivors' key pairs ride along as
    ``vkeys`` [G, W, 2] uint32 (and no dense ``sigs`` tensor is
    emitted). Combine across tiles with
    ``extraction.results.select_from_tiles`` — no pass over ``packed``
    is needed.

    ``lane_width`` narrows the emitted lanes below the merge capacity
    (the adaptive two-pass emit pass, sized by ``fused_probe_count``);
    the tile height stays derived from ``candidates`` so the count and
    emit passes share one grid — see ``fused_probe.compact_tile_height``.
    """
    if candidates <= 0:
        raise ValueError(
            f"fused_probe_compact(candidates={candidates}): the compaction "
            "epilogue needs a positive [G, NC] lane width (NC = "
            "ExtractParams.max_candidates); use fused_probe() if you only "
            "want the packed survival bitmap"
        )
    if max_len > 32:
        raise ValueError(
            f"fused_probe_compact(max_len={max_len}): the packed survival "
            "bitmap holds one window length per uint32 bit, so the epilogue "
            "supports max_len <= 32; route longer windows through "
            "engine.fused_filter_compact, which falls back to the "
            "standalone window_filter kernel + dense compaction"
        )
    if lane_width is not None and not 0 < lane_width <= candidates:
        raise ValueError(
            f"fused_probe_compact(lane_width={lane_width}): the emit-pass "
            f"lane width must be in (0, candidates={candidates}] — wider "
            "lanes than the merge capacity are never read, and the merge "
            "is only exact when every tile's survivors fit the lane "
            "(choose the width with fused_probe.round_lane_width over "
            "fused_probe_count's per-tile counts)"
        )
    D, T = doc_tokens.shape
    bd = _fp.compact_tile_height(D, T, candidates)
    width = _fp.lane_width_for(lane_width or candidates, bd, T, max_len)
    return _probe(doc_tokens, flt, max_len, sig_mode, bands, rows, width,
                  bd=bd)


def fused_probe_count(
    doc_tokens,
    flt: tuple | None,
    max_len: int,
    candidates: int,
):
    """Count-only probe pass: per-tile survivor counts, no lane store.

    The cheap first pass of the adaptive two-pass compaction: streams
    the same tiles as ``fused_probe_compact(..., candidates)`` (same
    ``compact_tile_height`` grid, so counts line up tile for tile) but
    emits only the [G] int32 SMEM-accumulated survivor counts. Size the
    emit pass with ``fused_probe.round_lane_width(counts.max(), NC)``.
    """
    if candidates <= 0:
        raise ValueError(
            f"fused_probe_count(candidates={candidates}): the count pass "
            "sizes lanes for a positive merge capacity (NC = "
            "ExtractParams.max_candidates)"
        )
    D, T = doc_tokens.shape
    bd = _fp.compact_tile_height(D, T, candidates)
    _, _, counts, _, _ = _probe(
        doc_tokens, flt, max_len, _fp.SIG_MODE_NONE, 4, 2, candidates,
        bd=bd, count_only=True,
    )
    return counts


def fused_probe_stream(
    doc_tokens,
    flt: tuple | None,
    max_len: int,
    candidates: int,
    row_offs,
    sig_mode: str = _fp.SIG_MODE_NONE,
    bd: int | None = None,
    lane_width: int | None = None,
    count_only: bool = False,
):
    """Single-launch streamed probe over a whole shard (DMA pipeline).

    ``doc_tokens`` [G*bd, T] must be pre-padded so each [bd, T] chunk is
    full height; ``row_offs`` [G] int32 carries each chunk's absolute
    doc-row offset (upstream tile boundaries and shard offsets fold in
    here, which is what keeps flat indices bit-identical to the
    per-tile launch loop). Returns ``(counts [G], cands [G, W], vkeys)``
    — the same wire unit as ``fused_probe_compact`` minus the packed
    bitmap and dense sigs, which the streamed kernel never materialises
    (``sig_mode="lsh"`` therefore raises; streaming paths recompute
    band sigs post-compaction). ``count_only=True`` is the adaptive
    sizing pass: lanes are skipped, only ``counts`` comes back.
    """
    if candidates <= 0:
        raise ValueError(
            f"fused_probe_stream(candidates={candidates}): the streamed "
            "kernel has no bitmap output, so it always runs the compaction "
            "epilogue — a positive merge capacity (NC = "
            "ExtractParams.max_candidates) is required"
        )
    if max_len > 32:
        raise ValueError(
            f"fused_probe_stream(max_len={max_len}): the packed survival "
            "bitmap holds one window length per uint32 bit, so the "
            "streamed epilogue supports max_len <= 32"
        )
    if lane_width is not None and not 0 < lane_width <= candidates:
        raise ValueError(
            f"fused_probe_stream(lane_width={lane_width}): the emit-pass "
            f"lane width must be in (0, candidates={candidates}]"
        )
    if flt is None:
        bits = jnp.zeros((8,), dtype=jnp.uint32)
        num_bits, num_hashes, use_filter = 256, 1, False
    else:
        bits, num_bits, num_hashes = flt
        use_filter = True
    if bd is None:
        bd = _fp.compact_tile_height(doc_tokens.shape[0],
                                     doc_tokens.shape[1], candidates)
    width = _fp.lane_width_for(lane_width or candidates, bd,
                               doc_tokens.shape[1], max_len)
    return _fp.fused_probe_stream_pallas(
        doc_tokens,
        bits,
        row_offs,
        num_bits=num_bits,
        num_hashes=num_hashes,
        max_len=max_len,
        sig_mode=sig_mode,
        use_filter=use_filter,
        bd=bd,
        candidates=width,
        count_only=count_only,
        interpret=_interpret(),
    )


def _probe(doc_tokens, flt, max_len, sig_mode, bands, rows, candidates,
           bd: int = _fp.DEFAULT_BD, count_only: bool = False):
    if flt is None:
        bits = jnp.zeros((8,), dtype=jnp.uint32)
        num_bits, num_hashes, use_filter = 256, 1, False
    else:
        bits, num_bits, num_hashes = flt
        use_filter = True
    return _fp.fused_probe_pallas(
        doc_tokens,
        bits,
        num_bits=num_bits,
        num_hashes=num_hashes,
        max_len=max_len,
        sig_mode=sig_mode,
        bands=bands,
        rows=rows,
        use_filter=use_filter,
        bd=bd,
        candidates=candidates,
        count_only=count_only,
        interpret=_interpret(),
    )

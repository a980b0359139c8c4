"""Sharded streaming extraction: the per-device ``fused_probe`` driver.

The paper's operator exists because extraction must scale past one
machine's memory: documents are split into shards and the filter/verify
plan is costed per shard. This module is the execution layer for that
regime — it converts the engine from "one big array per call" into a
*stream of shards per device pool*:

    corpus [D, T]
      └─ shards of ``shard_docs`` rows          (host-side split, PAD-padded)
           └─ wave of ``n_workers`` shards      (shard_map over the mesh axis)
                └─ tiles of ``tile_docs`` rows  (double-buffered probe stream)
                     └─ fused_probe epilogue    (per-tile count + index lanes)

Inside a device, tiles stream through the ``fused_probe`` megakernel
with its in-kernel compaction epilogue; the loop is *double-buffered*:
the next tile's probe is issued before the current tile's lanes are
folded into the shard accumulator, so the two have no data dependency
and a real TPU overlaps the next tile's HBM->VMEM DMA with the current
tile's epilogue math (in interpret mode the structure is identical, the
overlap is just not observable). Every combine step — tile lanes ->
shard lane -> global candidate buffer — runs ``select_from_tiles`` over
tiny [G, NC] count/index lanes, never over the [D, T] survival bitmap.

Because per-tile and per-shard lanes keep the *first NC* survivors in
ascending flat order and true totals ride along, the final selection is
bit-identical to the unsharded ``engine.fused_filter_compact`` fast
path at any shard geometry (uneven shards, PAD-only shards,
zero-survivor shards, more shards than devices) — asserted in
``tests/test_sharded.py`` and re-checked by the sharded smoke bench.

Two PR 4 extensions ride the same lanes: the fused *variant* scheme's
set-hash key pairs travel as a [G, NC, 2] payload next to the index
lanes (``gather_from_tiles`` keeps payload and index selection in
lockstep), and ``ExtractParams(adaptive_lanes=True)`` narrows the tile
lanes to a measured width via a count-only sizing pass
(``stream_tile_counts`` + ``round_lane_width``; under ``shard_map`` a
count *wave* runs first and the width is traced in statically).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.dictionary import PAD
from repro.extraction import engine
from repro.extraction.results import (
    check_lanes,
    gather_from_tiles,
    load_lane_checkpoint,
    save_lane_checkpoint,
    select_from_tiles,
)
from repro.tracing import span

#: default rows per streaming tile: big enough to amortise kernel launch
#: overhead, small enough that two tiles' working sets double-buffer in
#: VMEM (docs + packed bitmap + candidate lanes per tile).
DEFAULT_TILE_DOCS = 64

DEFAULT_AXIS = "workers"


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Static geometry of one sharded streaming run."""

    total_docs: int  # true corpus rows (pre-padding)
    shard_docs: int  # rows per shard (last shard PAD-padded up to this)
    num_shards: int
    tile_docs: int  # rows per double-buffered probe tile within a shard

    @property
    def tiles_per_shard(self) -> int:
        return -(-self.shard_docs // self.tile_docs)


def plan_shards(
    total_docs: int,
    n_workers: int = 1,
    shard_docs: int | None = None,
    tile_docs: int | None = None,
) -> ShardSpec:
    """Choose a shard geometry: default one shard per worker per wave.

    Both the shard height and the tile height are clamped to the corpus:
    a requested ``shard_docs`` (or ``tile_docs``) larger than
    ``total_docs`` would otherwise pad every shard — and therefore every
    tile — to the full requested width with PAD rows that can never
    survive, paying kernel work proportional to the *request* instead of
    the corpus (the 1-shard tiny-corpus edge).
    """
    assert total_docs > 0
    sd = min(shard_docs or -(-total_docs // max(n_workers, 1)), total_docs)
    td = min(tile_docs or DEFAULT_TILE_DOCS, sd)
    return ShardSpec(
        total_docs=total_docs,
        shard_docs=sd,
        num_shards=-(-total_docs // sd),
        tile_docs=td,
    )


def resolve_streamed(params: engine.ExtractParams, n_tiles: int) -> bool:
    """Per-shard launch mode: single streamed launch vs per-tile loop.

    ``params.streamed`` is the override (True/False); ``None`` is auto —
    stream whenever the shard spans >= 2 tiles, since a single tile has
    no copy-in to overlap (the per-tile launch is then one launch too).
    """
    if params.streamed is not None:
        return bool(params.streamed)
    return n_tiles >= 2


def _streamed_layout(docs, td: int, n_tiles: int, bd: int):
    """Chunk layout for the single-launch streamed kernel.

    The per-tile loop pads each [td, T] tile *independently* to a
    multiple of the NC-derived sub-tile height ``bd`` inside
    ``fused_probe_compact``; to be bit-identical the streamed buffer
    replays that layout — each tile padded to ``td_p = ceil(td/bd)*bd``
    rows, concatenated — and the per-chunk row offsets carry the
    *original* (unpadded) row numbering ``i*td + j*bd`` so flat indices
    match the per-tile path exactly. Returns ``(docs [n_tiles*td_p, T],
    offs [n_tiles*(td_p//bd)] int32 host array)``.
    """
    T = docs.shape[1]
    td_p = -(-td // bd) * bd
    if td_p != td:
        docs = jnp.pad(
            docs.reshape(n_tiles, td, T),
            ((0, 0), (0, td_p - td), (0, 0)),
            constant_values=PAD,
        ).reshape(n_tiles * td_p, T)
    gp = td_p // bd
    offs = (np.arange(n_tiles)[:, None] * td
            + np.arange(gp)[None, :] * bd).reshape(-1).astype(np.int32)
    return docs, offs


def stream_probe_tiles(
    docs,
    max_len: int,
    flt: tuple | None,
    params: engine.ExtractParams,
    tile_docs: int = DEFAULT_TILE_DOCS,
    row_offset=0,
    lane_width: int | None = None,
    sig_mode: str | None = None,
    stream_stats: dict | None = None,
):
    """Stream a [S, T] doc shard through ``fused_probe`` tile by tile.

    Returns ``(counts [G], cands [G, W], vkeys)`` candidate lanes
    covering the whole shard (``W = lane_width or NC``; ``vkeys``
    [G, W, 2] variant key payload when ``sig_mode == "variant"``, else
    ``None``), with flat indices globalised by ``row_offset`` rows
    (``row_offset`` may be a traced scalar, e.g. a worker index inside
    ``shard_map``).

    Launch mode is per-shard (``resolve_streamed``): by default a shard
    spanning >= 2 tiles goes through the *single-launch* streamed
    megakernel (``ops.fused_probe_stream`` — the tile loop runs inside
    the kernel over a double-buffered DMA pipeline, and only the tiny
    per-chunk lanes come back); otherwise — or with
    ``params.streamed=False`` — the per-tile launch loop runs, itself
    double-buffered at the dispatch level: tile i+1's probe is issued
    before tile i's lanes are globalised, so the probe DMA and the
    combine arithmetic have no dependency edge between them. Both modes
    are bit-identical at any geometry (same sub-tile grid, same
    epilogue). ``lane_width`` is the adaptive emit width — the sub-tile
    grid stays NC-derived so counts line up with a
    ``stream_tile_counts`` sizing pass at the same geometry.
    ``stream_stats`` (mutable dict) accumulates streaming observability
    counters: ``streamed_launches`` and ``tiles_streamed`` (one per
    in-kernel chunk, each a DMA wait).
    """
    from repro.kernels import ops as kops
    from repro.kernels.fused_probe import (
        SIG_MODE_NONE,
        SIG_MODE_VARIANT,
        compact_tile_height,
    )

    sig_mode = SIG_MODE_NONE if sig_mode is None else sig_mode
    var = sig_mode == SIG_MODE_VARIANT
    S, T = docs.shape
    L = max_len
    NC = params.max_candidates
    td = min(tile_docs, S)
    n_tiles = -(-S // td)
    if n_tiles * td != S:
        docs = jnp.pad(docs, ((0, n_tiles * td - S), (0, 0)),
                       constant_values=PAD)

    if resolve_streamed(params, n_tiles):
        bd = compact_tile_height(td, T, NC)
        sdocs, offs = _streamed_layout(docs, td, n_tiles, bd)
        row_offs = (row_offset + jnp.asarray(offs)).astype(jnp.int32)
        counts, cands, vkeys = kops.fused_probe_stream(
            sdocs, flt, L, NC, row_offs, sig_mode=sig_mode, bd=bd,
            lane_width=lane_width,
        )
        if stream_stats is not None:
            chunks = int(offs.shape[0])
            stream_stats["streamed_launches"] = (
                stream_stats.get("streamed_launches", 0) + 1)
            stream_stats["tiles_streamed"] = (
                stream_stats.get("tiles_streamed", 0) + chunks)
        return counts, cands, vkeys

    def probe(i):
        return kops.fused_probe_compact(
            docs[i * td:(i + 1) * td], flt, L, NC, sig_mode,
            params.lsh.bands, params.lsh.rows, lane_width=lane_width,
        )

    def globalise(cnt, cd, vk, tile_row):
        off = (row_offset + tile_row) * T * L
        return cnt, jnp.where(cd >= 0, cd + off, -1), vk

    out_counts, out_cands, out_keys = [], [], []

    def emit(cur, cur_row):
        c, x, vk = globalise(*cur, cur_row)
        out_counts.append(c)
        out_cands.append(x)
        if var:
            out_keys.append(vk)

    _, _, cnt, cd, vk = probe(0)
    cur, cur_row = (cnt, cd, vk), 0
    for i in range(1, n_tiles):
        _, _, cnt, cd, vk = probe(i)  # issue next probe (buffer B) ...
        emit(cur, cur_row)  # ... while current tile combines
        cur, cur_row = (cnt, cd, vk), i * td
    emit(cur, cur_row)
    return (
        jnp.concatenate(out_counts),
        jnp.concatenate(out_cands, axis=0),
        jnp.concatenate(out_keys, axis=0) if var else None,
    )


def stream_tile_counts(
    docs,
    max_len: int,
    flt: tuple | None,
    params: engine.ExtractParams,
    tile_docs: int = DEFAULT_TILE_DOCS,
    stream_stats: dict | None = None,
):
    """Count-only streaming pass: per-sub-tile survivor counts [G].

    The cheap sizing half of the adaptive two-pass scheme — streams the
    exact tile/sub-tile grid of ``stream_probe_tiles`` (the emit width
    never changes the grid) but stores only the per-tile counts.
    ``round_lane_width(counts.max(), NC)`` then sizes the emit pass so
    every sub-tile's lane holds all of its survivors. Follows the same
    ``resolve_streamed`` launch-mode choice as the emit pass, so a
    streamed run's sizing pass is one launch too.
    """
    from repro.kernels import ops as kops
    from repro.kernels.fused_probe import compact_tile_height

    S, T = docs.shape
    NC = params.max_candidates
    td = min(tile_docs, S)
    n_tiles = -(-S // td)
    if n_tiles * td != S:
        docs = jnp.pad(docs, ((0, n_tiles * td - S), (0, 0)),
                       constant_values=PAD)
    if resolve_streamed(params, n_tiles):
        bd = compact_tile_height(td, T, NC)
        sdocs, offs = _streamed_layout(docs, td, n_tiles, bd)
        counts, _, _ = kops.fused_probe_stream(
            sdocs, flt, max_len, NC, jnp.asarray(offs), bd=bd,
            count_only=True,
        )
        if stream_stats is not None:
            chunks = int(offs.shape[0])
            stream_stats["streamed_launches"] = (
                stream_stats.get("streamed_launches", 0) + 1)
            stream_stats["tiles_streamed"] = (
                stream_stats.get("tiles_streamed", 0) + chunks)
        return counts
    return jnp.concatenate([
        kops.fused_probe_count(docs[i * td:(i + 1) * td], flt, max_len, NC)
        for i in range(n_tiles)
    ])


def _adaptive_width(docs, max_len, flt, params, tile_docs) -> int:
    """Measure per-tile survivor maxima and round to the emit width."""
    from repro.kernels.fused_probe import MIN_LANE_WIDTH, round_lane_width

    counts = stream_tile_counts(docs, max_len, flt, params, tile_docs)
    return round_lane_width(
        int(np.asarray(counts).max()),
        params.max_candidates,
        params.lane_width or MIN_LANE_WIDTH,
    )


def _stream_sig_mode(params: engine.ExtractParams, D: int, T: int,
                     max_len: int) -> str:
    """Signature mode for the streaming tile lanes.

    Tile lanes carry the variant key payload, but *dense* in-kernel
    band-sig tensors ([td, T, L, B], lsh) have no lane to ride — the
    streaming path computes bit-identical band sigs post-compaction
    instead (``engine.window_sigs_for``), so the lsh mode is coerced to
    ``none`` here rather than paying a kernel store that would be
    discarded. An explicit ``kernel_sigs=True`` force for lsh therefore
    cannot be honored on this path and raises instead of silently
    falling back.
    """
    from repro.kernels.fused_probe import SIG_MODE_LSH, SIG_MODE_NONE

    mode = engine.resolve_sig_mode(params, D, T, max_len)
    if mode == SIG_MODE_LSH:
        if params.kernel_sigs:
            raise ValueError(
                "ExtractParams(kernel_sigs=True, scheme='lsh') cannot run "
                "on the sharded/serving streaming path: dense in-kernel "
                "band sigs do not ride the candidate lanes, so the kernel "
                "store would be discarded and the sigs recomputed "
                "post-compaction anyway; use the single-call "
                "engine.fused_filter_compact for forced in-kernel band "
                "sigs, or leave kernel_sigs unset (the streaming path "
                "recomputes bit-identical band sigs post-compaction)"
            )
        return SIG_MODE_NONE
    return mode


def stream_filter_compact(
    doc_tokens,
    max_len: int,
    flt: tuple | None,
    params: engine.ExtractParams,
    tile_docs: int = DEFAULT_TILE_DOCS,
) -> dict:
    """Single-device streaming equivalent of ``engine.fused_filter_compact``.

    Tiles the doc array through the megakernel (double-buffered) instead
    of one monolithic ``pallas_call``, then merges the per-tile lanes.
    Output is bit-identical to the unsharded fast path; LSH schemes get
    their signatures post-compaction (``window_sigs_for`` recomputes
    bit-identical band sigs from the gathered windows), so the dict
    never carries in-kernel band ``sigs`` — the *variant* scheme's key
    pairs, however, ride the tile lanes ([G, W, 2] payload) and arrive
    attached exactly as on the unsharded path. Honors
    ``params.adaptive_lanes`` (two-pass: count stream sizes the emit
    stream's lane width). Falls back to the single-call engine path
    when the epilogue cannot run (L > 32 or
    ``params.kernel_compact=False``).
    """
    from repro.kernels.fused_probe import SIG_MODE_VARIANT

    if max_len > 32 or not params.kernel_compact:
        return engine.fused_filter_compact(doc_tokens, max_len, flt, params)
    D, T = doc_tokens.shape
    sig_mode = _stream_sig_mode(params, D, T, max_len)
    NC = params.max_candidates
    lane_w = None
    if params.adaptive_lanes:
        lane_w = _adaptive_width(doc_tokens, max_len, flt, params, tile_docs)
    counts, cands, vkeys = stream_probe_tiles(
        doc_tokens, max_len, flt, params, tile_docs,
        lane_width=lane_w, sig_mode=sig_mode,
    )
    sel, ok, n = select_from_tiles(counts, cands, NC)
    out = engine.candidates_from_flat(doc_tokens, sel, ok, n, max_len, NC)
    if sig_mode == SIG_MODE_VARIANT:
        out = engine.attach_variant_keys(
            out, gather_from_tiles(counts, vkeys, NC)
        )
    return out


def shard_lane(docs, row_offset, max_len, flt, params,
               tile_docs: int = DEFAULT_TILE_DOCS,
               lane_width: int | None = None,
               sig_mode: str | None = None,
               stream_stats: dict | None = None):
    """Stream one doc shard and reduce it to a single candidate lane —
    the *wire unit* of every lane-shipping consumer (sharded driver
    waves, the serving probe→verify handoff).

    Lane wire format (``[G, NC]`` with ``G = 1`` here):

    * ``cand`` — ``[1, NC]`` **int32**: the shard's first ``NC``
      (``params.max_candidates``) surviving windows as **ascending**
      global flat indices ``(doc * T + pos) * L + (len - 1)``, where
      ``doc`` is globalised by ``row_offset`` rows and ``L`` is
      ``max_len``. Unused slots hold the sentinel ``-1`` (PAD); real
      indices are always ``>= 0``, so sign is the validity bit.
    * ``count`` — ``[1]`` **int32**: the shard's *true* survivor total,
      which may exceed ``NC`` (overflow is surfaced downstream, never
      silent).
    * ``keys`` — ``[1, NC, 2]`` **uint32** or ``None``: the lane
      slots' variant key pairs when the fused variant scheme is on
      (``sig_mode == "variant"``), 0 in padded slots — the verify side
      then never recomputes set hashes.

    One ``(cand, count[, keys])`` triple is exactly one row of a
    ``results.select_from_tiles`` (+ ``gather_from_tiles``) input, so
    lanes compose hierarchically — tile lanes into a shard lane, shard
    lanes across waves or micro-batches into a global selection — and
    are cheap enough (``(1 + NC) * 4`` [+ ``8 NC``] bytes) to ship
    across hosts or device pools. ``row_offset`` may be a traced scalar
    (e.g. a worker index inside ``shard_map``).

    With ``params.adaptive_lanes`` the internal tile lanes are two-pass
    sized (the wire lane stays ``NC`` wide — its ``G = 1`` makes it
    cheap already). Under jit/shard_map tracing the sizing host sync is
    impossible, so the traced caller must pre-measure and pass
    ``lane_width`` explicitly (see ``sharded_filter_compact``'s count
    wave); a traced call with ``adaptive_lanes`` and no ``lane_width``
    raises rather than silently falling back to worst-case lanes.
    """
    from repro.kernels.fused_probe import SIG_MODE_VARIANT

    if sig_mode is None:
        D, T = docs.shape
        sig_mode = _stream_sig_mode(params, D, T, max_len)
    NC = params.max_candidates
    if params.adaptive_lanes and lane_width is None:
        if isinstance(docs, jax.core.Tracer):
            raise ValueError(
                "shard_lane: ExtractParams(adaptive_lanes=True) under "
                "jit/shard_map tracing needs an explicit lane_width — the "
                "count-pass host sync cannot run inside a trace; measure "
                "with stream_tile_counts + round_lane_width outside the "
                "trace (sharded_filter_compact's count wave does this) "
                "and pass the width in"
            )
        lane_width = _adaptive_width(docs, max_len, flt, params, tile_docs)
    counts, cands, vkeys = stream_probe_tiles(
        docs, max_len, flt, params, tile_docs, row_offset=row_offset,
        lane_width=lane_width, sig_mode=sig_mode, stream_stats=stream_stats,
    )
    sel, ok, n = select_from_tiles(counts, cands, NC)
    keys = None
    if sig_mode == SIG_MODE_VARIANT:
        keys = gather_from_tiles(counts, vkeys, NC)[None, :, :]
    return jnp.where(ok, sel, -1)[None, :], n[None].astype(jnp.int32), keys


def shard_lane_steady(docs, row_offset, max_len, flt, params,
                      tile_docs: int = DEFAULT_TILE_DOCS,
                      width_hint: int | None = None,
                      sig_mode: str | None = None,
                      stream_stats: dict | None = None):
    """``shard_lane`` with steady-state adaptive sizing for serving.

    The adaptive two-pass scheme pays a count-only probe pass per call
    to size the emit lanes; on steady serving traffic consecutive
    batches of the same (session, bucket) see near-identical survivor
    densities, so the previous batch's measured per-tile maximum
    (``width_hint``) sizes this batch's emit width directly and the
    count pass is amortised away. Correctness never depends on the
    hint: the emit pass's SMEM counts are *true* totals, so an
    undersized hint is detected (``max(counts) > width``) and the emit
    re-runs at the measured width — still no count pass.

    Returns ``(lane, count, keys, tile_max, sizing)``: the ``shard_lane``
    wire triple plus the measured per-tile survivor max (the next
    batch's hint; ``-1`` on the non-adaptive path) and the sizing mode
    actually used (``fixed`` | ``count_pass`` | ``hint`` | ``refit``).
    """
    from repro.kernels.fused_probe import (
        MIN_LANE_WIDTH,
        SIG_MODE_VARIANT,
        round_lane_width,
    )

    if sig_mode is None:
        D, T = docs.shape
        sig_mode = _stream_sig_mode(params, D, T, max_len)
    NC = params.max_candidates
    if not params.adaptive_lanes:
        lane, n, keys = shard_lane(
            docs, row_offset, max_len, flt, params, tile_docs,
            sig_mode=sig_mode, stream_stats=stream_stats,
        )
        return lane, n, keys, -1, "fixed"
    if isinstance(docs, jax.core.Tracer):
        raise ValueError(
            "shard_lane_steady cannot run under jit/shard_map tracing: "
            "both the hint-overflow check and the count-pass fallback "
            "need host reads of the per-tile counts; serving calls it "
            "un-traced (the kernel passes are jitted internally)"
        )
    floor = params.lane_width or MIN_LANE_WIDTH
    if width_hint is not None and width_hint >= 0:
        W, sizing = round_lane_width(width_hint, NC, floor), "hint"
    else:
        counts = stream_tile_counts(docs, max_len, flt, params, tile_docs,
                                    stream_stats=stream_stats)
        W = round_lane_width(int(np.asarray(counts).max()), NC, floor)
        sizing = "count_pass"

    def emit(width):
        return stream_probe_tiles(
            docs, max_len, flt, params, tile_docs, row_offset=row_offset,
            lane_width=width, sig_mode=sig_mode, stream_stats=stream_stats,
        )

    counts, cands, vkeys = emit(W)
    tile_max = int(np.asarray(counts).max())
    if tile_max > W and W < NC:
        # stale hint undersized the lanes: the emit pass's counts are
        # true totals, so refit straight to the measured maximum — the
        # fallback costs one extra emit pass, never a count pass. At
        # W == NC there is nothing to refit (lanes never exceed the
        # merge capacity, and the select below is exact regardless).
        W = round_lane_width(tile_max, NC, floor)
        counts, cands, vkeys = emit(W)
        sizing = "refit"
    check_lanes(tile_max, cands.shape[1], NC)
    sel, ok, n = select_from_tiles(counts, cands, NC)
    keys = None
    if sig_mode == SIG_MODE_VARIANT:
        keys = gather_from_tiles(counts, vkeys, NC)[None, :, :]
    return (jnp.where(ok, sel, -1)[None, :], n[None].astype(jnp.int32),
            keys, tile_max, sizing)


def lanes_to_wire(docs, lanes, meta: dict | None = None) -> bytes:
    """Frame a probed batch's lanes for transport (fabric FT_LANES).

    ``lanes`` is the probe→verify handoff list: per plan side one
    ``(count [G] i32, cand [G, NC] i32, keys [G, NC, 2] u32 | None)``
    triple as produced by ``shard_lane`` / ``shard_lane_steady``;
    ``docs`` the batch's ``[D, T]`` token rows the remote verify pool
    gathers candidate windows from. The payload is the sha256-guarded
    npz container of ``updates.delta.pack_arrays``, so a truncated or
    cross-wired lane frame is detected at decode, and round-trips are
    bit-exact — remote ``select_from_tiles`` merges stay bit-identical
    to the in-process handoff.
    """
    from repro.updates.delta import pack_arrays

    m = dict(meta or {})
    m["kind"] = "lane_frame"
    m["n_sides"] = len(lanes)
    arrays = {"docs": np.asarray(docs, dtype=np.int32)}
    for i, (count, cand, keys) in enumerate(lanes):
        arrays[f"side{i}_count"] = np.asarray(count, dtype=np.int32)
        arrays[f"side{i}_cand"] = np.asarray(cand, dtype=np.int32)
        if keys is not None:
            arrays[f"side{i}_keys"] = np.asarray(keys, dtype=np.uint32)
    return pack_arrays(m, arrays)


def lanes_from_wire(data: bytes):
    """Inverse of ``lanes_to_wire`` → ``(meta, docs, lanes)``.

    Raises ``ValueError`` (from the container's fingerprint check) on
    any corruption; a decoded frame is the exact arrays that were
    framed.
    """
    from repro.updates.delta import unpack_arrays

    meta, arrays = unpack_arrays(data)
    if meta.get("kind") != "lane_frame":
        raise ValueError(
            f"lanes_from_wire: payload kind {meta.get('kind')!r} is not "
            "a lane_frame"
        )
    lanes = []
    for i in range(int(meta["n_sides"])):
        lanes.append((
            arrays[f"side{i}_count"],
            arrays[f"side{i}_cand"],
            arrays.get(f"side{i}_keys"),
        ))
    return meta, arrays["docs"], lanes


def sharded_filter_compact(
    doc_tokens,
    max_len: int,
    flt: tuple | None,
    params: engine.ExtractParams,
    mesh: Mesh | None = None,
    axis_name: str = DEFAULT_AXIS,
    shard_docs: int | None = None,
    tile_docs: int | None = None,
    checkpoint_dir: str | None = None,
    stream_stats: dict | None = None,
) -> dict:
    """Shard-parallel streaming candidate front end.

    Splits the corpus into ``shard_docs``-row shards, maps each wave of
    ``n_workers`` shards onto the mesh axis with ``shard_map`` (each
    device streams its shard's tiles through ``fused_probe``), and
    merges the per-shard candidate lanes into one global
    ``compact_candidates`` dict — bit-identical to running the
    unsharded ``engine.fused_filter_compact`` on the whole array. With
    ``mesh=None`` the wave loop degenerates to a sequential stream on
    the local device (same lanes, same merge, same outputs). More
    shards than devices are handled by multiple waves; short corpora
    and ragged tails are PAD-padded (PAD rows can never survive, so
    padding never perturbs the selection).

    ``checkpoint_dir`` makes the run killable and resumable: every
    finished shard's lane wire unit is persisted there (atomic npz, see
    ``LaneCheckpointStore``) and a restarted call with the same
    geometry/params/filter loads finished lanes instead of re-probing —
    the merge consumes the identical lanes either way, so resumed
    results are bit-identical. A manifest guards against resuming into
    a different job. ``stream_stats`` accumulates streaming +
    checkpoint observability counters.
    """
    from repro.kernels.fused_probe import SIG_MODE_VARIANT

    if max_len > 32 or not params.kernel_compact:
        # no epilogue -> no lanes to shard over; single-call fallback
        return engine.fused_filter_compact(doc_tokens, max_len, flt, params)
    D, T = doc_tokens.shape
    engine.check_flat_index_space(D, T, max_len)
    sig_mode = _stream_sig_mode(params, D, T, max_len)
    var = sig_mode == SIG_MODE_VARIANT
    n_workers = int(mesh.shape[axis_name]) if mesh is not None else 1
    spec = plan_shards(D, n_workers, shard_docs, tile_docs)
    NC = params.max_candidates
    n_waves = -(-spec.num_shards // n_workers)
    rows_padded = n_waves * n_workers * spec.shard_docs
    padded = doc_tokens
    if rows_padded != D:
        padded = jnp.pad(doc_tokens, ((0, rows_padded - D), (0, 0)),
                         constant_values=PAD)
    store = None
    if checkpoint_dir is not None:
        store = LaneCheckpointStore(
            checkpoint_dir,
            job_manifest(spec, T, max_len, params, flt, sig_mode),
        )

    lanes, totals, keys = [], [], []
    if mesh is None:
        for s in range(n_waves * n_workers):
            if store is not None and store.has(s):
                lane, n, vk = store.load(s)
            else:
                lane, n, vk = shard_lane(
                    padded[s * spec.shard_docs:(s + 1) * spec.shard_docs],
                    s * spec.shard_docs,
                    max_len, flt, params, spec.tile_docs, sig_mode=sig_mode,
                    stream_stats=stream_stats,
                )
                if store is not None:
                    store.save(s, lane, n, vk if var else None)
            lanes.append(lane)
            totals.append(n)
            if var:
                keys.append(vk)
    else:
        def wave_body(docs, row_off, lane_width=None):
            out = shard_lane(
                docs, row_off[0], max_len, flt, params, spec.tile_docs,
                lane_width=lane_width, sig_mode=sig_mode,
            )
            return out if var else out[:2]

        n_out = 3 if var else 2
        if params.adaptive_lanes:
            # adaptive under shard_map: the sizing host sync cannot live
            # inside the trace, so each wave runs a count-only shard_map
            # pass first and the measured width is traced in statically
            # (power-of-two rounding bounds the retrace count).
            from repro.kernels.fused_probe import (
                MIN_LANE_WIDTH,
                round_lane_width,
            )

            def count_body(docs):
                c = stream_tile_counts(
                    docs, max_len, flt, params, spec.tile_docs
                )
                return jnp.max(c)[None]

            count_fn = shard_map(
                count_body,
                mesh=mesh,
                in_specs=(P(axis_name),),
                out_specs=P(axis_name),
                check_vma=False,
            )
        else:
            count_fn = None
        wave_cache: dict = {}

        def wave_fn_for(lane_width):
            if lane_width not in wave_cache:
                wave_cache[lane_width] = shard_map(
                    lambda d, o: wave_body(d, o, lane_width=lane_width),
                    mesh=mesh,
                    in_specs=(P(axis_name), P(axis_name)),
                    out_specs=tuple([P(axis_name)] * n_out),
                    check_vma=False,
                )
            return wave_cache[lane_width]

        for w in range(n_waves):
            wave_shards = [w * n_workers + k for k in range(n_workers)]
            if store is not None and all(store.has(s) for s in wave_shards):
                # whole wave already checkpointed: load, skip the probes
                loaded = [store.load(s) for s in wave_shards]
                lanes.append(jnp.concatenate([x[0] for x in loaded], axis=0))
                totals.append(jnp.concatenate([x[1] for x in loaded]))
                if var:
                    keys.append(
                        jnp.concatenate([x[2] for x in loaded], axis=0)
                    )
                continue
            block = padded[
                w * n_workers * spec.shard_docs:(w + 1) * n_workers * spec.shard_docs
            ]
            offs = (
                (w * n_workers + jnp.arange(n_workers)) * spec.shard_docs
            ).astype(jnp.int32)
            lane_w = None
            if count_fn is not None:
                lane_w = round_lane_width(
                    int(np.asarray(count_fn(block)).max()),
                    NC,
                    params.lane_width or MIN_LANE_WIDTH,
                )
            # gather the wave's lanes on one device: the merge indexes
            # across shards (a mesh with explicit axes refuses that on
            # sharded operands) and the verify after it is a
            # single-device program (Mosaic kernels are not partitioned)
            out = jax.device_put(wave_fn_for(lane_w)(block, offs),
                                 mesh.devices.flat[0])
            wave_lanes = out[0].reshape(n_workers, NC)
            wave_totals = out[1].reshape(n_workers)
            wave_keys = out[2].reshape(n_workers, NC, 2) if var else None
            if store is not None:
                for k, s in enumerate(wave_shards):
                    store.save(
                        s, wave_lanes[k:k + 1], wave_totals[k:k + 1],
                        wave_keys[k:k + 1] if var else None,
                    )
            lanes.append(wave_lanes)
            totals.append(wave_totals)
            if var:
                keys.append(wave_keys)

    if store is not None and stream_stats is not None:
        store.flush_stats(stream_stats)
    if mesh is not None:
        # the window gather joins the lanes on the merge device, whatever
        # the documents' placement (sharded over the mesh, the natural
        # input here, or one device)
        doc_tokens = jax.device_put(doc_tokens, mesh.devices.flat[0])
    counts = jnp.concatenate(totals)
    cands = jnp.concatenate(lanes, axis=0)
    sel, ok, n = select_from_tiles(counts, cands, NC)
    out = engine.candidates_from_flat(doc_tokens, sel, ok, n, max_len, NC)
    if var:
        out = engine.attach_variant_keys(
            out, gather_from_tiles(counts, jnp.concatenate(keys, axis=0), NC)
        )
    return out


# --------------------------------------------------------------------------
# Corpus spill streaming: shards as *file regions*, resumable merges
# --------------------------------------------------------------------------

#: default device-resident budget for spill streaming: how many bytes of
#: staged documents one shard may occupy on device (see
#: ``shard_docs_for_budget`` for the headroom rule).
DEFAULT_DEVICE_BUDGET_BYTES = 256 << 20


def filter_fingerprint(flt: tuple | None) -> str:
    """Content hash of an ISH filter triple (checkpoint-manifest guard).

    Resuming a corpus job against a *different* filter would merge
    lanes probed under incompatible survival sets — the sha256 of the
    bit array (plus the probe parameters) makes that a manifest
    mismatch instead of silent corruption.
    """
    if flt is None:
        return "none"
    bits, num_bits, num_hashes = flt
    h = hashlib.sha256(np.asarray(bits).tobytes())
    h.update(f":{num_bits}:{num_hashes}".encode())
    return h.hexdigest()


def job_manifest(spec: ShardSpec, seq_len: int, max_len: int,
                 params: engine.ExtractParams, flt: tuple | None,
                 sig_mode: str) -> dict:
    """Everything that must match for two runs to share lane checkpoints.

    Geometry (shard/tile heights fix the lane layout and flat-index
    numbering), extraction params (capacity, scheme, lane sizing...) and
    the filter fingerprint (survival sets). JSON-round-tripped so the
    stored and compared forms are identical.
    """
    m = {
        "format": 1,
        "total_docs": spec.total_docs,
        "shard_docs": spec.shard_docs,
        "num_shards": spec.num_shards,
        "tile_docs": spec.tile_docs,
        "seq_len": seq_len,
        "max_len": max_len,
        "sig_mode": sig_mode,
        "filter": filter_fingerprint(flt),
        "params": dataclasses.asdict(params),
    }
    return json.loads(json.dumps(m))


class LaneCheckpointStore:
    """Per-shard lane checkpoints + job manifest under one directory.

    Layout: ``manifest.json`` (the ``job_manifest`` of the run) plus one
    ``shard_NNNNNN.npz`` per finished shard (atomic writes — a kill
    leaves whole files or none). A second run with an equal manifest
    resumes: ``has``/``load`` skip finished probes; a run with a
    *different* manifest raises instead of merging foreign lanes
    (``reset=True`` wipes the stale checkpoints and starts over).
    """

    def __init__(self, root: str, manifest: dict, reset: bool = False):
        self.root = root
        self.writes = 0
        self.hits = 0
        os.makedirs(root, exist_ok=True)
        mpath = os.path.join(root, "manifest.json")
        existing = None
        if os.path.exists(mpath):
            with open(mpath) as f:
                existing = json.load(f)
        if existing is not None and not reset:
            if existing != manifest:
                diff = sorted(
                    k for k in set(existing) | set(manifest)
                    if existing.get(k) != manifest.get(k)
                )
                raise ValueError(
                    f"checkpoint manifest mismatch in {root!r} (differing "
                    f"keys: {diff}): these lane checkpoints belong to a "
                    "different corpus job (other geometry, params, or "
                    "filter) and merging them would corrupt the selection; "
                    "point checkpoint_dir at a fresh directory, or pass "
                    "reset=True to discard the stale checkpoints"
                )
            return  # same job: resume against the existing checkpoints
        if existing is not None:
            for name in os.listdir(root):
                if name.startswith("shard_") and name.endswith(".npz"):
                    os.remove(os.path.join(root, name))
        tmp = f"{mpath}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, mpath)

    def _path(self, shard: int) -> str:
        return os.path.join(self.root, f"shard_{shard:06d}.npz")

    def has(self, shard: int) -> bool:
        return os.path.exists(self._path(shard))

    def load(self, shard: int):
        self.hits += 1
        return load_lane_checkpoint(self._path(shard))

    def save(self, shard: int, lane, count, keys=None) -> None:
        save_lane_checkpoint(self._path(shard), lane, count, keys)
        self.writes += 1

    def flush_stats(self, stream_stats: dict) -> None:
        """Fold this store's counters into a ``stream_stats`` dict."""
        stream_stats["checkpoint_writes"] = (
            stream_stats.get("checkpoint_writes", 0) + self.writes)
        stream_stats["checkpoint_hits"] = (
            stream_stats.get("checkpoint_hits", 0) + self.hits)


@dataclasses.dataclass
class MemmapCorpus:
    """A corpus as a *file*, not an array: flat int32 bin + JSON header.

    The spill-streaming driver treats a shard as a region of this file:
    only one staged shard is ever host/device resident. ``tokens`` is
    usually an ``np.memmap`` (``open``), but any [D, T] int32 array
    duck-types, so the driver also accepts in-memory corpora untouched.
    """

    tokens: np.ndarray  # [D, T] int32 (np.memmap after ``open``)

    @property
    def rows(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def seq_len(self) -> int:
        return int(self.tokens.shape[1])

    @classmethod
    def write(cls, path_base: str, docs) -> "MemmapCorpus":
        """Persist ``docs`` [D, T] as ``<base>.bin`` + ``<base>.json``."""
        arr = np.ascontiguousarray(np.asarray(docs, dtype=np.int32))
        with open(path_base + ".bin", "wb") as f:
            f.write(arr.tobytes())
        with open(path_base + ".json", "w") as f:
            json.dump({"format": 1, "rows": int(arr.shape[0]),
                       "seq_len": int(arr.shape[1]), "dtype": "int32"}, f)
        return cls.open(path_base)

    @classmethod
    def open(cls, path_base: str) -> "MemmapCorpus":
        with open(path_base + ".json") as f:
            hdr = json.load(f)
        assert hdr.get("dtype", "int32") == "int32", hdr
        tokens = np.memmap(path_base + ".bin", dtype=np.int32, mode="r",
                           shape=(hdr["rows"], hdr["seq_len"]))
        return cls(tokens=tokens)


class HostSpillStreamer:
    """Host->device spill feed: one reusable staging buffer per job.

    Stages shard-sized file regions through a single preallocated
    [shard_docs, T] host buffer (the pinned-host staging slot: on TPU
    this is the page-locked array ``device_put`` DMAs from; in
    interpret mode a plain ndarray plays the role) — no per-shard host
    allocation, ragged tails PAD-padded in place. ``bytes_staged``
    accumulates the host->device spill traffic for the corpus bench.
    """

    def __init__(self, corpus: MemmapCorpus, shard_docs: int):
        self.corpus = corpus
        self.shard_docs = shard_docs
        self._buf = np.empty((shard_docs, corpus.seq_len), dtype=np.int32)
        self.bytes_staged = 0

    def stage(self, shard: int):
        """Copy shard ``shard``'s file region in; return the device array."""
        start = shard * self.shard_docs
        rows = min(self.shard_docs, self.corpus.rows - start)
        assert rows > 0, f"shard {shard} starts past the corpus"
        with span("eejoin.spill.stage"):
            self._buf[:rows] = self.corpus.tokens[start:start + rows]
            if rows < self.shard_docs:
                self._buf[rows:] = PAD
            self.bytes_staged += self._buf.nbytes
            return jnp.asarray(self._buf)


def shard_docs_for_budget(total_docs: int, seq_len: int, budget_bytes: int,
                          tile_docs: int | None = None) -> int:
    """Largest shard height whose working set fits ``budget_bytes``.

    The spill-buffer sizing rule: per-shard residency is dominated by
    the staged doc region (``rows * T * 4`` bytes) and the budget must
    hold *two* of them — the shard being probed plus the next one's
    host staging copy in flight (the host-level double buffer mirroring
    the in-kernel one). Lane outputs are O(G * W) ints and ride in the
    slack. Rounded down to whole tiles so shard geometry stays
    tile-aligned, floored at one tile (a budget below one tile streams
    tile-sized shards rather than failing).
    """
    td = tile_docs or DEFAULT_TILE_DOCS
    rows = int(budget_bytes) // (seq_len * 4 * 2)
    rows = max(td, (rows // td) * td)
    return max(1, min(rows, total_docs))


def spill_filter_compact(
    corpus,
    max_len: int,
    flt: tuple | None,
    params: engine.ExtractParams,
    device_budget_bytes: int | None = None,
    shard_docs: int | None = None,
    tile_docs: int | None = None,
    checkpoint_dir: str | None = None,
    reset_checkpoints: bool = False,
    stream_stats: dict | None = None,
    fail_after_shards: int | None = None,
) -> dict:
    """Corpus-scale candidate front end: shards as file regions.

    Streams a corpus that need not (and typically cannot) be
    device-resident: each shard is a region of ``corpus`` (a
    ``MemmapCorpus`` or any host [D, T] int32 array) staged through one
    reusable host buffer (``HostSpillStreamer``), probed by the
    streamed megakernel (``shard_lane`` -> single-launch DMA pipeline),
    and reduced to its lane wire unit; only lanes and one staged shard
    ever exist on device. Shard height comes from ``shard_docs`` or the
    ``device_budget_bytes`` sizing rule (``shard_docs_for_budget``;
    default ``DEFAULT_DEVICE_BUDGET_BYTES``).

    With ``checkpoint_dir`` every finished shard's lane is persisted
    (``LaneCheckpointStore``) and an interrupted run resumes from the
    last finished shard to *bit-identical* merged results — the final
    ``select_from_tiles`` merge consumes the same lanes either way. The
    final [N, L] window gather reads straight from the host corpus
    (``engine.candidates_from_flat_host``), so the merged output is
    field-for-field identical to ``sharded_filter_compact`` on a
    resident copy.

    ``stream_stats`` accumulates, besides the streaming counters,
    ``spill_bytes_staged``, ``windows`` (the ``D * T * max_len`` window
    grid) and ``survivors`` (the true filter survivors among them).

    ``fail_after_shards`` is the kill-switch test hook: raise after
    probing that many *fresh* shards this run (checkpoint loads don't
    count), simulating an interrupted job.
    """
    from repro.kernels.fused_probe import SIG_MODE_VARIANT

    if not isinstance(corpus, MemmapCorpus):
        corpus = MemmapCorpus(tokens=np.asarray(corpus))
    D, T = corpus.rows, corpus.seq_len
    engine.check_flat_index_space(D, T, max_len)
    if max_len > 32 or not params.kernel_compact:
        raise ValueError(
            "spill_filter_compact requires the in-kernel compaction "
            "epilogue (use_kernel=True with kernel_compact on, and "
            "max_len <= 32): without per-shard lanes there is nothing to "
            "spill-merge — run engine.fused_filter_compact on a resident "
            "corpus instead"
        )
    if shard_docs is None:
        budget = (DEFAULT_DEVICE_BUDGET_BYTES
                  if device_budget_bytes is None else device_budget_bytes)
        shard_docs = shard_docs_for_budget(D, T, budget, tile_docs)
    spec = plan_shards(D, 1, shard_docs, tile_docs)
    sig_mode = _stream_sig_mode(params, D, T, max_len)
    var = sig_mode == SIG_MODE_VARIANT
    NC = params.max_candidates
    store = None
    if checkpoint_dir is not None:
        store = LaneCheckpointStore(
            checkpoint_dir,
            job_manifest(spec, T, max_len, params, flt, sig_mode),
            reset=reset_checkpoints,
        )
    streamer = HostSpillStreamer(corpus, spec.shard_docs)

    lanes, totals, keys = [], [], []
    fresh = 0
    for s in range(spec.num_shards):
        if store is not None and store.has(s):
            lane, n, vk = store.load(s)
        else:
            if fail_after_shards is not None and fresh >= fail_after_shards:
                raise RuntimeError(
                    f"spill_filter_compact: simulated interruption after "
                    f"{fresh} fresh shards (fail_after_shards test hook)"
                )
            docs = streamer.stage(s)
            with span("eejoin.spill.probe"):
                lane, n, vk = shard_lane(
                    docs, s * spec.shard_docs, max_len, flt, params,
                    spec.tile_docs, sig_mode=sig_mode,
                    stream_stats=stream_stats,
                )
            del docs  # one staged shard on the device at a time
            if store is not None:
                store.save(s, lane, n, vk if var else None)
            fresh += 1
        lanes.append(lane)
        totals.append(n)
        if var:
            keys.append(vk)

    if stream_stats is not None:
        stream_stats["spill_bytes_staged"] = (
            stream_stats.get("spill_bytes_staged", 0) + streamer.bytes_staged)
        # the window grid the probe enumerates
        stream_stats["windows"] = (
            stream_stats.get("windows", 0) + D * T * max_len)
        if store is not None:
            store.flush_stats(stream_stats)
    with span("eejoin.lanes.merge"):
        counts = jnp.concatenate(totals)
        cands = jnp.concatenate(lanes, axis=0)
        sel, ok, n = select_from_tiles(counts, cands, NC)
    out, survivors = engine.candidates_from_flat_host(
        corpus.tokens, sel, ok, n, max_len, NC
    )
    if stream_stats is not None:
        stream_stats["survivors"] = (
            stream_stats.get("survivors", 0) + survivors)
    if var:
        with span("eejoin.lanes.merge"):
            vkeys = gather_from_tiles(
                counts, jnp.concatenate(keys, axis=0), NC)
        out = engine.attach_variant_keys(out, vkeys)
    return out

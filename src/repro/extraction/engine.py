"""Single-shard extraction cores.

These pure functions are the per-device bodies that the distributed
(shard_map) algorithms in ``extraction/distributed.py`` wrap. Both the
Index-on-Entities and the (ISHFilter &) SSJoin paths share the candidate
machinery: enumerate → (filter) → compact → probe → verify → emit.

Everything is static-shape: candidate and result buffers have fixed
capacities with surfaced overflow counts (never silent truncation).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.dictionary import PAD, Dictionary
from repro.core.filter import BloomFilter, token_in_filter
from repro.core.index import (
    INDEX_VARIANT,
    InvertedIndex,
    VariantIndex,
    build_inverted_index,
    build_variant_index,
    query_inverted,
    query_variant,
)
from repro.core.signatures import (
    SIG_LSH,
    SIG_NAMES,
    SIG_PREFIX,
    SIG_VARIANT,
    SIG_WORD,
    EntitySignatures,
    LshParams,
    entity_signatures,
    num_window_signatures,
    window_signatures,
)
from repro.core.variants import VARIANT_SEEDS, window_variant_key
from repro.extraction.results import (
    Matches,
    compact_matches,
    gather_from_tiles,
    select_from_tiles,
    select_nonzero,
)
from repro.extraction.substrings import window_base
from repro.extraction.verify import dedup_hits, verify_pairs
from repro.tracing import span

_SIGKEY_SEED = 33
# Bucket choice uses an independent hash of the signature so that bucket
# bits do not correlate with the owner-routing bits (sig % ndev) in the
# distributed shuffle — both are powers of two.
_BUCKET_SEED = 47


def _bucket_of(sig, n_buckets: int, *, xp):
    return (hashing.hash_u32(sig, seed=_BUCKET_SEED, xp=xp) % xp.uint32(n_buckets))


@dataclasses.dataclass(frozen=True)
class ExtractParams:
    """Static knobs of one extraction sub-job (one side of a plan).

    Construction validates every cross-field constraint up front (with
    the failing knob and the fix in the message) so misconfigurations
    surface here instead of as a shape/assert error deep inside a
    Pallas kernel.
    """

    gamma: float
    scheme: str  # index kind or signature scheme: word|prefix|lsh|variant
    sim_name: str = "extra"
    use_filter: bool = True
    max_candidates: int = 4096
    result_capacity: int = 4096
    lsh: LshParams = LshParams()
    use_kernel: bool = False
    # use_kernel only: compact candidates inside the fused_probe epilogue
    # (per-tile count + packed-index lanes). None resolves to
    # ``use_kernel`` (the epilogue lives inside the kernel, so it is the
    # default exactly when the kernel path is on). False keeps the
    # legacy XLA cumsum+searchsorted pass over the packed bitmap as a
    # live fallback.
    kernel_compact: bool | None = None
    # kernel_compact only: adaptive two-pass lane compaction — a cheap
    # count-only probe pass sizes the emit pass's lane width to the
    # measured per-tile survivor maximum (exact at any density) instead
    # of paying worst-case [G, NC] lanes. Needs a host sync between the
    # passes, so it is rejected under jit tracing.
    adaptive_lanes: bool = False
    # adaptive_lanes only: floor (and power-of-two rounding base) for
    # the adaptive emit-pass lane width. None -> fused_probe.MIN_LANE_WIDTH.
    lane_width: int | None = None
    # use_kernel only: emit window signatures inside the fused kernel.
    # None = auto (variant: lane-resident keys whenever the compaction
    # epilogue runs, dense tensor in the high-density regime; lsh: dense
    # tensor in the high-density regime — see ``resolve_sig_mode``).
    # True forces in-kernel emission (rejected for word/prefix, which
    # have no in-kernel recurrence); False forces the post-compaction
    # jnp signature path.
    kernel_sigs: bool | None = None
    # kernel_compact only: run whole shards through the single-launch
    # streamed megakernel (in-kernel double-buffered DMA over the tile
    # loop, ``ops.fused_probe_stream``) instead of one ``pallas_call``
    # per tile. None = auto: the streaming drivers stream whenever a
    # shard spans >= 2 tiles (a single tile has no pipeline to win).
    # True forces the streamed launch even for one tile; False pins the
    # per-tile launch loop (the parity baseline).
    streamed: bool | None = None

    def __post_init__(self):
        if self.kernel_compact is None:
            object.__setattr__(self, "kernel_compact", self.use_kernel)
        if self.scheme not in SIG_NAMES:
            raise ValueError(
                f"ExtractParams.scheme={self.scheme!r} is not a known "
                f"index kind / signature scheme; pick one of {SIG_NAMES}"
            )
        if not 0.0 < self.gamma <= 1.0:
            raise ValueError(
                f"ExtractParams.gamma={self.gamma} must be in (0, 1]: it is "
                "the similarity threshold of Def. 1 (1.0 = exact match)"
            )
        if self.max_candidates <= 0:
            raise ValueError(
                f"ExtractParams.max_candidates={self.max_candidates} must be "
                "positive: it is the static candidate-buffer capacity (and "
                "the [G, NC] lane width of ops.fused_probe_compact — the "
                "select_from_tiles merge requires lane width >= capacity)"
            )
        if self.result_capacity <= 0:
            raise ValueError(
                f"ExtractParams.result_capacity={self.result_capacity} must "
                "be positive: it is the static Matches-buffer capacity"
            )
        if self.lsh.bands <= 0 or self.lsh.rows <= 0:
            raise ValueError(
                f"ExtractParams.lsh bands={self.lsh.bands} rows="
                f"{self.lsh.rows} must both be positive"
            )
        if self.kernel_compact and not self.use_kernel:
            raise ValueError(
                "ExtractParams(kernel_compact=True) requires use_kernel=True: "
                "the compaction epilogue runs inside the fused_probe Pallas "
                "kernel, so there is no epilogue to enable on the unfused "
                "path (set use_kernel=True, or leave kernel_compact unset "
                "to track use_kernel automatically)"
            )
        if self.adaptive_lanes and not self.kernel_compact:
            raise ValueError(
                "ExtractParams(adaptive_lanes=True) requires "
                "kernel_compact=True: the two-pass lane sizing narrows the "
                "compaction epilogue's [G, NC] lanes, so there are no lanes "
                "to size on the legacy XLA compaction path (set "
                "use_kernel=True and leave kernel_compact unset, or drop "
                "adaptive_lanes)"
            )
        if self.lane_width is not None and not self.adaptive_lanes:
            raise ValueError(
                f"ExtractParams(lane_width={self.lane_width}) requires "
                "adaptive_lanes=True: lane_width is the floor of the "
                "adaptive emit-pass width — a fixed width below "
                "max_candidates cannot guarantee bit-exact lane merges, so "
                "the one-pass path always emits full [G, NC] lanes (enable "
                "adaptive_lanes, or drop lane_width)"
            )
        if self.lane_width is not None and not (
            0 < self.lane_width <= self.max_candidates
        ):
            raise ValueError(
                f"ExtractParams(lane_width={self.lane_width}) must be in "
                f"(0, max_candidates={self.max_candidates}]: it floors the "
                "adaptive emit-pass lane width, and lanes wider than the "
                "select_from_tiles merge capacity are never read"
            )
        if self.streamed and not self.kernel_compact:
            raise ValueError(
                "ExtractParams(streamed=True) requires kernel_compact=True: "
                "the streamed megakernel has no packed-bitmap output — its "
                "only products are the compaction epilogue's per-tile "
                "count/index lanes, so there is nothing to stream on the "
                "legacy XLA compaction path (set use_kernel=True and leave "
                "kernel_compact unset, or drop streamed)"
            )
        if self.kernel_sigs and not self.use_kernel:
            raise ValueError(
                "ExtractParams(kernel_sigs=True) requires use_kernel=True: "
                "in-kernel signature emission happens inside the fused_probe "
                "megakernel (set use_kernel=True, or leave kernel_sigs unset "
                "to let resolve_sig_mode decide)"
            )
        if self.kernel_sigs and self.scheme in (SIG_WORD, SIG_PREFIX):
            raise ValueError(
                f"ExtractParams(kernel_sigs=True, scheme={self.scheme!r}): "
                "the word/prefix schemes have no in-kernel signature "
                "recurrence — their window-side signatures are plain token "
                "hashes computed post-compaction, which previously made "
                "this combination fall back silently; use scheme='lsh' or "
                "'variant', or leave kernel_sigs unset"
            )


def check_flat_index_space(D: int, T: int, max_len: int) -> None:
    """Fail fast (and actionably) when flat window indices overflow int32.

    The [G, NC] candidate lanes carry flat (doc*T + pos)*L + (len-1)
    window indices as int32 end to end; past 2**31 the row offsets in
    ``sharded.stream_probe_tiles`` would wrap silently. Checked at every
    lane-producing entry point (sharded driver, serving pipeline).
    """
    if D * T * max_len >= 2**31:
        raise ValueError(
            f"flat window index space D*T*L = {D}x{T}x{max_len} = "
            f"{D * T * max_len} overflows int32 lane indices; split the "
            "corpus into separate driver calls (or shrink shard/batch rows)"
        )


@dataclasses.dataclass
class DeviceDictionary:
    """Device-resident dictionary slice (tokens + weights)."""

    tokens: jnp.ndarray  # [E, L] int32
    token_weight: jnp.ndarray  # [V] f32
    entity_offset: int  # global id of entity 0 in this slice

    @classmethod
    def from_host(cls, d: Dictionary, entity_offset: int = 0) -> "DeviceDictionary":
        return cls(
            tokens=jnp.asarray(d.tokens),
            token_weight=jnp.asarray(d.token_weight),
            entity_offset=entity_offset,
        )


# --------------------------------------------------------------------------
# Candidate gathering (shared front end; fused-filter Pallas kernel target)
# --------------------------------------------------------------------------


def survival_mask(doc_tokens, max_len: int, flt: tuple | None, use_kernel: bool = False):
    """[D,T] docs -> (base [D,T,L], survive [D,T,L]).

    ``flt`` is (bits, num_bits, num_hashes) or None. Candidate (p, l)
    survives iff valid (no PAD inside) and — when filtering — at least
    one of its tokens probes into the Bloom filter.
    """
    base = window_base(doc_tokens, max_len)
    real = base != PAD
    valid = jnp.cumprod(real.astype(jnp.int32), axis=-1).astype(bool)
    if flt is None:
        return base, valid
    bits, num_bits, num_hashes = flt
    if use_kernel:
        from repro.kernels import ops as kops

        surv = kops.window_filter(doc_tokens, bits, num_bits, num_hashes, max_len)
    else:
        tok_hit = token_in_filter(bits, num_bits, num_hashes, base)
        surv = jnp.cumsum(tok_hit.astype(jnp.int32), axis=-1) > 0
    return base, valid & surv


def _compact_bit_indices(rows, max_candidates: int):
    """rows [M, L] bool -> ascending flat set-bit indices [NC] (-1 pad).

    Two-stage static-shape compaction: survivor density is low (the
    whole point of the ISH filter), so a flat ``nonzero`` over M*L
    elements is the pipeline bottleneck — selecting the (at most NC)
    rows with any set bit first shrinks the second ``nonzero`` to NC*L
    elements (~5x wall-clock on CPU at D128xT512xL8). Exact at any
    density: every selected row holds >= 1 set bit, so NC rows always
    cover the first NC set bits.
    """
    M, L = rows.shape
    starts, _ = select_nonzero(rows.any(axis=-1), max_candidates)
    sub = rows[jnp.maximum(starts, 0)] & (starts >= 0)[:, None]  # [NC, L]
    sel, ok = select_nonzero(sub.reshape(-1), max_candidates)
    safe = jnp.maximum(sel, 0)
    idx = jnp.maximum(starts[safe // L], 0) * L + safe % L
    return jnp.where(ok, idx, -1), ok


@partial(jax.jit, static_argnames=("max_candidates",))
def compact_candidates(base, survive, max_candidates: int):
    """Flatten surviving candidates into fixed-capacity buffers.

    Returns dict with win_tokens [N, L], doc/pos/length [N] (-1 pad),
    n_survive [] and overflow [] counters.
    """
    D, T, L = base.shape
    idx, ok = _compact_bit_indices(survive.reshape(-1, L), max_candidates)
    flat = survive.reshape(-1)
    safe = jnp.maximum(idx, 0)
    d = safe // (T * L)
    rem = safe % (T * L)
    p = rem // L
    l = rem % L  # length-1
    toks = base[d, p]  # [N, L]
    lens_mask = jnp.arange(L)[None, :] <= l[:, None]
    toks = jnp.where(lens_mask & ok[:, None], toks, PAD)
    n = flat.sum().astype(jnp.int32)
    return dict(
        win_tokens=toks.astype(jnp.int32),
        win_valid=ok,
        doc=jnp.where(ok, d, -1).astype(jnp.int32),
        pos=jnp.where(ok, p, -1).astype(jnp.int32),
        length=jnp.where(ok, l + 1, -1).astype(jnp.int32),
        n_survive=n,
        overflow=jnp.maximum(n - max_candidates, 0).astype(jnp.int32),
    )


@partial(jax.jit, static_argnames=("max_len", "max_candidates"))
def candidates_from_flat(doc_tokens, flat_idx, ok, n_survive, max_len: int,
                         max_candidates: int) -> dict:
    """Build the ``compact_candidates`` dict from selected flat indices.

    ``flat_idx`` [N] are (doc*T + pos)*max_len + (len-1) window indices
    (already clamped >= 0 where ``ok`` is False); windows are gathered
    straight from the [D, T] token rows — no [D,T,L] base tensor. Shared
    tail of the fused single-call, legacy-XLA, and sharded-streaming
    compaction paths, so they stay field-for-field identical.
    """
    D, T = doc_tokens.shape
    L = max_len
    safe = jnp.maximum(flat_idx, 0)
    d = safe // (T * L)
    rem = safe % (T * L)
    p = rem // L
    l = rem % L  # length-1
    cols = p[:, None] + jnp.arange(L)[None, :]  # [N, L]
    toks = doc_tokens[d[:, None], jnp.minimum(cols, T - 1)]
    lens_mask = (jnp.arange(L)[None, :] <= l[:, None]) & (cols < T)
    toks = jnp.where(lens_mask & ok[:, None], toks, PAD)
    n = n_survive.astype(jnp.int32)
    return dict(
        win_tokens=toks.astype(jnp.int32),
        win_valid=ok,
        doc=jnp.where(ok, d, -1).astype(jnp.int32),
        pos=jnp.where(ok, p, -1).astype(jnp.int32),
        length=jnp.where(ok, l + 1, -1).astype(jnp.int32),
        n_survive=n,
        overflow=jnp.maximum(n - max_candidates, 0).astype(jnp.int32),
    )


def candidates_from_flat_host(doc_tokens, flat_idx, ok, n_survive,
                              max_len: int, max_candidates: int
                              ) -> tuple[dict, int]:
    """``candidates_from_flat`` with the window gather on the *host*.

    The spill-streaming driver selects candidates from per-shard lanes
    without the corpus ever being device-resident, so the final [N, L]
    window gather must read token rows from the host corpus (typically
    a ``np.memmap`` — fancy-indexing it touches only the ~N needed
    rows, not the file). Field-for-field and bit-identical to the
    device gather; only the produced [N, L] windows (N = NC, tiny) are
    shipped to the device. Three sibling spans time the device wait,
    the gather and the upload. Returns the fields and the true survivor
    total (``n_survive`` as read on the host).
    """
    T = doc_tokens.shape[1]
    L = max_len
    with span("eejoin.windows.sync"):
        flat = np.asarray(flat_idx)
        okh = np.asarray(ok)
        n = np.int32(np.asarray(n_survive))
    with span("eejoin.windows.gather"):
        safe = np.maximum(flat, 0).astype(np.int64)
        d = safe // (T * L)
        rem = safe % (T * L)
        p = rem // L
        l = rem % L  # length-1
        rows = np.asarray(doc_tokens[d])  # [N, T]: the only corpus touch
        cols = p[:, None] + np.arange(L)[None, :]  # [N, L]
        toks = rows[np.arange(rows.shape[0])[:, None],
                    np.minimum(cols, T - 1)]
        lens_mask = (np.arange(L)[None, :] <= l[:, None]) & (cols < T)
        toks = np.where(lens_mask & okh[:, None], toks, PAD)
    with span("eejoin.windows.upload"):
        return dict(
            win_tokens=jnp.asarray(toks.astype(np.int32)),
            win_valid=jnp.asarray(okh),
            doc=jnp.asarray(np.where(okh, d, -1).astype(np.int32)),
            pos=jnp.asarray(np.where(okh, p, -1).astype(np.int32)),
            length=jnp.asarray(np.where(okh, l + 1, -1).astype(np.int32)),
            n_survive=jnp.asarray(n),
            overflow=jnp.asarray(np.int32(max(int(n) - max_candidates, 0))),
        ), int(n)


def attach_kernel_sigs(cands: dict, kernel_sigs, params: ExtractParams) -> dict:
    """Gather in-kernel [D,T,L,B] band sigs at the compacted candidates.

    Padded slots carry the all-invalid-window band constants so the
    tensor stays bit-identical to ``window_signatures`` on them too.
    """
    from repro.kernels.fused_probe import empty_band_sigs

    ok = cands["win_valid"]
    d = jnp.maximum(cands["doc"], 0)
    p = jnp.maximum(cands["pos"], 0)
    l = jnp.maximum(cands["length"] - 1, 0)
    gathered = kernel_sigs[d, p, l]  # [N, B]
    empty = jnp.asarray(empty_band_sigs(params.lsh.bands, params.lsh.rows))
    cands["sigs"] = jnp.where(ok[:, None], gathered, empty[None, :])
    cands["sig_mask"] = jnp.broadcast_to(ok[:, None], gathered.shape)
    return cands


def resolve_sig_mode(params: ExtractParams, D: int, T: int, L: int) -> str:
    """Pick the kernel's in-kernel signature emission mode for a shape.

    * ``lsh`` — band-sig emission computes minima for every (pos, len)
      window and stores a [D,T,L,B] tensor: profitable only when the
      compacted candidate stream covers the whole window grid (then the
      post-compaction re-gather would move the same bytes); in the
      filter's target low-density regime, post-compaction signatures
      over [N, L] windows are far less work. ``kernel_sigs=True``
      forces dense emission regardless.
    * ``variant`` — with the compaction epilogue the key pairs ride the
      candidate lanes ([G, NC, 2], no dense tensor), which is cheap at
      *any* density, so the fused path is the default whenever the
      epilogue runs; without the epilogue the dense [D,T,L,2] tensor
      follows the same density rule as lsh (or ``kernel_sigs=True``).
    * ``kernel_sigs=False`` forces the post-compaction jnp path.
    """
    from repro.kernels.fused_probe import (
        SIG_MODE_LSH,
        SIG_MODE_NONE,
        SIG_MODE_VARIANT,
    )

    if params.kernel_sigs is False:
        return SIG_MODE_NONE
    forced = params.kernel_sigs is True
    dense = params.max_candidates >= D * T * L
    if params.scheme == SIG_LSH and (dense or forced):
        return SIG_MODE_LSH
    if params.scheme == SIG_VARIANT and (
        params.kernel_compact or dense or forced
    ):
        return SIG_MODE_VARIANT
    return SIG_MODE_NONE


def attach_variant_keys(cands: dict, keys) -> dict:
    """Attach fused variant key pairs [N, 2] to compacted candidates.

    Sets ``sigs``/``sig_mask`` bit-identically to
    ``window_signatures("variant", ...)`` over the gathered windows
    (the window-side SSJoin signature is key1) and ``variant_keys`` =
    (k1, k2) for the variant index probe (``extract_index_part``).
    Padded slots carry 0 — the ``set_hash`` of an all-PAD window under
    either seed — so no consumer needs a special case for them.
    """
    ok = cands["win_valid"]
    k1 = jnp.where(ok, keys[:, 0], jnp.uint32(0))
    k2 = jnp.where(ok, keys[:, 1], jnp.uint32(0))
    cands["sigs"] = k1[:, None]
    cands["sig_mask"] = ok[:, None]
    cands["variant_keys"] = (k1, k2)
    return cands


def fused_filter_compact(
    doc_tokens,
    max_len: int,
    flt: tuple | None,
    params: ExtractParams,
    sig_mode: str | None = None,
) -> dict:
    """use_kernel fast path: one-pass megakernel -> direct compaction.

    Replaces ``survival_mask`` + ``compact_candidates`` (and, for the
    LSH scheme, ``window_signatures``) with a single streaming
    ``fused_probe`` kernel pass: the [D,T,L] int32 base and int8 mask
    are never materialised — survival arrives as a packed [D,T] uint32
    bitmap, candidate windows are gathered straight from the [D,T]
    token array, and LSH band signatures come out of the kernel
    (bit-identical to ``window_signatures``; padded slots carry the
    all-invalid-window band constants). Returns the ``compact_candidates``
    dict, plus ``sigs``/``sig_mask`` when the scheme is ``lsh``.

    Candidate selection runs in the kernel's compaction epilogue by
    default (per-tile survivor counts + packed-index lanes merged by
    ``select_from_tiles``; the [D, T] bitmap is never re-read).
    ``params.kernel_compact=False`` keeps the legacy two-stage XLA
    compaction over the packed bitmap — same outputs, exercised by tests
    so the fallback cannot rot.

    For the ``variant`` scheme the kernel emits both 32-bit set-hash
    keys in-kernel (lane payload with the epilogue, dense tensor on the
    legacy path in the high-density regime) — bit-identical to
    ``core.variants.window_variant_key`` over the gathered windows.
    ``params.adaptive_lanes`` enables the two-pass lane compaction: a
    count-only pass measures per-tile survivor maxima, the emit pass
    then runs with ``round_lane_width``-sized lanes (exact merge at any
    density). The sizing needs a host sync, so adaptive runs cannot be
    traced under jit — call un-jitted (every step here is jitted
    internally) or drop ``adaptive_lanes``.
    """
    import numpy as _np

    from repro.kernels import ops as kops
    from repro.kernels.fused_probe import (
        MIN_LANE_WIDTH,
        SIG_MODE_LSH,
        SIG_MODE_VARIANT,
        round_lane_width,
    )

    D, T = doc_tokens.shape
    L = max_len
    if L > 32:
        # the packed bitmap holds one length per uint32 bit; longer
        # windows fall back to the standalone window_filter kernel +
        # dense compaction (still a single streaming probe pass)
        base, surv = survival_mask(doc_tokens, max_len, flt, use_kernel=True)
        return compact_candidates(base, surv, params.max_candidates)
    if sig_mode is None:
        sig_mode = resolve_sig_mode(params, D, T, L)
    lsh = sig_mode == SIG_MODE_LSH
    var = sig_mode == SIG_MODE_VARIANT
    NC = params.max_candidates
    keys = None
    if params.kernel_compact:
        lane_w = None
        if params.adaptive_lanes:
            if isinstance(doc_tokens, jax.core.Tracer):
                raise ValueError(
                    "ExtractParams(adaptive_lanes=True) cannot run under "
                    "jit tracing: sizing the emit pass's lane width needs "
                    "a host read of the count pass's per-tile survivor "
                    "maxima; call fused_filter_compact un-jitted (its "
                    "kernel passes are jitted internally) or use the "
                    "fixed worst-case lanes"
                )
            counts0 = kops.fused_probe_count(doc_tokens, flt, max_len, NC)
            mx = int(_np.asarray(counts0).max())
            lane_w = round_lane_width(
                mx, NC, params.lane_width or MIN_LANE_WIDTH
            )
        # in-kernel compaction epilogue: per-tile survivor counts and
        # ascending packed-index lanes; the O(G + NC) merge below is the
        # only XLA-side work — no pass over the [D, T] bitmap.
        packed, kernel_sigs, counts, tiles, vkeys = kops.fused_probe_compact(
            doc_tokens, flt, max_len, NC, sig_mode,
            params.lsh.bands, params.lsh.rows, lane_width=lane_w,
        )
        sel, ok, n = select_from_tiles(counts, tiles, NC)
        if var:
            keys = gather_from_tiles(counts, vkeys, NC)  # [NC, 2]
    else:
        packed, kernel_sigs = kops.fused_probe(
            doc_tokens, flt, max_len, sig_mode, params.lsh.bands, params.lsh.rows
        )
        # legacy two-stage compaction off the packed bitmap: nonzero over
        # the [D*T] word stream, then unpack only the selected words' bits
        # — the [D,T,L] bool survival tensor is never materialised.
        shifts = jnp.arange(L, dtype=jnp.uint32)
        flat_words = packed.reshape(-1)
        starts, _ = select_nonzero(flat_words != 0, NC)
        words = flat_words[jnp.maximum(starts, 0)] * (starts >= 0)
        sub = ((words[:, None] >> shifts[None, :]) & jnp.uint32(1)).astype(bool)
        ssel, ok = select_nonzero(sub.reshape(-1), NC)
        ssafe = jnp.maximum(ssel, 0)
        sel = jnp.maximum(starts[ssafe // L], 0) * L + ssafe % L
        n = jax.lax.population_count(packed).sum().astype(jnp.int32)
        if var:
            # dense [D, T, L, 2] key tensor: gather at the selection
            safe = jnp.maximum(sel, 0)
            d, rem = safe // (T * L), safe % (T * L)
            keys = kernel_sigs[d, rem // L, rem % L]  # [NC, 2]
    cands = candidates_from_flat(doc_tokens, sel, ok, n, max_len, NC)
    if lsh:
        cands = attach_kernel_sigs(cands, kernel_sigs, params)
    if var:
        cands = attach_variant_keys(cands, keys)
    return cands


def window_sigs_for(cands: dict, params: ExtractParams):
    """Window signatures for compacted candidates: kernel-emitted when
    the fused path provided them (``cands["sigs"]``), else computed from
    the gathered windows. Returns (sigs [N, S], mask [N, S]); callers
    still AND the mask with ``cands["win_valid"]``."""
    if "sigs" in cands:
        return cands["sigs"], cands["sig_mask"]
    toks = cands["win_tokens"]
    return window_signatures(params.scheme, toks, toks != PAD, params.gamma, params.lsh)


def _emit(cands, hits, scores, ent_global, params: ExtractParams) -> Matches:
    """Flatten per-candidate [N,K] hits into a Matches buffer."""
    N, K = hits.shape
    rep = lambda a: jnp.repeat(a, K)
    return compact_matches(
        hits.reshape(-1),
        rep(cands["doc"]),
        rep(cands["pos"]),
        rep(cands["length"]),
        ent_global.reshape(-1),
        scores.reshape(-1),
        params.result_capacity,
    )


# --------------------------------------------------------------------------
# Index-on-Entities (§3.2): broadcast index, local lookups, multi-pass
# --------------------------------------------------------------------------


@dataclasses.dataclass
class BuiltIndex:
    """One memory-budget partition of an entity index (device arrays)."""

    kind: str
    entity_offset: int
    # inverted kinds
    postings: jnp.ndarray | None = None  # [V, P]
    # variant kind
    keys1: jnp.ndarray | None = None
    keys2: jnp.ndarray | None = None
    ents: jnp.ndarray | None = None
    n_buckets: int = 0
    nbytes: int = 0


def build_index_partitions(
    dictionary: Dictionary,
    kind: str,
    gamma: float,
    memory_budget_bytes: int,
    entity_offset: int = 0,
) -> list[BuiltIndex]:
    """Split entities into ranges whose index each fits the budget
    (Def. 3's |E| / M_e multi-pass structure)."""
    E = dictionary.num_entities
    if E == 0:
        return []
    parts: list[BuiltIndex] = []
    start = 0
    # Estimate bytes/entity from a probe build on a small slice, then
    # partition; rebuild per part (host-side, cheap vs corpus work).
    probe = dictionary.slice(0, min(E, 256))
    if kind == INDEX_VARIANT:
        probe_idx = build_variant_index(probe, gamma)
    else:
        probe_idx = build_inverted_index(probe, kind, gamma)
    per_entity = max(probe_idx.nbytes / probe.num_entities, 1.0)
    chunk = max(int(memory_budget_bytes / per_entity), 1)
    while start < E:
        stop = min(start + chunk, E)
        sl = dictionary.slice(start, stop)
        if kind == INDEX_VARIANT:
            vi = build_variant_index(sl, gamma)
            parts.append(
                BuiltIndex(
                    kind=kind,
                    entity_offset=entity_offset + start,
                    keys1=jnp.asarray(vi.keys1),
                    keys2=jnp.asarray(vi.keys2),
                    ents=jnp.asarray(vi.entity_id),
                    n_buckets=vi.n_buckets,
                    nbytes=vi.nbytes,
                )
            )
        else:
            ii = build_inverted_index(sl, kind, gamma)
            parts.append(
                BuiltIndex(
                    kind=kind,
                    entity_offset=entity_offset + start,
                    postings=jnp.asarray(ii.postings_padded),
                    nbytes=ii.nbytes,
                )
            )
        start = stop
    return parts


def extract_index_part(
    cands: dict,
    part: BuiltIndex,
    ddict: DeviceDictionary,
    params: ExtractParams,
) -> Matches:
    """One pass of index lookups + verification over compacted candidates."""
    toks, ok = cands["win_tokens"], cands["win_valid"]
    if part.kind == INDEX_VARIANT:
        if "variant_keys" in cands:
            # fused path: both set-hash keys were computed in-kernel
            # (bit-identical to window_variant_key, incl. padded slots)
            k1, k2 = cands["variant_keys"]
        else:
            k1, k2 = window_variant_key(toks, toks != PAD, xp=jnp)
        ents = query_variant(part.keys1, part.keys2, part.ents, part.n_buckets, k1, k2)
        ents = jnp.where(ok[:, None], ents, -1)
        hits, scores = verify_pairs(
            toks,
            ents + jnp.int32(part.entity_offset - ddict.entity_offset) * (ents >= 0),
            ddict.tokens,
            ddict.token_weight,
            gamma=0.0,  # variant lookups are exact: no threshold re-check
            sim_name=params.sim_name,
            use_kernel=params.use_kernel,
        )
    else:
        local = query_inverted(part.postings, toks, toks != PAD)  # [N, L*P]
        local = jnp.where(ok[:, None], local, -1)
        hits, scores = verify_pairs(
            toks,
            local + jnp.int32(part.entity_offset - ddict.entity_offset) * (local >= 0),
            ddict.tokens,
            ddict.token_weight,
            gamma=params.gamma,
            sim_name=params.sim_name,
            use_kernel=params.use_kernel,
        )
        ents = local
    hits = dedup_hits(hits, ents)
    ent_global = jnp.where(ents >= 0, ents + part.entity_offset, -1)
    return _emit(cands, hits, scores, ent_global, params)


# --------------------------------------------------------------------------
# (ISHFilter &) SSJoin (§3.1/3.3): signature probe against a sig table
# --------------------------------------------------------------------------


@dataclasses.dataclass
class SigTable:
    """Static bucketed hash table: signature -> entity ids."""

    keys1: jnp.ndarray  # [B, cap] uint32
    keys2: jnp.ndarray
    ents: jnp.ndarray  # [B, cap] int32, -1 pad
    n_buckets: int
    bucket_cap: int
    entity_offset: int
    nbytes: int = 0
    skew: float = 1.0  # max/mean bucket load (feeds the cost model)


def build_sig_table(
    esigs: EntitySignatures,
    entity_offset: int = 0,
    load_factor: float = 0.5,
) -> SigTable:
    sig = esigs.sig.astype(np.uint32)
    n = max(len(sig), 1)
    n_buckets = 1 << max(3, int(np.ceil(np.log2(n / load_factor + 1))))
    k2 = hashing.hash_u32(sig, seed=_SIGKEY_SEED, xp=np)
    bucket = _bucket_of(sig, n_buckets, xp=np).astype(np.int64)
    counts = np.bincount(bucket, minlength=n_buckets)
    cap = max(4, int(counts.max()) if counts.size else 4)
    keys1 = np.zeros((n_buckets, cap), dtype=np.uint32)
    keys2 = np.zeros((n_buckets, cap), dtype=np.uint32)
    ents = np.full((n_buckets, cap), -1, dtype=np.int32)
    if len(sig):
        # vectorised bucket fill: stable argsort groups rows by bucket
        # (preserving insertion order within each), the rank-in-bucket is
        # position minus the bucket's first position, and one fancy
        # scatter lands every row — no Python-level loop over signatures.
        order = np.argsort(bucket, kind="stable")
        sb = bucket[order]
        rank = np.arange(len(sig)) - np.searchsorted(sb, sb)
        keys1[sb, rank] = sig[order]
        keys2[sb, rank] = k2[order]
        ents[sb, rank] = esigs.entity_id[order]
    mean = max(counts.mean(), 1e-9)
    return SigTable(
        keys1=jnp.asarray(keys1),
        keys2=jnp.asarray(keys2),
        ents=jnp.asarray(ents),
        n_buckets=n_buckets,
        bucket_cap=cap,
        entity_offset=entity_offset,
        nbytes=int(keys1.nbytes + keys2.nbytes + ents.nbytes),
        skew=float(counts.max() / mean) if counts.size else 1.0,
    )


def probe_sig_table(table: SigTable, sigs, sig_mask):
    """sigs [N, S] uint32 -> candidate entities [N, S*cap] (-1 invalid)."""
    k2 = hashing.hash_u32(sigs, seed=_SIGKEY_SEED, xp=jnp)
    b = _bucket_of(sigs, table.n_buckets, xp=jnp).astype(jnp.int32)
    tk1, tk2, te = table.keys1[b], table.keys2[b], table.ents[b]  # [N,S,cap]
    hit = (tk1 == sigs[..., None]) & (tk2 == k2[..., None]) & (te >= 0)
    hit = hit & sig_mask[..., None]
    ents = jnp.where(hit, te, -1)
    return ents.reshape(ents.shape[0], -1)


def extract_ssjoin_local(
    cands: dict,
    table: SigTable,
    ddict: DeviceDictionary,
    params: ExtractParams,
) -> Matches:
    """SSJoin probe+verify with the signature table fully local.

    The distributed version routes candidates to the table's owner
    device between ``window_signatures`` and ``probe_sig_table``.
    """
    toks, ok = cands["win_tokens"], cands["win_valid"]
    sigs, mask = window_sigs_for(cands, params)
    ents = probe_sig_table(table, sigs, mask & ok[:, None])
    gamma = 0.0 if params.scheme == SIG_VARIANT else params.gamma
    hits, scores = verify_pairs(
        toks,
        ents + jnp.int32(table.entity_offset - ddict.entity_offset) * (ents >= 0),
        ddict.tokens,
        ddict.token_weight,
        gamma=gamma,
        sim_name=params.sim_name,
        use_kernel=params.use_kernel,
    )
    hits = dedup_hits(hits, ents)
    ent_global = jnp.where(ents >= 0, ents + table.entity_offset, -1)
    return _emit(cands, hits, scores, ent_global, params)

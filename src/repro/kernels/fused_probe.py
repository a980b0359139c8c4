"""Pallas TPU megakernel: fused ISH-filter probe + window signatures.

This fuses the whole map-side candidate front end — validity, Bloom
survival, and (for the LSH scheme) per-window MinHash band signatures —
into ONE ``pallas_call`` that streams each ``[Bd, T]`` document tile
HBM->VMEM exactly once. The unfused pipeline runs three jitted passes
(``window_base`` -> ``token_in_filter`` -> ``window_signatures``) and
round-trips the ``L``-times-expanded ``[D, T, L]`` base / survival
tensors through HBM between them; here every per-window quantity is a
*running* recurrence over one in-register token stream:

    real[t]          = tok[t] != PAD
    hit[t]           = all k Bloom probes of tok[t] set   (sign bit, see below)
    valid[t, l]      = AND(real[t .. t+l])                (running-and)
    survive[t, l]    = valid[t, l] & OR(hit[t .. t+l])    (running-or)
    rmin_i[t, l]     = MIN(h_i(tok[t .. t+l]))            (running-min, i < B*R)
    sig[t, l, b]     = combine(rmin_{bR} .. rmin_{bR+R-1}, b+1)
    dup[t, l]        = OR(tok[t+l] == tok[t .. t+l-1])    (shifted compares)
    fold_i[t, l]     = (SUM, XOR, COUNT) of h_i(tok[t+j]) over the
                       first-occurrence positions j <= l  (running fold)
    key_i[t, l]      = mix(sum ^ xor*C1 ^ cnt*GOLDEN)     (set_hash finalise)

The survival mask is emitted *packed*: bit ``l`` of ``packed[d, t]``
(uint32, so L <= 32) is ``survive[d, t, l]`` — a 4 B/token store instead
of the unfused path's L B/token int8 mask and 4L B/token int32 base.
Band signatures are bit-identical to ``core.signatures.window_signatures``
for the ``lsh`` scheme: MinHash minima are duplicate-insensitive, so the
first-occurrence masking the jnp path applies never changes a row
minimum, and the seeds / murmur3 finaliser / combine below match
``core.hashing`` exactly.

The ``variant`` scheme (paper Definition 2) is fused the same way:
``core.hashing.set_hash`` is a commutative (sum, xor, count) fold over
per-token hashes, so both 32-bit variant keys extend token by token —
the only obstacle to streaming is first-occurrence masking, which the
kernel makes streamable with a *register-resident duplicate mask*:
token ``t+l`` is a duplicate inside window ``[t, t+l]`` iff it equals
any of ``tok[t .. t+l-1]``, i.e. iff the current shifted token stream
matches any of the <= 31 previously shifted streams (all VMEM/register
resident, no HBM traffic). Masked contributions then feed the running
fold, and the finalised keys are bit-identical to
``core.variants.window_variant_key`` at every (pos, len) — including
PAD-heavy and all-duplicate windows (see ``streaming_first_occurrence``
for the host-testable reference of the mask). With the compaction
epilogue on, the keys are not stored densely: they ride the candidate
lanes as a tiny ``[G, NC, 2]`` payload gathered at the surviving flat
indices.

HBM-traffic accounting (per document token; L = max_len, K = num_hashes,
B = bands; see ``hbm_bytes_unfused`` / ``hbm_bytes_fused``):

    unfused  read 4 (docs) + write 4L (base) + read 4L (filter probe)
             + write L (int8 mask) + read L (compaction scan)
    fused    read 4 (docs) + write 4 (packed bitmap)
             [+ write 4LB (band sigs, lsh mode only)]
             [+ G*(1+W)*4 lane ints + G*W*8 variant-key payload,
                epilogue mode; W = NC one-pass, measured two-pass]

For the filter stages alone that is a ~(10L+4)/8 ≈ 10x traffic cut at
L = 8; the kernel additionally hashes each token K times instead of the
unfused path's K*L times (the [D,T,L] base repeats every token L times).
Downstream, the engine's fused compaction gathers candidate windows
straight from the [D, T] token array — ``window_base`` is never
materialised (see ``extraction.engine.fused_filter_compact``).

With ``candidates > 0`` the kernel also runs a *compaction epilogue*:
the per-tile survivor count is accumulated in registers as the length
recurrence runs, and the tile's first ``candidates``
surviving (doc, pos, len) triples are rank-compacted into an ascending
[G, candidates] flat-index lane: prefix counts are exact triangular
matmuls and the selection one-hot matmul gathers (``_emit_lanes``),
since the TPU lowering has no cumsum, searchsorted or gather. Candidate selection then reads only these lanes — the
last XLA pass over the full [D, T] bitmap (cumsum + searchsorted in
``extraction.results.select_nonzero``) disappears, which matters because
candidate-generation traffic, not verification, dominates at scale.

The lane width is *decoupled* from the candidate capacity: a one-pass
emit must keep ``candidates = NC`` wide lanes for bit parity (the
global first-NC could all land in one tile), but an **adaptive
two-pass** run first streams a ``count_only=True`` pass (per-tile
counts, no lane store), sizes the emit pass's lane width to the
measured per-tile survivor maximum (``round_lane_width``), and re-runs
with ``candidates = W << NC`` — every tile's lane then holds *all* of
its survivors, so the ``select_from_tiles`` merge stays bit-identical
while lane traffic drops from ``G*(1+NC)`` to ``G*(1+W)`` ints. Both
passes share the NC-derived tile height (``compact_tile_height``) so
their grids — and therefore the per-tile counts — line up exactly.

Tiling: one full document row per grid row ([Bd, T] tiles) so windows
never straddle a tile edge. The Bloom probe is one XLA gather per token
*before* the kernel (``flag_bloom_hits``): Mosaic has no arbitrary 1-D
VMEM gather, so each token's hit rides its int32 sign bit and the
kernel streams a single flagged token array. Compiled for the TPU
(``tests/test_tpu_compile.py`` lowers it for a described v5e) and run
in interpret mode on the CPU.

**Streaming mode** (``fused_probe_stream_pallas``): the per-tile grid
itself becomes an in-kernel loop. The doc array stays in HBM
(``memory_space=ANY``) and the kernel double-buffers [bd, T] chunks
through a 2-slot VMEM buffer with ``make_async_copy`` — the DMA for
chunk g+1 is started before chunk g's recurrence runs, so one launch
consumes an entire shard with copy-in overlapped against compute. The
recurrence and lane epilogue are the *same functions* the grid kernel
runs (``_probe_recurrence`` / ``_emit_lanes``), so streamed outputs are
bit-identical to the per-tile launch loop; only the packed bitmap and
dense signature tensors are dropped (they are exactly the per-launch
HBM round trips streaming exists to elide — see ``hbm_bytes_fused``
with ``streamed=True``).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing
from repro.core.filter import token_in_filter
from repro.core.hashing import _C1, _GOLDEN
from repro.core.signatures import _LSH_SEED_BASE
from repro.core.variants import VARIANT_SEEDS
from repro.kernels._hashing import combine as _combine
from repro.kernels._hashing import hash_seeded as _hash
from repro.kernels._hashing import mix as _mix
from repro.kernels._hashing import umin as _umin

_MAX_U32 = 0xFFFFFFFF

DEFAULT_BD = 8

#: most [bd, T] words one compaction tile may hold (VMEM bound of the
#: epilogue's per-tile prefix counts and one-hot row gathers)
MAX_TILE_WORDS = 1 << 15

#: smallest adaptive emit-pass lane width: keeps the lane store aligned
#: and bounds recompiles (widths are rounded up to powers of two).
MIN_LANE_WIDTH = 8


def compact_tile_height(D: int, T: int, candidates: int) -> int:
    """Doc-tile height for the compaction epilogue.

    Each grid tile emits a full-width [1 + candidates] lane (parity
    requires it — the global first-NC could all land in one tile), so
    lane traffic is G * (1 + NC) * 8 B and only stays well under the
    bitmap bytes it replaces when bd >= 4 * NC / T. Single source of
    truth for ``ops.fused_probe_compact`` and ``hbm_bytes_fused``.

    Adaptive two-pass runs keep this NC-derived height for *both* the
    count pass and the narrower emit pass: the emit width W is chosen
    from the count pass's per-tile maxima, which is only sound if the
    two grids tile the documents identically. The narrower lanes then
    undercut even this conservative geometry (G*(1+W) vs G*(1+NC)
    ints); see ``hbm_bytes_fused(two_pass=True)`` for the full trade.
    The height is a multiple of 8 (the int32 sublane tile) and at most
    ``MAX_TILE_WORDS // T`` rows, so the epilogue's per-tile state fits
    VMEM at any merge capacity; ``lane_width_for`` then caps the lane at
    the tile's window count.
    """
    cap = max(DEFAULT_BD, MAX_TILE_WORDS // max(T, 1) // 8 * 8)
    bd = min(max(DEFAULT_BD, -(-4 * candidates // max(T, 1))), cap)
    return min(-(-bd // 8) * 8, max(D, 1))


def lane_width_for(width: int, bd: int, T: int, max_len: int) -> int:
    """Emitted lane width: ``width`` capped at the tile's window count.

    A [bd, T] tile holds at most ``bd * T * max_len`` survivors, so a
    lane that wide always holds all of them: the capped lane is
    *complete*, and ``select_from_tiles`` merges complete lanes exactly
    at any capacity. Every lane narrower than the merge capacity is
    therefore complete — capped here, or sized from a count pass.
    """
    return min(int(width), bd * T * max_len)


def round_lane_width(max_count: int, cap: int,
                     floor: int = MIN_LANE_WIDTH) -> int:
    """Adaptive emit-pass lane width for a measured per-tile maximum.

    Rounds the measured per-tile survivor maximum up to a power of two
    (>= ``floor``) so repeated runs at similar densities reuse the same
    compiled kernel, and caps at ``cap`` (= NC: wider lanes than the
    merge capacity are never read). Any W >= max_count keeps the merge
    exact — every tile's lane holds all of its survivors.
    """
    w = max(int(max_count), int(floor), 1)
    w = 1 << (w - 1).bit_length()
    return max(min(w, int(cap)), 1)


SIG_MODE_NONE = "none"
SIG_MODE_LSH = "lsh"
SIG_MODE_VARIANT = "variant"


def streaming_first_occurrence(tokens, *, xp=np):
    """First-occurrence mask via the kernel's shifted-compare recurrence.

    Host-testable reference of the in-kernel duplicate mask: position
    ``j`` of each padded window row is marked iff it is real (non-PAD)
    and equals none of positions ``0 .. j-1`` — exactly the <= L-1
    shifted compares the kernel performs against its previously shifted
    token streams. Bit-identical to
    ``core.semantics.first_occurrence_mask`` (property-tested); kept
    next to the kernel so the trick has a readable, testable form.
    """
    L = tokens.shape[-1]
    dup = xp.zeros(tokens.shape, dtype=bool)
    for j in range(1, L):
        hit = xp.zeros(tokens.shape[:-1], dtype=bool)
        for i in range(j):
            hit = hit | (tokens[..., i] == tokens[..., j])
        if xp is np:
            dup[..., j] = hit
        else:
            dup = dup.at[..., j].set(hit)
    return (tokens != 0) & ~dup  # PAD == 0


def empty_band_sigs(bands: int, rows: int) -> np.ndarray:
    """[bands] uint32: the band signatures of an all-invalid window.

    Matches ``signatures._minhash_np`` on a row with no valid tokens
    (every row-minimum is 0xFFFFFFFF). Used by the engine to pad
    non-surviving candidate slots so the fused signature tensor is
    bit-identical to ``window_signatures`` on PAD-only windows too.
    """
    row = np.full((1,), _MAX_U32, dtype=np.uint32)
    out = []
    for b in range(bands):
        band = row
        for _ in range(1, rows):
            band = hashing.combine(band, row, xp=np)
        band = hashing.combine(band, np.full((1,), b + 1, dtype=np.uint32), xp=np)
        out.append(band[0])
    return np.array(out, dtype=np.uint32)


_HIT_FLAG = -(2**31)  # int32 sign bit: the token's Bloom hit, set host-side


def flag_bloom_hits(doc_tokens, bits, num_bits: int, num_hashes: int):
    """Fold each token's Bloom-filter hit into its int32 sign bit.

    The probe is an XLA gather over the ``[num_bits // 32]`` word array
    (``core.filter.token_in_filter``), run once per token before the
    kernel: Mosaic has no arbitrary 1-D VMEM gather, so the kernel reads
    the hit from the token stream it already streams (token ids are
    non-negative, so bit 31 is free) and strips it with ``_split_stream``.
    One path on every backend; the kernel never sees the bitmap.
    """
    hit = token_in_filter(bits, num_bits, num_hashes, doc_tokens)
    return jnp.where(hit, doc_tokens | jnp.int32(_HIT_FLAG), doc_tokens)


def _split_stream(enc, use_filter: bool):
    """(tokens, real, hit) of a flagged token stream (see flag_bloom_hits)."""
    tok = enc & jnp.int32(0x7FFFFFFF)
    real = tok != 0  # PAD == 0
    return tok, real, (enc < 0) if use_filter else real


def _shift(x, fill):
    """One window-length step: ``x[:, t] <- x[:, t + 1]``, last <- fill."""
    col = jnp.full((x.shape[0], 1), fill, dtype=x.dtype)
    return jnp.concatenate([x[:, 1:], col], axis=1)


def _probe_recurrence(
    enc,
    *,
    max_len: int,
    bands: int,
    rows: int,
    use_filter: bool,
    sig_mode: str,
    sig_store=None,
):
    """The filter -> signature recurrence over one [Bd, T] doc tile.

    Pure function of the tile's flagged token block: runs the
    validity/survival/signature recurrences documented in the module
    docstring and returns ``(pack, count, keys)`` — the packed survival
    bitmap, the tile's true survivor total, and (variant mode) the two
    lists of per-length [Bd, T] finalised key planes (``None``
    otherwise). ``sig_store(l, values)`` is the dense-emission hook:
    called once per window length with the band sigs (lsh) or key pair
    (variant) so the grid-mode kernel can store them without the
    streaming kernel paying for a dense tensor.

    Shared verbatim by the per-tile grid kernel (``_kernel``) and the
    in-kernel DMA streaming kernel (``_stream_kernel``) so the two are
    bit-identical by construction. Only 32-bit streams are shifted and
    masks become integers through ``jnp.where`` (Mosaic rejects i1 ->
    i32 vector casts).
    """
    docs, real, _ = _split_stream(enc, use_filter)
    lsh = sig_mode == SIG_MODE_LSH
    var = sig_mode == SIG_MODE_VARIANT
    if lsh:
        # per-token row hashes, invalid -> MAX so they never win a min
        hv = [
            jnp.where(real, _hash(docs, _LSH_SEED_BASE + i), jnp.uint32(_MAX_U32))
            for i in range(bands * rows)
        ]
        rmin = [jnp.full(docs.shape, _MAX_U32, dtype=jnp.uint32) for _ in hv]
    if var:
        # variant set-hash recurrence: per-window running (sum, xor,
        # count) folds for both 32-bit keys; first-occurrence masking is
        # streamed via the duplicate mask below (shifted compares
        # against the previously shifted token streams — all register
        # resident), bit-identical to core.variants.window_variant_key.
        zero = jnp.zeros(docs.shape, dtype=jnp.uint32)
        vs1, vx1, vs2, vx2, vcnt = zero, zero, zero, zero, zero
        prev_toks: list = []  # token streams shifted by 0 .. l-1
        vkeys1: list = []  # per-length finalised key planes
        vkeys2: list = []

    vand = jnp.ones(docs.shape, bool)
    vor = jnp.zeros(docs.shape, bool)
    pack = jnp.zeros(docs.shape, dtype=jnp.uint32)
    count = jnp.int32(0)
    sh_enc = enc
    sh_hv = list(hv) if lsh else []
    for l in range(max_len):
        sh_tok, sh_real, sh_hit = _split_stream(sh_enc, use_filter)
        vand = vand & sh_real
        vor = vor | sh_hit
        surv = vand & vor
        pack = pack | jnp.where(surv, jnp.uint32(1 << l), jnp.uint32(0))
        # per-tile survivor count, accumulated as the length recurrence
        # runs (feeds the compaction epilogue / sizing pass)
        count = count + jnp.sum(jnp.where(surv, 1, 0))
        if lsh:
            for i in range(bands * rows):
                rmin[i] = _umin(rmin[i], sh_hv[i])
            bands_l = []
            for b in range(bands):
                band = rmin[b * rows]
                for r in range(1, rows):
                    band = _combine(band, rmin[b * rows + r])
                band = _combine(band, jnp.full_like(band, jnp.uint32(b + 1)))
                bands_l.append(band)
            if sig_store is not None:
                sig_store(l, bands_l)
        if var:
            # duplicate mask: tok[t+l] repeats inside [t, t+l] iff the
            # current shifted stream equals any earlier shifted stream
            # (PAD-vs-PAD hits are masked out by sh_real below)
            dup = jnp.zeros(docs.shape, bool)
            for pv in prev_toks:
                dup = dup | (pv == sh_tok)
            contrib = sh_real & ~dup  # == first_occurrence_mask position
            h1 = jnp.where(contrib, _hash(sh_tok, VARIANT_SEEDS[0]),
                           jnp.uint32(0))
            h2 = jnp.where(contrib, _hash(sh_tok, VARIANT_SEEDS[1]),
                           jnp.uint32(0))
            vs1, vx1 = vs1 + h1, vx1 ^ h1
            vs2, vx2 = vs2 + h2, vx2 ^ h2
            vcnt = vcnt + jnp.where(contrib, jnp.uint32(1), jnp.uint32(0))
            # set_hash finalise (core.hashing.set_hash, bit-identical)
            fin = vcnt * jnp.uint32(_GOLDEN)
            k1 = _mix(vs1 ^ (vx1 * jnp.uint32(_C1)) ^ fin)
            k2 = _mix(vs2 ^ (vx2 * jnp.uint32(_C1)) ^ fin)
            vkeys1.append(k1)
            vkeys2.append(k2)
            if sig_store is not None:
                sig_store(l, [k1, k2])
            prev_toks.append(sh_tok)
        if l + 1 < max_len:
            sh_enc = _shift(sh_enc, 0)
            if lsh:
                sh_hv = [_shift(v, _MAX_U32) for v in sh_hv]
    return pack, count, ((vkeys1, vkeys2) if var else None)


# --------------------------------------------------------------------------
# Compaction epilogue: exact prefix counts and selection on the MXU
# --------------------------------------------------------------------------

#: lane slots selected per epilogue step (lane width of one chunk)
LANE_CHUNK = 512


def lane_chunking(width: int) -> tuple[int, int]:
    """``(chunks, chunk)`` of the [chunks, chunk] block holding a lane of
    ``width`` slots: chunk is a multiple of the 128-lane tile and, past
    one chunk, the chunk count a multiple of the 8-row sublane tile;
    slots past ``width`` are padding the wrappers slice off."""
    if width <= LANE_CHUNK:
        return 1, -(-width // 128) * 128
    return -(-width // (8 * LANE_CHUNK)) * 8, LANE_CHUNK


def _small_dot(a, b, dims):
    """Exact integer matmul for operands in [0, 256].

    Such integers are exact in bf16 and the MXU accumulates in f32, so
    every sum below 2**24 is exact: the prefix counts and one-hot
    gathers of the epilogue stay bit-identical to their integer
    definitions on every backend.
    """
    a = a.astype(jnp.float32).astype(jnp.bfloat16)
    b = b.astype(jnp.float32).astype(jnp.bfloat16)
    out = jax.lax.dot_general(a, b, (dims, ((), ())),
                              preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)


def exclusive_prefix_rows(x):
    """``out[i] = sum(x[:i])`` down axis 0 of a small-int [n, m] matrix.

    A strictly-lower-triangular 0/1 matmul (exact while entries are
    <= 256 and column sums < 2**24); the TPU lowering has no cumsum.
    """
    n = x.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return _small_dot(jnp.where(c < r, 1, 0), x, ((1,), (0,)))


def word_popcounts(pack, max_len: int):
    """[Bd, T] int32 survivor count of each packed survival word."""
    c = jnp.zeros(pack.shape, jnp.int32)
    for l in range(max_len):
        c = c + jnp.where(((pack >> l) & jnp.uint32(1)) != 0, 1, 0)
    return c


def _gather_row_bytes(x, onehot_rows, nbytes: int, pick):
    """Bytes of ``x[rsel[j], tsel[j]]`` reassembled per lane slot j.

    ``onehot_rows`` [Bd, J] selects one row per lane slot (MXU gather,
    exact per byte); ``pick`` reduces the gathered [T, J] rows to the
    selected column. Returns [1, J] uint32.
    """
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    acc = jnp.zeros((1, onehot_rows.shape[1]), jnp.int32)
    for b in range(nbytes):
        byte = (xi >> (8 * b)) & 0xFF
        acc = acc | (pick(_small_dot(byte, onehot_rows, ((0,), (0,))))
                     << (8 * b))
    return jax.lax.bitcast_convert_type(acc, jnp.uint32)


def _select_chunk(j, pack, c, row_ex, row_tot, max_len: int, key_planes,
                  row_width: int):
    """Lane slots ``j`` [1, J] -> tile-local flat survivor indices.

    Slot j holds the survivor of exclusive rank j in flat (row, pos,
    len) order: its row is the one whose [row_ex, row_ex + row_tot)
    range holds j, its position the word whose within-row range holds
    the remainder, its length the matching set bit of that word. Rows
    and words are gathered with one-hot matmuls — no gather, scatter,
    cumsum or sort in the lowering. Returns ``(flat [1, J], keys)``
    with ``keys`` the selected (k1, k2) variant keys or ``None``; flat
    indices number rows ``row_width`` positions apart (the unpadded
    document length).
    """
    Bd, T = pack.shape
    L = max_len
    inrow = (row_ex <= j) & (j < row_ex + row_tot)  # [Bd, J] one-hot
    onehot = jnp.where(inrow, 1, 0)
    r_io = jax.lax.broadcasted_iota(jnp.int32, (Bd, 1), 0)
    rsel = jnp.sum(jnp.where(inrow, r_io, 0), axis=0, keepdims=True)
    jr = j - jnp.sum(jnp.where(inrow, row_ex, 0), axis=0, keepdims=True)
    crow = _small_dot(c, onehot, ((0,), (0,)))  # [T, J] row counts
    pex = exclusive_prefix_rows(crow)
    inword = (pex <= jr) & (jr < pex + crow)  # [T, J] one-hot
    t_io = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
    tsel = jnp.sum(jnp.where(inword, t_io, 0), axis=0, keepdims=True)
    k = jr - jnp.sum(jnp.where(inword, pex, 0), axis=0, keepdims=True)

    def pick(g):
        return jnp.sum(jnp.where(inword, g, 0), axis=0, keepdims=True)

    word = _gather_row_bytes(pack, onehot, -(-L // 8), pick)
    lsel = jnp.zeros_like(k)
    seen = jnp.zeros_like(k)
    for l in range(L):
        bit = jnp.where(((word >> l) & jnp.uint32(1)) != 0, 1, 0)
        lsel = jnp.where((bit == 1) & (seen == k), l, lsel)
        seen = seen + bit
    flat = (rsel * row_width + tsel) * L + lsel
    if key_planes is None:
        return flat, None
    keys = []
    for planes in key_planes:
        key = jnp.zeros(k.shape, jnp.uint32)
        for l, plane in enumerate(planes):
            key = jnp.where(lsel == l,
                            _gather_row_bytes(plane, onehot, 4, pick), key)
        keys.append(key)
    return flat, keys


def _emit_lanes(pack, count, cand_cap: int, max_len: int, key_planes, store,
                row_width: int):
    """Compaction epilogue: the tile's first ``cand_cap`` survivors.

    Emits, chunk by chunk, the tile-local flat (row*T + pos)*L + (len-1)
    indices of the survivors of rank 0 .. cand_cap-1 (ascending), via
    ``store(ci, flat, ok, keys)`` with [1, J] rows; chunks past the
    tile's survivor count store only padding. Everything stays
    VMEM-resident, so the [D, T] bitmap is never re-read from HBM.
    """
    chunks, J = lane_chunking(cand_cap)
    c = word_popcounts(pack, max_len)
    row_tot = jnp.sum(c, axis=1, keepdims=True)  # [Bd, 1]
    row_ex = jnp.sum(exclusive_prefix_rows(c), axis=1, keepdims=True)
    live = jnp.minimum(count, cand_cap)

    def body(ci, carry):
        j = ci * J + jax.lax.broadcasted_iota(jnp.int32, (1, J), 1)

        @pl.when(ci * J < live)
        def _select():
            flat, keys = _select_chunk(j, pack, c, row_ex, row_tot,
                                       max_len, key_planes, row_width)
            store(ci, flat, j < live, keys)

        @pl.when(ci * J >= live)
        def _pad():
            store(ci, jnp.zeros((1, J), jnp.int32), j < 0, None)

        return carry

    jax.lax.fori_loop(0, chunks, body, 0)


def _lane_store(cand_ref, vkey_ref, base, var: bool):
    """``store`` callback of ``_emit_lanes`` for [nch, J] lane refs."""

    def store(ci, flat, ok, keys):
        cand_ref[pl.ds(ci, 1), :] = jnp.where(ok, base + flat, -1)
        if var:
            for i in range(2):
                vkey_ref[i, pl.ds(ci, 1), :] = (
                    jnp.where(ok, keys[i], jnp.uint32(0)) if keys is not None
                    else jnp.zeros(ok.shape, jnp.uint32))

    return store


def _kernel(
    doc_ref,
    packed_ref,
    *rest_refs,
    max_len: int,
    bands: int,
    rows: int,
    use_filter: bool,
    sig_mode: str,
    dense_sigs: bool,
    count_tiles: bool,
    cand_cap: int,
):
    # ref layout after packed_ref:
    #   [sig_ref] [count_ref] [cand_ref [vkey_ref]]
    refs = list(rest_refs)
    sig_ref = refs.pop(0) if dense_sigs else None
    count_ref = refs.pop(0) if count_tiles else None
    cand_ref = refs.pop(0) if cand_cap else None
    var = sig_mode == SIG_MODE_VARIANT
    vkey_ref = refs.pop(0) if (var and cand_cap) else None
    enc = doc_ref[...]  # [Bd, T] flagged int32 tokens
    Bd, T = enc.shape

    def sig_store(l, vals):
        for i, v in enumerate(vals):
            sig_ref[:, l, i, :] = v

    pack, count, keys = _probe_recurrence(
        enc,
        max_len=max_len,
        bands=bands,
        rows=rows,
        use_filter=use_filter,
        sig_mode=sig_mode,
        sig_store=sig_store if dense_sigs else None,
    )
    packed_ref[...] = pack
    if count_tiles:
        count_ref[pl.program_id(0)] = count
    if cand_cap:
        # compaction epilogue: the tile's surviving (doc, pos, len)
        # triples as ascending *global* flat indices, packed to the
        # front of a fixed [cand_cap] lane.
        base = pl.program_id(0) * Bd * T * max_len
        _emit_lanes(pack, count, cand_cap, max_len, keys,
                    _lane_store(cand_ref.at[0], vkey_ref.at[0] if var else None,
                                base, var), T)


def _unchunk_lanes(cands, vkeys, width: int):
    """[G, nch, J] lane blocks -> [G, width] (+ [G, width, 2] keys)."""
    G = cands.shape[0]
    cands = cands.reshape(G, -1)[:, :width]
    if vkeys is not None:
        vkeys = vkeys.reshape(G, 2, -1)[:, :, :width].transpose(0, 2, 1)
    return cands, vkeys


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_bits",
        "num_hashes",
        "max_len",
        "sig_mode",
        "bands",
        "rows",
        "use_filter",
        "bd",
        "candidates",
        "count_only",
        "interpret",
    ),
)
def fused_probe_pallas(
    doc_tokens,  # [D, T] i32
    bits,  # [num_bits // 32] uint32 (ignored when use_filter=False)
    num_bits: int,
    num_hashes: int,
    max_len: int,
    sig_mode: str = SIG_MODE_NONE,
    bands: int = 4,
    rows: int = 2,
    use_filter: bool = True,
    bd: int = DEFAULT_BD,
    candidates: int = 0,
    count_only: bool = False,
    *,
    interpret: bool,
):
    """One-pass filter+signature probe with optional compaction epilogue.

    Returns ``(packed, sigs, counts, cands, vkeys)``:

    * ``packed`` [D, T] uint32 with bit ``l`` = survive(pos, len=l+1)
      (validity AND Bloom survival; validity only when
      ``use_filter=False``);
    * ``sigs`` — [D, T, max_len, bands] uint32 MinHash band signatures
      when ``sig_mode == "lsh"``; [D, T, max_len, 2] uint32 variant key
      pairs when ``sig_mode == "variant"`` *without* the epilogue
      (dense mode); else ``None``;
    * with ``candidates > 0``, the in-kernel compaction epilogue:
      ``counts`` [G] int32 holds each grid tile's true survivor count
      (may exceed ``candidates``) and ``cands`` [G, candidates] int32
      the tile's first ``candidates`` survivors as ascending global
      flat (doc*T + pos)*max_len + (len-1) indices, -1 padded —
      downstream compaction reads these tiny per-tile lanes and never
      re-reads the [D, T] bitmap (see
      ``extraction.results.select_from_tiles``). ``candidates`` is the
      *lane width*: callers shrink it below the merge capacity after a
      count pass (adaptive two-pass; see ``round_lane_width``);
    * ``vkeys`` [G, candidates, 2] uint32 — the variant key pairs of
      each lane slot (``sig_mode == "variant"`` with the epilogue; the
      dense ``sigs`` tensor is *not* emitted then), 0 in padded slots;
    * ``count_only=True`` (with ``candidates > 0``) emits ``counts``
      but skips the lane (and key) stores — the cheap sizing pass of
      the adaptive two-pass scheme. ``candidates`` then only sets the
      tile geometry via the caller's ``bd`` choice.

    ``interpret`` has no default: callers choose it explicitly
    (``kernels.ops`` derives it from the platform).
    """
    assert max_len <= 32, "packed survival bitmap holds at most 32 lengths"
    assert candidates or not count_only, "count_only needs candidates > 0"
    assert not (count_only and sig_mode != SIG_MODE_NONE), (
        "count_only is the sizing pass: signatures belong to the emit pass"
    )
    if sig_mode not in (SIG_MODE_NONE, SIG_MODE_LSH, SIG_MODE_VARIANT):
        raise ValueError(f"unknown sig_mode {sig_mode!r}")
    D, T = doc_tokens.shape
    bd = min(bd, D)
    Dp = -(-D // bd) * bd
    G = Dp // bd
    if use_filter:
        doc_tokens = flag_bloom_hits(doc_tokens, bits, num_bits, num_hashes)
    if Dp != D:
        doc_tokens = jnp.pad(doc_tokens, ((0, Dp - D), (0, 0)))
    count_tiles = candidates > 0
    cand_cap = 0 if count_only else candidates
    var = sig_mode == SIG_MODE_VARIANT
    dense_sigs = sig_mode == SIG_MODE_LSH or (var and not cand_cap)
    sig_depth = {SIG_MODE_LSH: bands, SIG_MODE_VARIANT: 2}

    out_shape = [jax.ShapeDtypeStruct((Dp, T), jnp.uint32)]
    out_specs = [pl.BlockSpec((bd, T), lambda i: (i, 0))]
    if dense_sigs:
        # [D, L, S, T]: positions stay on the lane axis
        S = sig_depth[sig_mode]
        out_shape.append(
            jax.ShapeDtypeStruct((Dp, max_len, S, T), jnp.uint32)
        )
        out_specs.append(
            pl.BlockSpec((bd, max_len, S, T), lambda i: (i, 0, 0, 0))
        )
    if count_tiles:
        out_shape.append(jax.ShapeDtypeStruct((G,), jnp.int32))
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if cand_cap:
        nch, J = lane_chunking(cand_cap)
        out_shape.append(jax.ShapeDtypeStruct((G, nch, J), jnp.int32))
        out_specs.append(pl.BlockSpec((1, nch, J), lambda i: (i, 0, 0)))
        if var:
            out_shape.append(
                jax.ShapeDtypeStruct((G, 2, nch, J), jnp.uint32)
            )
            out_specs.append(
                pl.BlockSpec((1, 2, nch, J), lambda i: (i, 0, 0, 0))
            )

    outs = pl.pallas_call(
        functools.partial(
            _kernel,
            max_len=max_len,
            bands=bands,
            rows=rows,
            use_filter=use_filter,
            sig_mode=sig_mode,
            dense_sigs=dense_sigs,
            count_tiles=count_tiles,
            cand_cap=cand_cap,
        ),
        grid=(G,),
        in_specs=[pl.BlockSpec((bd, T), lambda i: (i, 0))],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=interpret,
    )(doc_tokens)
    outs = list(outs)
    packed = outs.pop(0)[:D]
    sigs = outs.pop(0)[:D].transpose(0, 3, 1, 2) if dense_sigs else None
    counts = outs.pop(0) if count_tiles else None
    cands = outs.pop(0) if cand_cap else None
    vkeys = outs.pop(0) if (cand_cap and var) else None
    if cand_cap:
        cands, vkeys = _unchunk_lanes(cands, vkeys, cand_cap)
    return packed, sigs, counts, cands, vkeys


# --------------------------------------------------------------------------
# Streaming mode: in-kernel double-buffered DMA over the tile loop
# --------------------------------------------------------------------------


def _stream_kernel(
    offs_ref,  # [G] i32 SMEM: absolute doc-row offset of each chunk
    doc_ref,  # [G*bd, T] flagged i32, memory_space=ANY (stays in HBM)
    counts_ref,  # [G] i32 SMEM out
    *rest_refs,  # [cand_ref [vkey_ref]] (HBM outs), then scratch
    max_len: int,
    bands: int,
    rows: int,
    use_filter: bool,
    sig_mode: str,
    chunks: int,
    bd: int,
    cand_cap: int,
    row_width: int,
):
    """One launch consumes an entire shard: the tile loop runs *inside*
    the kernel as a ``fori_loop`` over ``chunks`` [bd, T] tiles, each
    DMA'd HBM->VMEM into a 2-slot buffer. The copy-in of tile g+1 is
    issued before tile g's recurrence runs (double buffering), so on
    real hardware the DMA engine overlaps the VPU work; per-tile
    lane/count/key outputs come from the same ``_emit_lanes`` epilogue
    the grid-mode kernel uses, with the absolute row offset read from
    SMEM instead of ``pl.program_id`` — flat indices are bit-identical
    to the per-tile launch loop at any geometry. Each tile's lane is
    assembled in VMEM and copied out to its HBM row, so VMEM holds one
    lane, not the shard's. Rows are lane-padded to a multiple of 128
    for the DMA; flat indices use the unpadded ``row_width``.
    """
    var = sig_mode == SIG_MODE_VARIANT
    refs = list(rest_refs)
    cand_ref = refs.pop(0) if cand_cap else None
    vkey_ref = refs.pop(0) if (var and cand_cap) else None
    T = doc_ref.shape[1]
    L = max_len

    def body(buf, sem, lane_buf, key_buf, out_sem):
        def dma(slot, g):
            return pltpu.make_async_copy(
                doc_ref.at[pl.ds(g * bd, bd), :], buf.at[slot], sem.at[slot]
            )

        dma(0, 0).start()  # warm-up: first tile in flight before the loop

        def chunk(g, _):
            slot = jax.lax.rem(g, 2)

            @pl.when(g + 1 < chunks)
            def _prefetch():
                dma(jax.lax.rem(g + 1, 2), g + 1).start()

            dma(slot, g).wait()
            pack, cnt, keys = _probe_recurrence(
                buf[slot],
                max_len=max_len,
                bands=bands,
                rows=rows,
                use_filter=use_filter,
                sig_mode=sig_mode,
            )
            counts_ref[g] = cnt
            if cand_cap:
                _emit_lanes(pack, cnt, cand_cap, L, keys,
                            _lane_store(lane_buf, key_buf,
                                        offs_ref[g] * row_width * L, var),
                            row_width)
                out = pltpu.make_async_copy(lane_buf, cand_ref.at[g],
                                            out_sem.at[0])
                out.start()
                if var:
                    kout = pltpu.make_async_copy(key_buf, vkey_ref.at[g],
                                                 out_sem.at[1])
                    kout.start()
                    kout.wait()
                out.wait()
            return 0

        jax.lax.fori_loop(0, chunks, chunk, 0)

    nch, J = lane_chunking(max(cand_cap, 1))
    pl.run_scoped(
        body,
        buf=pltpu.VMEM((2, bd, T), jnp.int32),
        sem=pltpu.SemaphoreType.DMA((2,)),
        lane_buf=pltpu.VMEM((nch, J), jnp.int32),
        key_buf=pltpu.VMEM((2, nch, J), jnp.uint32),
        out_sem=pltpu.SemaphoreType.DMA((2,)),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_bits",
        "num_hashes",
        "max_len",
        "sig_mode",
        "bands",
        "rows",
        "use_filter",
        "bd",
        "candidates",
        "count_only",
        "interpret",
    ),
)
def fused_probe_stream_pallas(
    doc_tokens,  # [G*bd, T] i32, pre-padded so every chunk is full height
    bits,  # [num_bits // 32] uint32 (ignored when use_filter=False)
    row_offs,  # [G] i32: absolute doc-row offset of each chunk's tile
    num_bits: int,
    num_hashes: int,
    max_len: int,
    sig_mode: str = SIG_MODE_NONE,
    bands: int = 4,
    rows: int = 2,
    use_filter: bool = True,
    bd: int = DEFAULT_BD,
    candidates: int = 0,
    count_only: bool = False,
    *,
    interpret: bool,
):
    """Streamed megakernel: one launch, ``G`` double-buffered DMA tiles.

    The per-tile grid of ``fused_probe_pallas`` becomes an in-kernel
    loop: ``doc_tokens`` stays in HBM (``memory_space=ANY``) and each
    [bd, T] chunk is async-copied into a 2-slot VMEM buffer while the
    previous chunk's recurrence runs. Returns ``(counts, cands, vkeys)``
    with the same per-tile wire unit as the grid kernel — ``counts``
    [G] int32 true survivor totals, ``cands`` [G, candidates] int32
    ascending *global* flat indices (``row_offs[g]`` replaces the grid
    kernel's ``program_id * bd`` row base, so callers control the
    numbering — shard row offsets and uneven upstream tile heights fold
    into it), ``vkeys`` [G, candidates, 2] uint32 key lanes (variant
    mode). ``count_only=True`` emits only ``counts`` (the adaptive
    sizing pass).

    No packed bitmap and no dense signature tensor are emitted — that
    is the point: input bytes are paid once over the DMA pipeline and
    only the tiny per-tile lanes travel back (see ``hbm_bytes_fused``
    with ``streamed=True``). Dense-sig modes (``lsh`` without lane
    recompute, ``variant`` without the epilogue) therefore raise; the
    streaming paths in ``extraction.sharded`` recompute band signatures
    post-compaction instead.

    Callers pre-pad ``doc_tokens`` to a multiple of ``bd`` *per
    upstream tile* and pass the matching ``row_offs`` so flat indices
    stay bit-identical to the per-tile launch loop at any geometry
    (see ``extraction.sharded.stream_probe_tiles``).
    """
    assert max_len <= 32, "packed survival bitmap holds at most 32 lengths"
    if sig_mode not in (SIG_MODE_NONE, SIG_MODE_VARIANT):
        raise ValueError(
            "streamed kernel emits no dense signature tensor: sig_mode "
            f"{sig_mode!r} unsupported (lsh band sigs are recomputed "
            "post-compaction on streaming paths)"
        )
    if candidates <= 0:
        raise ValueError(
            "streamed kernel has no bitmap output: candidates > 0 required"
        )
    R, T = doc_tokens.shape
    if R % bd != 0:
        raise ValueError(
            f"streamed input rows ({R}) must be a multiple of bd ({bd}): "
            "callers pre-pad each upstream tile to full chunk height"
        )
    G = R // bd
    cand_cap = 0 if count_only else candidates
    var = sig_mode == SIG_MODE_VARIANT
    if use_filter:
        doc_tokens = flag_bloom_hits(doc_tokens, bits, num_bits, num_hashes)
    Tp = -(-T // 128) * 128  # PAD columns hold no valid window
    if Tp != T:
        doc_tokens = jnp.pad(doc_tokens, ((0, 0), (0, Tp - T)))

    out_shape = [jax.ShapeDtypeStruct((G,), jnp.int32)]
    out_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    if cand_cap:
        nch, J = lane_chunking(cand_cap)
        out_shape.append(jax.ShapeDtypeStruct((G, nch, J), jnp.int32))
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        if var:
            out_shape.append(
                jax.ShapeDtypeStruct((G, 2, nch, J), jnp.uint32)
            )
            out_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    outs = pl.pallas_call(
        functools.partial(
            _stream_kernel,
            max_len=max_len,
            bands=bands,
            rows=rows,
            use_filter=use_filter,
            sig_mode=sig_mode,
            chunks=G,
            bd=bd,
            cand_cap=cand_cap,
            row_width=T,
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # row_offs
            pl.BlockSpec(memory_space=pl.ANY),  # docs stay in HBM
        ],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=interpret,
    )(row_offs, doc_tokens)
    outs = [outs] if not isinstance(outs, (tuple, list)) else list(outs)
    counts = outs.pop(0)
    cands = outs.pop(0) if cand_cap else None
    vkeys = outs.pop(0) if (cand_cap and var) else None
    if cand_cap:
        cands, vkeys = _unchunk_lanes(cands, vkeys, cand_cap)
    return counts, cands, vkeys


# --------------------------------------------------------------------------
# HBM-traffic accounting (the analytic model the benchmark reports)
# --------------------------------------------------------------------------


def hbm_bytes_unfused(D: int, T: int, max_len: int, max_candidates: int,
                      sig_width: int, streamed: bool = False) -> int:
    """Bytes moved by the unfused survival_mask->compact->signatures
    pipeline: docs read, [D,T,L] int32 base write + probe re-read,
    [D,T,L] survival write + compaction re-read, compacted [N,L] window
    gather + [N,S] signature store.

    ``streamed=`` is accepted for symmetry with ``hbm_bytes_fused`` but
    changes nothing: the unfused pipeline's inter-pass tensors (the
    L-expanded base and survival arrays) are HBM-resident *between*
    jitted passes, so streaming the input cannot elide their round
    trips — which is exactly why only the fused megakernel has a
    streaming mode worth modeling.
    """
    del streamed  # see docstring: no term to elide
    tokens = D * T
    base = tokens * max_len * 4
    mask = tokens * max_len  # int8
    gather = max_candidates * max_len * 4
    sig = max_candidates * sig_width * 4
    return tokens * 4 + 2 * base + 2 * mask + 2 * gather + sig


def hbm_bytes_fused(D: int, T: int, max_len: int, max_candidates: int,
                    bands: int, lsh: bool, sig_width: int = 0,
                    kernel_compact: bool = False, bd: int | None = None,
                    lane_width: int | None = None, two_pass: bool = False,
                    variant_keys: bool = False,
                    streamed: bool = False) -> int:
    """Bytes moved by the fused megakernel pipeline: docs read once,
    packed [D,T] uint32 bitmap write (+ compaction re-read unless the
    in-kernel epilogue runs), compacted [N,L] window gather straight
    from docs, and either the in-kernel [D,T,L,B] signature store +
    [N,B] gather (``lsh=True``) or the same post-compaction
    [N, sig_width] signature store the unfused pipeline pays
    (``lsh=False``; pass the scheme's ``sig_width`` so the two models
    stay symmetric). With ``kernel_compact=True`` the epilogue emits
    per-tile [G, 1 + W] count/candidate lanes instead: the bitmap is
    written once for inspection but never re-read, and the host-side
    combine touches only the lanes. ``W = lane_width or
    max_candidates``: the adaptive two-pass scheme shrinks W to the
    measured per-tile survivor maximum, paying for it with a count-only
    sizing pass (``two_pass=True``: docs re-read + bitmap re-write +
    [G] count round trip). ``variant_keys=True`` models the fused
    variant scheme: the post-compaction [N, sig_width] signature store
    is replaced by the [G, W, 2] key-lane payload (write + combine
    read) riding the candidate lanes. ``streamed=True`` (requires
    ``kernel_compact``) models the in-kernel DMA pipeline
    (``fused_probe_stream_pallas``): input bytes are counted exactly
    once over the double-buffered copy-in and the packed bitmap is
    never materialised — the per-launch bitmap write of the per-tile
    loop disappears from both the emit and (``two_pass``) sizing
    passes, leaving only the docs read and the tiny per-tile lane
    round trips."""
    if streamed and not kernel_compact:
        raise ValueError("streamed modeling requires kernel_compact=True "
                         "(the streamed kernel has no bitmap output)")
    tokens = D * T
    packed = 0 if streamed else tokens * 4
    gather = max_candidates * max_len * 4
    if kernel_compact:
        if bd is None:
            bd = compact_tile_height(D, T, max_candidates)
        W = lane_width if lane_width is not None else max_candidates
        G = -(-D // bd)
        tiles = G * (1 + W) * 4  # write + combine read
        total = tokens * 4 + packed + 2 * tiles + 2 * gather
        if two_pass:
            # count-only sizing pass: docs read + bitmap write again
            # (elided when streamed), plus the [G] per-tile counts'
            # write and host read-back
            total += tokens * 4 + packed + 2 * G * 4
        if variant_keys:
            total += 2 * G * W * 8  # [G, W, 2] u32 key lanes, write+read
    else:
        total = tokens * 4 + 2 * packed + 2 * gather
        if variant_keys:
            # dense mode: [D, T, L, 2] key tensor store + [N, 2] gather
            total += tokens * max_len * 8 + max_candidates * 8
    if lsh:
        total += tokens * max_len * bands * 4 + max_candidates * bands * 4
    elif not variant_keys:
        total += max_candidates * sig_width * 4
    return total

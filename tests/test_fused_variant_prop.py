"""Property-based parity: the fused variant recurrence vs the jnp oracle.

Two contracts, fuzzed over adversarial windows (PAD-heavy, all-duplicate,
zero-survivor):

* the kernel's streaming duplicate mask (shifted compares against the
  previously shifted token streams, ``streaming_first_occurrence``) must
  be bit-identical to ``core.semantics.first_occurrence_mask``;
* the fused in-kernel variant keys (running (sum, xor, count) set-hash
  fold under that mask) must be bit-identical to
  ``core.variants.window_variant_key`` at every (pos, len) — dense mode
  checks the whole [D, T, L, 2] tensor, lane mode checks the epilogue's
  key payload at the emitted flat indices.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core.semantics import first_occurrence_mask
from repro.core.variants import window_variant_key
from repro.kernels import fused_probe as fp
from repro.kernels import ops as kops

# small vocabularies force duplicate-heavy windows; 0 is PAD
_rows = st.lists(
    st.lists(st.integers(0, 6), min_size=1, max_size=12),
    min_size=1,
    max_size=8,
)


def _pad(rows):
    L = max(len(r) for r in rows)
    out = np.zeros((len(rows), L), dtype=np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


@given(_rows)
@settings(max_examples=80, deadline=None)
def test_streaming_dup_mask_matches_first_occurrence(rows):
    toks = _pad(rows)
    got = fp.streaming_first_occurrence(toks, xp=np)
    want = np.asarray(first_occurrence_mask(toks, xp=np))
    np.testing.assert_array_equal(got, want)


@given(
    st.integers(1, 6),  # D
    st.integers(4, 24),  # T
    st.integers(1, 6),  # L
    st.integers(2, 9),  # vocab (incl. PAD -> duplicate- and PAD-heavy)
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_fused_variant_keys_match_oracle(D, T, L, vocab, seed):
    rng = np.random.default_rng(seed)
    docs = rng.integers(0, vocab, size=(D, T)).astype(np.int32)
    docsj = jnp.asarray(docs)
    # dense mode: every (pos, len) key must match the oracle
    _, sigs, _, _, _ = fp.fused_probe_pallas(
        docsj, jnp.zeros((8,), jnp.uint32), 256, 1, L,
        sig_mode="variant", use_filter=False, interpret=True,
    )
    sigs = np.asarray(sigs)  # [D, T, L, 2]
    for l in range(L):
        win = np.zeros((D, T, l + 1), dtype=np.int32)
        for o in range(l + 1):
            win[:, : T - o, o] = docs[:, o:]
        k1, k2 = window_variant_key(win, win != 0, xp=np)
        np.testing.assert_array_equal(sigs[..., l, 0], k1)
        np.testing.assert_array_equal(sigs[..., l, 1], k2)
    # lane mode: the epilogue's key payload must match at its indices
    _, _, _, cands, vkeys = fp.fused_probe_pallas(
        docsj, jnp.zeros((8,), jnp.uint32), 256, 1, L,
        sig_mode="variant", use_filter=False, candidates=16,
        interpret=True,
    )
    cands, vkeys = np.asarray(cands), np.asarray(vkeys)
    for g in range(cands.shape[0]):
        for j in range(cands.shape[1]):
            flat = cands[g, j]
            if flat < 0:
                assert vkeys[g, j, 0] == 0 and vkeys[g, j, 1] == 0
                continue
            d, rem = divmod(flat, T * L)
            p, l = divmod(rem, L)
            assert vkeys[g, j, 0] == sigs[d, p, l, 0]
            assert vkeys[g, j, 1] == sigs[d, p, l, 1]


@given(st.integers(0, 2**31 - 1), st.integers(1, 24))
@settings(max_examples=30, deadline=None)
def test_two_pass_lane_width_is_exact(seed, nc):
    """Any W >= the per-tile survivor max keeps the narrow emit pass a
    bit-exact prefix of the worst-case [G, NC] lanes."""
    rng = np.random.default_rng(seed)
    docs = jnp.asarray(rng.integers(0, 64, size=(9, 40)).astype(np.int32))
    counts = kops.fused_probe_count(docs, None, 5, nc)
    w = fp.round_lane_width(int(np.asarray(counts).max()), nc)
    _, _, c_wide, wide, _ = kops.fused_probe_compact(docs, None, 5, nc)
    _, _, c_narrow, narrow, _ = kops.fused_probe_compact(
        docs, None, 5, nc, lane_width=w
    )
    np.testing.assert_array_equal(np.asarray(c_wide), np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(c_narrow), np.asarray(counts))
    np.testing.assert_array_equal(
        np.asarray(narrow), np.asarray(wide)[:, :w]
    )

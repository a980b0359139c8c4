"""Pallas TPU kernel: batched weighted Jaccard-containment verification.

The verify step is the per-record hot loop of both EE-Join algorithms
(Def. 3's post-lookup check, Def. 4's reducer verify): for each candidate
window and each of its K candidate entities, compute

    score = w(e ∩ s) / w(e)        (mode "extra")
          = w(e ∩ s) / w(s)        (mode "missing")

over PAD(0)-padded token rows. Token weights are pre-gathered outside
the kernel (the [V] weight table stays in HBM; the kernel sees only
dense per-row tiles), so the kernel body is a pure VPU compare/reduce:

    eq[i,j][n,k] = ent_tokens[i][n,k] == win_tokens[j][n]   (L x L compares)
    inter[n,k]   = Σ_i ent_w[i][n,k] * any_j eq[i,j][n,k]

Layout: the token axis ``L`` (4–16) is the *leading* axis of every
block, so each compare is a full [Bn, Bk] vector tile (windows on
sublanes, candidate entities on lanes) and nothing is padded to the
128-lane width. The wrapper transposes to ``ent [L, N, K]`` and
``win [L, N, 1]`` (a [Bn, 1] column broadcasts across the K lanes).

Tiling: grid over (N/Bn, K/Bk); each step holds
  win  2 x [L, Bn, 1]     (lane-padded: L*Bn*512 B each)
  ent  2 x [L, Bn, Bk]
  out  [Bn, Bk] f32
in VMEM — ~1.5 MiB at (Bn=128, Bk=128, L=8) double-buffered, well
inside the default scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 128
DEFAULT_BK = 128


def _kernel(win_t_ref, win_w_ref, ent_t_ref, ent_w_ref, out_ref, *, mode: str):
    L = ent_t_ref.shape[0]
    win_t = [win_t_ref[j] for j in range(L)]  # L x [Bn, 1]
    ws = win_w_ref[0]
    for j in range(1, L):
        ws = ws + win_w_ref[j]
    inter = jnp.zeros(out_ref.shape, jnp.float32)
    denom = jnp.zeros(out_ref.shape, jnp.float32)
    for i in range(L):
        e = ent_t_ref[i]  # [Bn, Bk]
        hit = jnp.zeros(e.shape, bool)
        for j in range(L):
            hit = hit | ((e == win_t[j]) & (win_t[j] != 0))
        hit = hit & (e != 0)
        w = ent_w_ref[i]
        inter = inter + jnp.where(hit, w, 0.0)
        denom = denom + w
    if mode != "extra":  # missing
        denom = jnp.broadcast_to(ws, inter.shape)
    score = inter / jnp.maximum(denom, 1e-30)
    out_ref[...] = jnp.where(ws > 0, score, 0.0).astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("mode", "bn", "bk", "interpret")
)
def jaccard_verify_pallas(
    win_tokens,  # [N, L] i32
    win_w,  # [N, L] f32
    ent_tokens,  # [N, K, L] i32
    ent_w,  # [N, K, L] f32
    mode: str = "extra",
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    *,
    interpret: bool,
):
    N, L = win_tokens.shape
    K = ent_tokens.shape[1]
    # sublane tiles are multiples of 8 and lane tiles of 128 unless the
    # block spans the whole (padded) axis
    bn = min(bn, -(-N // 8) * 8)
    bk = K if K <= bk else bk
    Np = -(-N // bn) * bn
    Kp = -(-K // bk) * bk
    # pad to tile multiples (PAD tokens give zero scores)
    if (Np, Kp) != (N, K):
        win_tokens = jnp.pad(win_tokens, ((0, Np - N), (0, 0)))
        win_w = jnp.pad(win_w, ((0, Np - N), (0, 0)))
        ent_tokens = jnp.pad(ent_tokens, ((0, Np - N), (0, Kp - K), (0, 0)))
        ent_w = jnp.pad(ent_w, ((0, Np - N), (0, Kp - K), (0, 0)))
    win_tokens = win_tokens.T[:, :, None]  # [L, N, 1]
    win_w = win_w.T[:, :, None]
    ent_tokens = ent_tokens.transpose(2, 0, 1)  # [L, N, K]
    ent_w = ent_w.transpose(2, 0, 1)

    grid = (Np // bn, Kp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((L, bn, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((L, bn, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((L, bn, bk), lambda i, j: (0, i, j)),
            pl.BlockSpec((L, bn, bk), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Kp), jnp.float32),
        interpret=interpret,
    )(win_tokens, win_w, ent_tokens, ent_w)
    return out[:N, :K]

"""Pallas TPU kernel: fused ISH-filter probe over every document window.

This is the paper's key pruning step fused into one pass: instead of
materialising the L x |d| candidate substrings and probing each (the
baseline SSJoin's failure mode, §3.1), the per-token Bloom hits are
probed once (an XLA gather over the bitmap words, the same explicit
path as ``fused_probe.flag_bloom_hits``) and the kernel streams the
[D, T] hit tiles HBM->VMEM once, emitting the [D, T, L] survival mask:

    hit[d, t]        = all k probes of token (d, t) set in the bitmap
    survive[d, t, l] = any(hit[d, t .. t+l])     (running-or, registers)

Tiling: one full document row per grid row ([Bd, T] tiles) so windows
never straddle a tile edge.

NOTE: the production fast path is ``fused_probe``, which subsumes this
kernel (packed uint32 survival bitmap instead of the [D, T, L] int8
mask — L x less output traffic — plus optional in-pass signature
emission). This standalone version is kept as the minimal reference
fusion and for the ops/ref parity sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.filter import token_in_filter

DEFAULT_BD = 8


def _kernel(hit_ref, out_ref, *, max_len: int):
    hit = hit_ref[...] != 0  # [Bd, T] per-token Bloom hits
    Bd, T = hit.shape
    acc = jnp.zeros((Bd, T), bool)
    shifted = hit
    for l in range(max_len):
        acc = acc | shifted
        out_ref[:, :, l] = jnp.where(acc, 1, 0).astype(jnp.int8)
        if l + 1 < max_len:
            shifted = jnp.concatenate(
                [shifted[:, 1:], jnp.zeros((Bd, 1), bool)], axis=1
            )


@functools.partial(
    jax.jit, static_argnames=("num_bits", "num_hashes", "max_len", "bd", "interpret")
)
def window_filter_pallas(
    doc_tokens,  # [D, T] i32
    bits,  # [num_bits // 32] uint32
    num_bits: int,
    num_hashes: int,
    max_len: int,
    bd: int = DEFAULT_BD,
    *,
    interpret: bool,
):
    D, T = doc_tokens.shape
    # Bloom probe as an XLA gather feeding the kernel (the kernel never
    # indexes the bitmap; see fused_probe.flag_bloom_hits)
    hits = token_in_filter(bits, num_bits, num_hashes, doc_tokens)
    hits = jnp.where(hits, 1, 0).astype(jnp.int32)
    bd = min(bd, D)
    Dp = -(-D // bd) * bd
    if Dp != D:
        hits = jnp.pad(hits, ((0, Dp - D), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, max_len=max_len),
        grid=(Dp // bd,),
        in_specs=[pl.BlockSpec((bd, T), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bd, T, max_len), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Dp, T, max_len), jnp.int8),
        interpret=interpret,
    )(hits)
    return out[:D].astype(bool)

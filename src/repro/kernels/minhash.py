"""Pallas TPU kernel: banded MinHash (LSH) signature generation.

Signature generation is the map-side cost ``C_sig`` of Def. 4 for the
LSH scheme: for every candidate window, hash its tokens with B*R
affine-mix hash functions, take per-row minima over the (masked) window,
and fold R row-minima into one band signature.

The whole computation is elementwise uint32 arithmetic + an L-reduce —
pure VPU work with zero MXU involvement, so the kernel's job is purely
bandwidth discipline: one HBM->VMEM stream of [Bn, L] token tiles and one
[Bn, B] store, with all B*R hash evaluations fused in VMEM (the unfused
jnp version re-reads the token tile from HBM once per hash function —
B*R x more HBM traffic).

Bit-identical to ``core.signatures._minhash_np/_jnp`` (same seeds,
murmur3 finaliser, and combine), which the EE-Join dictionary side uses —
a signature produced here matches the host-built table.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.signatures import _LSH_SEED_BASE
from repro.kernels._hashing import combine as _combine
from repro.kernels._hashing import hash_seeded as _hash
from repro.kernels._hashing import umin_reduce

DEFAULT_BN = 256


def _kernel(tok_ref, valid_ref, out_ref, *, bands: int, rows: int):
    toks = tok_ref[...]  # [Bn, L] int32
    valid = valid_ref[...] != 0  # [Bn, L]
    for b in range(bands):
        band = None
        for r in range(rows):
            h = _hash(toks, _LSH_SEED_BASE + b * rows + r)
            h = jnp.where(valid, h, jnp.uint32(0xFFFFFFFF))
            m = umin_reduce(h, axis=-1)  # [Bn]
            band = m if band is None else _combine(band, m)
        band = _combine(band, jnp.full_like(band, jnp.uint32(b + 1)))
        out_ref[:, b] = band


@functools.partial(jax.jit, static_argnames=("bands", "rows", "bn", "interpret"))
def minhash_pallas(
    tokens,  # [N, L] i32
    valid,  # [N, L] bool
    bands: int = 4,
    rows: int = 2,
    bn: int = DEFAULT_BN,
    *,
    interpret: bool,
):
    N, L = tokens.shape
    bn = min(bn, N)
    Np = -(-N // bn) * bn
    if Np != N:
        tokens = jnp.pad(tokens, ((0, Np - N), (0, 0)))
        valid = jnp.pad(valid, ((0, Np - N), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, bands=bands, rows=rows),
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec((bn, L), lambda i: (i, 0)),
            pl.BlockSpec((bn, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bands), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, bands), jnp.uint32),
        interpret=interpret,
    )(tokens, valid.astype(jnp.int8))
    return out[:N]

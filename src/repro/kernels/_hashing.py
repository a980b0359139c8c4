"""Shared jnp hash helpers for Pallas kernel bodies.

One copy of the murmur3-finaliser family for every kernel module, with
the constants imported from ``core.hashing`` — bit-parity with the
host-side builds is a hard correctness contract, so there is exactly
one in-kernel implementation to keep in sync. ``ref.py`` keeps its own
independent copy on purpose: it is the oracle the kernels are tested
against and must not share the implementation under test.

Plain jnp ops, usable inside Pallas kernel bodies and under jit alike.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.hashing import _C1, _C2, _GOLDEN


def mix(x):
    """murmur3 finaliser over uint32 (bit-identical to hashing._mix)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_C1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_C2)
    x = x ^ (x >> 16)
    return x


def hash_seeded(x, seed: int):
    """hashing.hash_u32 for kernel bodies (seed folded in host-side)."""
    off = np.uint32((_GOLDEN * (seed + 1)) & 0xFFFFFFFF)
    return mix(x.astype(jnp.uint32) + off)


def combine(h, g):
    """Order-dependent combine (bit-identical to hashing.combine)."""
    return mix(h ^ (g + jnp.uint32(_GOLDEN) + (h << 6) + (h >> 2)))


_BIAS = np.uint32(0x80000000)


def _biased(x):
    """uint32 -> int32 preserving order (flip the sign bit)."""
    return jax.lax.bitcast_convert_type(x ^ _BIAS, jnp.int32)


def umin(a, b):
    """Elementwise uint32 minimum as an int32 compare (Mosaic has no
    unsigned min)."""
    return jnp.where(_biased(a) < _biased(b), a, b)


def umin_reduce(x, axis: int = -1):
    """uint32 minimum along ``axis`` as a biased int32 reduction (Mosaic
    has no unsigned reductions); bit-identical to ``x.min(axis)``."""
    m = _biased(x).min(axis=axis)
    return jax.lax.bitcast_convert_type(m, jnp.uint32) ^ _BIAS

"""Int8 error-feedback gradient compression for cross-pod reductions.

Cross-pod DP traffic rides the slowest links of a multi-pod job; int8
quantisation cuts those bytes 4x vs fp32 (2x vs bf16). Plain
quantisation biases the update, so we keep the classic error-feedback
residual (1-bit Adam / EF-SGD lineage): the quantisation error of step t
is added back into the gradient at step t+1, making the long-run update
unbiased.

``compressed_psum`` is built for use inside a shard_map over the ``pod``
axis; ``quantize``/``dequantize`` are exposed separately so the trainer
can also use them for checkpoint-size reduction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.lax import axis_size


def quantize(g, axis=None):
    """fp -> (int8, scale). Symmetric per-tensor scaling."""
    gf = g.astype(jnp.float32)
    amax = jnp.max(jnp.abs(gf))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def ef_compress_tree(grads, residual):
    """Error-feedback: corrected = grads + residual; returns
    (quantized tree [(q, scale) leaves], new residual tree)."""
    def one(g, r):
        corrected = g.astype(jnp.float32) + r
        q, s = quantize(corrected)
        deq = dequantize(q, s)
        return (q, s), corrected - deq

    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = tdef.flatten_up_to(residual)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    qtree = tdef.unflatten([o[0] for o in outs])
    new_res = tdef.unflatten([o[1] for o in outs])
    return qtree, new_res


def compressed_psum(grads, residual, axis_name: str):
    """Inside shard_map: int8-quantise (with error feedback), all-gather
    the int8 payload over ``axis_name``, and dequant-sum locally.

    Summing int8 directly overflows, so the exchange is an all_gather of
    int8 + local fp32 reduction — the wire bytes are the int8 payload.
    Returns (mean-reduced grads, new residual).
    """
    n = axis_size(axis_name)

    def one(g, r):
        corrected = g.astype(jnp.float32) + r
        q, s = quantize(corrected)
        new_r = corrected - dequantize(q, s)
        qs = jax.lax.all_gather(q, axis_name)  # [n, ...] int8 on the wire
        ss = jax.lax.all_gather(s, axis_name)  # [n] scales (negligible)
        summed = (qs.astype(jnp.float32) * ss.reshape((n,) + (1,) * g.ndim)).sum(0)
        return summed / n, new_r

    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = tdef.flatten_up_to(residual)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return (
        tdef.unflatten([o[0] for o in outs]),
        tdef.unflatten([o[1] for o in outs]),
    )


def init_residual(grads_template):
    return jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_template
    )

"""Top-k MoE with expert parallelism over the ``model`` mesh axis.

Two execution paths, chosen by sequence length:

* **train/prefill** (``S % n_model == 0``): an explicit shard_map — the
  tokens are fully partitioned over (pod, data, model) (sequence goes to
  the model axis for the MoE block), each device routes its local tokens
  with the same sort-into-capacity-buckets dispatch the EE-Join shuffle
  uses (see extraction/distributed.py), exchanges them over the model
  axis with ``all_to_all``, runs its expert shard, and reverses the
  exchange. Per-device expert compute waste is ``E / n_model`` relative
  to a perfect grouped GEMM (== 1 for dbrx's 16 experts on a 16-way
  axis).
* **decode** (``S == 1``): tokens are too few to shard further, so all
  (sharded) experts evaluate the batch densely and the router mask
  combines — compute waste E/top_k, negligible at decode arithmetic
  intensities and free of routing collectives beyond the psum TP already
  pays. Flagged in EXPERIMENTS.md as a hillclimb target.

Dropping semantics: per-destination capacity ``C = ceil(N*k/n * cf)``;
overflowing assignments contribute zero (standard dropping MoE) and the
dropped fraction is returned for diagnostics. Router aux loss is the
usual load-balancing loss ``E * Σ_e f_e P_e``.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init
from repro.models.sharding import ShardingRules


def init_moe(rng, cfg, rules: ShardingRules):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(rng, 4)
    p, s = {}, {}
    p["wr"], s["wr"] = dense_init(ks[0], (d, E), (None, None), rules)
    # Expert weights are EP(model)×FSDP(data)-sharded like every other
    # weight. (§Perf hillclimb #3 REFUTED the EP-only variant: replicating
    # experts over `data` swaps the per-microbatch bf16 all-gather for a
    # per-microbatch full-size f32 grad accumulator + all-reduce — granite
    # train_4k collective went 12.2s -> 20.8s. Whether FSDP applies at all
    # is decided per-arch by the param-memory rule in launch/specs.py.)
    p["wi"], s["wi"] = dense_init(ks[1], (E, d, f), ("experts", "embed", None), rules)
    p["wg"], s["wg"] = dense_init(ks[2], (E, d, f), ("experts", "embed", None), rules)
    p["wo"], s["wo"] = dense_init(ks[3], (E, f, d), ("experts", "embed", None), rules)
    return p, s


def _expert_ffn(wi, wg, wo, x):
    """x [..., d] through one (or a stacked batch of) expert(s)."""
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def _aux_loss(probs, ids, E: int):
    """Load-balancing loss: E * sum_e mean(route frac) * mean(prob)."""
    f = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(ids.size, 1)
    pbar = probs.mean(axis=0)
    return E * jnp.sum(f * pbar)


def apply_moe(cfg, p, x, rules: ShardingRules, capacity_factor: float | None = None):
    """x [B, S, d] -> (y [B, S, d], aux dict)."""
    E, k = cfg.num_experts, cfg.top_k
    mesh = rules.mesh
    n_model = int(mesh.shape.get("model", 1))
    S = x.shape[1]
    cf = capacity_factor or cfg.moe_capacity_factor

    if S == 1 or n_model == 1 or S % n_model != 0 or E % n_model != 0:
        return _apply_moe_dense(cfg, p, x)

    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    E_loc = E // n_model

    def body(xl, wr, wi, wg, wo):
        # xl [B_loc, S_loc, d]; wi/wg/wo local expert shards [E_loc, d, f]
        B_loc, S_loc, d = xl.shape
        N = B_loc * S_loc
        toks = xl.reshape(N, d)
        logits = (toks @ wr).astype(jnp.float32)  # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_ids = jax.lax.top_k(probs, k)  # [N, k]
        top_w = (top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)).astype(xl.dtype)
        aux = _aux_loss(probs, top_ids, E)

        # ---- dispatch (same sort-into-buckets as the EE-Join shuffle)
        C = max(8, math.ceil(N * k / n_model * cf))
        a_rank = (top_ids // E_loc).reshape(-1)  # [N*k]
        a_eloc = (top_ids % E_loc).reshape(-1)
        order = jnp.argsort(a_rank, stable=True)
        counts = jnp.bincount(a_rank, length=n_model + 1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(N * k) - starts[a_rank[order]]
        keep = pos < C
        dst_r = jnp.where(keep, a_rank[order], n_model - 1)
        dst_p = jnp.where(keep, pos, C)  # C -> dropped by mode="drop"

        tok_of = order // k
        send_x = jnp.zeros((n_model, C, d), xl.dtype)
        send_e = jnp.full((n_model, C), -1, jnp.int32)
        send_x = send_x.at[dst_r, dst_p].set(toks[tok_of], mode="drop")
        send_e = send_e.at[dst_r, dst_p].set(a_eloc[order].astype(jnp.int32), mode="drop")
        # remember where each assignment went (original order)
        slot = jnp.full((N * k,), n_model * C, jnp.int32)
        slot = slot.at[order].set(
            jnp.where(keep, dst_r * C + dst_p, n_model * C), mode="drop"
        )
        dropped = (~keep).sum()

        a2a = partial(jax.lax.all_to_all, axis_name="model", split_axis=0, concat_axis=0)
        recv_x = a2a(send_x)  # [n_model, C, d]
        recv_e = a2a(send_e)

        # ---- local expert compute (masked per local expert)
        rx = recv_x.reshape(n_model * C, d)
        re = recv_e.reshape(n_model * C)
        out = jnp.zeros((n_model * C, d), xl.dtype)
        for e in range(E_loc):
            h = _expert_ffn(wi[e], wg[e], wo[e], rx)
            out = out + h * (re == e)[:, None].astype(h.dtype)

        back = a2a(out.reshape(n_model, C, d))  # [n_model, C, d] at sender
        back_flat = jnp.concatenate(
            [back.reshape(n_model * C, d), jnp.zeros((1, d), xl.dtype)], axis=0
        )
        per_assign = back_flat[slot].reshape(N, k, d)
        y = (per_assign * top_w[..., None]).sum(axis=1).reshape(B_loc, S_loc, d)

        aux = jax.lax.pmean(aux, batch_axes + ("model",))
        drop_frac = jax.lax.pmean(dropped / (N * k), batch_axes + ("model",))
        return y, aux, drop_frac

    x_spec = P(batch_axes, "model", None)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            x_spec,
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )
    y, aux, drop = fn(x, p["wr"], p["wi"], p["wg"], p["wo"])
    return y, {"moe_aux": aux, "moe_drop_frac": drop}


def _apply_moe_dense(cfg, p, x):
    """Decode fallback: every (sharded) expert computes the whole batch."""
    E, k = cfg.num_experts, cfg.top_k
    B, S, d = x.shape
    toks = x.reshape(B * S, d)
    logits = (toks @ p["wr"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, k)
    top_w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    gate = jnp.zeros((B * S, E), jnp.float32)
    gate = jax.vmap(lambda g, i, w: g.at[i].set(w))(gate, top_ids, top_w)

    h = jnp.einsum("nd,edf->nef", toks, p["wg"])
    hi = jnp.einsum("nd,edf->nef", toks, p["wi"])
    h = jax.nn.silu(h) * hi
    y_e = jnp.einsum("nef,efd->ned", h, p["wo"])
    y = jnp.einsum("ned,ne->nd", y_e, gate.astype(y_e.dtype))
    aux = _aux_loss(probs, top_ids, E)
    return y.reshape(B, S, d), {"moe_aux": aux, "moe_drop_frac": jnp.float32(0.0)}

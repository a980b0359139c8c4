"""Training loop: microbatch gradient accumulation, sharded train_step,
metrics, periodic checkpointing, deterministic resume.

``make_train_step`` builds the jitted step for any (model, mesh):
  * the global batch enters sharded over (pod, data);
  * gradient accumulation scans over microbatches (the memory lever that
    fits dbrx-132b's train_4k — see EXPERIMENTS.md runtime table);
  * grads are accumulated in fp32 and fed to AdamW with fp32 masters;
  * optional int8 error-feedback compression for the cross-pod
    gradient reduction (train/compression.py).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Iterator

import numpy as np

import jax
import jax.numpy as jnp
from jax import set_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.model import LM, fused_ce_loss
from repro.train import checkpoint as ckpt_lib
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    total_steps: int = 100
    microbatches: int = 1  # gradient-accumulation chunks per step
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    seed: int = 0


def make_train_step(model: LM, opt_cfg: AdamWConfig, microbatches: int = 1):
    """Returns jit-able fn(params, opt_state, batch) -> (params, opt, metrics)."""
    cfg = model.cfg

    def loss_fn(params, tokens, labels, context):
        x, aux = model.forward_features(params, tokens, context)
        loss, parts = fused_ce_loss(
            cfg, x, params["lm_head"], labels, moe_aux=aux["moe_aux"]
        )
        return loss, parts

    def step(params, opt_state, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        context = batch.get("context")
        B = tokens.shape[0]
        assert B % microbatches == 0, (B, microbatches)
        mb = B // microbatches

        if microbatches == 1:
            (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, tokens, labels, context
            )
        else:
            t_r = tokens.reshape(microbatches, mb, *tokens.shape[1:])
            l_r = labels.reshape(microbatches, mb, *labels.shape[1:])
            c_r = (
                context.reshape(microbatches, mb, *context.shape[1:])
                if context is not None
                else None
            )

            def acc_fn(carry, xs):
                g_acc, loss_acc = carry
                t, l = xs[0], xs[1]
                c = xs[2] if len(xs) > 2 else None
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, t, l, c
                )
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / microbatches, g_acc, g
                )
                return (g_acc, loss_acc + loss / microbatches), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            xs = (t_r, l_r) + ((c_r,) if c_r is not None else ())
            (grads, loss), _ = jax.lax.scan(acc_fn, (g0, 0.0), xs)
            parts = {"nll": loss, "zloss": jnp.float32(0.0)}

        new_params, new_opt, om = adamw_update(opt_cfg, params, grads, opt_state)
        metrics = {"loss": loss, **{k: v for k, v in parts.items()}, **om}
        return new_params, new_opt, metrics

    return step


def data_shardings(mesh, batch_axes=("data",)):
    spec = P(batch_axes)
    return NamedSharding(mesh, spec)


def train(
    model: LM,
    data_iter: Iterator[dict],
    opt_cfg: AdamWConfig,
    tcfg: TrainerConfig,
    mesh,
    params=None,
    specs=None,
    resume: bool = False,
) -> dict:
    """Run the loop; returns final metrics history. Restart-safe."""
    if params is None:
        params, specs = model.init(jax.random.PRNGKey(tcfg.seed))
    opt_state = init_opt_state(params)
    start_step = 0

    if resume:
        restored = ckpt_lib.try_restore_latest(
            tcfg.checkpoint_dir, params, opt_state, mesh, specs
        )
        if restored is not None:
            params, opt_state, start_step = restored

    step_fn = jax.jit(make_train_step(model, opt_cfg, tcfg.microbatches))
    history = []
    t0 = time.time()
    with set_mesh(mesh):
        for step in range(start_step, tcfg.total_steps):
            batch = next(data_iter)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if (step + 1) % tcfg.log_every == 0 or step == start_step:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step + 1
                m["sec_per_step"] = (time.time() - t0) / max(step - start_step + 1, 1)
                history.append(m)
            if (step + 1) % tcfg.checkpoint_every == 0:
                ckpt_lib.save(
                    tcfg.checkpoint_dir, step + 1, params, opt_state,
                    keep=tcfg.keep_checkpoints,
                )
    return {"history": history, "params": params, "opt_state": opt_state}

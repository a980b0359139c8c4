"""Per-architecture smoke tests: reduced config, one forward + one decode
step on CPU; asserts shapes and finiteness (deliverable f)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import set_mesh

from repro.configs.registry import ARCH_IDS, get_smoke_config
from repro.models.model import build_model, lm_loss
from repro.models.sharding import ShardingRules

B, S = 2, 64


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def _context(cfg, batch):
    if cfg.context_len:
        rng = np.random.default_rng(0)
        return jnp.asarray(
            rng.normal(size=(batch, cfg.context_len, cfg.context_dim)).astype(np.float32)
        ).astype(jnp.bfloat16)
    return None


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_loss(arch):
    cfg = get_smoke_config(arch)
    mesh = _mesh()
    model = build_model(cfg, ShardingRules(mesh))
    params, specs = model.init(jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 1, cfg.vocab_size)
    ctx = _context(cfg, B)
    with set_mesh(mesh):
        logits, aux = jax.jit(model.forward)(params, tokens, ctx)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), f"{arch}: NaN/inf logits"
    labels = jnp.roll(tokens, -1, axis=1)
    loss, parts = lm_loss(cfg, logits, labels, moe_aux=aux["moe_aux"])
    assert bool(jnp.isfinite(loss)), f"{arch}: NaN loss"
    assert float(parts["nll"]) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_decreases_loss(arch):
    """One SGD step on a repeated batch should reduce the loss."""
    cfg = get_smoke_config(arch)
    mesh = _mesh()
    model = build_model(cfg, ShardingRules(mesh))
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 1, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    ctx = _context(cfg, B)

    def loss_fn(p):
        logits, aux = model.forward(p, tokens, ctx)
        return lm_loss(cfg, logits, labels, moe_aux=aux["moe_aux"])[0]

    with set_mesh(mesh):
        l0, g = jax.jit(jax.value_and_grad(loss_fn))(params)
        gnorm = jax.tree.reduce(
            lambda a, b: a + b, jax.tree.map(lambda x: jnp.abs(x.astype(jnp.float32)).sum(), g)
        )
        assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0, f"{arch}: bad grads"
        lr = 3e-3
        p1 = jax.tree.map(lambda p, gg: (p - lr * gg.astype(p.dtype)).astype(p.dtype), params, g)
        l1 = jax.jit(loss_fn)(p1)
    assert float(l1) < float(l0), f"{arch}: loss did not decrease ({l0} -> {l1})"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    """Token-by-token decode logits == teacher-forced forward logits."""
    cfg = get_smoke_config(arch)
    n = 8
    mesh = _mesh()
    model = build_model(cfg, ShardingRules(mesh))
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, n), 1, cfg.vocab_size)
    ctx = _context(cfg, B)
    with set_mesh(mesh):
        full_logits, _ = jax.jit(model.forward)(params, tokens, ctx)
        cache = model.init_cache(params, B, max_len=32, kv_splits=2, context=ctx)
        step = jax.jit(model.decode_step)
        decode_logits = []
        for t in range(n):
            lg, cache = step(params, cache, tokens[:, t], ctx)
            decode_logits.append(lg)
    dec = jnp.stack(decode_logits, axis=1).astype(jnp.float32)
    ref = full_logits.astype(jnp.float32)
    # bf16 accumulation noise through deep stacks: absolute tolerance
    # (logits are O(1) at init; relative error is meaningless near 0)
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(ref), rtol=0.0, atol=0.15,
        err_msg=f"{arch}: incremental decode diverges from forward",
    )
    # argmax agreement is the semantically meaningful check at bf16
    # (tiny random smoke models have near-tied logits -> 0.9 bar)
    agree = (dec.argmax(-1) == ref.argmax(-1)).mean()
    assert float(agree) >= 0.9, f"{arch}: decode argmax agreement {agree}"

"""Training substrate: convergence, checkpoint/restart, fault tolerance,
elastic re-mesh, gradient compression, pipeline, serving."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import set_mesh

from repro.configs.registry import get_smoke_config
from repro.core.cost_model import CostParams
from repro.core.eejoin import EEJoinConfig, EEJoinOperator
from repro.data.pipeline import PipelineConfig, annotate_docs, batches
from repro.data.synth import make_corpus
from repro.models.model import build_model
from repro.models.sharding import ShardingRules
from repro.train.fault_tolerance import (
    RestartPolicy,
    StepBarrierMonitor,
    run_with_restarts,
)
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state, lr_schedule
from repro.train.trainer import TrainerConfig, make_train_step, train
from repro.train import checkpoint as ckpt_lib


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("olmo-1b")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    model = build_model(cfg, ShardingRules(mesh))
    corpus = make_corpus(
        num_docs=16, doc_len=256, vocab_size=cfg.vocab_size, num_entities=16, seed=0
    )
    return dict(cfg=cfg, mesh=mesh, model=model, corpus=corpus)


def _data(setup, batch=4, seq=32):
    return batches(
        setup["corpus"], PipelineConfig(seq_len=seq, global_batch=batch, annotate=False)
    )


def test_lr_schedule_shapes():
    cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(cfg, jnp.int32(s))) for s in [0, 5, 10, 50, 100]]
    assert lrs[0] < lrs[1] < lrs[2]  # warmup
    assert lrs[2] >= lrs[3] >= lrs[4]  # decay
    assert lrs[4] >= cfg.lr * cfg.min_lr_frac * 0.99


def test_training_reduces_loss(setup):
    opt = AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=60, grad_clip=1.0)
    tcfg = TrainerConfig(
        total_steps=60, log_every=10, checkpoint_every=1000,
        checkpoint_dir="/tmp/repro_test_nockpt",
    )
    out = train(setup["model"], _data(setup), opt, tcfg, setup["mesh"])
    hist = out["history"]
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.8, hist
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_microbatching_matches_full_batch(setup):
    """Gradient accumulation must not change the update (up to fp32 sum order)."""
    model = setup["model"]
    params, _ = model.init(jax.random.PRNGKey(0))
    opt = AdamWConfig(lr=1e-3)
    batch = next(_data(setup, batch=4))
    s1 = jax.jit(make_train_step(model, opt, microbatches=1))
    s2 = jax.jit(make_train_step(model, opt, microbatches=2))
    with set_mesh(setup["mesh"]):
        p1, _, m1 = s1(params, init_opt_state(params), batch)
        p2, _, m2 = s2(params, init_opt_state(params), batch)
    d = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        p1, p2,
    )
    assert max(jax.tree.leaves(d)) < 2e-2  # bf16 params: one ulp-ish


def test_checkpoint_roundtrip_and_resume(setup, tmp_path):
    ckpt_dir = str(tmp_path / "ck")
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    tcfg = TrainerConfig(
        total_steps=10, log_every=5, checkpoint_every=5, checkpoint_dir=ckpt_dir,
    )
    # run 10 steps straight
    out_full = train(setup["model"], _data(setup), opt, tcfg, setup["mesh"])
    # run 5, then resume to 10 on a fresh data iterator (determinism)
    tcfg5 = TrainerConfig(
        total_steps=5, log_every=5, checkpoint_every=5,
        checkpoint_dir=ckpt_dir + "_b",
    )
    train(setup["model"], _data(setup), opt, tcfg5, setup["mesh"])
    tcfg10 = TrainerConfig(
        total_steps=10, log_every=5, checkpoint_every=5,
        checkpoint_dir=ckpt_dir + "_b",
    )
    # NOTE: the resumed run must skip consumed batches deterministically;
    # pipeline batches are a pure function of step, but the iterator
    # restarts at step 0 here — emulate by dropping the first 5 batches.
    it = _data(setup)
    for _ in range(5):
        next(it)
    out_res = train(
        setup["model"], it, opt, tcfg10, setup["mesh"], resume=True
    )
    pa = out_full["params"]
    pb = out_res["params"]
    diff = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        pa, pb,
    )
    assert max(jax.tree.leaves(diff)) < 1e-6, "resume must be bit-stable"


def test_checkpoint_gc_and_latest(setup, tmp_path):
    model = setup["model"]
    params, _ = model.init(jax.random.PRNGKey(0))
    opt_state = init_opt_state(params)
    d = str(tmp_path / "gc")
    for s in [5, 10, 15, 20]:
        ckpt_lib.save(d, s, params, opt_state, keep=2)
    assert ckpt_lib.latest_step(d) == 20
    kept = sorted(x for x in os.listdir(d) if x.startswith("step_"))
    assert len(kept) == 2


def test_fault_injection_restart(setup, tmp_path):
    """Crash at step 7 -> supervisor restores from step-5 checkpoint."""
    ckpt_dir = str(tmp_path / "ft")
    opt = AdamWConfig(lr=1e-3)
    crashes = {"n": 0}
    restarts = []

    def train_fn(resume: bool) -> dict:
        tcfg = TrainerConfig(
            total_steps=12, log_every=4, checkpoint_every=5, checkpoint_dir=ckpt_dir,
        )
        it = _data(setup)
        if resume:
            start = ckpt_lib.latest_step(ckpt_dir) or 0
            for _ in range(start):
                next(it)
            return train(setup["model"], it, opt, tcfg, setup["mesh"], resume=True)
        # first attempt: wrap the iterator to crash mid-run
        def crashing():
            for i, b in enumerate(it):
                if i == 7 and crashes["n"] == 0:
                    crashes["n"] += 1
                    raise RuntimeError("injected node failure")
                yield b

        return train(setup["model"], crashing(), opt, tcfg, setup["mesh"])

    out = run_with_restarts(
        train_fn,
        RestartPolicy(max_restarts=2, backoff_s=0.01),
        on_restart=lambda a, e: restarts.append(str(e)),
    )
    assert crashes["n"] == 1 and len(restarts) == 1
    assert out["history"][-1]["step"] == 12


def test_elastic_remesh_restore(setup, tmp_path):
    """Checkpoint saved under one mesh restores onto another factorisation."""
    from repro.train.fault_tolerance import elastic_remesh

    model = setup["model"]
    params, specs = model.init(jax.random.PRNGKey(0))
    opt_state = init_opt_state(params)
    d = str(tmp_path / "re")
    ckpt_lib.save(d, 3, params, opt_state, keep=1)
    new_mesh = jax.make_mesh((1, 1), ("data", "model"))
    p2, o2, step = elastic_remesh(d, params, opt_state, new_mesh, specs)
    assert step == 3
    same = jax.tree.map(
        lambda a, b: bool(jnp.all(a == b)), params, p2
    )
    assert all(jax.tree.leaves(same))


def test_compression_error_feedback_unbiased():
    """EF residual makes repeated compression average to the truth."""
    from repro.train.compression import ef_compress_tree, dequantize, init_residual

    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
    res = init_residual(g)
    acc = jnp.zeros((64, 64), jnp.float32)
    n = 50
    for _ in range(n):
        q, res = ef_compress_tree(g, res)
        acc = acc + dequantize(*q["w"])
    err_ef = float(jnp.abs(acc / n - g["w"]).mean())
    # without EF the bias stays at the quantisation error level
    q1, _ = ef_compress_tree(g, init_residual(g))
    err_plain = float(jnp.abs(dequantize(*q1["w"]) - g["w"]).mean())
    assert err_ef < err_plain * 0.2, (err_ef, err_plain)


def test_straggler_monitor_flags_outliers():
    import time

    mon = StepBarrierMonitor(threshold=3.0)
    for i in range(8):
        mon.start()
        time.sleep(0.03 if i == 6 else 0.002)
        mon.stop(i)
    assert any(s == 6 for s, _, _ in mon.flagged)


def test_pipeline_annotation_marks_entities(zipf_corpus):
    c = zipf_corpus
    op = EEJoinOperator(c.dictionary, EEJoinConfig(gamma=0.8))
    stats = op.gather_statistics(c.doc_tokens[:8], total_docs=c.doc_tokens.shape[0])
    plan = op.choose_plan(stats, CostParams(num_devices=1))
    prepared = op.prepare(plan, CostParams(num_devices=1))
    mask = annotate_docs(op, prepared, c.doc_tokens)
    assert mask.shape == c.doc_tokens.shape
    assert mask.sum() > 0
    # every planted (unnoised) mention should be covered for the
    # variant-exact side at minimum; check coverage is plausible
    frac = mask.mean()
    assert 0.0 < frac < 0.5


def test_serve_engine_generates(setup):
    from repro.serve.engine import Request, ServeEngine

    model = setup["model"]
    params, _ = model.init(jax.random.PRNGKey(0))
    with set_mesh(setup["mesh"]):
        eng = ServeEngine(model, params, batch_slots=4, max_len=64)
        reqs = [Request(prompt=[5, 9, 12], max_new_tokens=4) for _ in range(6)]
        for r in reqs:
            eng.submit(r)
        eng.run()
    done = [r for r in reqs if r.done]
    assert len(done) >= 4  # 64-token window fits at least the first wave
    for r in done:
        assert len(r.out) == 4
        assert all(0 <= t < setup["cfg"].padded_vocab for t in r.out)

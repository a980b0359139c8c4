"""System-level tests: the dry-run/roofline stack and launch plumbing.

The 512-device production dry-run runs out of process (launch/dryrun.py);
here we exercise the same machinery in-process on small meshes so a
sharding or analysis regression fails fast in CI.
"""
from __future__ import annotations

import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
from jax import set_mesh

from repro.configs.base import SHAPES, ShapeConfig
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.launch import hlo_analysis as H
from repro.launch import roofline as RL
from repro.launch.mesh import make_cpu_mesh
from repro.launch.specs import abstract_params, build_cell
from repro.launch.tuning import default_microbatches, resolve
from repro.models.model import build_model
from repro.models.sharding import ShardingRules


# --------------------------------------------------------------------------
# hlo_analysis: trip-count awareness + parser robustness
# --------------------------------------------------------------------------


def _analyze(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return H.analyze(txt)


def test_scan_flops_are_trip_multiplied():
    A = jnp.zeros((128, 128), jnp.float32)

    def body(x, _):
        return x @ A, None

    def scanned(x):
        return jax.lax.scan(body, x, None, length=8)[0]

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    s_scan = _analyze(scanned, x)
    one = 2 * 128**3
    # XLA's own cost_analysis reports ~1x here; ours must report ~8x.
    assert s_scan.mxu_flops == pytest.approx(8 * one, rel=0.05), s_scan.mxu_flops


def test_nested_scan_flops():
    A = jnp.zeros((64, 64), jnp.float32)

    def inner(x, _):
        return x @ A, None

    def outer(x, _):
        return jax.lax.scan(inner, x, None, length=3)[0], None

    def fn(x):
        return jax.lax.scan(outer, x, None, length=5)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    s = _analyze(fn, x)
    assert s.mxu_flops == pytest.approx(15 * 2 * 64**3, rel=0.05)


def test_parser_stable_on_scan_without_collectives():
    def fn(x):
        return jax.lax.scan(lambda c, _: (c * 2.0, None), x, None, length=4)[0]

    s = _analyze(fn, jax.ShapeDtypeStruct((32,), jnp.float32))
    assert s.wire_bytes == 0.0
    assert s.unknown_trip_whiles == 0


def test_type_bytes_parses_tuples_and_layouts():
    assert H._type_bytes("f32[8,16]{1,0}") == 8 * 16 * 4
    assert H._type_bytes("(bf16[4,4]{1,0}, s32[2]{0})") == 32 + 8
    assert H._type_bytes("pred[]") == 1
    assert H._type_bytes("token[]") == 0


@given(st.lists(st.integers(1, 64), min_size=0, max_size=4))
@settings(max_examples=50, deadline=None)
def test_elems_matches_product(dims):
    s = ",".join(str(d) for d in dims)
    want = int(np.prod(dims)) if dims else 1
    assert H._elems(s) == want


def test_group_size_iota_and_list():
    assert H._group_size("replica_groups=[32,16]<=[512]") == 16
    assert H._group_size("replica_groups={{0,1,2,3}}") == 4
    assert H._group_size("no groups here") == 1


# --------------------------------------------------------------------------
# roofline model
# --------------------------------------------------------------------------


def test_model_flops_dense_train_matches_6nd():
    cfg = get_config("yi-9b")
    shape = SHAPES["train_4k"]
    mf = RL.model_flops(cfg, shape)
    # yi-9b ~8.8B params; 6*N*D within a loose band
    n_est = mf / (6.0 * shape.global_batch * shape.seq_len)
    assert 7e9 < n_est < 10e9, n_est


def test_model_flops_moe_counts_active_only():
    import dataclasses

    cfg = get_config("dbrx-132b")
    active = RL.model_flops(cfg, SHAPES["train_4k"])
    all_on = dataclasses.replace(cfg, top_k=cfg.num_experts)
    assert RL.model_flops(all_on, SHAPES["train_4k"]) > 2 * active


def test_decode_flops_scale_with_batch_not_seq():
    cfg = get_config("olmo-1b")
    d32 = RL.model_flops(cfg, SHAPES["decode_32k"])
    tr = RL.model_flops(cfg, SHAPES["train_4k"])
    assert d32 < tr / 1000


# --------------------------------------------------------------------------
# specs/build_cell on tiny meshes (same code path as the 512-dev dry-run)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["train", "prefill", "decode"])
def test_build_cell_lowers_on_cpu_mesh(mode):
    mesh = make_cpu_mesh(1, 1)
    cfg = get_smoke_config("olmo-1b")
    shape = ShapeConfig("t", seq_len=64, global_batch=4, mode=mode)
    cell = build_cell(cfg, shape, mesh)
    with set_mesh(mesh):
        lowered = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        ).lower(*cell.args)
        compiled = lowered.compile()
    assert compiled.cost_analysis() is not None
    stats = H.analyze(compiled.as_text())
    assert stats.mxu_flops > 0


def test_abstract_params_allocate_nothing_and_match_init():
    mesh = make_cpu_mesh(1, 1)
    cfg = get_smoke_config("xlstm-125m")
    model = build_model(cfg, ShardingRules(mesh))
    p_shapes, specs = abstract_params(model)
    p_real, specs_real = model.init(jax.random.PRNGKey(0))
    flat_a = jax.tree.leaves(p_shapes)
    flat_b = jax.tree.leaves(p_real)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert jax.tree.structure(specs) == jax.tree.structure(specs_real)


def test_default_microbatches_fit_budget():
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    for arch in ("dbrx-132b", "yi-9b", "olmo-1b"):
        cfg = get_config(arch)
        mb = default_microbatches(cfg, SHAPES["train_4k"], FakeMesh())
        per_dev = SHAPES["train_4k"].global_batch // 16
        assert 1 <= mb <= per_dev
        stash = cfg.num_groups * (per_dev / mb) * 4096 * cfg.d_model * 2
        assert stash <= 4e9 or mb == per_dev, (arch, mb, stash)


def test_resolve_tuned_overrides_cfg():
    from repro.launch import tuning

    mesh = make_cpu_mesh(1, 1)
    cfg = get_config("olmo-1b")
    key = (cfg.name, "train_4k")
    old = tuning.TUNED.get(key)
    tuning.TUNED[key] = {"cfg": {"attn_chunk": 512}, "microbatches": 4}
    try:
        cfg2, knobs = resolve(cfg, SHAPES["train_4k"], mesh, tuned=True)
        assert cfg2.attn_chunk == 512 and knobs["microbatches"] == 4
        cfg3, _ = resolve(cfg, SHAPES["train_4k"], mesh, tuned=False)
        assert cfg3.attn_chunk == cfg.attn_chunk
    finally:
        if old is None:
            tuning.TUNED.pop(key)
        else:
            tuning.TUNED[key] = old


# --------------------------------------------------------------------------
# registry coverage: every assigned arch present with the exact shapes
# --------------------------------------------------------------------------


def test_all_ten_archs_registered_with_assigned_dims():
    assert len(ARCH_IDS) == 10
    spec = {
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
    }
    for arch, (L, d, h, kv, ff, v) in spec.items():
        cfg = get_config(arch)
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads,
                cfg.num_kv_heads, cfg.d_ff, cfg.vocab_size) == (L, d, h, kv, ff, v), arch

"""``chip_smoke.py`` phases at a tiny size on the CPU (interpret mode),
plus the kernel helpers that make the main path lower for the TPU,
each against its plain reference."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.filter import token_in_filter
from repro.kernels import _hashing
from repro.kernels import fused_probe as fp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(cs):
    return cs.make_smoke_corpus(0, num_entities=64, vocab_size=1024,
                                num_docs=32, doc_len=64)


# ------------------------------------------------------------- phases
def test_batch_phase_parity_tiny(cs, tiny, tmp_path):
    rep = cs.batch_phase(tiny, work_dir=tmp_path, oracle_docs=4,
                         stats_docs=16, tile_docs=8)
    assert rep["matches"] > 0 and rep["oracle_matches"] > 0
    assert rep["shards"] >= 4
    assert 0 < rep["survival_share"] <= 100
    assert set(rep["verify_impl"].values()) <= {"pallas", "jnp"}


def test_serving_phase_parity_tiny(cs, tiny):
    rep = cs.serving_phase(tiny, requests=8, batch_docs=4)
    assert rep["matches"] > 0 and rep["batches"] >= 2


def test_overflow_is_a_failure(cs, tiny):
    """A result buffer too small for the matches fails the smoke."""
    cfg = cs.batch_config(tiny, result_capacity=4)
    op, _, prepared = cs.plan_and_prepare(tiny, cfg, stats_docs=16)
    m = op.execute(prepared, jnp.asarray(tiny.doc_tokens))
    assert int(m.count) > 4
    with pytest.raises(cs.SmokeFailure, match="overflowed"):
        cs._no_overflow(m, cfg.result_capacity, "execute")


#: the --chips 4 legs on four fake CPU devices (the device count is
#: fixed before JAX starts, so they run in a child process)
MULTICHIP_CHILD = """
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
import chip_smoke as cs
corpus = cs.make_smoke_corpus(0, num_entities=64, vocab_size=1024,
                              num_docs=32, doc_len=64)
rep = cs.multichip_phase(corpus, jax.devices()[:4], stats_docs=16)
print(json.dumps({"devices": len(jax.devices()), **rep}, default=str))
"""


def test_multichip_phase_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", MULTICHIP_CHILD, REPO],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["devices"] == 4
    assert rep["sharded"]["matches"] > 0
    assert "disjoint" in rep["pools"]["pools"] and rep["pools"]["matches"] > 0
    assert rep["replicas"]["served"] == {f"replica{i}": 1 for i in range(4)}
    assert rep["replicas"]["matches"] > 0


def test_main_refuses_without_tpu(cs):
    assert jax.default_backend() != "tpu"
    assert cs.main([]) == 2


# ---------------------------------------------------- kernel helpers
@pytest.mark.parametrize("shape", [(8, 24), (16, 256), (3, 7)])
def test_exclusive_prefix_rows_matches_cumsum(shape):
    rng = np.random.default_rng(sum(shape))
    x = rng.integers(0, 33, size=shape).astype(np.int32)
    got = np.asarray(fp.exclusive_prefix_rows(jnp.asarray(x)))
    want = np.cumsum(x, axis=0) - x
    np.testing.assert_array_equal(got, want)


def test_word_popcounts_matches_numpy():
    rng = np.random.default_rng(1)
    L = 7
    pack = rng.integers(0, 1 << L, size=(8, 40), dtype=np.uint32)
    got = np.asarray(fp.word_popcounts(jnp.asarray(pack), L))
    want = np.vectorize(lambda w: bin(int(w)).count("1"))(pack)
    np.testing.assert_array_equal(got, want)


def test_biased_unsigned_minimum():
    rng = np.random.default_rng(2)
    a = rng.integers(0, 2**32, size=(64, 9), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(64, 9), dtype=np.uint32)
    a[0, :3] = [0, 0x7FFFFFFF, 0xFFFFFFFF]
    b[0, :3] = [0xFFFFFFFF, 0x80000000, 0]
    np.testing.assert_array_equal(
        np.asarray(_hashing.umin(jnp.asarray(a), jnp.asarray(b))),
        np.minimum(a, b))
    np.testing.assert_array_equal(
        np.asarray(_hashing.umin_reduce(jnp.asarray(a), axis=-1)),
        a.min(axis=-1))


def test_bloom_hit_flag_matches_filter_probe():
    """The per-token Bloom word lookup that feeds the kernels rides the
    token's sign bit; stripping it gives back the token and the hit of
    ``token_in_filter``."""
    rng = np.random.default_rng(3)
    num_bits = 1 << 12
    bits = jnp.asarray(rng.integers(0, 2**32, size=(num_bits // 32,),
                                    dtype=np.uint32))
    toks = rng.integers(0, 2**31 - 1, size=(6, 50)).astype(np.int32)
    toks[:, -5:] = 0  # PAD
    enc = fp.flag_bloom_hits(jnp.asarray(toks), bits, num_bits, 3)
    tok, real, hit = fp._split_stream(enc, use_filter=True)
    np.testing.assert_array_equal(np.asarray(tok), toks)
    np.testing.assert_array_equal(np.asarray(real), toks != 0)
    np.testing.assert_array_equal(
        np.asarray(hit),
        np.asarray(token_in_filter(bits, num_bits, 3, jnp.asarray(toks))))


@pytest.mark.parametrize("width", [1, 100, 128, 513, 5000])
def test_lane_chunking_covers_width(width):
    chunks, chunk = fp.lane_chunking(width)
    assert chunks * chunk >= width and chunk % 128 == 0
    assert chunks == 1 or chunks % 8 == 0


# ------------------------------------------------------ compile cache
def test_compile_cache_dir(monkeypatch):
    from repro import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", was)
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was  # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# ------------------------------------------------------ chunked verify
@pytest.mark.parametrize("rows", [2048, 3000])  # even chunks, sliding tail
def test_chunked_verify_matches_one_shot(cs, tiny, monkeypatch, rows):
    """Candidate buffers beyond ``VERIFY_ROWS`` verify chunk by chunk
    with the same matches and the true count (overflow included)."""
    from repro.core import eejoin

    cfg = cs.batch_config(tiny)
    op, _, prepared = cs.plan_and_prepare(tiny, cfg, stats_docs=16)
    docs = jnp.asarray(tiny.doc_tokens)
    want = op.execute(prepared, docs)
    monkeypatch.setattr(eejoin, "VERIFY_ROWS", rows)
    assert cfg.max_candidates > 3 * eejoin.VERIFY_ROWS
    got = op.execute(prepared, docs)
    assert got.to_set() == want.to_set() and int(got.count) == int(want.count)
    small = cs.batch_config(tiny, result_capacity=16)
    op2 = eejoin.EEJoinOperator(tiny.dictionary, small)
    over = op2.execute(op2.prepare(prepared.plan), docs)
    assert int(over.count) == int(want.count) > 16

"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with JAX lowers each kernel
with ``interpret=False`` for one chip of a described (not attached)
``v5e:2x2`` topology, at the widths ``chip_smoke.py`` drives. This
catches what interpret mode cannot — unaligned block shapes, operations
Mosaic does not lower, scoped-VMEM overuse — without a chip.

The topology is described inside a module fixture (never at import or
collection), and the fixture skips where it cannot be described.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_probe as fp
from repro.kernels import jaccard_verify as jv
from repro.kernels import minhash as mh

# chip_smoke.py: 4096 x 256-token documents, entities of <= 5 tokens, a
# window-grid candidate capacity, the default 2**18-bit Bloom filter
D, T, L = 4096, 256, 5
NC = D * T * L
NUM_BITS = 1 << 18
NUM_HASHES = 3
N_VERIFY, K_VERIFY = 1 << 16, 8  # candidate windows x entities per window
STREAM_TILE_DOCS = 64  # extraction.sharded.DEFAULT_TILE_DOCS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


def _probe_args(one_chip, rows=D):
    return (jax.ShapeDtypeStruct((rows, T), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((NUM_BITS // 32,), jnp.uint32,
                                 sharding=one_chip))


@pytest.mark.parametrize("mode", ["bitmap", "count_only", "lanes", "variant"])
def test_fused_probe_compiles_for_v5e(one_chip, mode):
    bd = fp.DEFAULT_BD if mode == "bitmap" else fp.compact_tile_height(D, T, NC)
    width = 0 if mode == "bitmap" else fp.lane_width_for(NC, bd, T, L)
    kw = dict(bd=bd, candidates=width, count_only=mode == "count_only",
              sig_mode="variant" if mode == "variant" else "none")
    _compile(
        lambda d, b: fp.fused_probe_pallas(d, b, NUM_BITS, NUM_HASHES, L,
                                           interpret=False, **kw),
        *_probe_args(one_chip),
    )


@pytest.mark.parametrize("sig_mode", ["none", "variant"])
def test_fused_probe_stream_compiles_for_v5e(one_chip, sig_mode):
    bd = fp.compact_tile_height(STREAM_TILE_DOCS, T, NC)
    width = fp.lane_width_for(NC, bd, T, L)
    rows = 1024  # one execute_corpus shard of the chip_smoke corpus
    offs = jax.ShapeDtypeStruct((rows // bd,), jnp.int32, sharding=one_chip)
    _compile(
        lambda d, b, o: fp.fused_probe_stream_pallas(
            d, b, o, NUM_BITS, NUM_HASHES, L, sig_mode=sig_mode, bd=bd,
            candidates=width, interpret=False),
        *_probe_args(one_chip, rows), offs,
    )


def test_minhash_compiles_for_v5e(one_chip):
    _compile(
        lambda t, v: mh.minhash_pallas(t, v, bands=4, rows=2,
                                       interpret=False),
        jax.ShapeDtypeStruct((N_VERIFY, L), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((N_VERIFY, L), jnp.bool_, sharding=one_chip),
    )


@pytest.mark.parametrize("mode", ["extra", "missing"])
def test_jaccard_verify_compiles_for_v5e(one_chip, mode):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _compile(
        lambda a, b, c, d: jv.jaccard_verify_pallas(a, b, c, d, mode=mode,
                                                    interpret=False),
        s((N_VERIFY, L), jnp.int32), s((N_VERIFY, L), jnp.float32),
        s((N_VERIFY, K_VERIFY, L), jnp.int32),
        s((N_VERIFY, K_VERIFY, L), jnp.float32),
    )

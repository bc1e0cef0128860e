"""Ahead-of-time compiles for a described v5e chip at qwen3_4b widths.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
described by ``topologies.get_topology_desc`` and refuses what the chip
would refuse (unaligned kernel blocks, programs that do not fit HBM).
The topology is described inside a fixture, never at import, because
only one process at a time may load the TPU library.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.paged_attention import paged_attention
from repro.models.model import Model
from repro.models.params import split_params
from repro.serving import engine as engine_mod
from repro.serving.engine import PagedEngine

QWEN = get_config("qwen3_4b")
MAX_BATCH, NUM_PAGES, PAGE_SIZE, MAX_PAGES_PER_SEQ = 8, 1024, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_compiles_for_v5e(one_chip, dtype):
    h, kv, dh = QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim
    pages = _on(one_chip, (NUM_PAGES, kv, PAGE_SIZE, dh), dtype)
    compiled = jax.jit(paged_attention).lower(
        _on(one_chip, (MAX_BATCH, h, dh), dtype), pages, pages,
        _on(one_chip, (MAX_BATCH, MAX_PAGES_PER_SEQ), jnp.int32),
        _on(one_chip, (MAX_BATCH,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def engine_2l(one_chip):
    """The serving engine at qwen3_4b widths, 2 layers, with its params
    and arenas as TPU-placed shapes."""
    cfg = replace(QWEN, n_layers=2)
    shapes, _ = split_params(Model(cfg, dtype=jnp.float32).abstract_params())
    params = jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype), shapes)
    # the compiles take the arena shapes below, not the pool's own arrays,
    # so the pool is built at its smallest
    eng = PagedEngine(cfg, max_batch=MAX_BATCH, num_pages=2,
                      page_size=PAGE_SIZE, params=params)
    arena = _on(one_chip, (cfg.n_layers, NUM_PAGES, cfg.n_kv_heads,
                           PAGE_SIZE, cfg.head_dim), jnp.float32)
    return eng, arena


def test_decode_step_compiles_with_kernel(one_chip, engine_2l, monkeypatch):
    # off the chip the engine picks the jnp reference; steer the kernel in
    monkeypatch.setattr(engine_mod, "_paged_attention", paged_attention)
    eng, arena = engine_2l
    lane = _on(one_chip, (MAX_BATCH,), jnp.int32)
    compiled = eng._decode_jit.lower(
        eng.params, arena, arena, lane, lane,
        _on(one_chip, (MAX_BATCH, MAX_PAGES_PER_SEQ), jnp.int32),
        lane, lane, lane).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_prefill_bucket_compiles(one_chip, engine_2l):
    eng, _ = engine_2l
    s_pad = 7 * PAGE_SIZE
    eng._prefill_jit.lower(eng.params, _on(one_chip, (1, s_pad), jnp.int32),
                           _on(one_chip, (), jnp.int32),
                           s_pad=s_pad).compile()

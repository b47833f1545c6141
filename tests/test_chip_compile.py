"""Compile the Pallas kernels for a described TPU v5e, at published widths.

Interpret mode (tests/test_kernels.py) checks what a kernel computes; it
cannot check that the TPU lowering accepts the kernel's block shapes and
operations. These tests run the real TPU compiler against a v5e topology
that is described, not attached — nothing executes. They call the kernel
modules directly with ``interpret=False``: ``kernels.ops`` sees the CPU
backend and would pick the jnp reference.

The topology is described only inside the module fixture below, never
while a module is imported: one process at a time may load the TPU
library, and pytest-xdist workers import every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rglru_scan import rglru
from repro.kernels.rwkv6_wkv import wkv6


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page", [16, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_compiles_qwen3_8b(one_chip, page, dtype):
    cfg = get_config("qwen3-8b")
    b, n_blocks = 8, 2048 // page
    pool = (b * n_blocks + 1, page, cfg.n_kv_heads, cfg.head_dim)
    _compile(functools.partial(paged_attention, interpret=False), one_chip,
             ((b, cfg.n_heads, cfg.head_dim), dtype), (pool, dtype),
             (pool, dtype), ((b, n_blocks), jnp.int32), ((b,), jnp.int32))


def test_wkv6_compiles_rwkv6_1_6b(one_chip):
    cfg = get_config("rwkv6-1.6b")
    hd = cfg.rwkv.head_dim
    x = ((2, 512, cfg.d_model // hd, hd), jnp.float32)
    _compile(functools.partial(wkv6, interpret=False), one_chip,
             x, x, x, x, ((cfg.d_model // hd, hd), jnp.float32))


def test_rglru_compiles_recurrentgemma_2b(one_chip):
    w = get_config("recurrentgemma-2b").rglru.lru_width
    x = ((2, 512, w), jnp.float32)
    _compile(functools.partial(rglru, interpret=False), one_chip,
             x, x, ((2, w), jnp.float32))


def test_flash_prefill_compiles_qwen3_8b(one_chip):
    cfg = get_config("qwen3-8b")
    kv = ((1, 2048, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    _compile(functools.partial(flash_prefill, interpret=False), one_chip,
             ((1, 2048, cfg.n_heads, cfg.head_dim), jnp.float32), kv, kv)

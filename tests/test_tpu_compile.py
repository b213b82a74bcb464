"""Mosaic compiles of the main path's Pallas kernels for a TPU v5e.

Interpret mode (what every other kernel test runs on the CPU) cannot see
misaligned BlockSpecs, VMEM overruns or ops Mosaic cannot lower. These
tests compile each kernel at the widths of Yi-9B (head_dim 128, 4 KV
heads x 8 query groups, block_len 16, vocabulary 64000) against a
described ``v5e:2x2`` topology; nothing runs, so they need no chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.

The last test checks where the entry points put the persistent
compilation cache (``repro.launch.compile_cache``).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cordic_act, paged_attention, softmax_cordic
from repro.launch import compile_cache

# Yi-9B decode geometry: 8 slots, 32 query heads over 4 KV heads,
# block_len 16, 32 table chunks (max_len 512), worst-case pool + scratch
B, KH, G, HD, L, M = 8, 4, 8, 128, 16, 32
N = B * M + 1
VOCAB = 64000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache off around it
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("softmax_impl,kv_quant", [
    ("exact", "none"), ("cordic_pallas", "none"), ("exact", "int8")])
def test_gqa_decode_compiles(one_chip, softmax_impl, kv_quant):
    pool_dtype = jnp.int8 if kv_quant == "int8" else jnp.float32
    shapes = [((B, KH, G, HD), jnp.bfloat16),
              ((N, L, KH, HD), pool_dtype), ((N, L, KH, HD), pool_dtype),
              ((B, M), jnp.int32), ((B,), jnp.int32)]
    if kv_quant == "none":
        fn = functools.partial(paged_attention.gqa_decode, scale=HD ** -0.5,
                               softmax_impl=softmax_impl,
                               kv_dtype=jnp.bfloat16)
    else:
        shapes += [((N, 1, KH, 1), jnp.float32)] * 2

        def fn(q, kp, vp, t, kl, ks, vs):
            return paged_attention.gqa_decode(
                q, kp, vp, t, kl, scale=HD ** -0.5, kv_quant=kv_quant,
                k_scale_pool=ks, v_scale_pool=vs)
    _compile(fn, one_chip, *shapes)


def test_mla_decode_compiles(one_chip):
    H, R, P = 16, 512, 64
    fn = functools.partial(paged_attention.mla_decode, scale=(R + P) ** -0.5)
    _compile(fn, one_chip, ((B, H, R), jnp.float32), ((B, H, P), jnp.float32),
             ((N, L, R), jnp.float32), ((N, L, P), jnp.float32),
             ((B, M), jnp.int32), ((B,), jnp.int32))


def test_sigmoid_act_compiles(one_chip):
    fn = functools.partial(cordic_act.act_2d, op="sigmoid")
    _compile(fn, one_chip, ((512, 1024), jnp.float32))


def test_silu_mul_bf16_compiles(one_chip):
    _compile(cordic_act.silu_mul_2d, one_chip, ((512, 1024), jnp.bfloat16),
             ((512, 1024), jnp.bfloat16))


@pytest.mark.parametrize("name,cols", [("softmax_2d", 2048),
                                       ("log_softmax_2d", VOCAB)])
def test_row_softmax_compiles(one_chip, name, cols):
    # 64000 columns is Yi-9B's vocabulary under loss_impl="cordic_pallas":
    # its 8-row floor tile needs more than the default scoped VMEM limit
    _compile(getattr(softmax_cordic, name), one_chip,
             ((256, cols), jnp.float32))


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax_cache"])
def test_compile_cache_placement(monkeypatch, env_dir):
    # JAX reads JAX_COMPILATION_CACHE_DIR itself; only without it does
    # setup() pick the fixed in-checkout directory
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.setup()
        if env_dir is None:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

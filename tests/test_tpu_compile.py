"""Main-path programs compiled for a described TPU v5e (no chip attached).

The TPU compiler is installed with libtpu, so it can compile for a v5e
topology that is described rather than attached.  That refuses what
interpret mode accepts (misaligned blocks, too much VMEM, a program that
does not fit HBM) at no chip time.  Nothing runs: these tests check that
each program compiles, that the Pallas kernel lowers to a Mosaic
``tpu_custom_call``, and the compiler's memory analysis.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_fwd)
from repro.models.registry import bundle_for
from repro.serving.engine import InferenceEngine

#: One v5e chip's HBM (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def no_compile_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """ShapeDtypeStructs of `tree`'s leaves, placed on `sharding`."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "smollm-360m"])
def test_decode_kernel_compiles_to_mosaic(one_chip, arch):
    cfg = C.get(arch)
    b, s = 8, 2048
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    args = (_spec(one_chip, (b, h, d), jnp.bfloat16),
            _spec(one_chip, (b, s, kvh, d), jnp.bfloat16),
            _spec(one_chip, (b, s, kvh, d), jnp.bfloat16),
            _spec(one_chip, (b,), jnp.int32),
            _spec(one_chip, (b,), jnp.int32))
    compiled = decode_attention_fwd.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen2(one_chip):
    bundle = bundle_for(C.get("qwen2-1.5b"))
    return bundle, _on(one_chip, bundle.abstract_params())


def test_published_prefill_compiles(one_chip, qwen2):
    bundle, params = qwen2
    b, p, max_len = 8, 256, 2048
    cache = _on(one_chip, jax.eval_shape(
        lambda: bundle.init_cache(b, max_len)))
    toks = _spec(one_chip, (b, p), jnp.int32)
    mask = _spec(one_chip, (b, p), jnp.bool_)
    compiled = jax.jit(
        lambda pr, t, c, m: bundle.prefill(pr, t, c, attn_mask=m)
    ).lower(params, toks, cache, mask).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes > 0


def test_continuous_decode_fits_one_chip(one_chip, qwen2):
    """The engine's chunked continuous-decode program at 8 slots over a
    1024-position arena: weights + cache + output + temporaries must fit
    one chip with a quarter of its HBM to spare."""
    bundle, params = qwen2
    b, s, chunk = 8, 1024, 16
    eng = InferenceEngine(bundle, params, max_batch=b, max_seq_len=s)
    cache = _on(one_chip, jax.eval_shape(lambda: bundle.init_cache(b, s)))
    i32 = lambda shape=(): _spec(one_chip, shape, jnp.int32)  # noqa: E731
    compiled = eng._fused_continuous.lower(
        params, i32((b,)), cache, _spec(one_chip, (b, s), jnp.bool_),
        i32(), _spec(one_chip, (b,), jnp.bool_), i32((b,)), i32(), i32(),
        i32(), chunk).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights
    assert total < 0.75 * V5E_HBM_BYTES, (
        f"args {mem.argument_size_in_bytes} + out "
        f"{mem.output_size_in_bytes} + temp {mem.temp_size_in_bytes}")

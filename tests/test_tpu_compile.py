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

import re

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


def _computations(hlo: str) -> dict:
    """Optimised HLO text -> {computation name: its instruction lines};
    the entry computation is keyed "ENTRY"."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = "ENTRY" if head.group(1) else head.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line.strip())
    return comps


def _loop_bodies(comps: dict, comp: str) -> list:
    """Body computations of the while loops that `comp` runs."""
    return [m.group(1) for line in comps[comp] if " while(" in line
            for m in [re.search(r"body=%?([\w.\-]+)", line)] if m]


#: `temp_size_in_bytes` of the served decode program below when every
#: decode step re-emitted the layer slabs through the layer scan's outputs.
SLAB_SCAN_TEMP_BYTES = 6_157_379_072

#: `temp_size_in_bytes` of the served decode program and of the admission
#: program (prompt bucket 256) when every call gathered a copy of each
#: layer-weight stack before the layer scan, one while loop per stack.
GATHERED_LAYERS_TEMP_BYTES = {"continuous": 4_953_484_800,
                              "admit": 4_277_091_840}

#: Instructions in each program's layer-scan body with that gather.
GATHERED_LAYERS_SCAN_OPS = {"continuous": 89, "admit": 97}


@pytest.fixture(scope="module")
def served(one_chip, qwen2):
    """The served programs of qwen2-1.5b at 16 slots x 2048: the chunked
    continuous decode (chunk 16) and the admission of one prompt of
    bucket 256, compiled; and the pool cache's shapes."""
    bundle, params = qwen2
    b, s, chunk, bucket = 16, 2048, 16, 256
    eng = InferenceEngine(bundle, params, max_batch=b, max_seq_len=s)
    cache = _on(one_chip, jax.eval_shape(lambda: bundle.init_cache(b, s)))
    i32 = lambda shape=(): _spec(one_chip, shape, jnp.int32)  # noqa: E731
    continuous = eng._fused_continuous.lower(
        params, i32((b,)), cache, _spec(one_chip, (b, s), jnp.bool_),
        i32(), _spec(one_chip, (b,), jnp.bool_), i32((b,)), i32(), i32(),
        i32(), chunk).compile()
    admit = eng._admit.lower(
        params, i32((1, bucket)), _spec(one_chip, (1, bucket), jnp.bool_),
        cache, i32(), i32()).compile()
    return {"continuous": continuous, "admit": admit}, cache


def test_continuous_decode_updates_cache_in_place(served):
    """The served decode program (qwen2-1.5b, 16 slots x 2048, chunk 16):
    each step writes one position per layer into the carried cache stacks.
    The chunk loop's body (the entry's while loop that runs the layer scan)
    holds no copy and no fresh buffer of a whole stack, and the program's
    temporaries are at least one stack smaller than with the slab scan."""
    programs, cache = served
    compiled = programs["continuous"]

    stack = cache["global"]["k"]
    assert stack.dtype == jnp.bfloat16
    shape = f"bf16[{','.join(map(str, stack.shape))}]"
    comps = _computations(compiled.as_text())
    chunk_bodies = [body for body in _loop_bodies(comps, "ENTRY")
                    if _loop_bodies(comps, body)]
    assert len(chunk_bodies) == 1, chunk_bodies
    stack_ops = [line for line in comps[chunk_bodies[0]]
                 if line.split(" = ", 1)[-1].startswith(shape)]
    assert stack_ops, f"no {shape} value in the chunk loop's body"
    moved = [line[:160] for line in stack_ops
             if re.search(r"\bcopy\(", line) or "AllocateBuffer" in line]
    assert not moved, moved

    stack_bytes = stack.size * jnp.dtype(stack.dtype).itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= SLAB_SCAN_TEMP_BYTES - stack_bytes, temp


@pytest.mark.parametrize("program", ["continuous", "admit"])
def test_layer_scan_reads_weights_in_place(served, qwen2, program):
    """The served programs scan the stored layer stack as it lies.  The
    entry computation runs one while loop (the chunk loop, whose body runs
    the layer scan; for the admission, the layer scan itself), so no loop
    gathers a copy of a weight stack; the layer scan's body is no larger
    than with the gather, and the temporaries are at least the layer
    weights' bytes below what they were with it."""
    _, params = qwen2
    compiled = served[0][program]
    comps = _computations(compiled.as_text())
    loops = _loop_bodies(comps, "ENTRY")
    assert len(loops) == 1, f"{len(loops)} while loops in the entry"
    scan = loops[0]
    if program == "continuous":
        (scan,) = _loop_bodies(comps, loops[0])
    assert len(comps[scan]) <= GATHERED_LAYERS_SCAN_OPS[program], (
        len(comps[scan]))

    layer_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params["layers"]))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= GATHERED_LAYERS_TEMP_BYTES[program] - layer_bytes, temp

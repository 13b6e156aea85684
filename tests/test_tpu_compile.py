"""Compile the main-path kernels and the qwen3-0.6b decode step for a
described TPU v5e, without a chip.

Interpret mode (the CPU tests) cannot see what Mosaic refuses: unsupported
primitives, tile-misaligned slices, scalar stores to VMEM, VMEM overflow.
The TPU compiler is installed here and compiles for a topology that is
described but not attached, so these compiles guard every change to the
kernels and the model at no chip time.  Nothing runs: they say nothing
about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.steps import make_decode_step, make_prefill_step
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.knn_topk import knn_topk
from repro.kernels.rmsnorm import rmsnorm
from repro.launch.mesh import auto_mesh
from repro.models.lm import init_params


@pytest.fixture(scope="module")
def topo():
    # the described chip's compiler would otherwise write logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_knn_topk_compiles_for_v5e(one_chip):
    m, n, d, k = 1024, 8192, 64, 5
    c = _compile(lambda a, b, y: knn_topk(a, b, y, k=k),
                 jax.ShapeDtypeStruct((m, d), jnp.float32, sharding=one_chip),
                 jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip),
                 jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip))
    assert _has_kernel(c)


@pytest.mark.parametrize("n,d,k", [(65536, 10, 4), (65536, 128, 16)])
def test_kmeans_assign_compiles_for_v5e(one_chip, n, d, k):
    c = _compile(kmeans_assign,
                 jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip),
                 jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip))
    assert _has_kernel(c)


def test_rmsnorm_compiles_for_v5e(one_chip):
    c = _compile(rmsnorm,
                 jax.ShapeDtypeStruct((8192, 1024), jnp.bfloat16,
                                      sharding=one_chip),
                 jax.ShapeDtypeStruct((1024,), jnp.bfloat16, sharding=one_chip))
    assert _has_kernel(c)


def test_qwen3_decode_step_compiles_for_v5e_at_full_width(topo):
    """The serve driver's decode step, qwen3-0.6b as published, batch 8
    with a 544-slot cache (prompt 512 + 32 generated), on one chip."""
    cfg = get_config("qwen3-0.6b")
    batch, prompt, gen = 8, 512, 32
    mesh = auto_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    pshapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    prompts = {"tokens": jax.ShapeDtypeStruct((batch, prompt), jnp.int32)}
    prefill, _, _, _ = make_prefill_step(cfg, mesh, cache_len=prompt + gen,
                                         sample_batch=prompts)
    _, caches = jax.eval_shape(prefill, pshapes, prompts)
    step_in = {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}
    decode, din, dout, donate = make_decode_step(
        cfg, mesh, sample_batch=step_in, sample_caches=caches)
    compiled = jax.jit(decode, in_shardings=din, out_shardings=dout,
                       donate_argnums=donate).lower(
        pshapes, step_in, caches, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    # bf16 weights with tied embeddings (~0.6e9 parameters, 1.19 GB)
    # plus the bf16 KV cache (0.50 GB)
    assert 1.0e9 < mem.argument_size_in_bytes < 2.0e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9

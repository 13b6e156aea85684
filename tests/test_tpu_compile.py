"""Compile the main-path kernels (the fused attention forward and backward
among them), the qwen3-0.6b decode step and the train step of the
benchmark's latent-attention MoE cell for a described TPU v5e, without a
chip.

Interpret mode (the CPU tests) cannot see what Mosaic refuses: unsupported
primitives, tile-misaligned slices, scalar stores to VMEM, VMEM overflow.
The TPU compiler is installed here and compiles for a topology that is
described but not attached, so these compiles guard every change to the
kernels and the model at no chip time.  Nothing runs: they say nothing
about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.steps import make_decode_step, make_prefill_step
from repro.kernels import ops
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.knn_topk import knn_topk
from repro.kernels.rmsnorm import rmsnorm
from repro.launch.mesh import auto_mesh
from repro.layers import attention
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


@pytest.mark.parametrize("B,K,G,S", [(4, 8, 2, 2048), (1, 8, 2, 4096)])
def test_flash_attention_fwd_bwd_compiles_for_v5e(one_chip, monkeypatch,
                                                  B, K, G, S):
    """The training path's attention at the benchmark's shape (and a longer
    sequence): the forward and the fused backward are Mosaic kernels."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)   # the CPU's branch
    hd = 128
    q = jax.ShapeDtypeStruct((B, K, G, S, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, K, S, hd), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention_op(q, k, v).astype(jnp.float32))

    c = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    text = c.as_text()
    assert "splash_mqa_fwd_residuals" in text and "splash_mqa_dkv" in text
    assert "splash_mqa_dq" not in text          # dq comes from the dkv kernel


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


def test_moonlight_cell_train_step_compiles_for_v5e(topo, monkeypatch):
    """The whole train step of the benchmark cell ``train-moonlight-s8k``:
    moonlight-16b-a3b at its published widths on one chip's share (the
    dense layer and 4 MoE layers, 8 of 64 experts held, 20,480 vocabulary
    rows), 4 x 8192 tokens, AdamW, full remat. Latent attention takes the
    splash kernels at query/key width 192 and value width 128, the held
    experts megablox's grouped matmuls, their rows moved by the row
    kernels (no XLA gather takes or gives all N·k = 196,608 assignment
    rows). Full remat keeps the splash forward's output and log-sum-exp,
    so the forward kernel runs once per attention layer: twice in the step,
    the dense layer's and the scanned MoE body's, where recomputing it in
    the backward made four. The step fits the chip's 15.75 GB (5.69 GB of
    arguments and 9.81 GB of temporaries, 0.51 GB of them the kept
    residuals; a fifth MoE layer does not fit)."""
    from repro.distributed.steps import make_train_step
    from repro.optim.adamw import adamw, cosine_schedule
    monkeypatch.setattr(ops, "_interpret", lambda: False)        # the CPU's branches
    monkeypatch.setattr(attention, "attn_path",
                        functools.partial(attention.attn_path, platform="tpu"))
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), n_layers=5,
                              n_experts_held=8, vocab_size=20480)
    mesh = auto_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    opt = adamw(cosine_schedule(3e-4, 10, 1000), weight_decay=0.01)
    batch = {k: jax.ShapeDtypeStruct((4, 8192), dt) for k, dt in (
        ("tokens", jnp.int32), ("targets", jnp.int32), ("loss_mask", jnp.float32))}
    step, in_sh, out_sh, donate = make_train_step(cfg, mesh, opt, sample_batch=batch)
    pshapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    compiled = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate).lower(
        pshapes, jax.eval_shape(opt.init, pshapes), batch).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%splash_mqa_fwd_residuals[\w.\-]* = ", text)) == 2
    assert len(re.findall(r"%splash_mqa_dkv[\w.\-]* = ", text)) == 2
    assert "%gmm" in text and "%tgmm" in text
    assert "%gather_rows" in text and "%combine_rows" in text
    assert _gathers_of_rows(text, 4 * 8192 * 6, 2048) == []
    mem = compiled.memory_analysis()
    assert 5.6e9 < mem.argument_size_in_bytes < 5.8e9      # 10 B a parameter, 569M
    assert mem.temp_size_in_bytes <= 9.806e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _gathers_of_rows(text, rows, width):
    """XLA gathers in compiled HLO text whose operand or result is (rows,
    width): (name, shape) pairs. Instruction names are unique in a module,
    fused computations' parameters included."""
    shapes = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])", text))
    found = re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])\S* gather\((%[\w.\-]+)", text)
    return [(name, shape) for name, result, operand in found
            for shape in (result, shapes.get(operand, ""))
            if shape.endswith(f"[{rows},{width}]")]

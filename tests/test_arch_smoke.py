"""Assignment-required smoke tests: every architecture instantiates a
REDUCED config and runs one forward/train step (+ a decode step) on CPU,
asserting output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import (
    ARCH_IDS,
    SMOKE_SHAPES,
    get_config,
    make_batch,
    shape_applicable,
)
from repro.models.lm import forward, init_params, loss_fn

# minutes of JAX compile+run on CPU: opt-in via `-m slow` (see pytest.ini)
pytestmark = pytest.mark.slow



@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = get_config(arch, reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    b = make_batch(cfg, SMOKE_SHAPES["train_4k"])
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, b["batch"]), has_aux=True)(params)
    assert jnp.isfinite(loss), arch
    assert all(jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)), arch


# the architectures that keep a KV cache in every layer
SERVED = [a for a in ARCH_IDS if shape_applicable(get_config(a), "decode_32k")]


@pytest.mark.parametrize("arch", SERVED)
def test_prefill_smoke(arch):
    cfg = get_config(arch, reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    shape = SMOKE_SHAPES["prefill_32k"]
    b = make_batch(cfg, shape)
    logits, caches, _ = forward(cfg, params, b["batch"],
                                make_cache_len=shape.seq, last_only=True)
    assert logits.shape == (shape.batch, 1, cfg.vocab_size), arch
    assert jnp.all(jnp.isfinite(logits)), arch
    assert caches is not None


@pytest.mark.parametrize("arch", SERVED)
def test_decode_step_smoke(arch):
    cfg = get_config(arch, reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    d = make_batch(cfg, SMOKE_SHAPES["decode_32k"])
    logits, new_caches, _ = forward(cfg, params, d["batch"],
                                    caches=d["caches"], pos_offset=d["pos"])
    assert logits.shape[0] == SMOKE_SHAPES["decode_32k"].batch
    assert logits.shape[1] == 1 and logits.shape[2] == cfg.vocab_size
    assert jnp.all(jnp.isfinite(logits)), arch
    assert jax.tree.structure(new_caches) == jax.tree.structure(d["caches"])


@pytest.mark.parametrize("arch", ["mamba2-780m", "recurrentgemma-9b"])
def test_long_context_smoke_for_subquadratic(arch):
    cfg = get_config(arch, reduced=True)
    assert shape_applicable(get_config(arch), "long_500k")
    params = init_params(cfg, jax.random.PRNGKey(0))
    d = make_batch(cfg, SMOKE_SHAPES["long_500k"])
    logits, _, _ = forward(cfg, params, d["batch"], caches=d["caches"],
                           pos_offset=d["pos"])
    assert jnp.all(jnp.isfinite(logits)), arch


def test_full_attention_archs_skip_long_500k():
    for arch in ARCH_IDS:
        full = get_config(arch)
        expect = arch in ("mamba2-780m", "recurrentgemma-9b")
        assert shape_applicable(full, "long_500k") == expect, arch


def test_exact_published_configs():
    """Spot-check the FULL configs against the assignment table."""
    g = get_config("granite-20b")
    assert (g.n_layers, g.d_model, g.n_heads, g.n_kv_heads, g.d_ff,
            g.vocab_size) == (52, 6144, 48, 1, 24576, 49152)
    q = get_config("qwen3-moe-235b-a22b")
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.n_experts,
            q.top_k, q.vocab_size) == (94, 4096, 64, 4, 128, 8, 151936)
    d = get_config("deepseek-moe-16b")
    assert (d.n_experts, d.top_k, d.n_shared_experts, d.d_ff_expert) == \
        (64, 6, 2, 1408)
    m = get_config("mamba2-780m")
    assert (m.n_layers, m.d_model, m.ssm_state, m.vocab_size) == \
        (48, 1536, 128, 50280)
    r = get_config("recurrentgemma-9b")
    assert (r.n_layers, r.d_model, r.vocab_size, r.local_window) == \
        (38, 4096, 256000, 2048)
    assert r.block_pattern == ("rglru", "rglru", "local_attn")

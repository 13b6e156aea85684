"""Performance knobs must not change the computed function beyond float
tolerance."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.models.lm import LMConfig, init_params, loss_fn

# minutes of JAX compile+run on CPU: opt-in via `-m slow` (see pytest.ini)
pytestmark = pytest.mark.slow



def tiny(**kw):
    base = dict(name="t", n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab_size=97, attn_impl="chunked", attn_chunk=4)
    base.update(kw)
    return LMConfig(**base)


def batch_for(cfg, B=2, S=8):
    k = jax.random.PRNGKey(0)
    return {
        "tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
        "targets": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((B, S)),
    }


def test_ssm_chunk_is_a_pure_performance_knob():
    cfg_a = tiny(block_pattern=("ssd",), ssm_state=16, ssm_headdim=8,
                 ssm_chunk=8, n_heads=0, n_kv_heads=0, d_ff=0)
    cfg_b = dataclasses.replace(cfg_a, ssm_chunk=2)
    params = init_params(cfg_a, jax.random.PRNGKey(1))
    b = batch_for(cfg_a)
    la, _ = loss_fn(cfg_a, params, b)
    lb, _ = loss_fn(cfg_b, params, b)
    assert float(la) == pytest.approx(float(lb), abs=1e-5)

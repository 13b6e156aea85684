"""Model-layer behaviour: family forward/grad, prefill+decode consistency,
remat equivalence, MoE dispatch vs dense reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.layers import moe, rglru
from repro.models.lm import LMConfig, forward, init_params, loss_fn

# minutes of JAX compile+run on CPU: opt-in via `-m slow` (see pytest.ini)
pytestmark = pytest.mark.slow



def tiny(name, **kw):
    base = dict(name=name, n_layers=4, d_model=32, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab_size=97, cache_dtype=jnp.float32)
    base.update(kw)
    return LMConfig(**base)


FAMILIES = {
    "dense": tiny("dense"),
    "mqa_qknorm": tiny("mqa", n_kv_heads=1, qk_norm=True),
    "gelu": tiny("gelu", mlp_gated=False),
    "moe": tiny("moe", block_pattern=("moe",), n_experts=8, top_k=2,
                d_ff_expert=16, n_shared_experts=2, moe_capacity_factor=4.0),
    "ssd": tiny("ssd", block_pattern=("ssd",), ssm_state=16, ssm_headdim=8,
                ssm_chunk=4),
    "hybrid": tiny("hybrid", n_layers=7,
                   block_pattern=("rglru", "rglru", "local_attn"),
                   rnn_width=32, local_window=4),
    "vlm": tiny("vlm", input_mode="prefix_embeds", prefix_len=3),
    "audio": tiny("audio", input_mode="embeds", vocab_size=64),
}


def make_batch(cfg, B=2, S=8, key=jax.random.PRNGKey(0)):
    batch = {}
    if cfg.input_mode == "tokens":
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    elif cfg.input_mode == "embeds":
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model))
    else:
        batch["prefix_embeds"] = jax.random.normal(key, (B, cfg.prefix_len,
                                                         cfg.d_model))
        batch["tokens"] = jax.random.randint(key, (B, S - cfg.prefix_len), 0,
                                             cfg.vocab_size)
    batch["targets"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch["loss_mask"] = jnp.ones((B, S))
    return batch


@pytest.mark.parametrize("fam", list(FAMILIES))
def test_train_grad_finite(fam):
    cfg = FAMILIES[fam]
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
    assert jnp.isfinite(loss)
    assert all(jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("fam", list(FAMILIES))
def test_prefill_decode_matches_full_forward(fam):
    cfg = FAMILIES[fam]
    B, S = 2, 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, B, S)
    logits_full, _, _ = forward(cfg, params, batch)
    if cfg.input_mode == "prefix_embeds":
        pre = {"prefix_embeds": batch["prefix_embeds"],
               "tokens": batch["tokens"][:, :-1]}
        dec = {"tokens": batch["tokens"][:, -1:]}
    elif cfg.input_mode == "embeds":
        pre = {"embeds": batch["embeds"][:, :S - 1]}
        dec = {"embeds": batch["embeds"][:, -1:]}
    else:
        pre = {"tokens": batch["tokens"][:, :S - 1]}
        dec = {"tokens": batch["tokens"][:, -1:]}
    logits_pre, caches, _ = forward(cfg, params, pre, make_cache_len=S + 2)
    logits_dec, _, _ = forward(cfg, params, dec, caches=caches,
                               pos_offset=S - 1)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full[:, -1:]), atol=5e-4)
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(logits_full[:, :S - 1]), atol=5e-4)


@pytest.mark.parametrize("fam", ["dense", "moe", "ssd", "hybrid"])
@pytest.mark.parametrize("remat", ["full", "dots"])
def test_remat_equivalence(fam, remat):
    cfg = FAMILIES[fam]
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    l0, _ = loss_fn(cfg, params, batch, remat="none")
    l1, _ = loss_fn(cfg, params, batch, remat=remat)
    assert float(l0) == pytest.approx(float(l1), abs=1e-5)


@pytest.mark.parametrize("fam", ["dense", "ssd", "hybrid"])
def test_unrolled_equals_scanned(fam):
    """The dry-run probe path computes the same function."""
    cfg = FAMILIES[fam]
    cfg_u = dataclasses.replace(cfg, scan_layers=False, unroll_scans=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    l0, _ = loss_fn(cfg, params, batch)
    l1, _ = loss_fn(cfg_u, params, batch)
    assert float(l0) == pytest.approx(float(l1), abs=1e-5)


def test_moe_dispatch_matches_dense_reference():
    p = moe.init_moe(jax.random.PRNGKey(0), 16, 32, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    out_d, aux_d, _ = moe.moe_apply_local(p, x, top_k=2)
    out_r, aux_r = moe.moe_reference(p, x, top_k=2)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_r), atol=1e-5)
    assert float(aux_d) == pytest.approx(float(aux_r))


def test_moe_drops_nothing_under_forced_imbalance():
    """Every token routed to one expert first: the single-device path still
    computes every assignment (a capacity of 1.0 would drop most of them)."""
    p = moe.init_moe(jax.random.PRNGKey(0), 16, 32, 4)
    p["w_router"] = p["w_router"].at[:, 1].add(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 64, 16)))
    out, _, rows = moe.moe_apply_local(p, x, top_k=2)
    out_r, _ = moe.moe_reference(p, x, top_k=2)
    assert [int(r) for r in rows] == [64 * 2, 64]        # all rows; expert 1 has all 64
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r), atol=1e-5)


def test_moe_grads_flow_to_router_and_experts():
    p = moe.init_moe(jax.random.PRNGKey(0), 16, 32, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))

    def loss(p):
        out, aux, _ = moe.moe_apply_local(p, x, top_k=2)
        return jnp.sum(out ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    assert float(jnp.sum(jnp.abs(g["w_router"]))) > 0
    assert float(jnp.sum(jnp.abs(g["w_gate"]))) > 0


def test_ssd_chunk_invariance():
    """Different chunk sizes compute the same function."""
    cfg8 = tiny("s8", block_pattern=("ssd",), ssm_state=16, ssm_headdim=8,
                ssm_chunk=8)
    cfg4 = dataclasses.replace(cfg8, ssm_chunk=4)
    params = init_params(cfg8, jax.random.PRNGKey(0))
    batch = make_batch(cfg8)
    l8, _ = loss_fn(cfg8, params, batch)
    l4, _ = loss_fn(cfg4, params, batch)
    assert float(l8) == pytest.approx(float(l4), abs=1e-5)


def test_rglru_state_continuation():
    """Scanning a sequence in two halves with carried state == one scan."""
    p = rglru.init_rglru(jax.random.PRNGKey(0), 16, 24)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 24))
    y_full, h_full = rglru.rglru_scan(p, u)
    y1, h1 = rglru.rglru_scan(p, u[:, :6])
    y2, h2 = rglru.rglru_scan(p, u[:, 6:], h0=h1)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), atol=1e-5)


def test_loss_mask_zeroes_positions():
    cfg = FAMILIES["dense"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    batch["loss_mask"] = jnp.zeros_like(batch["loss_mask"]).at[:, 0].set(1.0)
    loss_masked, m = loss_fn(cfg, params, batch)
    assert float(m["tokens"]) == 2.0

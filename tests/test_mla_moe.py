"""Latent attention on the fused kernels, drop-free routed experts, the
expert-parallel share, and the model that puts them together
(moonlight-16b-a3b), on the CPU at small sizes (kernels in interpret mode)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tracing
from repro.core.tracing import TaskStream
from repro.kernels import ops
from repro.layers import attention, mla, moe
from repro.layers.attention import _dense_attn, _flash_attn, attn_path
from repro.layers.mlp import init_mlp, mlp_forward
from repro.models.lm import LMConfig, forward, init_params, loss_fn

# bfloat16 keeps 8 significant bits; the fused path rounds q (after the
# scale), the probabilities and the output to it where the CPU's dense path
# stays in float32 (as tests/test_flash_path.py)
REL_L2 = 1e-2
QK, V = 192, 128        # latent attention's query/key and value widths


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture
def on_tpu(monkeypatch):
    """The path a TPU would take; the kernels still run interpreted."""
    monkeypatch.setattr(attention, "attn_path",
                        functools.partial(attn_path, platform="tpu"))


# ------------------------------------------------- attention at 192 / 128
def _qkv(key, s=256, heads=2):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, s, heads, 1, QK), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, s, heads, QK), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, s, heads, V), jnp.bfloat16)
    return q, k, v


def _dense(q, k, v, scale):
    pos = jnp.arange(q.shape[1], dtype=jnp.int32)
    return _dense_attn(q, k, v, pos, pos, None, scale=scale)


SCALE = QK ** -0.5 * 0.75      # a scale that is not 1/sqrt(width): it must be the one used


def test_flash_op_latent_widths_forward_matches_dense():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    o = jax.jit(functools.partial(_flash_attn, scale=SCALE))(q, k, v)
    assert o.shape == q.shape[:-1] + (V,) and o.dtype == jnp.bfloat16
    assert _rel(o, jax.jit(functools.partial(_dense, scale=SCALE))(q, k, v)) < REL_L2


@pytest.mark.parametrize("argnum", [0, 1, 2])
def test_flash_op_latent_widths_gradients_match_dense(argnum):
    q, k, v = _qkv(jax.random.PRNGKey(1))
    do = jax.random.normal(jax.random.PRNGKey(2), q.shape[:-1] + (V,))

    def grad(attn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(
            attn(*a, scale=SCALE).astype(jnp.float32) * do), argnums=argnum))(q, k, v)

    assert _rel(grad(_flash_attn), grad(_dense)) < REL_L2


@pytest.mark.parametrize("hd,hd_v,path", [
    (192, 128, "flash"), (256, 128, "flash"), (128, None, "flash"),
    (192, 64, "dense"), (64, None, "dense"), (160, 128, "dense")])
def test_attn_path_takes_widths_the_tiles_hold(hd, hd_v, path):
    assert attn_path("auto", seq_len=2048, kv_len=2048, head_dim=hd, head_dim_v=hd_v,
                     dtype=jnp.bfloat16, window=None, cached=False,
                     platform="tpu") == path
    assert (ops.flash_block_sizes(2048, hd, hd_v) is not None) == (path == "flash")


# ------------------------------------------------------------ MLA layer
MLA_KW = dict(n_heads=2, kv_lora_rank=64, qk_nope_dim=128, qk_rope_dim=64,
              v_head_dim=128, rope_theta=50_000.0, norm_eps=1e-5)


def test_mla_layer_flash_path_matches_dense(on_tpu, monkeypatch):
    ring = TaskStream(capacity=64)
    monkeypatch.setattr(tracing, "SPANS", ring)
    p = mla.init_mla(jax.random.PRNGKey(3), 256, 2, 64, 128, 64, 128, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 256), jnp.bfloat16)
    o_flash = jax.jit(lambda p, x: mla.mla_forward(p, x, **MLA_KW))(p, x)
    o_dense = jax.jit(lambda p, x: mla.mla_forward(p, x, impl="dense", **MLA_KW))(p, x)
    assert [r["args"]["impl"] for r in ring.since(0) if r["name"] == "attn.impl"] == [
        "flash", "dense"]
    assert _rel(o_flash, o_dense) < REL_L2


def test_mla_chunked_path_equals_dense():
    """The jnp paths past 2048 keys take the value width apart too."""
    p = mla.init_mla(jax.random.PRNGKey(5), 64, 2, 32, 16, 8, 24)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 96, 64))
    kw = dict(MLA_KW, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=24)
    o_c = mla.mla_forward(p, x, impl="chunked", chunk=32, **kw)
    o_d = mla.mla_forward(p, x, impl="dense", **kw)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_d), atol=1e-5)


# --------------------------------------------------------- drop-free MoE
def _router(scoring, n_experts=16, d=32, key=0):
    p = moe.init_moe(jax.random.PRNGKey(key), d, 24, n_experts, scoring=scoring)
    if scoring == "sigmoid":     # a nonzero correction bias moves the choice
        p["e_score_correction_bias"] = jnp.linspace(-0.3, 0.3, n_experts)
    return p


ROUTE = {"softmax": dict(top_k=4, scoring="softmax"),
         "sigmoid": dict(top_k=4, scoring="sigmoid", routed_scale=2.446)}


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_drop_free_matches_reference_with_its_gradients(scoring):
    p = _router(scoring)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    out, aux, rows = moe.moe_apply_local(p, x, **ROUTE[scoring])
    ref, aux_r = moe.moe_reference(p, x, **ROUTE[scoring])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert float(aux) == pytest.approx(float(aux_r))
    assert int(rows[0]) == 2 * 24 * 4
    g = jax.grad(lambda p, x: jnp.sum(moe.moe_apply_local(p, x, **ROUTE[scoring])[0] ** 2),
                 argnums=(0, 1))(p, x)
    g_r = jax.grad(lambda p, x: jnp.sum(moe.moe_reference(p, x, **ROUTE[scoring])[0] ** 2),
                   argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_drop_free_under_forced_imbalance(scoring):
    """Every token to expert 5 first, by its router column (softmax) or its
    correction bias (sigmoid): all N·k rows computed, 5 takes N of them."""
    p = _router(scoring)
    if scoring == "sigmoid":
        p["e_score_correction_bias"] = p["e_score_correction_bias"].at[5].set(50.0)
    else:
        p["w_router"] = p["w_router"].at[:, 5].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (1, 64, 32)))
    out, _, rows = moe.moe_apply_local(p, x, **ROUTE[scoring])
    ref, _ = moe.moe_reference(p, x, **ROUTE[scoring])
    assert [int(r) for r in rows] == [64 * 4, 64]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_expert_shares_sum_to_the_uncut_layer(scoring):
    """Eight shares of 64 experts (offsets 0, 8, ..., 56), each routing over
    all 64 and computing its own experts' part, plus the shared expert once,
    give the uncut layer."""
    p = _router(scoring, n_experts=64, key=7)
    shared = init_mlp(jax.random.PRNGKey(8), 32, 48)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 40, 32))
    route = dict(ROUTE[scoring], top_k=6)
    whole, _ = moe.moe_reference(p, x, **route)
    whole = whole + mlp_forward(shared, x)
    total = mlp_forward(shared, x)
    for off in range(0, 64, 8):
        share = dict(p, **{k: p[k][off:off + 8] for k in ("w_gate", "w_up", "w_down")})
        part, _, _ = moe.moe_apply_local(share, x, expert_offset=off, **route)
        ref_part, _ = moe.moe_reference(share, x, expert_offset=off, **route)
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part), atol=1e-5)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-4)


# --------------------------------------------------- the routed-row kernels
ROWS_N, ROWS_D, ROWS_K, ROWS_E = 24, 256, 4, 16


def _routing(held, count, seed=0):
    """topi (N, k) over ROWS_E experts with exactly ``count`` assignments to
    the first ``held`` (each token's experts distinct), and its sort."""
    rng = np.random.default_rng(seed)
    cap = min(ROWS_K, held)
    per_token = [cap] * (count // cap) + [count % cap]
    per_token += [0] * (ROWS_N - len(per_token))
    topi = np.stack([rng.permutation(np.concatenate([
        rng.permutation(held)[:h], held + rng.permutation(ROWS_E - held)[:ROWS_K - h]]))
        for h in rng.permutation(per_token[:ROWS_N])])
    order, pos, sizes = moe._sort_assignments(jnp.asarray(topi, jnp.int32), 0, held)
    assert int(jnp.sum(sizes)) == count
    return order, pos, jnp.sum(sizes), ROWS_N * cap


def _row_case(held, fill):
    worst = ROWS_N * min(ROWS_K, held)
    count = {"none": 0, "one": 1, "ragged": 13, "worst": worst}[fill]
    order, pos, count, m = _routing(held, count)
    ks = jax.random.split(jax.random.PRNGKey(held), 4)
    xf = jax.random.normal(ks[0], (ROWS_N, ROWS_D), jnp.bfloat16)
    live = (jnp.arange(m) < count)[:, None]
    # rows past the count hold what nothing may read
    y = jnp.where(live, jax.random.normal(ks[1], (m, ROWS_D), jnp.bfloat16), jnp.nan)
    w = jax.random.uniform(ks[2], (ROWS_N, ROWS_K))
    g = jax.random.normal(ks[3], (ROWS_N, ROWS_D), jnp.bfloat16)
    return order, pos, count, m, xf, y, w, g


def _plain_combine(y, pos, w, count):
    """sum_j [pos < count] w y[pos], float32 in the order of j, on y with its
    rows past the count cleared."""
    y = jnp.where((jnp.arange(y.shape[0]) < count)[:, None], y, 0).astype(jnp.float32)
    out = 0.0
    for j in range(pos.shape[1]):
        live = (pos[:, j] < count)[:, None]
        out = out + jnp.where(live, w[:, j, None] * y[jnp.minimum(pos[:, j], y.shape[0] - 1)], 0)
    return out


FILLS = ["none", "one", "ragged", "worst"]


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("held", [2, 8])     # fewer and more held experts than k = 4
def test_gather_rows_matches_jnp(held, fill):
    """Rows below the count gathered (and scaled, and dotted); rows past it
    in the last tile zeros; tiles past it never written."""
    from repro.kernels.moe_rows import gather_rows
    order, pos, count, m, xf, y, w, _ = _row_case(held, fill)
    src, scale = order[:m] // ROWS_K, w.reshape(-1)[order[:m]]
    n, tm = int(count), 8
    ours = gather_rows(xf, src, count, tm=tm, interpret=True)
    scaled, dots = gather_rows(xf, src, count, scale, y, tm=tm, interpret=True)
    want = xf[src].astype(jnp.float32)
    edge = -(-n // tm) * tm
    np.testing.assert_array_equal(np.asarray(ours[:n]), np.asarray(xf[src][:n]))
    assert not np.any(np.asarray(ours[n:edge], np.float32))
    np.testing.assert_array_equal(
        np.asarray(scaled[:n]), np.asarray((want * scale[:, None]).astype(jnp.bfloat16)[:n]))
    assert not np.any(np.asarray(scaled[n:edge], np.float32))
    np.testing.assert_allclose(np.asarray(dots[:n]), np.asarray(
        jnp.sum(want * y.astype(jnp.float32), axis=1)[:n]), rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(dots[n:edge]))


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("held", [2, 8])
def test_combine_rows_matches_jnp(held, fill):
    """Only rows below the count reach the output: those past it are NaN."""
    from repro.kernels.moe_rows import combine_rows
    _, pos, count, _, _, y, w, _ = _row_case(held, fill)
    ours = combine_rows(y, pos, w, count, tn=8, interpret=True)
    assert ours.dtype == y.dtype
    np.testing.assert_array_equal(
        np.asarray(ours, np.float32),
        np.asarray(_plain_combine(y, pos, w, count).astype(y.dtype), np.float32))


@pytest.mark.parametrize("fill", FILLS)
@pytest.mark.parametrize("held", [2, 8])
def test_row_movement_gradients_match_jnp(held, fill):
    """The layer's dispatch and combine, forward and every gradient, against
    jnp on the rows below the count; the NaN rows past it (in y, and in the
    rows' gradient) reach no output and no gradient."""
    order, pos, count, m, xf, y, w, g = _row_case(held, fill)
    src, n = order[:m] // ROWS_K, int(count)
    live = (jnp.arange(m) < count)[:, None]

    rows, pull = jax.vjp(lambda x: moe._dispatch(x, src, pos, count), xf)
    np.testing.assert_array_equal(np.asarray(rows[:n]), np.asarray(xf[src][:n]))
    g_rows = jnp.where(live, y, jnp.nan)
    (dx,) = pull(g_rows)
    np.testing.assert_array_equal(
        np.asarray(dx, np.float32),
        np.asarray(_plain_combine(g_rows, pos, jnp.ones_like(w), count).astype(dx.dtype),
                   np.float32))

    out, pull = jax.vjp(lambda y, w: moe._combine(y, w, order, pos, count), y, w)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(_plain_combine(y, pos, w, count).astype(y.dtype), np.float32))
    dy, dw = pull(g)
    dy_r, dw_r = jax.vjp(lambda y, w: _plain_combine(y, pos, w, count).astype(y.dtype),
                         jnp.where(live, y, 0), w)[1](g)
    np.testing.assert_array_equal(np.asarray(dy[:n], np.float32),
                                  np.asarray(dy_r[:n], np.float32))
    assert np.all(np.isfinite(np.asarray(dw)))
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r), rtol=1e-4, atol=1e-3)


def test_moe_impl_counted_once_per_traced_call(monkeypatch):
    ring = TaskStream(capacity=64)
    monkeypatch.setattr(tracing, "SPANS", ring)
    p = _router("sigmoid")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    step = jax.jit(lambda p, x: moe.moe_apply_local(p, x, **ROUTE["sigmoid"])[0])
    step(p, x)
    step(p, x)                   # compiled once: not traced again
    assert [r["args"] for r in ring.since(0) if r["name"] == "moe.impl"] == [{"impl": "rows"}]


def test_held_experts_are_the_first_of_the_full_draw():
    full = moe.init_moe(jax.random.PRNGKey(3), 32, 24, 16, scoring="sigmoid")
    held = moe.init_moe(jax.random.PRNGKey(3), 32, 24, 16, n_held=4, scoring="sigmoid")
    assert held["w_router"].shape == (32, 16) and held["w_gate"].shape == (4, 32, 24)
    for k in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(np.asarray(held[k]), np.asarray(full[k][:4]))
    assert not np.any(np.asarray(held["e_score_correction_bias"]))


# ------------------------------------------------------------- the model
def test_published_moonlight_config():
    c = get_config("moonlight-16b-a3b")
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab_size, c.first_k_dense) == (
        27, 2048, 16, 11264, 163840, 1)
    assert (c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim) == (512, 128, 64, 128)
    assert (c.n_experts, c.top_k, c.d_ff_expert, c.n_shared_experts) == (64, 6, 1408, 2)
    assert (c.moe_scoring, c.moe_routed_scale, c.norm_eps, c.rope_theta) == (
        "sigmoid", 2.446, 1e-5, 50_000.0)
    assert c.layer_types == ("dense",) + ("moe",) * 26 and c.n_super == 26


def test_norm_eps_defaults_to_what_every_other_config_computed():
    assert LMConfig.__dataclass_fields__["norm_eps"].default == 1e-6
    assert get_config("internlm2-1.8b").norm_eps == 1e-6


def _tiny(**kw):
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b", reduced=True),
                              n_layers=3, n_experts_held=8, **kw)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1),
             "loss_mask": jnp.ones((2, 32))}
    return cfg, params, batch


def test_leading_dense_layer_then_scanned_moe_layers():
    cfg, params, batch = _tiny()
    assert [len(params["lead"])] == [1] and "tail" not in params
    assert params["lead"][0]["mlp"]["w_up"].shape == (128, 256)
    assert params["scan"]["b0"]["moe"]["w_gate"].shape == (2, 8, 128, 64)
    assert params["scan"]["b0"]["moe"]["w_router"].shape == (2, 128, 16)
    _, _, aux = forward(cfg, params, batch)
    assert aux["moe_rows"].shape == (2, 2) and float(aux["balance"]) == 0.0
    loss, metrics = loss_fn(cfg, params, batch)
    assert np.isfinite(float(loss)) and metrics["moe_rows"].shape == (2, 2)


def test_remat_and_unrolled_layers_compute_the_same_loss_and_gradients():
    cfg, params, batch = _tiny()

    def value_grad(c, remat):
        return jax.value_and_grad(lambda p: loss_fn(c, p, batch, remat=remat)[0])(params)

    l0, g0 = value_grad(cfg, "none")
    for c, remat in ((cfg, "full"), (dataclasses.replace(cfg, scan_layers=False), "none")):
        l1, g1 = value_grad(c, remat)
        assert float(l1) == pytest.approx(float(l0), abs=1e-5)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_latent_attention_and_leading_layers_keep_no_cache():
    cfg, params, batch = _tiny()
    with pytest.raises(NotImplementedError):
        forward(cfg, params, batch, make_cache_len=64)


def test_train_loop_counts_moe_rows_at_the_sync(monkeypatch):
    """``moe.rows``: one count per MoE layer and step, fetched with the loss."""
    from repro.launch.train import train_loop
    ring = TaskStream(capacity=4096)
    monkeypatch.setattr(tracing, "SPANS", ring)
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b", reduced=True), n_experts_held=8)
    train_loop(cfg, steps=2, batch=2, seq=32, workers=1, log_every=0)
    records = ring.since(0)
    syncs = {r["id"]: r["step"] for r in records if r.get("name") == "train.sync"}
    counts = [r for r in records if r.get("name") == "moe.rows"]
    assert [(r["step"], r["args"]["layer"]) for r in counts] == [
        (s, layer) for s in (0, 1) for layer in (0, 1)]
    for r in counts:
        assert r["parent"] in syncs and 0 < r["args"]["largest"] <= r["value"] <= 2 * 32 * 4

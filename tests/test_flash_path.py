"""The fused attention path: the splash kernels (interpret mode on the CPU)
against the dense jnp path, forward and gradients, at tiny GQA shapes; the
chunked jnp path against the float32 oracle; the rule that selects the
path; the ``attn.impl`` and ``remat.policy`` counters; and the kept forward
residuals under rematerialisation."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tracing
from repro.core.tracing import TaskStream
from repro.kernels import ops, ref
from repro.layers import attention
from repro.layers.attention import (_chunked_attn, _dense_attn, _flash_attn, attn_forward,
                                    attn_path)
from repro.models.lm import LMConfig, init_params, loss_fn

B, K, G, S, HD = 1, 2, 2, 256, 128
# bfloat16 keeps 8 significant bits; the fused path rounds q (after the
# scale), the probabilities and the output to it where the CPU's dense path
# stays in float32, so the two differ by a few of its ulps (2^-8 ~ 4e-3).
REL_L2 = 1e-2


@pytest.fixture
def ring(monkeypatch):
    ring = TaskStream(capacity=64)
    monkeypatch.setattr(tracing, "SPANS", ring)
    return ring


@pytest.fixture
def on_tpu(monkeypatch):
    """attn_forward takes the path a TPU would take; the kernels still run
    in interpret mode, as the CPU is the platform underneath."""
    monkeypatch.setattr(attention, "attn_path",
                        functools.partial(attn_path, platform="tpu"))


def _impls(ring):
    """The ``attn.impl`` records in the ring (compile counters may sit
    beside them once JAX's monitoring listeners are registered)."""
    return [r for r in ring.since(0) if r["name"] == "attn.impl"]


def _qkv(key, s=S, k_heads=K, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, s, k_heads, G, HD), dtype)
    k = jax.random.normal(ks[1], (B, s, k_heads, HD), dtype)
    v = jax.random.normal(ks[2], (B, s, k_heads, HD), dtype)
    return q, k, v


# one tile per kernel at S 256; at 2048 the forward runs 4 x 2 tiles and
# the backward 2 x 2, skipping those above the diagonal, and dq sums two
# partials
SHAPES = [(S, K), (2048, 1)]


def _dense(q, k, v):
    pos = jnp.arange(q.shape[1], dtype=jnp.int32)
    return _dense_attn(q, k, v, pos, pos, None)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("s,k_heads", SHAPES)
def test_fused_forward_matches_dense_causal(s, k_heads):
    q, k, v = _qkv(jax.random.PRNGKey(0), s, k_heads)
    o = jax.jit(_flash_attn)(q, k, v)
    assert o.shape == q.shape and o.dtype == jnp.bfloat16
    assert _rel(o, jax.jit(_dense)(q, k, v)) < REL_L2


@pytest.mark.parametrize("s,k_heads", SHAPES)
@pytest.mark.parametrize("argnum,name", [(0, "q"), (1, "k"), (2, "v")])
def test_fused_gradients_match_dense(argnum, name, s, k_heads):
    q, k, v = _qkv(jax.random.PRNGKey(1), s, k_heads)
    do = jax.random.normal(jax.random.PRNGKey(2), q.shape)

    def grad(attn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(attn(*a).astype(jnp.float32) * do),
            argnums=argnum))(q, k, v)

    g_fused, g_dense = grad(_flash_attn), grad(_dense)
    assert g_fused.shape == g_dense.shape, name
    assert _rel(g_fused, g_dense) < REL_L2, name


@pytest.mark.parametrize("s,k_heads", SHAPES)
def test_fused_path_is_causal(s, k_heads):
    """Changing the last key and value moves no output but the last one."""
    q, k, v = _qkv(jax.random.PRNGKey(3), s, k_heads)
    o1 = jax.jit(_flash_attn)(q, k, v)
    o2 = jax.jit(_flash_attn)(q, k.at[:, -1].add(3.0), v.at[:, -1].add(3.0))
    np.testing.assert_array_equal(np.asarray(o1[:, :-1], np.float32),
                                  np.asarray(o2[:, :-1], np.float32))
    assert not np.array_equal(np.asarray(o1[:, -1], np.float32),
                              np.asarray(o2[:, -1], np.float32))


@pytest.mark.parametrize("window", [None, 7])
def test_chunked_attn_matches_reference(window):
    """The streaming-softmax jnp path over three KV chunks, causal and
    sliding-window, against ``kernels.ref.flash_attention_ref``."""
    b, k_heads, g, s, d = 1, 2, 2, 48, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, s, k_heads, g, d))
    k = jax.random.normal(ks[1], (b, s, k_heads, d))
    v = jax.random.normal(ks[2], (b, s, k_heads, d))
    pos = jnp.arange(s, dtype=jnp.int32)
    o = jax.jit(_chunked_attn, static_argnames=("window", "chunk"))(
        q, k, v, pos, pos, window=window, chunk=16)
    r = ref.flash_attention_ref(
        q.transpose(0, 2, 3, 1, 4).reshape(b, k_heads * g, s, d),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), window=window)
    r = r.reshape(b, k_heads, g, s, d).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=3e-5)


# the benchmark's training shape on one chip
TRAIN = dict(seq_len=2048, kv_len=2048, head_dim=128, dtype=jnp.bfloat16,
             window=None, cached=False, devices=1, platform="tpu")


@pytest.mark.parametrize("change,path", [
    ({}, "flash"),
    ({"seq_len": 4096, "kv_len": 4096}, "flash"),         # long prefill
    ({"cached": True, "seq_len": 1}, "dense"),             # decode
    ({"cached": True, "seq_len": 1, "kv_len": 4096}, "chunked"),
    ({"window": 1024}, "dense"),                           # local attention
    ({"head_dim": 64}, "dense"),
    ({"seq_len": 2000, "kv_len": 2000}, "dense"),          # not whole lanes
    ({"dtype": jnp.float32}, "dense"),                     # float32 model
    ({"devices": 4}, "dense"),                             # a GSPMD mesh
    ({"devices": 4, "seq_len": 4096, "kv_len": 4096}, "chunked"),
    ({"platform": "cpu"}, "dense"),
    ({"platform": "cpu", "seq_len": 4096, "kv_len": 4096}, "chunked"),
    ({"platform": "gpu"}, "dense"),
])
def test_attn_path_selects_from_platform_and_shapes(change, path):
    assert attn_path("auto", **{**TRAIN, **change}) == path


@pytest.mark.parametrize("impl", ["dense", "chunked"])
def test_attn_path_forced_impl_wins(impl):
    assert attn_path(impl, **TRAIN) == impl


def test_attn_path_defaults_to_the_backend():
    args = {k: v for k, v in TRAIN.items() if k != "platform"}
    assert attn_path("auto", **args) == "dense"    # the tests' CPU


def _layer(key, d_model=256, n_heads=K * G, n_kv=K):
    p = attention.init_attention(key, d_model, n_heads, n_kv, HD,
                                 dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(9), (B, S, d_model), jnp.bfloat16)
    kw = dict(n_heads=n_heads, n_kv_heads=n_kv, head_dim=HD)
    return p, x, kw


@pytest.mark.parametrize("on_tpu_path,impl,path", [
    (False, "auto", "dense"), (False, "chunked", "chunked"),
    (True, "auto", "flash"), (True, "dense", "dense"),
])
def test_attn_impl_counted_once_per_traced_call(request, ring, on_tpu_path,
                                                impl, path):
    if on_tpu_path:
        request.getfixturevalue("on_tpu")
    p, x, kw = _layer(jax.random.PRNGKey(4))
    f = jax.jit(lambda p, x: attn_forward(p, x, impl=impl, chunk=128, **kw)[0])
    f(p, x).block_until_ready()
    f(p, x).block_until_ready()                     # cached: no new trace
    assert [(r["kind"], r["value"], r["args"]) for r in _impls(ring)] == [
        ("count", 1, {"impl": path})]


def test_attn_forward_flash_matches_dense(on_tpu, ring):
    p, x, kw = _layer(jax.random.PRNGKey(5))
    o_flash, _ = jax.jit(lambda p, x: attn_forward(p, x, **kw))(p, x)
    o_dense, _ = jax.jit(lambda p, x: attn_forward(p, x, impl="dense", **kw))(p, x)
    assert [r["args"]["impl"] for r in _impls(ring)] == ["flash", "dense"]
    assert _rel(o_flash, o_dense) < REL_L2


def test_prefill_cache_is_the_same_on_the_flash_path(on_tpu):
    p, x, kw = _layer(jax.random.PRNGKey(6))
    _, c_flash = attn_forward(p, x, make_cache_len=S + 16, **kw)
    _, c_dense = attn_forward(p, x, make_cache_len=S + 16, impl="dense", **kw)
    for name in ("k", "v", "pos_map"):
        np.testing.assert_array_equal(np.asarray(c_flash[name]),
                                      np.asarray(c_dense[name]))


def test_remat_train_step_flash_matches_dense(on_tpu, ring):
    """Loss and gradients of a tiny bfloat16 model under full remat and the
    scanned layers, flash against dense, at the same parameters."""
    cfg = LMConfig(name="flash-tiny", n_layers=2, d_model=256, n_heads=K * G,
                   n_kv_heads=K, head_dim=HD, d_ff=512, vocab_size=128,
                   param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
                   remat="full")
    params = init_params(cfg, jax.random.PRNGKey(7))
    kt = jax.random.split(jax.random.PRNGKey(8))
    batch = {"tokens": jax.random.randint(kt[0], (B, S), 0, cfg.vocab_size),
             "targets": jax.random.randint(kt[1], (B, S), 0, cfg.vocab_size),
             "loss_mask": jnp.ones((B, S))}

    def loss_and_grad(c):
        f = lambda p: loss_fn(c, p, batch, remat=c.remat)[0]  # noqa: E731
        return jax.jit(jax.value_and_grad(f))(params)

    l_flash, g_flash = loss_and_grad(cfg)
    l_dense, g_dense = loss_and_grad(dataclasses.replace(cfg, attn_impl="dense"))
    assert {r["args"]["impl"] for r in _impls(ring)} == {"flash", "dense"}
    assert abs(float(l_flash) - float(l_dense)) < 1e-3 * float(l_dense)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_flash),
                            jax.tree.leaves(g_dense)):
        assert _rel(a, b) < 3e-2, jax.tree_util.keystr(path)


def _splash_calls(jaxpr, calls=None):
    """Names of the ``pallas_call`` equations in ``jaxpr`` and in every
    jaxpr its equations carry (scan, checkpoint, custom_vjp, pjit)."""
    calls = [] if calls is None else calls
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append(str(eqn.params["name"]))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    _splash_calls(sub.jaxpr, calls)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    _splash_calls(sub, calls)
    return calls


# (config changes, attention call sites): the scanned layers are one site;
# latent attention adds a leading dense layer, run unrolled
REMAT_MODELS = {
    "gqa": ({}, 1),
    "mla": (dict(n_layers=3, n_heads=2, n_kv_heads=2, first_k_dense=1,
                 kv_lora_rank=64, qk_nope_dim=128, qk_rope_dim=64,
                 v_head_dim=128), 2),
}


@pytest.mark.parametrize("model", sorted(REMAT_MODELS))
@pytest.mark.parametrize("remat", ["full", "dots"])
def test_remat_keeps_the_fused_forward_residuals(on_tpu, ring, remat, model):
    """Rematerialised, the gradient's program runs the splash forward once
    per attention call site (the backward takes the kept output and
    log-sum-exp in place of a second forward), and gives the loss and
    gradients of the program that keeps everything."""
    change, sites = REMAT_MODELS[model]
    cfg = LMConfig(**{**dict(name="remat-tiny", n_layers=2, d_model=256,
                             n_heads=K * G, n_kv_heads=K, head_dim=HD, d_ff=512,
                             vocab_size=128, param_dtype=jnp.bfloat16,
                             compute_dtype=jnp.bfloat16, remat=remat), **change})
    params = init_params(cfg, jax.random.PRNGKey(12))
    kt = jax.random.split(jax.random.PRNGKey(13))
    batch = {"tokens": jax.random.randint(kt[0], (B, S), 0, cfg.vocab_size),
             "targets": jax.random.randint(kt[1], (B, S), 0, cfg.vocab_size),
             "loss_mask": jnp.ones((B, S))}

    def value_and_grad(mode):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, remat=mode)[0]))

    step = value_and_grad(remat)
    calls = _splash_calls(jax.make_jaxpr(step)(params).jaxpr)
    assert sum(c.startswith("splash_mqa_fwd") for c in calls) == sites, calls
    assert sum(c.startswith("splash_mqa_dkv") for c in calls) == sites, calls
    assert [(r["value"], r["args"]) for r in ring.since(0)
            if r["name"] == "remat.policy"] == [
        (1, {"mode": remat, "keeps": ops.ATTN_RESIDUALS})]

    l_remat, g_remat = step(params)
    l_none, g_none = value_and_grad("none")(params)
    assert abs(float(l_remat) - float(l_none)) < 1e-3 * float(l_none)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_remat),
                            jax.tree.leaves(g_none)):
        assert _rel(a, b) < 3e-2, jax.tree_util.keystr(path)
    assert {r["args"]["mode"] for r in ring.since(0)
            if r["name"] == "remat.policy"} == {remat}     # none records nothing


def test_flash_op_refuses_shapes_it_cannot_tile():
    q = jnp.zeros((1, 2, 2, 200, 128), jnp.bfloat16)
    k = jnp.zeros((1, 2, 200, 128), jnp.bfloat16)
    with pytest.raises(ValueError):
        ops.flash_attention_op(q, k, k)
    assert ops.flash_block_sizes(200, 128) is None
    assert ops.flash_block_sizes(256, 64) is None


@pytest.mark.parametrize("seq_len", [128, 256, 384, 2048, 4096])
def test_flash_blocks_divide_the_sequence(seq_len):
    b = ops.flash_block_sizes(seq_len, 128)
    assert b.use_fused_bwd_kernel
    for size in (b.block_q, b.block_kv, b.block_kv_compute, b.block_q_dkv,
                 b.block_kv_dkv, b.block_kv_dkv_compute):
        assert size % 128 == 0 and seq_len % size == 0, (seq_len, b)

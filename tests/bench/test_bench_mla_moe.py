"""The latent-attention MoE training cell on the CPU: the program against
its float32 reference (``bench/reference/mla_moe_train.py``) at small
widths, the cell's check driven whole with sound and broken steps, the
adapter's pass over a trace, the operation counts and the per-layer
readers."""
import dataclasses
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate_mla_moe, flops_mla_moe, harness
from bench.reference import mla_moe_train as reference
from repro.core import tracing
from repro.core.tracing import TaskStream

CELL = "train-moonlight-s8k"
# the cell's head widths and latent rank, a quarter of its model width and
# heads, one dense and one MoE layer, 8 of 64 experts held, a tenth of its
# vocabulary slice, 4 x 256 tokens: at this size the sound readings lie
# within the cell's limits (the gaps grow as widths, vocabulary and tokens
# shrink: at 2 heads, 512 rows and 4 x 64 tokens the loss gap reads 1.2e-4)
SMALL = dict(hidden_size=512, intermediate_size=1024, num_attention_heads=4,
             moe_intermediate_size=256, num_hidden_layers=2, vocab_size=2048)
SEED = 2**31 + 11


def _cell(**extra):
    cell = harness.find_cell(CELL)
    cell.config.update(SMALL, **extra)
    cell.traffic.update(batch=4, seq=256, workers=2)
    return cell


def _drv(cell):
    return harness.driver(cell)


def _program(cell, dtype="float32"):
    """The program's config and its seeded weights, at ``dtype``."""
    cfg = dataclasses.replace(_drv(cell).lm_config(cell.config), remat="none",
                              param_dtype=jnp.dtype(dtype), compute_dtype=jnp.dtype(dtype))
    from repro.models.lm import init_params
    return cfg, init_params(cfg, jax.random.PRNGKey(SEED))


def _as_reference(tree):
    """A program tree in the reference's layout: ``dense``/``moe`` groups of
    per-layer stacks, by the adapter's leaf names (the correction bias left
    out)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out, groups = {}, {}
    for path, x in flat:
        name = _drv(_cell())._leaf_name(path)
        if name is None:
            continue
        if "." in name:
            group, leaf = name.split(".")
            x = x if path[0].key == "scan" else x[None]
            groups.setdefault(group, {}).setdefault(leaf, []).append(x)
        else:
            out[name] = x
    for group, leaves in groups.items():
        out[group] = {k: jnp.concatenate(v) for k, v in leaves.items()}
    return out


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------- program vs reference
def test_reference_draws_the_programs_weights():
    cell = _cell()
    _, params = _program(cell, "bfloat16")
    ref = reference.init_params(_drv(cell).reference_arch(cell.config), SEED)
    mine = _as_reference(params)
    assert jax.tree.structure(mine) == jax.tree.structure(ref)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))


def _batch(vocab, seq=64):
    b = reference.synth_batch(vocab, 2, seq, 0, SEED)
    return {k: jnp.asarray(v) for k, v in b.items()}


def test_latent_attention_layer_matches_the_reference():
    """One MLA sub-layer (norm, attention, residual) in float32, forward and
    gradients: the program's layer and the reference's, on the same weights.
    Both are float32 on the CPU; what is left is the order of sums."""
    from repro.layers.mla import mla_forward
    from repro.layers.norms import rmsnorm
    cell = _cell()
    cfg, params = _program(cell)
    arch = _drv(cell).reference_arch(cell.config)
    lp = jax.tree.map(lambda a: a[0], _as_reference(params)["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.d_model))

    def program(lp, x):
        p = {"wq": lp["wq"], "wkv_a": lp["wkv_a"], "kv_norm": {"scale": lp["kv_norm"]},
             "wkv_b": lp["wkv_b"], "wo": lp["wo"]}
        h = rmsnorm({"scale": lp["ln1"]}, x[None], cfg.norm_eps)
        return x + mla_forward(p, h, n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
                               qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                               v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
                               norm_eps=cfg.norm_eps)[0]

    def ref(lp, x):
        return reference._attention(arch, "f32", x, lp, q_block=16)

    np.testing.assert_allclose(np.asarray(program(lp, x)), np.asarray(ref(lp, x)),
                               rtol=1e-5, atol=1e-5)
    dy = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    g_p = jax.grad(lambda lp, x: jnp.sum(program(lp, x) * dy), argnums=(0, 1))(lp, x)
    g_r = jax.grad(lambda lp, x: jnp.sum(ref(lp, x) * dy), argnums=(0, 1))(lp, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_p)[0],
                            jax.tree.leaves(g_r)):
        assert _rel(a, b) < 1e-5, path          # the layer's leaves, x; zeros elsewhere


def test_whole_cut_model_loss_and_gradients_match_the_reference():
    """The cut model (a dense layer, a MoE layer of 8 held experts of 64,
    the vocabulary slice) in float32: loss and every parameter's gradient."""
    from repro.models.lm import loss_fn
    cell = _cell()
    cfg, params = _program(cell)
    arch = dict(_drv(cell).reference_arch(cell.config), param_dtype="float32")
    batch = _batch(cfg.vocab_size)
    loss_p, g_p = jax.value_and_grad(lambda p: loss_fn(cfg, p, batch)[0])(params)
    loss_r, g_r = jax.value_and_grad(
        lambda p: reference.batch_loss(arch, "f32", 16, 16, p, batch))(_as_reference(params))
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-6)
    mine = _as_reference(g_p)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                            jax.tree.leaves(g_r)):
        assert _rel(a, b) < 1e-4, path


# ------------------------------------------------------ the cell's check
def _run(cell):
    import time
    return harness.run_cell(cell, SEED, 0.3, False, time.perf_counter(), jax.devices()[:1])


def test_sound_run_is_correct():
    res = _run(_cell())
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks" and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


def _broken(real, fault):
    def make(*args, **kw):
        fn, in_sh, out_sh, donate = real(*args, **kw)
        if fault == "unchanged":
            def step(p, s, b):
                return p, s, fn(p, s, b)[2]
            return step, in_sh, out_sh, ()

        def step(p, s, b):          # half the batch left out, the mean over the rest
            return fn(p, s, jax.tree.map(lambda x: x[: x.shape[0] // 2], b))
        return step, (in_sh[0], in_sh[1], None), out_sh, donate
    return make


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "capacity_1.25"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    """A step that changes nothing, one that leaves half the batch out, and
    the capacity dispatch in place of the drop-free experts (rows past an
    expert's capacity dropped) each fail a limit."""
    import repro.launch.train as train_mod
    if fault == "capacity_1.25":
        with calibrate_mla_moe.capacity_fault():
            res = _run(_cell())
    else:
        monkeypatch.setattr(train_mod, "make_train_step",
                            _broken(train_mod.make_train_step, fault))
        res = _run(_cell())
    assert not res["correct"], res["checks"]


def test_float8_control_is_not_correct():
    cell = _cell()
    drv, t = _drv(cell), cell.traffic
    arch = drv.reference_arch(cell.config)
    ref = reference.run(arch, t["optimizer"], t["batch"], t["seq"], SEED, drv.WARM_STEPS)
    control = reference.run(arch, t["optimizer"], t["batch"], t["seq"], SEED,
                            drv.WARM_STEPS, matmul="fp8")
    readings = drv.compare(control, ref)
    assert any(readings[k] > lim for k, lim in t["limits"].items()), readings


# ------------------------------------------------- the adapter's trace pass
def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[SimpleNamespace(name=n, start_ns=a, duration_ns=d)
                                         for n, a, d in evs]) for ln, evs in lines])


def _planes():
    host = _plane("/host:CPU", [("python3", [("bench_window", 1000, 9000)])])
    ops = [("%gmm.43 = bf16[8,4] custom-call(...)", 500, 1000),          # half inside
           ("%tgmm.2 = bf16[8,4] custom-call(...)", 2000, 500),
           ("%splash_mqa_fwd_residuals.1 = (f32[2]) custom-call(...)", 3000, 700),
           ("%splash_mqa_dkv_no_residuals.1 = (f32[2]) custom-call(...)", 4000, 300),
           ("%while.1 = (s32[]) while(...)", 1500, 8000),                 # holds others
           ("%fusion.7 = bf16[8] fusion(...)", 6000, 1000),
           ("%gmm.9 = bf16[8,4] custom-call(...)", 9500, 1000)]           # half inside
    device = _plane("/device:TPU:0", [("XLA Modules", [("jit_train_step", 0, 20000)]),
                                      ("XLA Ops", ops)])
    return [host, device]


def test_kernel_seconds_of_a_made_up_trace():
    drv = _drv(_cell())
    assert drv.kernel_seconds(_planes()) == pytest.approx(
        {"gmm": 1.5e-6, "attention": 1.0e-6}, rel=1e-9)
    assert drv.kernel_kind("%fusion.1 = f32[2] fusion(%gmm.3)") is None


def test_kernel_seconds_needs_the_window_and_a_device():
    drv = _drv(_cell())
    with pytest.raises(ValueError):
        drv.kernel_seconds(_planes()[1:])
    with pytest.raises(ValueError):
        drv.kernel_seconds(_planes()[:1])


# ------------------------------------------------------- operation counts
def test_flops_count_the_programs_matmul_weights():
    """``matmul_params_per_token`` against the program's own weights: every
    matrix but the embedding's, the routed experts at top_k of 64 of one
    expert, and the router."""
    cell = harness.find_cell(CELL)
    c = cell.config
    cfg = _drv(cell).lm_config(c)
    from repro.models.lm import init_params
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    total = 0.0
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = [getattr(k, "key", None) for k in path]
        layers, shape = (s.shape[0], s.shape[1:]) if keys[0] == "scan" else (1, s.shape)
        if keys[0] == "embed" or len(shape) < 2:
            continue
        n = math.prod(shape)
        if keys[-2:-1] == ["moe"] and keys[-1] != "w_router":
            n *= c["num_experts_per_tok"] / c["published"]["n_routed_experts"]
        total += layers * n
    assert flops_mla_moe.matmul_params_per_token(c) == pytest.approx(total, rel=1e-12)
    per_token = flops_mla_moe.train_flops_per_token(c, 8192)
    assert per_token == pytest.approx(6 * total + 6 * 5 * 16 * 8192 * 320, rel=1e-12)


def test_kernel_costs_at_the_cell_shape():
    gmm = flops_mla_moe.gmm_cost(24576, 2048, 1408, 8)
    assert gmm["flops"] == 24 * 24576 * 2048 * 1408
    attn = flops_mla_moe.attention_cost(4, 16, 8192, 192, 128)
    pairs = 8192 * 8193 / 2
    assert attn["flops"] == 64 * 2 * pairs * (2 * 320 + 3 * 192 + 2 * 128)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # both compute-bound at these shapes
    for cost in (gmm, attn):
        assert flops_mla_moe.roofline_s(cost, peaks) == cost["flops"] / 197e12


# ---------------------------------------------------------- the readers
READERS = ["mfu.mla_moe", "expert_imbalance.mla_moe", "gmm_roofline.mla_moe",
           "attn_roofline.mla_moe"]


def _ring(steps=6, rows=((24000, 6000), (30000, 9000))):
    ring = TaskStream(capacity=256)

    def add(kind, **kw):
        ring.append(kind, **dict(dict(thread=1, id=0, parent=None, run=1, args={},
                                      error=None, t=0.0), **kw))
    add("span", name="train.init", t0=0.0, t1=1.0, step=None)
    for k in range(steps):
        add("span", name="train.step", t0=10.0 * k, t1=10.0 * k + 1, step=k)
        for layer, (here, largest) in enumerate(rows):
            add("count", name="moe.rows", value=here, step=k,
                args={"layer": layer, "largest": largest})
    return ring


def _ctx(kernel_s=None, window_steps=3):
    cell = harness.find_cell(CELL)
    t = cell.traffic
    facts = {"tokens": window_steps * t["batch"] * t["seq"], "seq": t["seq"],
             "window_s": 3.0}
    if kernel_s is not None:
        facts["kernel_s"] = kernel_s
    outcome = harness.Outcome(metrics={}, attempted=window_steps, failed=0, checks=[],
                              memory_peak_bytes=None, facts=facts)
    return harness.Context(cell, outcome, None, "TPU v5 lite", 1)


def _read(name, ctx):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(ctx)


def test_readers_of_a_made_up_window(monkeypatch):
    monkeypatch.setattr(tracing, "SPANS", _ring())
    ctx = _ctx(kernel_s={"gmm": 0.5, "attention": 2.0})
    c = ctx.cell.config
    assert _read("expert_imbalance.mla_moe", ctx) == pytest.approx(
        (6000 * 8 / 24000 + 9000 * 8 / 30000) / 2)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    gmm = sum(flops_mla_moe.roofline_s(flops_mla_moe.gmm_cost(r, 2048, 1408, 8), peaks)
              for r in (24000, 30000)) * 3
    assert _read("gmm_roofline.mla_moe", ctx) == pytest.approx(100 * gmm / 0.5)
    attn = 3 * 5 * flops_mla_moe.roofline_s(
        flops_mla_moe.attention_cost(4, 16, 8192, 192, 128), peaks)
    assert _read("attn_roofline.mla_moe", ctx) == pytest.approx(100 * attn / 2.0)
    assert _read("mfu.mla_moe", ctx) == pytest.approx(
        100 * flops_mla_moe.train_flops_per_token(c, 8192) * 3 * 4 * 8192 / 3.0 / 197e12)


@pytest.mark.parametrize("name", READERS[1:])
def test_readers_are_silent_where_the_program_keeps_no_ring(monkeypatch, name):
    monkeypatch.delattr(tracing, "SPANS")
    assert _read(name, _ctx(kernel_s={"gmm": 0.5, "attention": 2.0})) is None


@pytest.mark.parametrize("name", ["gmm_roofline.mla_moe", "attn_roofline.mla_moe"])
def test_kernel_readers_are_silent_without_the_kernels_time(monkeypatch, name):
    monkeypatch.setattr(tracing, "SPANS", _ring())
    assert _read(name, _ctx()) is None
    assert _read(name, _ctx(kernel_s={})) is None

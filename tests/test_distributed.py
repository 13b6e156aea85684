"""Distribution layer: sharding rule logic (host-side) + an 8-device
pjit/shard_map integration test run in a subprocess (device count is
process-global, so the forced-host-device test cannot share this process).
"""
import os
import subprocess
import sys
import textwrap

import pytest

# minutes of JAX compile+run on CPU: opt-in via `-m slow` (see pytest.ini)
pytestmark = pytest.mark.slow


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_spec_for_divisibility_and_axis_reuse():
    code = """
    import jax
    from repro.launch.mesh import auto_mesh
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import ShardingRules, spec_for, default_rules
    mesh = auto_mesh((2, 4), ("data", "model"))
    rules = default_rules(mesh)
    # divisible: sharded; non-divisible: replicated
    assert spec_for((16, 8), ("embed", "heads"), rules, mesh) == P("data", "model")
    assert spec_for((16, 6), ("embed", "heads"), rules, mesh) == P("data", None)
    assert spec_for((3, 8), ("embed", "heads"), rules, mesh) == P(None, "model")
    # the same mesh axis is never used twice
    s = spec_for((8, 8), ("heads", "mlp"), rules, mesh)
    assert s == P("model", None)
    print("OK")
    """
    assert "OK" in run_sub(code, devices=8)


def test_train_and_decode_on_8_forced_devices():
    code = """
    import dataclasses, jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    from repro.configs import get_config, SMOKE_SHAPES, make_batch
    from repro.distributed.steps import make_train_step, make_decode_step
    from repro.models.lm import init_params
    from repro.optim.adamw import adamw

    mesh = auto_mesh((2, 4), ("data", "model"))
    for arch in ("granite-3-2b", "deepseek-moe-16b", "mamba2-780m"):
        cfg = get_config(arch, reduced=True)
        shape = dataclasses.replace(SMOKE_SHAPES["train_4k"], batch=4)
        b = make_batch(cfg, shape)
        opt = adamw(1e-3)
        fn, in_sh, out_sh, don = make_train_step(
            cfg, mesh, opt, microbatches=2, sample_batch=b["batch"])
        params = init_params(cfg, jax.random.PRNGKey(0))
        state = opt.init(params)
        j = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=don)
        p2, s2, m = j(params, state, b["batch"])
        assert jnp.isfinite(m["loss"]), arch
        print(arch, float(m["loss"]))
    print("OK")
    """
    assert "OK" in run_sub(code)


def test_moe_sharded_matches_local_on_4_devices():
    """EP shard_map MoE == single-shard dispatch (same capacity)."""
    code = """
    import jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    from repro.layers import moe
    mesh = auto_mesh((1, 4), ("data", "model"))
    p = moe.init_moe(jax.random.PRNGKey(0), 16, 32, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
    # capacity: local sees N=32 tokens on the single data shard either way
    out_l, aux_l = moe.moe_apply_local(p, x, top_k=2, capacity_factor=8.0)
    out_s, aux_s = jax.jit(lambda p, x: moe.moe_apply_sharded(
        p, x, mesh=mesh, top_k=2, data_axes=("data",),
        capacity_factor=8.0))(p, x)
    import numpy as np
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_l), atol=2e-5)
    assert abs(float(aux_s) - float(aux_l)) < 1e-5
    print("OK")
    """
    assert "OK" in run_sub(code, devices=4)


def test_tp_shard_map_equals_gspmd():
    """The §Perf shard_map-TP path and the GSPMD-sharded baseline compute
    the same function (loss and grads) as the *replicated* ground truth.

    jaxlib 0.4.36's CPU SPMD partitioner miscompiled the GSPMD attention
    path when params carry the FSDP shardings (loss off by ~3e-2, grad
    max-diff ~0.15; DESIGN.md §9), so this test once held GSPMD to a coarse
    bound only.  JAX 0.9 partitions it correctly, so both paths are held to
    the same bound."""
    code = """
    import dataclasses, jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    from repro.configs import get_config, SMOKE_SHAPES, make_batch
    from repro.models.lm import loss_fn, init_params, param_axes
    from repro.distributed.sharding import (default_rules, param_pspecs,
                                            to_shardings)
    mesh = auto_mesh((2, 4), ("data", "model"))
    rules = default_rules(mesh)
    for arch in ("granite-3-2b", "recurrentgemma-9b"):
        cfg = get_config(arch, reduced=True)
        shape = dataclasses.replace(SMOKE_SHAPES["train_4k"], batch=4)
        b = make_batch(cfg, shape)
        params = init_params(cfg, jax.random.PRNGKey(0))
        cfg_tp = dataclasses.replace(cfg, tp_block="shard_map")
        # replicated ground truth (single-device semantics)
        l_ref, _ = jax.jit(lambda p, bb: loss_fn(cfg, p, bb, mesh=mesh))(params, b["batch"])
        g_ref = jax.jit(jax.grad(
            lambda p: loss_fn(cfg, p, b["batch"], mesh=mesh)[0]))(params)
        # production contract: parameters carry explicit shardings
        p_sh = to_shardings(param_pspecs(param_axes(cfg), params, rules, mesh),
                            mesh)
        params_sh = jax.tree.map(jax.device_put, params, p_sh)
        l_g, _ = jax.jit(lambda p, bb: loss_fn(cfg, p, bb, mesh=mesh))(params_sh, b["batch"])
        l_t, _ = jax.jit(lambda p, bb: loss_fn(cfg_tp, p, bb, mesh=mesh))(params_sh, b["batch"])
        g_t = jax.jit(jax.grad(
            lambda p: loss_fn(cfg_tp, p, b["batch"], mesh=mesh)[0]))(params_sh)
        g_g = jax.jit(jax.grad(
            lambda p: loss_fn(cfg, p, b["batch"], mesh=mesh)[0]))(params_sh)

        def max_diff(g):
            return max(float(jnp.max(jnp.abs(a - c)))
                       for a, c in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)))
        assert abs(float(l_g) - float(l_ref)) < 1e-4, (arch, "gspmd fwd")
        assert abs(float(l_t) - float(l_ref)) < 1e-4, (arch, "tp fwd")
        assert max_diff(g_g) < 1e-3, (arch, "gspmd grad", max_diff(g_g))
        assert max_diff(g_t) < 1e-3, (arch, "tp grad", max_diff(g_t))
        print(arch, "tp==truth", float(l_t))
    print("OK")
    """
    assert "OK" in run_sub(code)


def test_elastic_checkpoint_restore_across_meshes():
    """Fault-tolerance at 1000-node scale means restarting on a different
    machine shape: save a sharded state on a (4,2) mesh, restore it onto a
    (2,4) mesh, and continue training — losses must continue unperturbed."""
    code = """
    import dataclasses, jax, jax.numpy as jnp, tempfile
    from repro.launch.mesh import auto_mesh
    from repro.configs import get_config, SMOKE_SHAPES, make_batch
    from repro.checkpoint.manager import restore_checkpoint, save_checkpoint
    from repro.distributed.sharding import (default_rules, param_pspecs,
                                            to_shardings)
    from repro.distributed.steps import make_train_step
    from repro.models.lm import init_params, param_axes
    from repro.optim.adamw import adamw

    cfg = get_config("qwen3-0.6b", reduced=True)
    shape = dataclasses.replace(SMOKE_SHAPES["train_4k"], batch=8)
    b = make_batch(cfg, shape)
    opt = adamw(1e-3)

    def step_on(mesh, params, opt_state):
        fn, in_sh, out_sh, don = make_train_step(cfg, mesh, opt,
                                                 sample_batch=b["batch"])
        j = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=don)
        return j(params, opt_state, b["batch"])

    # phase 1: mesh A = (4, 2)
    mesh_a = auto_mesh((4, 2), ("data", "model"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = opt.init(params)
    params, state, m1 = step_on(mesh_a, params, state)
    ckpt = tempfile.mkdtemp()
    save_checkpoint(ckpt, {"params": params, "opt": state}, step=1)

    # uninterrupted continuation on mesh A (the reference)
    _, _, m_ref = step_on(mesh_a, params, state)

    # phase 2: RESTART on mesh B = (2, 4) — different data/model split
    mesh_b = auto_mesh((2, 4), ("data", "model"))
    target = {"params": params, "opt": state}
    p_sh = to_shardings(param_pspecs(param_axes(cfg), params,
                                     default_rules(mesh_b), mesh_b), mesh_b)
    restored, step = restore_checkpoint(ckpt, target)
    rp = jax.tree.map(jax.device_put, restored["params"], p_sh)
    _, _, m_b = step_on(mesh_b, rp, restored["opt"])
    assert step == 1
    assert abs(float(m_ref["loss"]) - float(m_b["loss"])) < 1e-4, (
        float(m_ref["loss"]), float(m_b["loss"]))
    print("elastic restore ok", float(m_b["loss"]))
    print("OK")
    """
    assert "OK" in run_sub(code)


def test_grad_compress_in_train_step():
    code = """
    import dataclasses, jax, jax.numpy as jnp
    from repro.launch.mesh import auto_mesh
    from repro.configs import get_config, SMOKE_SHAPES, make_batch
    from repro.distributed.steps import make_train_step
    from repro.models.lm import init_params
    from repro.optim.adamw import adamw
    mesh = auto_mesh((2, 1), ("data", "model"))
    cfg = get_config("qwen3-0.6b", reduced=True)
    shape = dataclasses.replace(SMOKE_SHAPES["train_4k"], batch=4)
    b = make_batch(cfg, shape)
    opt = adamw(1e-3)
    fn, in_sh, out_sh, don = make_train_step(cfg, mesh, opt,
        sample_batch=b["batch"], grad_compress="int8")
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = opt.init(params)
    p2, s2, m = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=don)(params, state, b["batch"])
    assert jnp.isfinite(m["loss"])
    print("OK")
    """
    assert "OK" in run_sub(code, devices=2)

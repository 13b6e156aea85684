"""Adapter: training of a DeepSeek-V3-style model (latent attention, a
leading dense layer, routed and shared experts) through
``repro.launch.train.train_loop``, on one chip's share of its experts.

Everything but the model is ``train_loop.py``'s: the window opens and
closes on the loop's per-step log line, the jitted step is wrapped (its
probe, with this model's leaf names) to keep the first ``WARM_STEPS``
steps' losses, gradient norms and parameter change, and the three readings
are compared with the float32 reference (``bench/reference/mla_moe_train.py``)
by ``compare``.

With a trace, after the profiler has written it, one more pass over the
window's ``.xplane.pb`` sums the device time of the grouped-matmul kernels
and of the splash-attention kernels into ``facts["kernel_s"]`` (the
harness reduces the trace to its top ten operations and then deletes it).
"""
from __future__ import annotations

import dataclasses
import gc
import sys
from collections import defaultdict
from typing import Any, Dict
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, trace_reduce
from bench.drivers import train_loop as dense_adapter
from bench.drivers.train_loop import WARM_STEPS, _JaxWithProbe, _StateProbe, _StepLog, compare
from bench.reference import mla_moe_train as reference

# configuration file key -> LMConfig field
KEYMAP = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "rms_norm_eps": "norm_eps",
    "first_k_dense_replace": "first_k_dense", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim", "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim", "n_routed_experts": "n_experts_held",
    "num_experts_per_tok": "top_k", "moe_intermediate_size": "d_ff_expert",
    "n_shared_experts": "n_shared_experts", "scoring_func": "moe_scoring",
    "routed_scaling_factor": "moe_routed_scale", "norm_topk_prob": "moe_renormalize",
}
# what the program and the reference both take as given
FIXED = {"q_lora_rank": None, "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "hidden_act": "silu", "attention_bias": False}
# the program's leaf (path below its layer) -> the reference's leaf name;
# None: the correction bias, held at zero outside the gradient
LEAF_NAMES = {
    "ln1/scale": "ln1", "ln2/scale": "ln2", "attn/wq": "wq", "attn/wkv_a": "wkv_a",
    "attn/kv_norm/scale": "kv_norm", "attn/wkv_b": "wkv_b", "attn/wo": "wo",
    "mlp/w_gate": "w_gate", "mlp/w_up": "w_up", "mlp/w_down": "w_down",
    "moe/w_router": "w_router", "moe/w_gate": "e_gate", "moe/w_up": "e_up",
    "moe/w_down": "e_down", "moe/e_score_correction_bias": None,
    "shared/w_gate": "s_gate", "shared/w_up": "s_up", "shared/w_down": "s_down",
}


def _check_fixed(config: Dict[str, Any]) -> None:
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    if wrong or config["num_hidden_layers"] <= config["first_k_dense_replace"]:
        raise ValueError(f"{config['arch']}: this adapter runs {FIXED} with a MoE layer "
                         f"after the dense ones; the file has {wrong}")


def lm_config(config: Dict[str, Any]):
    """The program's LMConfig: the registered architecture with the file's
    sizes put in; the router scores the published number of experts and
    this chip holds the file's ``n_routed_experts`` of them."""
    from repro.configs import get_config
    _check_fixed(config)
    fields = {f: config[k] for k, f in KEYMAP.items()}
    cfg = dataclasses.replace(get_config(config["arch"]), **fields,
                              n_experts=config["published"]["n_routed_experts"])
    if jnp.dtype(cfg.param_dtype) != jnp.dtype(config["torch_dtype"]):
        raise ValueError(f"{config['arch']}: dtype {cfg.param_dtype} does not match the file")
    return cfg


def reference_arch(config: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's description of the same model, from the file alone."""
    _check_fixed(config)
    n_dense = config["first_k_dense_replace"]
    return {"d_model": config["hidden_size"], "n_heads": config["num_attention_heads"],
            "n_dense": n_dense, "n_moe": config["num_hidden_layers"] - n_dense,
            "d_ff": config["intermediate_size"], "vocab_size": config["vocab_size"],
            "kv_lora_rank": config["kv_lora_rank"], "qk_nope_dim": config["qk_nope_head_dim"],
            "qk_rope_dim": config["qk_rope_head_dim"], "v_head_dim": config["v_head_dim"],
            "n_experts": config["published"]["n_routed_experts"],
            "n_experts_held": config["n_routed_experts"],
            "d_ff_expert": config["moe_intermediate_size"],
            "n_shared": config["n_shared_experts"], "top_k": config["num_experts_per_tok"],
            "routed_scale": config["routed_scaling_factor"],
            "rope_theta": config["rope_theta"], "rms_norm_eps": config["rms_norm_eps"],
            "param_dtype": config["torch_dtype"]}


def _leaf_name(path) -> str:
    keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    if keys[0] == "scan":                # scan / b0 / ...
        name = LEAF_NAMES["/".join(keys[2:])]
        return name and f"moe.{name}"
    if keys[0] == "lead":                # lead / i / ...
        name = LEAF_NAMES["/".join(keys[2:])]
        return name and f"dense.{name}"
    return {"final_norm/scale": "final_norm"}.get("/".join(keys), "/".join(keys))


def leaf_norms(tree, minus=None) -> Dict[str, np.ndarray]:
    """Norm of every leaf of a program tree (of ``tree - minus`` where
    given), by reference name; a layer leaf gives one norm per layer, the
    leading layers' in order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    names = [_leaf_name(p) for p, _ in flat]
    stacked = [p[0].key == "scan" for p, _ in flat]

    @jax.jit
    def norms(leaves, others):
        out = []
        for x, y, st in zip(leaves, others, stacked):
            x = x.astype(jnp.float32) - (0.0 if y is None else y.astype(jnp.float32))
            out.append(jnp.sqrt(jnp.sum(jnp.square(x),
                                        axis=tuple(range(1, x.ndim)) if st else None)))
        return out

    others = [None] * len(flat) if minus is None else jax.tree.leaves(minus)
    vals = jax.device_get(norms([x for _, x in flat], others))
    grouped: Dict[str, list] = defaultdict(list)
    for name, v in zip(names, vals):
        if name is not None:
            grouped[name].append(np.ravel(v))
    return {k: (np.concatenate(v) if k.startswith(("dense.", "moe.")) else v[0])
            for k, v in grouped.items()}


def kernel_kind(hlo: str):
    """``"gmm"`` for a grouped-matmul kernel (megablox's ``gmm``/``tgmm``),
    ``"attention"`` for a splash-attention kernel, else None: by the name
    of the HLO instruction, which a Pallas call takes from its kernel."""
    name = hlo.partition(" = ")[0]
    if "gmm" in name:
        return "gmm"
    if "splash" in name:
        return "attention"
    return None


def kernel_seconds(planes) -> Dict[str, float]:
    """Device seconds of each kind of kernel (``kernel_kind``) inside the
    window span, over the device planes' op lines (mean over devices)."""
    window, device_lines = None, []
    for plane in planes:
        lines = list(plane.lines)
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            device_lines += [ln for ln in lines if ln.name in trace_reduce.OPS_LINES][:1]
            continue
        for line in lines:
            win = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
                   for e in line.events if e.name == trace_reduce.WINDOW_SPAN]
            if win and window is None:
                window = win[0]
    if window is None or not device_lines:
        raise ValueError("trace has no window span or no device ops line")
    out: Dict[str, float] = defaultdict(float)
    for line in device_lines:
        evs = [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
               for e in line.events]
        for name, a, b in trace_reduce.leaves(evs):
            kind = kernel_kind(name)
            if kind:
                for lo, hi in trace_reduce.clip([(a, b)], *window):
                    out[kind] += (hi - lo) * 1e-9 / len(device_lines)
    return dict(out)


def run(cell: harness.Cell, seed: int, seconds: float, window: harness.Window):
    import repro.launch.train as train_mod
    t, opt = cell.traffic, cell.traffic["optimizer"]
    cfg = lm_config(cell.config)
    devices = jax.devices()[:cell.chips]
    probe = _StateProbe()
    log = _StepLog(window, seconds=seconds)
    with mock.patch.object(train_mod, "print", log.line, create=True), \
            mock.patch.object(train_mod, "jax", _JaxWithProbe(probe)), \
            mock.patch.object(dense_adapter, "leaf_norms", leaf_norms):   # the probe's
        try:
            train_mod.train_loop(cfg, steps=opt["total_steps"], batch=t["batch"],
                                 seq=t["seq"], lr=opt["lr"], warmup=opt["warmup"],
                                 workers=t["workers"], seed=seed)
        except harness.WindowClosed:
            pass
    window.finish()
    facts: Dict[str, Any] = {}
    if window.trace_dir is not None:
        from jax.profiler import ProfileData
        path = trace_reduce.find_trace(str(window.trace_dir))
        facts["kernel_s"] = kernel_seconds(ProfileData.from_file(path).planes)
    mem = harness.memory_peak(devices)
    n = len(log.done)
    tokens = n * t["batch"] * t["seq"]
    elapsed = log.done[-1] - window.t_open if n else float("nan")
    if n:
        steps = np.diff([window.t_open] + log.done)
        print(f"window: {n} steps in {elapsed:.3f} s, step min {steps.min():.4f} "
              f"median {np.median(steps):.4f} max {steps.max():.4f} s", file=sys.stderr)
    prog = {
        "losses": [float(x) for x in probe.losses],
        "grad_norms": {k: v / (1.0 - opt["b1"]) for k, v in probe.first_moment.items()},
        "change_norms": leaf_norms(probe.params_after, minus=probe.params_before),
    }
    del probe
    gc.collect()
    ref = reference.run(reference_arch(cell.config), opt, t["batch"], t["seq"], seed,
                        n_steps=WARM_STEPS)
    readings = compare(prog, ref)
    checks = [(k, readings[k], t["limits"][k]) for k in ("loss_gap", "grad_gap", "change_gap")]
    facts.update(tokens=tokens, window_s=elapsed, seq=t["seq"], program=prog, reference=ref)
    return harness.Outcome(
        metrics={"train_tokens_per_s": tokens / elapsed},
        attempted=n, failed=0, checks=checks, memory_peak_bytes=mem, facts=facts)

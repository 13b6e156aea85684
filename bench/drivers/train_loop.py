"""Adapter: LM training through ``repro.launch.train.train_loop``.

The job is run as the training driver runs it (the task runtime started
with the traffic's worker count, ``DataPipeline`` prefetch, AdamW with a
cosine schedule, the GSPMD step). ``train_loop`` runs a fixed number of
steps and reports only its own rate, so the adapter watches it from
outside:

* step completions: the per-step log line, which ``train_loop`` prints once
  ``float(loss)`` has synchronised with the device. The window opens at the
  line of the last warm step and closes at the first line ``seconds`` after;
  the adapter then ends the loop by raising from that line.
* the state of the first steps: the jitted step is wrapped where
  ``train_loop`` builds it. The wrapper copies the initial parameters to the
  host before the first call donates them, keeps the first steps' losses,
  the norms of the first moments after step one (the clipped gradient as
  the optimizer received it) and the parameters after the last warm step.
  After that it only passes calls through.

``train_tokens_per_s`` is the tokens of all steps completed in the window
over the time from the opening step boundary to the last completed step.
"""
from __future__ import annotations

import dataclasses
import gc
import re
import sys
import time
from typing import Any, Dict, List
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.reference import lm_train as reference

# configuration file key -> LMConfig field
KEYMAP = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
# the program's parameter path -> the reference's leaf name
LEAF_NAMES = {
    "embed": "embed", "final_norm/scale": "final_norm", "lm_head": "lm_head",
    "ln1/scale": "ln1", "ln2/scale": "ln2", "attn/wq": "wq", "attn/wk": "wk",
    "attn/wv": "wv", "attn/wo": "wo", "mlp/w_gate": "w_gate", "mlp/w_up": "w_up",
    "mlp/w_down": "w_down",
}
STEP_LINE = re.compile(r"^step\s+(\d+)\s")
# The loop's first steps: they compile and warm up, run before the window,
# and are the steps the reference follows. Three, because the change after
# one step is the sign of the gradient alone.
WARM_STEPS = 3
EXCLUDE_BELOW = 1e-3   # of the median leaf's reference gradient: no change to compare


def lm_config(config: Dict[str, Any]):
    """The program's LMConfig for this configuration file: the registered
    architecture with the file's sizes put in."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(config["arch"]),
                              **{f: config[k] for k, f in KEYMAP.items()})
    if cfg.hd != config["head_dim"] or (jnp.dtype(cfg.param_dtype)
                                        != jnp.dtype(config["torch_dtype"])):
        raise ValueError(f"{config['arch']}: head_dim {cfg.hd} and dtype "
                         f"{cfg.param_dtype} do not match the configuration file")
    return cfg


def reference_arch(config: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's description of the same model, from the file alone."""
    return {"d_model": config["hidden_size"], "n_layers": config["num_hidden_layers"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
            "d_ff": config["intermediate_size"], "vocab_size": config["vocab_size"],
            "rope_theta": config["rope_theta"], "rms_norm_eps": config["rms_norm_eps"],
            "param_dtype": config["torch_dtype"]}


def _leaf_name(path) -> str:
    keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    if keys[0] == "scan":
        keys = keys[2:]            # scan / b0 / ...
    return LEAF_NAMES["/".join(str(k) for k in keys)]


def leaf_norms(tree, minus=None) -> Dict[str, np.ndarray]:
    """Norm of every leaf of a program tree (of ``tree - minus`` where given),
    by reference name; a stacked layer leaf gives one norm per layer."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    paths = [p for p, _ in flat]
    stacked = [p[0].key == "scan" for p in paths]

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
    return {_leaf_name(p): np.asarray(v) for p, v in zip(paths, vals)}


class _StateProbe:
    """Wraps the jitted train step; records the first ``WARM_STEPS`` steps."""

    def __init__(self):
        self.calls = 0
        self.losses: List[Any] = []
        self.params_before = None
        self.params_after = None
        self.first_moment = None

    def wrap(self, jitted):
        def step(params, opt_state, batch):
            i = self.calls
            self.calls += 1
            if i == 0:
                self.params_before = jax.device_get(params)
            out = jitted(params, opt_state, batch)
            if i < WARM_STEPS:
                self.losses.append(out[2]["loss"])
            if i == 0:
                self.first_moment = leaf_norms(out[1].mu)
            if i == WARM_STEPS - 1:
                self.params_after = jax.device_get(out[0])
            return out
        return step


class _JaxWithProbe:
    """``jax`` as ``train_loop`` sees it, with ``jit`` wrapped by the probe."""

    def __init__(self, probe: _StateProbe):
        self._probe = probe

    def __getattr__(self, name):
        return getattr(jax, name)

    def jit(self, fn, **kw):
        return self._probe.wrap(jax.jit(fn, **kw))


class _StepLog:
    """Reads the loop's per-step log line; opens and closes the window."""

    def __init__(self, window: harness.Window, seconds: float):
        self.window, self.seconds = window, seconds
        self.done: List[float] = []       # completion times inside the window

    def line(self, *args, **kw):
        t = time.perf_counter()
        print(*args, **kw)
        m = STEP_LINE.match(str(args[0])) if args else None
        if not m:
            return
        step = int(m.group(1))
        if step == WARM_STEPS - 2:
            self.window.arm()
        elif step == WARM_STEPS - 1:
            self.window.open()
        elif step >= WARM_STEPS:
            self.done.append(t)
            if t - self.window.t_open >= self.seconds:
                self.window.close()
                raise harness.WindowClosed


def _gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray], keep=None) -> float:
    """Worst leaf's gap between the two norms, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    names = [n for n in ref if keep is None or n in keep]
    ref_all = np.concatenate([np.ravel(ref[n]) for n in names])
    median = float(np.median(ref_all))
    worst = 0.0
    for n in names:
        r, p = np.ravel(ref[n]).astype(np.float64), np.ravel(prog[n]).astype(np.float64)
        worst = max(worst, float(np.max(np.abs(p - r) / np.maximum(r, median))))
    return worst


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The three readings: each step's loss, the first gradient's norms and
    the parameters' change after the checked steps, each by its worst case.
    Leaves whose reference gradient is under ``EXCLUDE_BELOW`` of the median
    leaf's are left out of the change: they move by round-off alone."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    grads = ref["grad_norms"]
    g_median = float(np.median(np.concatenate([np.ravel(v) for v in grads.values()])))
    moved = {n for n, v in grads.items() if np.min(v) >= EXCLUDE_BELOW * g_median}
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": _gap(prog["grad_norms"], grads),
            "change_gap": _gap(prog["change_norms"], ref["change_norms"], keep=moved)}


def run(cell: harness.Cell, seed: int, seconds: float, window: harness.Window):
    import repro.launch.train as train_mod
    t, opt = cell.traffic, cell.traffic["optimizer"]
    cfg = lm_config(cell.config)
    devices = jax.devices()[:cell.chips]
    probe = _StateProbe()
    log = _StepLog(window, seconds=seconds)
    with mock.patch.object(train_mod, "print", log.line, create=True), \
            mock.patch.object(train_mod, "jax", _JaxWithProbe(probe)):
        try:
            train_mod.train_loop(cfg, steps=opt["total_steps"], batch=t["batch"],
                                 seq=t["seq"], lr=opt["lr"], warmup=opt["warmup"],
                                 workers=t["workers"], seed=seed)
        except harness.WindowClosed:
            pass
    window.finish()
    mem = harness.memory_peak(devices)
    n, b1 = len(log.done), opt["b1"]
    tokens = n * t["batch"] * t["seq"]
    elapsed = log.done[-1] - window.t_open if n else float("nan")
    if n:
        steps = np.diff([window.t_open] + log.done)
        slow = int(np.argmax(steps))
        print(f"window: {n} steps in {elapsed:.3f} s, step min {steps.min():.4f} "
              f"median {np.median(steps):.4f} max {steps.max():.4f} s (window step {slow}, "
              f"ending {log.done[slow] - window.t_open:.3f} s in)", file=sys.stderr)
    prog = {
        "losses": [float(x) for x in probe.losses],
        "grad_norms": {k: v / (1.0 - b1) for k, v in probe.first_moment.items()},
        "change_norms": leaf_norms(probe.params_after, minus=probe.params_before),
    }
    del probe
    gc.collect()
    ref = reference.run(reference_arch(cell.config), opt, t["batch"], t["seq"], seed,
                        n_steps=WARM_STEPS)
    readings = compare(prog, ref)
    checks = [(k, readings[k], t["limits"][k]) for k in ("loss_gap", "grad_gap", "change_gap")]
    return harness.Outcome(
        metrics={"train_tokens_per_s": tokens / elapsed},
        attempted=n, failed=0, checks=checks, memory_peak_bytes=mem,
        facts={"tokens": tokens, "window_s": elapsed, "seq": t["seq"],
               "program": prog, "reference": ref})

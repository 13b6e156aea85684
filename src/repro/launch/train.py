"""Production training driver.

The paper's technique is the orchestration layer here (DESIGN.md §4): data
prefetch, metric handling, and checkpoint saves run as RCOMPSs tasks on the
persistent-executor runtime, so I/O overlaps compute exactly the way the
paper hides I/O behind GEMMs (§5.3).  The compute step itself is the
pjit/GSPMD ``train_step`` from ``repro.distributed``.

Fault tolerance: checkpoint saves are retried tasks; ``--restore`` resumes
from the newest checkpoint onto *whatever mesh this launch has* (elastic
resharding).  Batches are deterministic in (seed, step), so a restored run
replays the exact data stream.

CPU-scale usage (the end-to-end example):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
        --steps 20 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import json
import math
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config
from ..core import api
from ..checkpoint.manager import CheckpointManager
from ..data.pipeline import DataPipeline
from ..distributed.sharding import default_rules, param_pspecs, to_shardings
from ..distributed.steps import make_train_step
from ..models.lm import LMConfig, init_params, param_axes
from ..optim.adamw import adamw, cosine_schedule
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh


def train_loop(
    cfg: LMConfig,
    *,
    steps: int = 20,
    batch: int = 8,
    seq: int = 64,
    lr: float = 3e-4,
    warmup: int = 10,
    microbatches: int = 1,
    workers: int = 4,
    seed: int = 0,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    restore: bool = False,
    grad_compress: Optional[str] = None,
    mesh=None,
    log_every: int = 1,
    manage_runtime: bool = True,
) -> Dict[str, Any]:
    """Returns {"losses": [...], "steps_done", "restored_from", "tokens_per_s"}."""
    if manage_runtime:
        api.runtime_start(n_workers=workers, policy="fifo", max_retries=2)
    try:
        mesh = mesh or make_local_mesh(model=1, data=1)
        rules = default_rules(mesh)
        opt = adamw(cosine_schedule(lr, warmup, steps), weight_decay=0.01)
        pipeline = DataPipeline(cfg, batch, seq, seed=seed, prefetch_depth=2)

        params = init_params(cfg, jax.random.PRNGKey(seed))
        opt_state = opt.init(params)
        pspecs = param_pspecs(param_axes(cfg), params, rules, mesh)
        p_sh = to_shardings(pspecs, mesh)

        manager = None
        start_step = 0
        restored_from = None
        if ckpt_dir:
            manager = CheckpointManager(ckpt_dir, keep=3, use_runtime=True)
            if restore and manager.latest_step() is not None:
                state = {"params": params, "opt": opt_state}
                state, start_step = manager.restore(state)
                params = jax.tree.map(
                    lambda a, s: jax.device_put(a, s), state["params"], p_sh)
                opt_state = state["opt"]
                restored_from = start_step
        sample = pipeline.get(start_step)
        step_fn, in_sh, out_sh, donate = make_train_step(
            cfg, mesh, opt, rules=rules, microbatches=microbatches,
            sample_batch=sample, grad_compress=grad_compress)
        jitted = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)

        losses: List[float] = []
        t0 = time.perf_counter()
        batch_np = sample
        for step in range(start_step, steps):
            dev_batch = jax.tree.map(jnp.asarray, batch_np)
            params, opt_state, metrics = jitted(params, opt_state, dev_batch)
            if step + 1 < steps:
                batch_np = pipeline.get(step + 1)  # prefetched task result
            loss = float(metrics["loss"])
            losses.append(loss)
            if math.isnan(loss):
                raise FloatingPointError(f"loss NaN at step {step}")
            if log_every and (step % log_every == 0 or step == steps - 1):
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
            if manager and ckpt_every and (step + 1) % ckpt_every == 0:
                manager.save({"params": params, "opt": opt_state}, step + 1,
                             blocking=False)
        wall = time.perf_counter() - t0
        if manager:
            manager.wait()
            manager.save({"params": params, "opt": opt_state}, steps)
        api.barrier()
        tokens = (steps - start_step) * batch * seq
        return {"losses": losses, "steps_done": steps - start_step,
                "restored_from": restored_from,
                "tokens_per_s": tokens / max(wall, 1e-9),
                "runtime_stats": api.current_runtime().stats()}
    finally:
        if manage_runtime:
            api.runtime_stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--grad-compress", default=None,
                    choices=[None, "int8", "topk"])
    args = ap.parse_args()
    use_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    out = train_loop(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                     lr=args.lr, microbatches=args.microbatches,
                     workers=args.workers, seed=args.seed,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     restore=args.restore, grad_compress=args.grad_compress)
    print(json.dumps({k: v for k, v in out.items() if k != "losses"}, indent=1,
                     default=str))
    print(f"loss: {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()

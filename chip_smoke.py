#!/usr/bin/env python3
"""Smoke run of the system's JAX main path on a TPU, through the entry
points a user calls.  It proves that the path starts and computes the right
thing on the chip; the times it prints are smoke results, not benchmark
metrics.

    python chip_smoke.py             # one chip: device, serve, train, knn, kmeans
    python chip_smoke.py --chips 4   # only the tensor-parallel serve path

Phases (one JSON line each on stdout):

* ``device`` -- the first device must be a TPU.  There is no CPU fallback:
  on any other platform the script exits non-zero and prints no result.
* ``serve``  -- ``repro.launch.serve.serve_batch`` on qwen3-0.6b at full
  published width: 8 requests, prompt 512, 32 generated tokens.  The
  prefill's last-position logits are compared with a float32 forward of the
  same parameters at ``highest`` matmul precision.
* ``train``  -- ``repro.launch.train.train_loop`` on the same config for 5
  steps at batch 4, sequence 512: every loss finite, the first one near
  ln(vocab) as random weights give.
* ``knn`` / ``kmeans`` -- the paper's two fused kernels through
  ``repro.kernels.ops`` at fragment sizes, compiled by Mosaic (the compiled
  program must hold a ``tpu_custom_call``), against ``repro.kernels.ref``.
* ``serve_tp`` (``--chips 4`` only) -- the serve phase on a (data=1,
  model=4) mesh, compared with the same model served on one chip.

Any failed check raises, so the script exits non-zero.  The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402
from repro.launch.serve import make_prompts, serve_batch  # noqa: E402
from repro.launch.train import train_loop  # noqa: E402
from repro.models.lm import forward, init_params  # noqa: E402

ARCH = "qwen3-0.6b"
REQUESTS, PROMPT, GEN = 8, 512, 32
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 5, 4, 512
SEED = 0
# bf16 keeps 8 significant bits (rounding error 2^-9 ~ 2e-3).  Each of the
# 28 layers rounds the residual stream, the weights and every matmul input
# to bf16, and those errors add up to 1-2% of the logits' norm (1.2-1.5% at
# 2-8 layers of this width on the CPU).  A wrong layer or a wrong shard is
# off by order 1, so 5% separates the two.
LOGITS_REL_L2 = 5e-2
# at random init every token is about equally likely: loss ~ ln(vocab)
INIT_LOSS_SLACK = 1.0


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def check_serve_output(cfg, out) -> np.ndarray:
    tokens = np.asarray(out["tokens"])
    check(tokens.shape == (REQUESTS, GEN), f"tokens shape {tokens.shape}")
    check(bool(np.all((tokens >= 0) & (tokens < cfg.vocab_size))),
          "generated token outside the vocabulary")
    logits = np.asarray(out["prefill_logits"], np.float32)
    check(logits.shape == (REQUESTS, cfg.vocab_size),
          f"prefill logits shape {logits.shape}")
    check(bool(np.all(np.isfinite(logits))), "non-finite prefill logits")
    return logits


def phase_serve(cfg) -> None:
    t0 = time.perf_counter()
    out = serve_batch(cfg, batch=REQUESTS, prompt_len=PROMPT, gen_len=GEN,
                      seed=SEED)
    t_serve = time.perf_counter() - t0
    logits = check_serve_output(cfg, out)

    # float32 reference of the same parameters and prompts
    t0 = time.perf_counter()
    cfg32 = dataclasses.replace(cfg, param_dtype=jnp.float32,
                                compute_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    param_bytes = nbytes(params)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    del params
    prompts = make_prompts(cfg, REQUESTS, PROMPT, SEED)
    fwd = jax.jit(functools.partial(forward, cfg32, remat="none",
                                    last_only=True))
    with jax.default_matmul_precision("highest"):
        ref_logits, _, _ = fwd(params32, jax.tree.map(jnp.asarray, prompts))
    ref_logits = np.asarray(ref_logits[:, -1])
    del params32
    err = rel_l2(logits, ref_logits)
    report("serve", arch=cfg.name, requests=REQUESTS, prompt=PROMPT, gen=GEN,
           tokens_shape=list(out["tokens"].shape), param_bytes=param_bytes,
           serve_s=t_serve, prefill_s=out["prefill_s"],
           decode_s=out["decode_s"],
           ref_s=time.perf_counter() - t0,
           logits_rel_l2_vs_f32=err, limit=LOGITS_REL_L2)
    check(err <= LOGITS_REL_L2,
          f"prefill logits rel L2 {err} vs float32 over {LOGITS_REL_L2}")


def phase_train(cfg) -> None:
    t0 = time.perf_counter()
    out = train_loop(cfg, steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                     workers=2, seed=SEED, log_every=0)
    wall = time.perf_counter() - t0
    losses = out["losses"]
    stats = jax.devices()[0].memory_stats() or {}
    report("train", arch=cfg.name, steps=len(losses), batch=TRAIN_BATCH,
           seq=TRAIN_SEQ, losses=losses, wall_s=wall,
           peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    check(len(losses) == TRAIN_STEPS, f"{len(losses)} train steps ran")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(abs(losses[0] - math.log(cfg.vocab_size)) < INIT_LOSS_SLACK,
          f"first loss {losses[0]} far from ln(vocab)")


def on_eighths(rng, shape) -> np.ndarray:
    """Normal samples rounded to multiples of 1/8: exact in bf16 and f32,
    and so are their squared distances, so kernel and reference must agree
    exactly, ties and their index order included."""
    return (np.round(rng.standard_normal(shape) * 8) / 8).astype(np.float32)


def compile_kernel(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "no Mosaic kernel in the compiled program")
    return compiled, time.perf_counter() - t0


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def phase_knn() -> None:
    m, n, d, k = 1024, 8192, 64, 5
    rng = np.random.default_rng(SEED)
    test_x, train_x = on_eighths(rng, (m, d)), on_eighths(rng, (n, d))
    train_y = rng.integers(0, 10, n).astype(np.int32)
    args = tuple(jnp.asarray(a) for a in (test_x, train_x, train_y))
    kernel, t_compile = compile_kernel(
        functools.partial(ops.knn_topk_op, k=k), *args)
    (dist, lab), t_run = timed(kernel, *args)
    with jax.default_matmul_precision("highest"):
        rdist, rlab = jax.jit(functools.partial(ref.knn_topk_ref, k=k))(*args)
    dist, lab = np.asarray(dist), np.asarray(lab)
    max_err = float(np.max(np.abs(dist - np.asarray(rdist))))
    report("knn", test=m, train=n, d=d, k=k, in_bytes=nbytes(args),
           compile_s=t_compile, run_s=t_run, max_dist_err=max_err,
           label_mismatches=int(np.sum(lab != np.asarray(rlab))))
    check(max_err == 0.0, f"knn distances off by {max_err}")
    np.testing.assert_array_equal(lab, np.asarray(rlab))


def phase_kmeans() -> None:
    n, d, k = 65536, 10, 4
    rng = np.random.default_rng(SEED + 1)
    args = (jnp.asarray(on_eighths(rng, (n, d))),
            jnp.asarray(on_eighths(rng, (k, d))))
    kernel, t_compile = compile_kernel(ops.kmeans_assign_op, *args)
    (sums, counts, sse), t_run = timed(kernel, *args)
    with jax.default_matmul_precision("highest"):
        rsums, rcounts, rsse = jax.jit(ref.kmeans_assign_ref)(*args)
    sums_err = float(np.max(np.abs(np.asarray(sums) - np.asarray(rsums))))
    # the per-block sse partials exceed f32's exact range once summed
    sse_rel = abs(float(sse) - float(rsse)) / abs(float(rsse))
    report("kmeans", points=n, d=d, k=k, in_bytes=nbytes(args),
           compile_s=t_compile, run_s=t_run, max_sum_err=sums_err,
           counts=np.asarray(counts).tolist(), sse_rel_err=sse_rel)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(rcounts))
    check(sums_err == 0.0, f"kmeans sums off by {sums_err}")
    check(sse_rel <= 1e-5, f"kmeans sse relative error {sse_rel}")


def phase_serve_tp(cfg, chips: int) -> None:
    devices = jax.devices()
    check(len(devices) >= chips, f"{len(devices)} devices, {chips} asked")
    runs = {}
    for name, n in (("tp", chips), ("one", 1)):
        mesh = auto_mesh((1, n), ("data", "model"), devices=devices[:n])
        t0 = time.perf_counter()
        out = serve_batch(cfg, batch=REQUESTS, prompt_len=PROMPT,
                          gen_len=GEN, seed=SEED, mesh=mesh)
        runs[name] = (out, check_serve_output(cfg, out),
                      time.perf_counter() - t0)
    err = rel_l2(runs["tp"][1], runs["one"][1])
    agree = float(np.mean(np.asarray(runs["tp"][0]["tokens"])
                          == np.asarray(runs["one"][0]["tokens"])))
    report("serve_tp", arch=cfg.name, mesh={"data": 1, "model": chips},
           requests=REQUESTS, prompt=PROMPT, gen=GEN,
           serve_s_tp=runs["tp"][2], serve_s_one=runs["one"][2],
           logits_rel_l2_tp_vs_one=err, limit=LOGITS_REL_L2,
           token_agreement=agree)
    check(err <= LOGITS_REL_L2,
          f"{chips}-chip prefill logits rel L2 {err} vs one chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {dev.platform!r})")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    report("device", **device, jax=jax.__version__)
    cfg = get_config(ARCH)
    if args.chips == 4:
        phase_serve_tp(cfg, args.chips)
    else:
        phase_serve(cfg)
        phase_train(cfg)
        phase_knn()
        phase_kmeans()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

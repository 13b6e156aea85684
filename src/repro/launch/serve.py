"""Batched serving driver: prefill + greedy decode with KV/recurrent caches.

Request pre-processing (prompt synthesis / tokenization stand-in) and
response post-processing run as RCOMPSs tasks; the prefill/decode steps are
the pjit functions from ``repro.distributed`` — the same split the paper
makes between orchestration (runtime) and compute (BLAS, here the MXU).

CPU-scale usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --requests 8 --prompt-len 32 --gen-len 16
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..core import api
from ..distributed.steps import make_decode_step, make_prefill_step
from ..models.lm import LMConfig, init_params
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh


def make_prompts(cfg: LMConfig, n: int, prompt_len: int, seed: int) -> Dict:
    rng = np.random.default_rng(seed)
    if cfg.input_mode == "embeds":
        return {"embeds": rng.standard_normal(
            (n, prompt_len, cfg.d_model)).astype(np.float32)}
    if cfg.input_mode == "prefix_embeds":
        p = min(cfg.prefix_len, prompt_len // 2)
        return {
            "prefix_embeds": rng.standard_normal((n, p, cfg.d_model)).astype(np.float32),
            "tokens": rng.integers(0, cfg.vocab_size,
                                   (n, prompt_len - p)).astype(np.int32),
        }
    return {"tokens": rng.integers(0, cfg.vocab_size,
                                   (n, prompt_len)).astype(np.int32)}


def serve_batch(cfg: LMConfig, *, batch: int = 4, prompt_len: int = 32,
                gen_len: int = 16, seed: int = 0, mesh=None,
                manage_runtime: bool = True) -> Dict[str, Any]:
    if manage_runtime:
        api.runtime_start(n_workers=2)
    try:
        mesh = mesh or make_local_mesh()
        cache_len = prompt_len + gen_len
        prompt_task = api.task(make_prompts, name="make_prompts")
        prompts_f = prompt_task(cfg, batch, prompt_len, seed)

        params = init_params(cfg, jax.random.PRNGKey(seed))
        prompts = api.wait_on(prompts_f)
        prefill, pin, pout, _ = make_prefill_step(
            cfg, mesh, cache_len=cache_len, sample_batch=prompts)
        prefill_j = jax.jit(prefill, in_shardings=pin, out_shardings=pout)

        t0 = time.perf_counter()
        dev_prompts = jax.tree.map(jnp.asarray, prompts)
        logits, caches = prefill_j(params, dev_prompts)
        prefill_logits = logits[:, -1]
        next_tok = jnp.argmax(prefill_logits, axis=-1).astype(jnp.int32)
        t_prefill = time.perf_counter() - t0

        dec_batch = ({"embeds": jnp.zeros((batch, 1, cfg.d_model))}
                     if cfg.input_mode == "embeds"
                     else {"tokens": next_tok[:, None]})
        decode, din, dout, ddon = make_decode_step(
            cfg, mesh, sample_batch=dec_batch, sample_caches=caches)
        decode_j = jax.jit(decode, in_shardings=din, out_shardings=dout,
                           donate_argnums=ddon)

        generated: List[np.ndarray] = [np.asarray(next_tok)]
        t1 = time.perf_counter()
        pos = prompt_len
        for i in range(gen_len - 1):
            if cfg.input_mode == "embeds":
                step_in = {"embeds": jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(seed), i),
                    (batch, 1, cfg.d_model))}
            else:
                step_in = {"tokens": next_tok[:, None]}
            logits, caches = decode_j(params, step_in, caches,
                                      jnp.asarray(pos, jnp.int32))
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            generated.append(np.asarray(next_tok))
            pos += 1
        t_decode = time.perf_counter() - t1

        post_task = api.task(lambda toks: np.stack(toks, axis=1),
                             name="postprocess")
        out_tokens = api.wait_on(post_task(generated))
        return {
            "tokens": out_tokens,
            "prefill_logits": prefill_logits,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "decode_tokens_per_s": batch * (gen_len - 1) / max(t_decode, 1e-9),
        }
    finally:
        if manage_runtime:
            api.runtime_stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    out = serve_batch(cfg, batch=args.requests, prompt_len=args.prompt_len,
                      gen_len=args.gen_len)
    print(json.dumps({k: (v.shape if hasattr(v, "shape") else v)
                      for k, v in out.items()}, indent=1, default=str))


if __name__ == "__main__":
    main()

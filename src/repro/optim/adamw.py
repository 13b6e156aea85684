"""AdamW with dtype-configurable moments (bf16 moments let the 235B MoE's
optimizer state fit HBM — DESIGN.md §5), cosine LR schedule, global-norm
clipping.  Functional, optax-style interface without the dependency."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    count: jnp.ndarray
    mu: Any
    nu: Any


@dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable  # (grads, state, params, step?) -> (new_params, new_state)


def cosine_schedule(base_lr: float, warmup_steps: int, total_steps: int,
                    min_ratio: float = 0.1) -> Callable:
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / jnp.maximum(1.0, warmup_steps)
        prog = jnp.clip((step - warmup_steps) /
                        jnp.maximum(1.0, total_steps - warmup_steps), 0.0, 1.0)
        cos = base_lr * (min_ratio + (1 - min_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup_steps, warm, cos)
    return lr


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads), norm


def adamw(lr: Callable | float, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.1,
          moment_dtype=jnp.float32, max_grad_norm: Optional[float] = 1.0) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        def zeros(p):
            return jnp.zeros(p.shape, dtype=moment_dtype)
        return AdamWState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
        )

    @jax.named_scope("adamw")
    def update(grads, state: AdamWState, params):
        gnorm = jnp.zeros((), jnp.float32)
        if max_grad_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        count = state.count + 1
        t = count.astype(jnp.float32)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t
        step_lr = lr_fn(count)

        def upd(p, g, mu, nu):
            gf = g.astype(jnp.float32)
            mu_n = b1 * mu.astype(jnp.float32) + (1 - b1) * gf
            nu_n = b2 * nu.astype(jnp.float32) + (1 - b2) * gf * gf
            mhat = mu_n / bc1
            vhat = nu_n / bc2
            delta = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p.astype(jnp.float32)
            new_p = p.astype(jnp.float32) - step_lr * delta
            return (new_p.astype(p.dtype), mu_n.astype(moment_dtype),
                    nu_n.astype(moment_dtype))

        out = jax.tree.map(upd, params, grads, state.mu, state.nu)
        new_params = jax.tree.map(lambda t3: t3[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_mu = jax.tree.map(lambda t3: t3[1], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        new_nu = jax.tree.map(lambda t3: t3[2], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        return new_params, AdamWState(count, new_mu, new_nu), gnorm

    return Optimizer(init=init, update=update)

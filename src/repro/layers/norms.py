"""RMSNorm (fused Pallas twin in kernels/rmsnorm.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm_axes():
    return {"scale": ("embed",)}


@jax.named_scope("rmsnorm")
def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * (1.0 / jnp.sqrt(var + eps))
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


@jax.named_scope("rmsnorm")
def rmsnorm_plain(x, eps: float = 1e-6):
    """Scale-free RMS normalization (qk-norm without learned gain)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * (1.0 / jnp.sqrt(var + eps))).astype(x.dtype)

"""MLP: gated (SwiGLU) or classic two-matrix GELU."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32, gated: bool = True):
    ks = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    p = {
        "w_up": (jax.random.normal(ks[1], (d_model, d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (d_ff, d_model)) * s_out).astype(dtype),
    }
    if gated:
        p["w_gate"] = (jax.random.normal(ks[0], (d_model, d_ff)) * s_in).astype(dtype)
    return p


def mlp_axes(gated: bool = True):
    ax = {
        "w_up": ("embed", "mlp"),
        "w_down": ("mlp", "embed"),
    }
    if gated:
        ax["w_gate"] = ("embed", "mlp")
    return ax


@jax.named_scope("mlp")
def mlp_forward(params, x):
    if "w_gate" in params:
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"])
    return h @ params["w_down"]

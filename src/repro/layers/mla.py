"""Multi-head latent attention (MLA, DeepSeek-V2/V3 and Moonlight), for
training and prefill, with no low-rank query (``q_lora_rank`` null).

Per position, with H heads, query/key width ``nope + rope`` and value width
``v``:

* ``q = x W_q``, split per head into ``q_nope`` (``nope``) and ``q_pe``
  (``rope``);
* ``[c, k_pe] = x W_kv_a``: the latent ``c`` (``kv_lora_rank``) and one
  ``rope``-wide key part that every head shares;
* ``[k_nope, v] = RMSNorm(c) W_kv_b``, split per head;
* RoPE on ``q_pe`` and ``k_pe``; ``q = [q_nope, q_pe]``,
  ``k = [k_nope, k_pe]``; causal softmax attention with scale
  ``(nope + rope)^-1/2``; ``W_o`` maps ``H * v`` back to the model width.

Rotary pairs are the two halves of ``q_pe``/``k_pe`` (``layers.rope``), a
fixed column permutation of the published interleaved pairs: the same
function of random weights.  The scores go through
``layers.attention.attend``, so on one TPU they take the fused splash
kernels at query/key width 192 and value width 128.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .attention import attend
from .norms import init_rmsnorm, rmsnorm
from .rope import apply_rope, rope_angles


def init_mla(key, d_model: int, n_heads: int, kv_lora_rank: int, qk_nope_dim: int,
             qk_rope_dim: int, v_head_dim: int, dtype=jnp.float32):
    """Weights in key order ``wq``, ``wkv_a``, ``wkv_b``, ``wo`` (one split of
    ``key`` in four), normal draws over ``sqrt(fan_in)``; the latent norm's
    gain is ones."""
    ks = jax.random.split(key, 4)
    qk = qk_nope_dim + qk_rope_dim

    def normal(k, shape):
        return (jax.random.normal(k, shape) / math.sqrt(shape[0])).astype(dtype)

    return {
        "wq": normal(ks[0], (d_model, n_heads * qk)),
        "wkv_a": normal(ks[1], (d_model, kv_lora_rank + qk_rope_dim)),
        "kv_norm": init_rmsnorm(kv_lora_rank, dtype),
        "wkv_b": normal(ks[2], (kv_lora_rank, n_heads * (qk_nope_dim + v_head_dim))),
        "wo": normal(ks[3], (n_heads * v_head_dim, d_model)),
    }


def mla_axes():
    return {
        "wq": ("embed", "heads"),
        "wkv_a": ("embed", "kv_lora"),
        "kv_norm": {"scale": ("kv_lora",)},
        "wkv_b": ("kv_lora", "heads"),
        "wo": ("heads", "embed"),
    }


@jax.named_scope("mla")
def mla_forward(params, x, *, n_heads: int, kv_lora_rank: int, qk_nope_dim: int,
                qk_rope_dim: int, v_head_dim: int, rope_theta: float,
                norm_eps: float, impl: str = "auto", chunk: int = 1024,
                unroll: bool = False, mesh=None):
    """x: (B, S, D) -> (B, S, D); causal self-attention over the block."""
    B, S, _ = x.shape
    H, nope, rope = n_heads, qk_nope_dim, qk_rope_dim
    q = (x @ params["wq"]).reshape(B, S, H, nope + rope)
    kv_a = x @ params["wkv_a"]
    c = rmsnorm(params["kv_norm"], kv_a[..., :kv_lora_rank], eps=norm_eps)
    kv = (c @ params["wkv_b"]).reshape(B, S, H, nope + v_head_dim)
    positions = jnp.arange(S, dtype=jnp.int32)
    cos, sin = rope_angles(positions, rope, rope_theta)
    q_pe = apply_rope(q[..., nope:], cos, sin)
    k_pe = apply_rope(kv_a[..., None, kv_lora_rank:], cos, sin)   # one shared head
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))],
                        axis=-1)
    o = attend(q[:, :, :, None], k, kv[..., nope:], positions, positions,
               scale=1.0 / math.sqrt(nope + rope), impl=impl, chunk=chunk,
               unroll=unroll, mesh=mesh)
    return o.astype(x.dtype).reshape(B, S, H * v_head_dim) @ params["wo"]

"""Grouped-query attention with RoPE, optional qk-norm, sliding window, and
KV caches (full or ring-buffer), plus two compute paths:

* ``dense``   — materialized scores; fine for short sequences.
* ``chunked`` — flash-style streaming softmax over KV chunks via
  ``lax.scan`` (O(S·chunk) memory).  This is the pure-JAX twin of the Pallas
  ``flash_attention`` kernel (kernels/flash_attention.py).  The model runs
  this path on every platform: no layer calls the kernel yet.

Cache layout: ``{"k": (B, Sc, K, hd), "v": ..., "pos_map": (Sc,) int32}``.
``pos_map[slot]`` holds the absolute position stored in that slot
(``INVALID_POS`` when empty).  A full cache uses ``slot == position``; a ring
cache (sliding-window attention, ``Sc == window``) uses
``slot == position % Sc`` — this is what keeps RecurrentGemma's 500k-token
decode at O(window) memory.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .norms import rmsnorm
from .rope import apply_rope, rope_angles

NEG_INF = -1e30
INVALID_POS = 1 << 30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qk_norm: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d_model)
    p = {
        "wq": (jax.random.normal(ks[0], (d_model, n_heads * head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, n_kv_heads * head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, n_kv_heads * head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_heads * head_dim, d_model))
               * (1.0 / math.sqrt(n_heads * head_dim))).astype(dtype),
    }
    if qk_norm:
        p["q_norm"] = {"scale": jnp.ones((head_dim,), dtype=dtype)}
        p["k_norm"] = {"scale": jnp.ones((head_dim,), dtype=dtype)}
    return p


def attention_axes(qk_norm: bool = False):
    ax = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if qk_norm:
        ax["q_norm"] = {"scale": ("head_dim",)}
        ax["k_norm"] = {"scale": ("head_dim",)}
    return ax


def _mask(q_pos, kv_pos, window: Optional[int]):
    """(Sq, Skv) boolean validity: causal + optional sliding window.
    Invalid cache slots carry ``INVALID_POS`` and fail the causal test."""
    m = kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= (q_pos[:, None] - kv_pos[None, :]) < window
    return m


def _dense_attn(q, k, v, q_pos, kv_pos, window):
    """q: (B,Sq,K,G,hd); k,v: (B,Skv,K,hd) -> (B,Sq,K,G,hd) fp32."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = _mask(q_pos, kv_pos, window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o


def _chunked_attn(q, k, v, q_pos, kv_pos, window, chunk: int = 1024,
                  unroll: bool = False, scores_dtype=jnp.float32):
    """Streaming (online-softmax) attention over KV chunks.

    ``scores_dtype=bfloat16`` stores the (B,K,G,Sq,chunk) score/probability
    tensors in bf16 (running max/denominator stay fp32) — the flash-kernel
    convention; halves the dominant HBM traffic of the jnp twin."""
    B, Sq, K, G, hd = q.shape
    Skv = k.shape[1]
    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=INVALID_POS)
    kc = k.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(n_chunks, chunk)
    scale = 1.0 / math.sqrt(hd)
    sd = scores_dtype
    qf = q.astype(sd)

    def step(carry, inp):
        m, lse, acc = carry
        k_i, v_i, pos_i = inp
        s = (jnp.einsum("bqkgh,bskh->bkgqs", qf, k_i.astype(sd)) * scale
             ).astype(sd)
        msk = _mask(q_pos, pos_i, window)
        s = jnp.where(msk[None, None, None], s, jnp.asarray(NEG_INF, sd))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1).astype(jnp.float32))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s.astype(jnp.float32) - m_new[..., None]).astype(sd)
        l_new = lse * alpha + jnp.sum(p.astype(jnp.float32), axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p, v_i.astype(sd)).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, Sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), dtype=jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, hd), dtype=jnp.float32)
    (m, lse, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc),
                                  unroll=n_chunks if unroll else 1)
    o = acc / jnp.maximum(lse, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4)  # (B,Sq,K,G,hd)


def init_kv_cache(batch: int, cache_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, cache_len, n_kv_heads, head_dim), dtype=dtype),
        "v": jnp.zeros((batch, cache_len, n_kv_heads, head_dim), dtype=dtype),
        "pos_map": jnp.full((cache_len,), INVALID_POS, dtype=jnp.int32),
    }


def _build_cache(k, v, positions, cache_len: int, dtype):
    """Construct a cache from freshly computed prefill K/V (no scatter:
    deterministic gather of the slot-owning positions)."""
    B, S, K, hd = k.shape
    if cache_len >= S:
        pad = cache_len - S
        ck = jnp.pad(k.astype(dtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v.astype(dtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos_map = jnp.concatenate([
            positions.astype(jnp.int32),
            jnp.full((pad,), INVALID_POS, dtype=jnp.int32),
        ])
        return {"k": ck, "v": cv, "pos_map": pos_map}
    # ring: slot s holds the latest position p < S with p % cache_len == s
    slots = jnp.arange(cache_len, dtype=jnp.int32)
    owner = (S - 1) - ((S - 1 - slots) % cache_len)  # index into current block
    ck = jnp.take(k, owner, axis=1).astype(dtype)
    cv = jnp.take(v, owner, axis=1).astype(dtype)
    pos_map = jnp.take(positions, owner).astype(jnp.int32)
    return {"k": ck, "v": cv, "pos_map": pos_map}


def attn_forward(
    params,
    x,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float = 10_000.0,
    qk_norm: bool = False,
    window: Optional[int] = None,
    pos_offset=0,
    cache: Optional[dict] = None,
    make_cache_len: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
    impl: str = "auto",
    chunk: int = 1024,
    unroll: bool = False,
    scores_dtype=jnp.float32,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Returns (output, new_cache).

    * training: ``cache=None, make_cache_len=None`` — block-local attention.
    * prefill:  ``make_cache_len=Sc`` — same attention, plus a cache built
      from the computed K/V (ring-truncated if ``Sc < S``).
    * decode:   ``cache=...`` — new K/V written at
      ``slot = position % Sc``; attention over the whole cache.
    """
    B, S, D = x.shape
    K, G = n_kv_heads, n_heads // n_kv_heads
    q = (x @ params["wq"]).reshape(B, S, n_heads, head_dim)
    k = (x @ params["wk"]).reshape(B, S, K, head_dim)
    v = (x @ params["wv"]).reshape(B, S, K, head_dim)
    if qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    positions = pos_offset + jnp.arange(S, dtype=jnp.int32)
    cos, sin = rope_angles(positions, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    new_cache = None
    if cache is not None:
        Sc = cache["k"].shape[1]
        slots = positions % Sc
        ck = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
        pos_map = cache["pos_map"].at[slots].set(positions)
        new_cache = {"k": ck, "v": cv, "pos_map": pos_map}
        k_all, v_all, kv_pos = ck, cv, pos_map
    else:
        k_all, v_all, kv_pos = k, v, positions
        if make_cache_len is not None:
            new_cache = _build_cache(k, v, positions, make_cache_len, cache_dtype)

    qg = q.reshape(B, S, K, G, head_dim)
    use_chunked = impl == "chunked" or (impl == "auto" and k_all.shape[1] > 2048)
    if use_chunked:
        o = _chunked_attn(qg, k_all, v_all, positions, kv_pos, window,
                          chunk=chunk, unroll=unroll, scores_dtype=scores_dtype)
    else:
        o = _dense_attn(qg, k_all, v_all, positions, kv_pos, window)
    o = o.astype(x.dtype).reshape(B, S, n_heads * head_dim)
    return o @ params["wo"], new_cache

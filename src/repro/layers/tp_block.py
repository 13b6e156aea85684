"""Explicit tensor-parallel transformer sub-blocks via shard_map.

§Perf hillclimb lever (EXPERIMENTS.md): the GSPMD baseline mis-shards the
5-D GQA score tensors (XLA's SPMD partitioner logs "involuntary full
rematerialization" and replicates them over the ``model`` axis).  This
module pins the Megatron-style layout explicitly:

* q/o projections column/row-sharded over heads (``model`` axis),
* for MQA/small-K archs the K/V projections are *replicated* (K·hd is tiny;
  recomputing K/V per shard costs nothing and removes all resharding),
* for K % tp == 0 the K/V heads shard alongside the q-head groups,
* one ``psum`` per sub-layer (attention out-proj, MLP down-proj) — exactly
  Megatron's two all-reduces per block, nothing else.

Weights arrive FSDP-sharded over ``data`` on the d_model dim; the shard_map
boundary's resharding is the standard per-layer FSDP all-gather.

Training path only (no KV cache) — prefill/decode stay on the GSPMD path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .attention import _chunked_attn, _dense_attn
from .norms import rmsnorm
from .rope import apply_rope, rope_angles


def _attn_param_specs(qk_norm: bool, shard_kv: bool):
    kv = P(None, "model") if shard_kv else P(None, None)
    sp = {
        "wq": P(None, "model"),
        "wk": kv,
        "wv": kv,
        "wo": P("model", None),
    }
    if qk_norm:
        sp["q_norm"] = {"scale": P(None)}
        sp["k_norm"] = {"scale": P(None)}
    return sp


def _mlp_param_specs(gated: bool):
    sp = {
        "w_up": P(None, "model"),
        "w_down": P("model", None),
    }
    if gated:
        sp["w_gate"] = P(None, "model")
    return sp


def tp_attn_sublayer(p_ln, p_attn, x, *, cfg, mesh, window: Optional[int],
                     pos_offset, data_axes: Tuple[str, ...]):
    """x + Wo·Attn(norm(x)) with explicit TP.  x: (B, S, D) sharded over
    data axes, replicated over model."""
    tp = mesh.shape["model"]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    shard_kv = K % tp == 0 and K >= tp
    H_l = H // tp
    K_l = K // tp if shard_kv else K
    sd = jnp.bfloat16 if cfg.attn_scores_bf16 else jnp.float32

    def local(x_l, ln_s, pa):
        B, S, D = x_l.shape
        h = rmsnorm(ln_s, x_l)
        q = (h @ pa["wq"]).reshape(B, S, H_l, hd)
        k = (h @ pa["wk"]).reshape(B, S, K_l, hd)
        v = (h @ pa["wv"]).reshape(B, S, K_l, hd)
        if cfg.qk_norm:
            q = rmsnorm(pa["q_norm"], q)
            k = rmsnorm(pa["k_norm"], k)
        positions = pos_offset + jnp.arange(S, dtype=jnp.int32)
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if shard_kv:
            # kv heads shard alongside their q-head groups (layouts align)
            qg = q.reshape(B, S, K_l, H_l // K_l, hd)
        else:
            # replicated K/V: local q heads are a contiguous *global* slice;
            # gather each one's kv group (global_head // G)
            G = H // K
            gidx = (jax.lax.axis_index("model") * H_l
                    + jnp.arange(H_l)) // G
            k = jnp.take(k, gidx, axis=2)
            v = jnp.take(v, gidx, axis=2)
            qg = q.reshape(B, S, H_l, 1, hd)
        if S > 2048 or cfg.attn_impl == "chunked":
            o = _chunked_attn(qg, k, v, positions, positions, window,
                              chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
                              scores_dtype=sd)
        else:
            o = _dense_attn(qg, k, v, positions, positions, window)
        o = o.astype(x_l.dtype).reshape(B, S, H_l * hd)
        out = o @ pa["wo"]                       # partial over model
        out = jax.lax.psum(out, "model")
        return x_l + out

    xspec = P(data_axes, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(xspec, {"scale": P(None)},
                  _attn_param_specs(cfg.qk_norm, shard_kv)),
        out_specs=xspec, check_vma=False,
    )(x, p_ln, p_attn)


def tp_rglru_sublayer(p_ln, p_rec, x, *, cfg, mesh,
                      data_axes: Tuple[str, ...]):
    """x + RG-LRU-block(norm(x)) with explicit TP: the rnn width R is
    column-sharded; every recurrence/gate op is elementwise over R, so the
    only communication is the out-projection psum — one all-reduce per
    block, vs. the GSPMD baseline's per-op reshards of (B,S,R) tensors."""
    from .rglru import _causal_conv, rglru_scan

    def local(x_l, ln_s, pr):
        h = rmsnorm(ln_s, x_l)
        u = h @ pr["w_x"]                       # (B, S, R_l)
        gate = jax.nn.gelu(h @ pr["w_gate"])
        u = _causal_conv(u, pr["conv_w"], pr["conv_b"])
        y, _ = rglru_scan(pr, u)                # per-channel: fully local
        out = (y * gate) @ pr["w_out"]          # partial over model
        out = jax.lax.psum(out, "model")
        return x_l + out

    rspec = {
        "w_x": P(None, "model"), "w_gate": P(None, "model"),
        "conv_w": P(None, "model"), "conv_b": P("model"),
        "lam": P("model"), "w_r": P("model"), "b_r": P("model"),
        "w_i": P("model"), "b_i": P("model"),
        "w_out": P("model", None),
    }
    xspec = P(data_axes, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(xspec, {"scale": P(None)}, rspec),
        out_specs=xspec, check_vma=False,
    )(x, p_ln, p_rec)


def tp_mlp_sublayer(p_ln, p_mlp, x, *, cfg, mesh,
                    data_axes: Tuple[str, ...]):
    """x + W2·act(W1·norm(x)) with explicit TP."""
    gated = "w_gate" in p_mlp

    def local(x_l, ln_s, pm):
        h = rmsnorm(ln_s, x_l)
        if gated:
            a = jax.nn.silu(h @ pm["w_gate"]) * (h @ pm["w_up"])
        else:
            a = jax.nn.gelu(h @ pm["w_up"])
        out = a @ pm["w_down"]                   # partial over model
        out = jax.lax.psum(out, "model")
        return x_l + out

    xspec = P(data_axes, None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(xspec, {"scale": P(None)}, _mlp_param_specs(gated)),
        out_specs=xspec, check_vma=False,
    )(x, p_ln, p_mlp)

"""Mixture-of-Experts layer with TPU-native expert parallelism.

Design (DESIGN.md §6): activations enter a block replicated over the
``model`` mesh axis and sharded over the data axes.  The routed-expert
computation runs inside ``shard_map``:

* expert weights are sharded **experts over `model`** (EP) and
  **d_ff over `data`** (FSDP storage); the local function all-gathers the
  d_ff shards (one layer at a time — the same per-layer gather FSDP pays),
* each device routes its local tokens, keeps the assignments that fall into
  its expert slice, and packs them into a static ``(E_local, C, D)`` buffer
  via an argsort over expert ids (sort-based capacity dispatch — no GShard
  one-hot blow-up),
* expert FFNs run as dense einsums over the packed buffer (MXU-friendly),
* results scatter back to token order and a ``psum`` over ``model`` combines
  the contributions of experts living on other shards (each token's top-k
  experts are spread across the EP shards).

Tokens overflowing an expert's capacity ``C = ceil(N·k·cf / E)`` are dropped
(pass through the residual only) — standard capacity-based semantics.

``moe_apply_local`` is the same algorithm without collectives (model-axis
size 1); it doubles as the test oracle target and the single-device path.
``moe_reference`` is the exact dense loop used to validate the dispatch.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def init_moe(key, d_model: int, d_ff_expert: int, n_experts: int,
             dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff_expert)
    return {
        "w_router": (jax.random.normal(ks[0], (d_model, n_experts)) * s_in
                     ).astype(jnp.float32),  # router kept fp32 (standard)
        "w_gate": (jax.random.normal(ks[1], (n_experts, d_model, d_ff_expert)) * s_in
                   ).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (n_experts, d_model, d_ff_expert)) * s_in
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (n_experts, d_ff_expert, d_model)) * s_out
                   ).astype(dtype),
    }


def moe_axes():
    return {
        "w_router": ("embed", "expert_router"),
        "w_gate": ("expert", "embed", "expert_mlp"),
        "w_up": ("expert", "embed", "expert_mlp"),
        "w_down": ("expert", "expert_mlp", "embed"),
    }


def _route(xf, w_router, top_k: int, renormalize: bool = True):
    """xf: (N, D) -> (weights (N,k) fp32, ids (N,k) int32, aux_loss scalar)."""
    logits = xf.astype(jnp.float32) @ w_router
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, top_k)
    if renormalize:
        topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * mean_e(frac_tokens_e * mean_prob_e)
    E = w_router.shape[1]
    me = jnp.mean(probs, axis=0)
    onehot = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(onehot, axis=0)
    aux = E * jnp.sum(me * ce)
    return topw, topi.astype(jnp.int32), aux


def _dispatch_compute(xf, topw, topi, w_gate, w_up, w_down,
                      expert_offset, n_experts_total: int, capacity: int):
    """Sort-based capacity dispatch for the local expert slice.

    xf: (N, D); topw/topi: (N, k); w_*: (E_l, D, F)/(E_l, F, D).
    Returns (N, D) contribution of the local experts (zeros elsewhere).
    """
    N, D = xf.shape
    k = topi.shape[1]
    E_l = w_gate.shape[0]
    Nk = N * k

    flat_e = topi.reshape(Nk)
    flat_w = topw.reshape(Nk)
    flat_tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    local = (flat_e >= expert_offset) & (flat_e < expert_offset + E_l)
    le = jnp.where(local, flat_e - expert_offset, E_l)  # E_l == overflow bucket

    order = jnp.argsort(le, stable=True)
    s_le = le[order]
    s_tok = flat_tok[order]
    s_w = flat_w[order]
    # position within the expert's segment
    first = jnp.searchsorted(s_le, s_le, side="left")
    pos = jnp.arange(Nk, dtype=jnp.int32) - first.astype(jnp.int32)
    valid = (s_le < E_l) & (pos < capacity)

    # pack into (E_l + 1, C, D); invalid slots land in the overflow row
    be = jnp.where(valid, s_le, E_l).astype(jnp.int32)
    bp = jnp.where(valid, pos, 0).astype(jnp.int32)
    buf = jnp.zeros((E_l + 1, capacity, D), dtype=xf.dtype)
    buf = buf.at[be, bp].set(jnp.where(valid[:, None], xf[s_tok], 0.0))
    buf = buf[:E_l]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(xf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_up.astype(xf.dtype))
    y = jnp.einsum("ecf,efd->ecd", h, w_down.astype(xf.dtype))

    # scatter back to token order, weighted
    y_rows = y[jnp.minimum(s_le, E_l - 1), bp]  # (Nk, D); garbage where invalid
    contrib = jnp.where(valid, s_w, 0.0)[:, None].astype(xf.dtype) * y_rows
    out = jnp.zeros((N, D), dtype=xf.dtype).at[s_tok].add(contrib)
    return out


def moe_capacity(n_tokens: int, top_k: int, n_experts: int,
                 capacity_factor: float, min_capacity: int = 4) -> int:
    return max(min_capacity, int(math.ceil(n_tokens * top_k * capacity_factor / n_experts)))


def moe_apply_local(params, x, *, top_k: int, capacity_factor: float = 1.25,
                    min_capacity: int = 4, renormalize: bool = True,
                    expert_offset=0, n_experts_total: Optional[int] = None,
                    capacity: Optional[int] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-shard routed-MoE: x (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    E_total = n_experts_total or params["w_gate"].shape[0]
    topw, topi, aux = _route(xf, params["w_router"], top_k, renormalize)
    C = capacity if capacity is not None else moe_capacity(
        B * S, top_k, E_total, capacity_factor, min_capacity)
    out = _dispatch_compute(xf, topw.astype(xf.dtype), topi,
                            params["w_gate"], params["w_up"], params["w_down"],
                            expert_offset, E_total, C)
    return out.reshape(B, S, D), aux


def moe_apply_sharded(params, x, *, mesh, top_k: int,
                      data_axes=("data",), model_axis: str = "model",
                      ff_shard_axis: Optional[str] = "data",
                      capacity_factor: float = 1.25, min_capacity: int = 4,
                      renormalize: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE over ``mesh`` (see module docstring).

    x is sharded (batch over data_axes, replicated over model_axis); expert
    weights are sharded experts-over-model and d_ff-over-``ff_shard_axis``.
    """
    n_experts = params["w_gate"].shape[0]
    ep = mesh.shape[model_axis]
    if n_experts % ep != 0:
        raise ValueError(f"{n_experts} experts not divisible by EP={ep}")
    E_l = n_experts // ep
    batch_spec = P(tuple(data_axes), None, None)
    ff_axis = ff_shard_axis if ff_shard_axis in mesh.axis_names else None
    gate_spec = P(model_axis, None, ff_axis)
    down_spec = P(model_axis, ff_axis, None)

    # static capacity from local token count
    dp = 1
    for a in data_axes:
        dp *= mesh.shape[a]
    B, S, D = x.shape
    n_local = (B // dp) * S
    C = moe_capacity(n_local, top_k, n_experts, capacity_factor, min_capacity)

    def local_fn(x_l, w_router, w_gate, w_up, w_down):
        if ff_axis is not None:
            w_gate = jax.lax.all_gather(w_gate, ff_axis, axis=2, tiled=True)
            w_up = jax.lax.all_gather(w_up, ff_axis, axis=2, tiled=True)
            w_down = jax.lax.all_gather(w_down, ff_axis, axis=1, tiled=True)
        Bl, Sl, Dl = x_l.shape
        xf = x_l.reshape(Bl * Sl, Dl)
        topw, topi, aux = _route(xf, w_router, top_k, renormalize)
        off = jax.lax.axis_index(model_axis) * E_l
        out = _dispatch_compute(xf, topw.astype(xf.dtype), topi,
                                w_gate, w_up, w_down, off, n_experts, C)
        out = jax.lax.psum(out, model_axis)
        aux = jax.lax.pmean(aux, tuple(data_axes))
        return out.reshape(Bl, Sl, Dl), aux

    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(batch_spec, P(None, None), gate_spec, gate_spec, down_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(x, params["w_router"], params["w_gate"], params["w_up"], params["w_down"])
    return out, aux


def moe_reference(params, x, *, top_k: int, renormalize: bool = True):
    """Exact dense oracle: every expert computed for every token, masked by
    the router's top-k choice.  O(E) cost — tests only."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    topw, topi, aux = _route(xf, params["w_router"], top_k, renormalize)
    E = params["w_gate"].shape[0]
    out = jnp.zeros_like(xf)
    for e in range(E):
        w_e = jnp.sum(jnp.where(topi == e, topw, 0.0), axis=1)  # (N,)
        h = jax.nn.silu(xf @ params["w_gate"][e]) * (xf @ params["w_up"][e])
        y = h @ params["w_down"][e]
        out = out + w_e[:, None].astype(xf.dtype) * y
    return out.reshape(B, S, D), aux

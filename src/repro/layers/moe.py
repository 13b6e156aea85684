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

``moe_apply_local`` is the single-device path, and drops nothing: the
N·k assignments are sorted by expert, the held experts' rows run through
grouped matmuls (``kernels.ops.grouped_matmul``) and the weighted rows are
added back in token order, so every assignment is computed under any
imbalance.  The rows are moved by kernels whose work follows how many rows
the held experts take, a count known at run time (``kernels.moe_rows``: a
row gather in, a per-token weighted sum out, each the other's backward),
not by gathers over the static bound of N·min(k, E_l) rows; each traced
call counts ``moe.impl``.  A layer that holds the experts
``expert_offset`` to ``expert_offset + E_l`` of the router's ``E`` routes
over all ``E`` and returns the part of the result its own experts give
(expert parallelism's share, without the exchange).

The router is ``softmax`` (top-k of the probabilities, with the Switch
load-balance term) or ``sigmoid`` (DeepSeek-V3's ``noaux_tc``: top-k of
the scores plus a correction bias that is state outside the gradient, the
weights the scores without it; no auxiliary term), optionally renormalised
to sum to one, then multiplied by ``routed_scale``.

``moe_reference`` is the exact dense loop used to validate both paths.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import tracing
from ..kernels.ops import combine_rows_op, gather_rows_op, grouped_matmul


def init_moe(key, d_model: int, d_ff_expert: int, n_experts: int,
             dtype=jnp.float32, n_held: Optional[int] = None,
             scoring: str = "softmax"):
    """Router over ``n_experts``; expert weights for the first ``n_held``
    of them (all where not given), drawn as for all and sliced, so an
    expert's weights do not depend on how many are held. The sigmoid
    router adds its correction bias, zero."""
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff_expert)
    held = n_experts if n_held is None else n_held
    p = {
        "w_router": (jax.random.normal(ks[0], (d_model, n_experts)) * s_in
                     ).astype(jnp.float32),  # router kept fp32 (standard)
        "w_gate": (jax.random.normal(ks[1], (n_experts, d_model, d_ff_expert))[:held]
                   * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (n_experts, d_model, d_ff_expert))[:held]
                 * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (n_experts, d_ff_expert, d_model))[:held]
                   * s_out).astype(dtype),
    }
    if scoring == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((n_experts,), jnp.float32)
    return p


def moe_axes(scoring: str = "softmax"):
    ax = {
        "w_router": ("embed", "expert_router"),
        "w_gate": ("expert", "embed", "expert_mlp"),
        "w_up": ("expert", "embed", "expert_mlp"),
        "w_down": ("expert", "expert_mlp", "embed"),
    }
    if scoring == "sigmoid":
        ax["e_score_correction_bias"] = ("expert_router",)
    return ax


def _route(xf, params, top_k: int, scoring: str = "softmax",
           renormalize: bool = True, routed_scale: float = 1.0):
    """xf: (N, D) -> (weights (N,k) fp32, ids (N,k) int32, aux_loss scalar)."""
    logits = jnp.dot(xf.astype(jnp.float32), params["w_router"],
                     precision=jax.lax.Precision.HIGHEST)
    E = logits.shape[1]
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = jax.lax.stop_gradient(params["e_score_correction_bias"])
        _, topi = jax.lax.top_k(scores + bias, top_k)
        topw = jnp.take_along_axis(scores, topi, axis=-1)
        aux = jnp.zeros((), jnp.float32)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, top_k)
        # Switch-style load-balance aux: E * mean_e(frac_tokens_e * mean_prob_e)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
        aux = E * jnp.sum(me * ce)
    if renormalize:
        topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)
    return topw * routed_scale, topi.astype(jnp.int32), aux


def _dispatch_compute(xf, topw, topi, w_gate, w_up, w_down,
                      expert_offset, n_experts_total: int, capacity: int):
    """Sort-based capacity dispatch for the local expert slice (the sharded
    path).

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


@jax.custom_vjp
def _dispatch(xf, src, pos, count):
    """Token rows in assignment order: row r is ``xf[src[r]]`` for r below
    ``count``, the rows the held experts take (``pos`` (N, k): each
    assignment's row). The backward sums each token's rows' gradients."""
    return gather_rows_op(xf, src, count)


def _dispatch_fwd(xf, src, pos, count):
    return _dispatch(xf, src, pos, count), (pos, count)


def _dispatch_bwd(res, g):
    pos, count = res
    return combine_rows_op(g, pos, 1.0, count), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, w, order, pos, count):
    """Each token's expert rows, weighted and summed: y (M, D) in
    assignment order (its first ``count`` rows), w (N, k) the routing
    weights, ``pos`` (N, k) each assignment's row (``order``, the sorted
    assignments) -> (N, D), summed in float32 and cast to y's dtype. The
    backward gathers: a row's gradient is its token's, times its weight."""
    return combine_rows_op(y, pos, w, count)


def _combine_fwd(y, w, order, pos, count):
    return _combine(y, w, order, pos, count), (y, w, order, pos, count)


def _combine_bwd(res, g):
    y, w, order, pos, count = res
    k, m = w.shape[1], y.shape[0]
    src = order[:m]
    dy, dots = gather_rows_op(g, src // k, count, scale=w.reshape(-1)[src], dot_with=y)
    dw = jnp.where(pos < count, dots[jnp.minimum(pos, m - 1)], 0.0)
    return dy, dw, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _sort_assignments(topi, expert_offset, n_held: int):
    """The N·k assignments of topi (N, k), sorted by held expert and the
    others last: (order (N·k,) the sorted assignments, pos (N, k) each
    assignment's place in that order, sizes (n_held,) the rows of each
    held expert)."""
    N, k = topi.shape
    flat_e = topi.reshape(N * k)
    local = (flat_e >= expert_offset) & (flat_e < expert_offset + n_held)
    le = jnp.where(local, flat_e - expert_offset, n_held)
    order = jnp.argsort(le, stable=True).astype(jnp.int32)
    pos = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32), unique_indices=True).reshape(N, k)
    sizes = jnp.bincount(le, length=n_held + 1)[:n_held].astype(jnp.int32)
    return order, pos, sizes


def _grouped_experts(xf, topw, topi, w_gate, w_up, w_down, expert_offset):
    """Every assignment to a held expert, computed: no capacity, no drop.

    The N·k assignments are sorted by held expert, the others last; the
    first ``count`` sorted rows (at most ``M = N·min(k, E_l)``, one token
    being routed to each expert at most once) are gathered, go through the
    three grouped matmuls and are summed back per token. The row kernels
    move only those ``count`` rows; the rows past it in the (M, D) buffers
    are never read (the grouped matmuls mask by group).

    Returns ((N, D) in ``xf``'s dtype, rows (2,) int32: the rows computed
    here and the largest held expert's rows)."""
    N, k = topi.shape
    E_l = w_gate.shape[0]
    order, pos, sizes = _sort_assignments(topi, expert_offset, E_l)
    count = jnp.sum(sizes)
    rows = _dispatch(xf, order[:N * min(k, E_l)] // k, pos, count)
    h = (jax.nn.silu(grouped_matmul(rows, w_gate.astype(xf.dtype), sizes))
         * grouped_matmul(rows, w_up.astype(xf.dtype), sizes))
    y = grouped_matmul(h, w_down.astype(xf.dtype), sizes)
    out = _combine(y, topw, order, pos, count)
    return out, jnp.stack([count, jnp.max(sizes)])


def moe_apply_local(params, x, *, top_k: int, scoring: str = "softmax",
                    renormalize: bool = True, routed_scale: float = 1.0,
                    expert_offset=0):
    """Single-device routed MoE, drop-free: x (B, S, D) -> (out, aux_loss,
    rows), ``rows`` as :func:`_grouped_experts` gives it. The router's
    width is the number of experts routed over; ``w_*`` hold the experts
    from ``expert_offset`` on."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    tracing.count("moe.impl", 1, impl="rows")
    with jax.named_scope("moe/route"):
        topw, topi, aux = _route(xf, params, top_k, scoring, renormalize, routed_scale)
    with jax.named_scope("moe/experts"):
        out, rows = _grouped_experts(xf, topw, topi, params["w_gate"], params["w_up"],
                                     params["w_down"], expert_offset)
    return out.reshape(B, S, D), aux, rows


def moe_apply_sharded(params, x, *, mesh, top_k: int,
                      data_axes=("data",), model_axis: str = "model",
                      ff_shard_axis: Optional[str] = "data",
                      capacity_factor: float = 1.25, min_capacity: int = 4,
                      scoring: str = "softmax", renormalize: bool = True,
                      routed_scale: float = 1.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
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

    router = {k: params[k] for k in ("w_router", "e_score_correction_bias") if k in params}

    def local_fn(x_l, router, w_gate, w_up, w_down):
        if ff_axis is not None:
            w_gate = jax.lax.all_gather(w_gate, ff_axis, axis=2, tiled=True)
            w_up = jax.lax.all_gather(w_up, ff_axis, axis=2, tiled=True)
            w_down = jax.lax.all_gather(w_down, ff_axis, axis=1, tiled=True)
        Bl, Sl, Dl = x_l.shape
        xf = x_l.reshape(Bl * Sl, Dl)
        topw, topi, aux = _route(xf, router, top_k, scoring, renormalize, routed_scale)
        off = jax.lax.axis_index(model_axis) * E_l
        out = _dispatch_compute(xf, topw.astype(xf.dtype), topi,
                                w_gate, w_up, w_down, off, n_experts, C)
        out = jax.lax.psum(out, model_axis)
        aux = jax.lax.pmean(aux, tuple(data_axes))
        return out.reshape(Bl, Sl, Dl), aux

    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(batch_spec, jax.tree.map(lambda _: P(), router), gate_spec,
                  gate_spec, down_spec),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(x, router, params["w_gate"], params["w_up"], params["w_down"])
    return out, aux


def moe_reference(params, x, *, top_k: int, scoring: str = "softmax",
                  renormalize: bool = True, routed_scale: float = 1.0,
                  expert_offset: int = 0):
    """Exact dense oracle: every held expert computed for every token,
    masked by the router's top-k choice.  O(E) cost — tests only."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    topw, topi, aux = _route(xf, params, top_k, scoring, renormalize, routed_scale)
    out = jnp.zeros(xf.shape, jnp.float32)
    for e in range(params["w_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(topi == expert_offset + e, topw, 0.0), axis=1)  # (N,)
        h = jax.nn.silu(xf @ params["w_gate"][e]) * (xf @ params["w_up"][e])
        out = out + w_e[:, None] * (h @ params["w_down"][e]).astype(jnp.float32)
    return out.astype(xf.dtype).reshape(B, S, D), aux

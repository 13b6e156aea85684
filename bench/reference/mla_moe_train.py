"""Plain reference for the first training steps of a DeepSeek-V3-style LM
on one chip's share of its experts (Moonlight-16B-A3B cut to size).

Written from the published description: pre-norm decoder layers of
multi-head latent attention (no low-rank query: ``q = x W_q`` split into
"nope" and "rope" parts; ``[c, k_pe] = x W_kv_a``;
``[k_nope, v] = RMSNorm(c) W_kv_b``; RoPE on ``q_pe`` and on the one
``k_pe`` every head shares; scale ``(nope + rope)^-1/2``), a leading dense
SwiGLU layer, then MoE layers: a float32 router ``sigmoid(x W_r)`` over all
experts, the top-k chosen on the scores plus the correction bias and
weighted by the scores without it, renormalised to sum to one and scaled by
``routed_scaling_factor``; the held experts' SwiGLU outputs, so weighted,
plus the shared experts' SwiGLU for every token. RMSNorm, an untied head,
masked next-token cross entropy, AdamW with global-norm clipping. It
imports nothing of the program under test; weights and batches are drawn
from the seed by the same public recipes as the job draws its own
(``bench.reference.lm_train`` holds the batch recipe and the numerics).

Departures, each the job's own:

* rotary pairs are the two halves of the rope part, a fixed column
  permutation of the published interleaved pairs (the same function of
  random weights);
* the correction bias is zero and not updated (its update speed is not in
  the configuration), and there is no sequence-wise balance loss (its
  weight is not in it);
* AdamW, where Moonlight was trained with Muon;
* the vocabulary and the experts are one chip's share: the logits and the
  loss are over the slice, and the routed output is what the held experts
  give (the router still scores every expert).

Numerics: float32 with ``HIGHEST`` matmul precision, parameters stored in
their configured dtype between steps (bfloat16, the router float32);
``matmul="fp8"`` is the control (``lm_train``). Routed experts are a masked
dense loop over the held experts: every held expert runs on every token,
weighted by the router's weight for it, zero where not chosen.

Memory: one sequence at a time, each layer rematerialised, attention in
blocks of query positions and the head in blocks of positions, so that it
fits on one chip once the job's state is freed.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.lm_train import F32, _dot, _rmsnorm, _rope, learning_rate, synth_batch

ATTN_KEYS = ("ln1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln2")
DENSE_KEYS = ATTN_KEYS + ("w_gate", "w_up", "w_down")
MOE_KEYS = ATTN_KEYS + ("w_router", "e_gate", "e_up", "e_down",
                        "s_gate", "s_up", "s_down")


# ------------------------------------------------------------------ inputs
def init_params(arch: Dict, seed: int) -> Dict:
    """The seeded weights, in the job's documented key order: the seed's
    key split in four (embedding; MoE layers, split per layer; the leading
    dense layers, split per layer; head); a layer's key (for a scanned MoE
    layer the first of a split in one) split in four (attention, MLP or
    routed experts, shared experts, unused). Normal draws: 0.02 (embedding,
    head); over sqrt(fan in) for attention (``wq``, ``wkv_a``, ``wkv_b``,
    ``wo``, one split in four); times 1/sqrt(fan in) for MLPs (gate, up,
    down from keys 0, 1, 2 of a split in three) and experts (router, gate,
    up, down from a split in four, every expert drawn and the held ones
    kept). Norm gains ones; the router float32, the rest bfloat16."""
    D, V, H = arch["d_model"], arch["vocab_size"], arch["n_heads"]
    nope, rope, dv, r = (arch["qk_nope_dim"], arch["qk_rope_dim"], arch["v_head_dim"],
                         arch["kv_lora_rank"])
    E, held, Fe = arch["n_experts"], arch["n_experts_held"], arch["d_ff_expert"]
    dt = jnp.dtype(arch["param_dtype"])

    def over_fan_in(key, shape):
        return (jax.random.normal(key, shape) / math.sqrt(shape[0])).astype(dt)

    def times_inv_fan_in(key, shape, fan_in):
        return jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))

    def attention(key):
        ka = jax.random.split(key, 4)
        return {"ln1": jnp.ones((D,), dt), "ln2": jnp.ones((D,), dt),
                "wq": over_fan_in(ka[0], (D, H * (nope + rope))),
                "wkv_a": over_fan_in(ka[1], (D, r + rope)),
                "kv_norm": jnp.ones((r,), dt),
                "wkv_b": over_fan_in(ka[2], (r, H * (nope + dv))),
                "wo": over_fan_in(ka[3], (H * dv, D))}

    def mlp(key, F):
        km = jax.random.split(key, 3)
        return (times_inv_fan_in(km[0], (D, F), D).astype(dt),
                times_inv_fan_in(km[1], (D, F), D).astype(dt),
                times_inv_fan_in(km[2], (F, D), F).astype(dt))

    k_emb, k_scan, k_tail, k_head = jax.random.split(jax.random.PRNGKey(seed), 4)
    dense = []
    for k_layer in jax.random.split(k_tail, arch["n_dense"]):
        ks = jax.random.split(k_layer, 4)
        lp = attention(ks[0])
        lp["w_gate"], lp["w_up"], lp["w_down"] = mlp(ks[1], arch["d_ff"])
        dense.append(lp)
    moe = []
    for k_layer in jax.random.split(k_scan, arch["n_moe"]):
        ks = jax.random.split(jax.random.split(k_layer, 1)[0], 4)   # one block a period
        lp = attention(ks[0])
        ke = jax.random.split(ks[1], 4)
        lp["w_router"] = times_inv_fan_in(ke[0], (D, E), D)
        lp["e_gate"] = (times_inv_fan_in(ke[1], (E, D, Fe), D)[:held]).astype(dt)
        lp["e_up"] = (times_inv_fan_in(ke[2], (E, D, Fe), D)[:held]).astype(dt)
        lp["e_down"] = (times_inv_fan_in(ke[3], (E, Fe, D), Fe)[:held]).astype(dt)
        lp["s_gate"], lp["s_up"], lp["s_down"] = mlp(ks[2], arch["n_shared"] * Fe)
        moe.append(lp)

    def stack(layers, keys):
        return {k: jnp.stack([lp[k] for lp in layers]) for k in keys}

    return {"embed": (jax.random.normal(k_emb, (V, D)) * 0.02).astype(dt),
            "dense": stack(dense, DENSE_KEYS), "moe": stack(moe, MOE_KEYS),
            "final_norm": jnp.ones((D,), dt),
            "lm_head": (jax.random.normal(k_head, (D, V)) * 0.02).astype(dt)}


# ------------------------------------------------------------------- model
def _swiglu(h, gate, up, down, matmul):
    g = _dot("sd,df->sf", h, gate, matmul)
    u = _dot("sd,df->sf", h, up, matmul)
    return _dot("sf,fd->sd", jax.nn.silu(g) * u, down, matmul)


def _attention(arch, matmul, x, lp, q_block):
    """Latent attention over one sequence, causal, in blocks of queries."""
    S = x.shape[0]
    H, nope, rope, dv, r = (arch["n_heads"], arch["qk_nope_dim"], arch["qk_rope_dim"],
                            arch["v_head_dim"], arch["kv_lora_rank"])
    eps = arch["rms_norm_eps"]
    h = _rmsnorm(x, lp["ln1"], eps)
    q = _dot("sd,dn->sn", h, lp["wq"], matmul).reshape(S, H, nope + rope)
    kv_a = _dot("sd,dn->sn", h, lp["wkv_a"], matmul)
    c = _rmsnorm(kv_a[:, :r], lp["kv_norm"], eps)
    kv = _dot("sr,rn->sn", c, lp["wkv_b"], matmul).reshape(S, H, nope + dv)
    k_pe = _rope(kv_a[:, None, r:], arch["rope_theta"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], arch["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (S, H, rope))], -1)
    v = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
    q_block = min(q_block, S)

    @jax.checkpoint
    def block(qb, pos):
        s = _dot("qhd,khd->hqk", qb, k, matmul) * scale
        causal = jnp.arange(S)[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return _dot("hqk,khd->qhd", p, v, matmul)

    qs = q.reshape(S // q_block, q_block, H, nope + rope)
    pos = jnp.arange(S).reshape(S // q_block, q_block)
    o = jax.lax.map(lambda a: block(*a), (qs, pos)).reshape(S, H * dv)
    return x + _dot("sn,nd->sd", o, lp["wo"], matmul)


def _dense_layer(arch, matmul, q_block, x, lp):
    x = _attention(arch, matmul, x, lp, q_block)
    h = _rmsnorm(x, lp["ln2"], arch["rms_norm_eps"])
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], matmul)


def _moe_layer(arch, matmul, q_block, x, lp):
    x = _attention(arch, matmul, x, lp, q_block)
    h = _rmsnorm(x, lp["ln2"], arch["rms_norm_eps"])
    scores = jax.nn.sigmoid(_dot("sd,de->se", h, lp["w_router"], matmul))
    _, top = jax.lax.top_k(scores, arch["top_k"])    # correction bias: zero
    w = jnp.take_along_axis(scores, top, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * arch["routed_scale"]
    y = _swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"], matmul)
    for e in range(lp["e_gate"].shape[0]):         # held experts 0 .. n-1
        w_e = jnp.sum(jnp.where(top == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * _swiglu(h, lp["e_gate"][e], lp["e_up"][e],
                                       lp["e_down"][e], matmul)
    return x + y


def _row_nll_sum(arch, matmul, head_block, q_block, params, tokens, targets, mask):
    """Sum of the masked next-token losses of one sequence."""
    x = params["embed"][tokens].astype(F32)
    for name, fn in (("dense", _dense_layer), ("moe", _moe_layer)):
        layer = jax.checkpoint(partial(fn, arch, matmul, q_block))
        x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x, params[name])
    x = _rmsnorm(x, params["final_norm"], arch["rms_norm_eps"])
    S = x.shape[0]
    head_block = min(head_block, S)

    @jax.checkpoint
    def block_nll(xb, tb, mb):
        logits = _dot("sd,dv->sv", xb, params["lm_head"], matmul)
        gold = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * mb)

    nb = S // head_block
    xs = (x.reshape(nb, head_block, -1), targets.reshape(nb, head_block),
          mask.reshape(nb, head_block))
    return jnp.sum(jax.lax.map(lambda a: block_nll(*a), xs))


def batch_loss(arch, matmul, head_block, q_block, params, batch):
    """Masked mean of the next-token loss over the whole batch."""
    row = jax.checkpoint(partial(_row_nll_sum, arch, matmul, head_block, q_block))

    def body(total, xs):
        return total + row(params, *xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32),
                            (batch["tokens"], batch["targets"], batch["loss_mask"]))
    return total / jnp.maximum(jnp.sum(batch["loss_mask"]), 1.0)


# --------------------------------------------------------------- optimizer
def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Norm of every leaf by name (``dense.<leaf>`` and ``moe.<leaf>`` give
    one norm per layer)."""
    out = {k: jnp.sqrt(jnp.sum(jnp.square(tree[k].astype(F32))))
           for k in ("embed", "final_norm", "lm_head")}
    for group in ("dense", "moe"):
        for k, v in tree[group].items():
            v = v.astype(F32)
            out[f"{group}.{k}"] = jnp.sqrt(jnp.sum(jnp.square(v),
                                                   axis=tuple(range(1, v.ndim))))
    return out


def make_grad(arch: Dict, matmul: str = "f32", head_block: int = 512, q_block: int = 512):
    """Loss and float32 gradient at the stored parameters."""
    grad = jax.value_and_grad(partial(batch_loss, arch, matmul, head_block, q_block))
    return jax.jit(lambda params, batch: grad(
        jax.tree.map(lambda p: p.astype(F32), params), batch))


def make_update(opt: Dict):
    """AdamW on the clipped gradient, as ``lm_train.make_update``; returns
    the parameters in their storage dtype, the moments, the count and the
    norms of the clipped gradient."""
    b1, b2 = opt["b1"], opt["b2"]

    def update(params, grads, mu, nu, count):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        count = count + 1
        t = count.astype(F32)
        lr = learning_rate(opt, count)
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            pf = p.astype(F32)
            delta = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"]) + opt["weight_decay"] * pf
            return (pf - lr * delta).astype(p.dtype), m, v

        out = jax.tree.map(upd, params, grads, mu, nu)
        pick = lambda i: jax.tree.map(lambda t3: t3[i], out,  # noqa: E731
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2), count, leaf_norms(grads)

    return jax.jit(update, donate_argnums=(0, 1, 2, 3))


def run(arch: Dict, opt: Dict, batch: int, seq: int, seed: int, n_steps: int,
        matmul: str = "f32", rows=None) -> Dict:
    """The first ``n_steps`` steps from the seed, as ``lm_train.run``:
    ``rows`` keeps only those rows of every batch (the half-batch fault);
    the moments wait on the host while a gradient is formed."""
    params = init_params(arch, seed)
    before = jax.device_get(params)
    grad_fn, update = make_grad(arch, matmul), make_update(opt)
    mu = nu = None
    count = jnp.zeros((), jnp.int32)
    losses, first_grad = [], None
    for i in range(n_steps):
        b = synth_batch(arch["vocab_size"], batch, seq, i, seed)
        if rows is not None:
            b = {k: v[rows] for k, v in b.items()}
        loss, grads = grad_fn(params, b)
        losses.append(float(loss))
        if mu is None:
            mu = jax.tree.map(jnp.zeros_like, grads)
            nu = jax.tree.map(jnp.zeros_like, grads)
        params, mu, nu, count, gn = update(params, grads, mu, nu, count)
        del grads
        if first_grad is None:
            first_grad = jax.device_get(gn)
        if i + 1 < n_steps:
            mu, nu = jax.device_get((mu, nu))
    del mu, nu
    change = jax.device_get(jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b)))(params, before))
    return {"losses": losses, "grad_norms": {k: np.asarray(v) for k, v in first_grad.items()},
            "change_norms": {k: np.asarray(v) for k, v in change.items()}}

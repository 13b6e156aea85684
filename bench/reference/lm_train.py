"""Plain reference for the dense decoder LM's first training steps.

Written from the architecture's description (pre-norm GQA attention with
rotary embeddings, SwiGLU MLP, RMSNorm, untied output head, masked
next-token cross entropy) and AdamW with global-norm clipping. It imports
nothing of the program under test. Weights and batches are drawn from the
same seed by the same public recipes (JAX's threefry normal draws, numpy's
``SeedSequence``) as the training job draws its own, so both sides start
from the same numbers without either handing its arrays to the other.

Everything is float32 with ``HIGHEST`` matmul precision; parameters are
stored in the configuration's dtype (bfloat16) between steps, as the job
stores them, and the gradient is taken at their float32 values. ``matmul="fp8"`` is the control, the step below the configuration's
bfloat16: every matmul, forward and backward, takes float8 operands with
one scale per tensor.

Memory: the batch is processed one row at a time inside a scan, every
layer is rematerialised, the head's logits are formed in blocks of
positions, and the moments wait on the host while a gradient is formed,
so the reference fits on one chip once the job's state is freed.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up", "w_down")


# ------------------------------------------------------------------ inputs
def init_params(arch: Dict, seed: int) -> Dict:
    """The seeded weights: normal draws scaled by 0.02 (embedding, head),
    1/sqrt(fan_in) (projections), ones (norm gains), stored in bfloat16.
    Keys are split per layer and per matrix in the job's documented order."""
    D, V, F = arch["d_model"], arch["vocab_size"], arch["d_ff"]
    H, K, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    dt = jnp.dtype(arch["param_dtype"])

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape) * scale).astype(dt)

    k_emb, k_scan, _k_tail, k_head = jax.random.split(jax.random.PRNGKey(seed), 4)
    layers: Dict[str, List] = {k: [] for k in LAYER_KEYS}
    for k_layer in jax.random.split(k_scan, arch["n_layers"]):
        k_block = jax.random.split(k_layer, 1)[0]
        k_attn, k_mlp, _, _ = jax.random.split(k_block, 4)
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)
        layers["wq"].append(normal(ka[0], (D, H * hd), 1.0 / math.sqrt(D)))
        layers["wk"].append(normal(ka[1], (D, K * hd), 1.0 / math.sqrt(D)))
        layers["wv"].append(normal(ka[2], (D, K * hd), 1.0 / math.sqrt(D)))
        layers["wo"].append(normal(ka[3], (H * hd, D), 1.0 / math.sqrt(H * hd)))
        layers["w_gate"].append(normal(km[0], (D, F), 1.0 / math.sqrt(D)))
        layers["w_up"].append(normal(km[1], (D, F), 1.0 / math.sqrt(D)))
        layers["w_down"].append(normal(km[2], (F, D), 1.0 / math.sqrt(F)))
        layers["ln1"].append(jnp.ones((D,), dt))
        layers["ln2"].append(jnp.ones((D,), dt))
    return {
        "embed": normal(k_emb, (V, D), 0.02),
        "layers": {k: jnp.stack(v) for k, v in layers.items()},
        "final_norm": jnp.ones((D,), dt),
        "lm_head": normal(k_head, (D, V), 0.02),
    }


def synth_batch(vocab: int, batch: int, seq: int, step: int, seed: int) -> Dict:
    """The job's synthetic token stream for (seed, step): a random walk of
    steps in [-3, 3] from a random start, folded into the vocabulary; the
    target is the next token and the last position carries no loss."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0, 1]))
    base = rng.integers(0, vocab, size=(batch, 1))
    walk = rng.integers(-3, 4, size=(batch, seq))
    tokens = (np.abs(base + np.cumsum(walk, axis=1)) % vocab).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = 0
    mask = np.ones((batch, seq), np.float32)
    mask[:, -1] = 0.0
    return {"tokens": tokens, "targets": targets.astype(np.int32), "loss_mask": mask}


# ------------------------------------------------------------------- model
def _quant(x, exponent_bits, mantissa_bits, top):
    """Round to a float8 format with one scale per tensor. ``reduce_precision``
    and not a round trip through the dtype: the compiler may drop a pair of
    converts as excess precision, but never this operation."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jax.lax.reduce_precision(x / scale, exponent_bits, mantissa_bits) * scale


def _e4m3(x):
    return _quant(x, 4, 3, 240.0)


def _e5m2(x):
    return _quant(x, 5, 2, 57344.0)


def _f32_dot(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_dot(eq, a, b):
    return _f32_dot(eq, _e4m3(a), _e4m3(b))


def _fp8_dot_fwd(eq, a, b):
    qa, qb = _e4m3(a), _e4m3(b)
    return _f32_dot(eq, qa, qb), (qa, qb)


def _fp8_dot_bwd(eq, res, g):
    _, vjp = jax.vjp(partial(_f32_dot, eq), *res)
    return vjp(_e5m2(g))


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def _dot(eq: str, a, b, matmul: str):
    """A matmul in float32 at HIGHEST precision, or for the control with
    float8 operands scaled per tensor: e4m3 for the forward operands, e5m2
    for the incoming gradient of the backward products."""
    a, b = a.astype(F32), b.astype(F32)
    return _fp8_dot(eq, a, b) if matmul == "fp8" else _f32_dot(eq, a, b)


def _rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _rope(x, theta):
    """Rotate the two halves of each head by position-dependent angles."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(arch, matmul, x, lp):
    S = x.shape[0]
    H, K, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["rms_norm_eps"]
    h = _rmsnorm(x, lp["ln1"], eps)
    q = _rope(_dot("sd,dn->sn", h, lp["wq"], matmul).reshape(S, H, hd), arch["rope_theta"])
    k = _rope(_dot("sd,dn->sn", h, lp["wk"], matmul).reshape(S, K, hd), arch["rope_theta"])
    v = _dot("sd,dn->sn", h, lp["wv"], matmul).reshape(S, K, hd)
    k = jnp.repeat(k, H // K, axis=1)        # query head i reads kv head i // (H/K)
    v = jnp.repeat(v, H // K, axis=1)
    s = _dot("qhd,khd->hqk", q, k, matmul) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _dot("hqk,khd->qhd", p, v, matmul).reshape(S, H * hd)
    x = x + _dot("sn,nd->sd", o, lp["wo"], matmul)
    h = _rmsnorm(x, lp["ln2"], eps)
    gate = _dot("sd,df->sf", h, lp["w_gate"], matmul)
    up = _dot("sd,df->sf", h, lp["w_up"], matmul)
    return x + _dot("sf,fd->sd", jax.nn.silu(gate) * up, lp["w_down"], matmul)


def _row_nll_sum(arch, matmul, head_block, params, tokens, targets, mask):
    """Sum of the masked next-token losses of one sequence."""
    x = params["embed"][tokens].astype(F32)
    layer = jax.checkpoint(partial(_layer, arch, matmul))
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], arch["rms_norm_eps"])
    S = x.shape[0]
    head_block = min(head_block, S)
    nb = S // head_block

    @jax.checkpoint
    def block_nll(xb, tb, mb):
        logits = _dot("sd,dv->sv", xb, params["lm_head"], matmul)
        gold = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * mb)

    xs = (x.reshape(nb, head_block, -1), targets.reshape(nb, head_block),
          mask.reshape(nb, head_block))
    return jnp.sum(jax.lax.map(lambda a: block_nll(*a), xs))


def batch_loss(arch, matmul, head_block, params, batch):
    """Masked mean of the next-token loss over the whole batch."""
    row = jax.checkpoint(partial(_row_nll_sum, arch, matmul, head_block))

    def body(total, xs):
        return total + row(params, *xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32),
                            (batch["tokens"], batch["targets"], batch["loss_mask"]))
    return total / jnp.maximum(jnp.sum(batch["loss_mask"]), 1.0)


# --------------------------------------------------------------- optimizer
def learning_rate(opt: Dict, count):
    """Linear warm-up to the base rate, then cosine decay to 10% of it."""
    step = count.astype(F32)
    base, warm = opt["lr"], opt["warmup"]
    warm_lr = base * step / max(1.0, warm)
    prog = jnp.clip((step - warm) / max(1.0, opt["total_steps"] - warm), 0.0, 1.0)
    cos_lr = base * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < warm, warm_lr, cos_lr)


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Norm of every leaf; a stacked layer leaf gives one norm per layer."""
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"],
           "lm_head": tree["lm_head"]}
    out = {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))) for k, v in out.items()}
    for k, v in tree["layers"].items():
        v = v.astype(F32)
        out[k] = jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(range(1, v.ndim))))
    return out


def make_grad(arch: Dict, matmul: str = "f32", head_block: int = 512):
    """Loss and float32 gradient at the stored parameters."""
    grad = jax.value_and_grad(partial(batch_loss, arch, matmul, head_block))
    return jax.jit(lambda params, batch: grad(
        jax.tree.map(lambda p: p.astype(F32), params), batch))


def make_update(opt: Dict):
    """AdamW on the clipped gradient. Returns the new parameters in their
    storage dtype (a real array of that dtype, so the rounding cannot be
    dropped as excess precision), the new moments, the count, and the norms
    of the clipped gradient as the moments receive it."""
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


def change_norms(p_after: Dict, p_before: Dict) -> Dict[str, jnp.ndarray]:
    return jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b)))(p_after, p_before)


def run(arch: Dict, opt: Dict, batch: int, seq: int, seed: int, n_steps: int,
        matmul: str = "f32", rows=None) -> Dict:
    """The first ``n_steps`` steps from the seed. ``rows`` keeps only those
    rows of every batch (the half-batch fault is read this way). The
    moments wait on the host while a gradient is formed, so that float32
    parameters, gradient and both moments never sit on the chip at once."""
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
    change = jax.device_get(change_norms(params, before))
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change}

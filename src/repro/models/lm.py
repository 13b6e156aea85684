"""Unified decoder-only LM covering all assigned architectures.

A model is a cycle of *block types* (``block_pattern``) over ``n_layers``:

* ``dense``      — attention + SwiGLU MLP (granite, qwen3, internlm2,
                   VLM/audio backbones)
* ``moe``        — attention + routed MoE (+ optional shared experts)
* ``ssd``        — Mamba-2 SSD block (attention-free)
* ``rglru``      — RG-LRU temporal mixing + MLP (RecurrentGemma)
* ``local_attn`` — sliding-window GQA + MLP (RecurrentGemma's 1:2 pattern)

Attention is GQA, or multi-head latent attention (``layers.mla``) where the
config gives a ``kv_lora_rank``, in every layer.  ``first_k_dense`` leading
``dense`` layers (DeepSeek-V3's ``first_k_dense_replace``) run unrolled
before the pattern starts.  The pattern's layers are grouped into
``lax.scan``-stacked *super-blocks* (one pattern period per step) so the
compiled HLO is O(1) in depth; the remainder layers run unrolled.  Caches
mirror the grouping (the leading layers and latent attention have none:
training and prefill without a cache only).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import tracing
from ..kernels.ops import ATTN_RESIDUALS
from ..layers.attention import attention_axes, attn_forward, init_attention, init_kv_cache
from ..layers.mlp import init_mlp, mlp_axes, mlp_forward
from ..layers.mla import init_mla, mla_axes, mla_forward
from ..layers.moe import init_moe, moe_apply_local, moe_apply_sharded, moe_axes
from ..layers.norms import init_rmsnorm, rmsnorm, rmsnorm_axes
from ..layers.rglru import init_rglru, init_rglru_cache, rglru_axes, rglru_forward
from ..layers.ssd import init_ssd, init_ssd_cache, ssd_axes, ssd_forward

AxTree = Any  # same structure as params, leaves = tuples of logical names


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    block_pattern: Tuple[str, ...] = ("dense",)
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-6            # every RMSNorm's epsilon (rms_norm_eps)
    first_k_dense: int = 0            # leading dense layers (first_k_dense_replace)
    # latent attention (MLA) in place of GQA where kv_lora_rank > 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # MoE
    n_experts: int = 0                # experts the router scores (n_routed_experts)
    top_k: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    moe_capacity_factor: float = 1.25   # the sharded path's capacity only
    moe_renormalize: bool = True        # norm_topk_prob
    moe_scoring: str = "softmax"        # scoring_func: softmax | sigmoid (noaux_tc)
    moe_routed_scale: float = 1.0       # routed_scaling_factor
    n_experts_held: int = 0             # experts held here (0: all), from 0 on
    # SSM (Mamba-2)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    # RG-LRU
    rnn_width: int = 0
    local_window: int = 2048
    # input modality: "tokens" | "embeds" (audio stub) | "prefix_embeds" (VLM stub)
    input_mode: str = "tokens"
    prefix_len: int = 0
    mlp_gated: bool = True
    # numerics / compilation
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    cache_dtype: Any = jnp.bfloat16
    # none | full (keeps each block's inputs and the fused attention's output
    # and log-sum-exp) | dots (those and the matmul outputs)
    remat: str = "none"
    attn_impl: str = "auto"
    attn_chunk: int = 1024
    # dry-run cost probes: python-loop the layers / unroll inner scans so
    # XLA cost_analysis (which counts while bodies once) sees every FLOP
    scan_layers: bool = True
    unroll_scans: bool = False
    # distribution hints (consumed by repro.distributed)
    moe_ff_shard_axis: Optional[str] = "data"

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        m = len(self.block_pattern)
        return ("dense",) * self.first_k_dense + tuple(
            self.block_pattern[i % m] for i in range(self.n_layers - self.first_k_dense))

    @property
    def n_super(self) -> int:
        return (self.n_layers - self.first_k_dense) // len(self.block_pattern)

    @property
    def n_tail(self) -> int:
        return (self.n_layers - self.first_k_dense) % len(self.block_pattern)

    @property
    def tail_types(self) -> Tuple[str, ...]:
        return self.layer_types[self.first_k_dense + self.n_super * len(self.block_pattern):]

    @property
    def is_recurrent_only(self) -> bool:
        return all(t in ("ssd", "rglru") for t in self.block_pattern)

    @property
    def sub_quadratic(self) -> bool:
        return all(t in ("ssd", "rglru", "local_attn") for t in self.block_pattern)


# ------------------------------------------------------------------ builders
def _init_attn(cfg: LMConfig, key) -> Dict:
    if cfg.kv_lora_rank:
        return init_mla(key, cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                        cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                        cfg.param_dtype)
    return init_attention(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                          cfg.qk_norm, cfg.param_dtype)


def _attn_axes(cfg: LMConfig) -> Dict:
    return mla_axes() if cfg.kv_lora_rank else attention_axes(cfg.qk_norm)


def _init_block(cfg: LMConfig, key, btype: str) -> Dict:
    """Keys: one split of ``key`` in four; the attention takes the first,
    the MLP or the routed experts the second, the shared experts the third."""
    ks = jax.random.split(key, 4)
    dt = cfg.param_dtype
    if btype in ("dense", "local_attn"):
        return {
            "ln1": init_rmsnorm(cfg.d_model, dt),
            "attn": _init_attn(cfg, ks[0]),
            "ln2": init_rmsnorm(cfg.d_model, dt),
            "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, dt, gated=cfg.mlp_gated),
        }
    if btype == "moe":
        p = {
            "ln1": init_rmsnorm(cfg.d_model, dt),
            "attn": _init_attn(cfg, ks[0]),
            "ln2": init_rmsnorm(cfg.d_model, dt),
            "moe": init_moe(ks[1], cfg.d_model, cfg.d_ff_expert, cfg.n_experts, dt,
                            n_held=cfg.n_experts_held or None, scoring=cfg.moe_scoring),
        }
        if cfg.n_shared_experts:
            p["shared"] = init_mlp(ks[2], cfg.d_model,
                                   cfg.n_shared_experts * cfg.d_ff_expert, dt)
        return p
    if btype == "ssd":
        return {
            "ln1": init_rmsnorm(cfg.d_model, dt),
            "ssd": init_ssd(ks[0], cfg.d_model, expand=cfg.ssm_expand,
                            headdim=cfg.ssm_headdim, d_state=cfg.ssm_state,
                            conv_width=cfg.conv_width, dtype=dt),
        }
    if btype == "rglru":
        return {
            "ln1": init_rmsnorm(cfg.d_model, dt),
            "rec": init_rglru(ks[0], cfg.d_model, cfg.rnn_width or cfg.d_model,
                              conv_width=cfg.conv_width, dtype=dt),
            "ln2": init_rmsnorm(cfg.d_model, dt),
            "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, dt, gated=cfg.mlp_gated),
        }
    raise ValueError(f"unknown block type {btype}")


def _block_axes(cfg: LMConfig, btype: str) -> Dict:
    if btype in ("dense", "local_attn"):
        return {"ln1": rmsnorm_axes(), "attn": _attn_axes(cfg),
                "ln2": rmsnorm_axes(), "mlp": mlp_axes(cfg.mlp_gated)}
    if btype == "moe":
        ax = {"ln1": rmsnorm_axes(), "attn": _attn_axes(cfg),
              "ln2": rmsnorm_axes(), "moe": moe_axes(cfg.moe_scoring)}
        if cfg.n_shared_experts:
            ax["shared"] = mlp_axes()
        return ax
    if btype == "ssd":
        return {"ln1": rmsnorm_axes(), "ssd": ssd_axes()}
    if btype == "rglru":
        return {"ln1": rmsnorm_axes(), "rec": rglru_axes(),
                "ln2": rmsnorm_axes(), "mlp": mlp_axes(cfg.mlp_gated)}
    raise ValueError(btype)


def _init_super(cfg: LMConfig, key) -> Dict:
    ks = jax.random.split(key, len(cfg.block_pattern))
    return {f"b{i}": _init_block(cfg, ks[i], t)
            for i, t in enumerate(cfg.block_pattern)}


def _super_axes(cfg: LMConfig) -> Dict:
    return {f"b{i}": _block_axes(cfg, t) for i, t in enumerate(cfg.block_pattern)}


def _stack(trees: List) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: LMConfig, key) -> Dict:
    """Keys: ``key`` split in four: the embedding; the scanned super-blocks
    (split once per super-block); the leading and the remainder layers
    (split once per layer, the leading ones first); the output head."""
    k_emb, k_scan, k_tail, k_head = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    if cfg.input_mode in ("tokens", "prefix_embeds"):
        params["embed"] = (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                           * 0.02).astype(cfg.param_dtype)
    if cfg.n_super > 0:
        ks = jax.random.split(k_scan, cfg.n_super)
        params["scan"] = _stack([_init_super(cfg, k) for k in ks])
    unrolled = ("dense",) * cfg.first_k_dense + cfg.tail_types
    if unrolled:
        ks = jax.random.split(k_tail, len(unrolled))
        blocks = [_init_block(cfg, ks[i], t) for i, t in enumerate(unrolled)]
        if cfg.first_k_dense:
            params["lead"] = blocks[:cfg.first_k_dense]
        if cfg.tail_types:
            params["tail"] = blocks[cfg.first_k_dense:]
    params["final_norm"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size))
                             * 0.02).astype(cfg.param_dtype)
    return params


def param_axes(cfg: LMConfig) -> AxTree:
    """Same tree structure as ``init_params``; leaves are tuples of logical
    axis names (scan groups get a leading ``"layers"``)."""
    ax: Dict[str, Any] = {}
    if cfg.input_mode in ("tokens", "prefix_embeds"):
        ax["embed"] = ("vocab", "embed")
    if cfg.first_k_dense:
        ax["lead"] = [_block_axes(cfg, "dense")] * cfg.first_k_dense
    if cfg.n_super > 0:
        sup = _super_axes(cfg)
        ax["scan"] = jax.tree.map(
            lambda t: ("layers",) + t, sup,
            is_leaf=lambda x: isinstance(x, tuple))
    if cfg.tail_types:
        ax["tail"] = [_block_axes(cfg, t) for t in cfg.tail_types]
    ax["final_norm"] = rmsnorm_axes()
    if not cfg.tie_embeddings:
        ax["lm_head"] = ("embed", "vocab")
    return ax


# -------------------------------------------------------------------- caches
def _init_block_cache(cfg: LMConfig, btype: str, batch: int, cache_len: int):
    if btype == "dense" or btype == "moe":
        return init_kv_cache(batch, cache_len, cfg.n_kv_heads, cfg.hd, cfg.cache_dtype)
    if btype == "local_attn":
        return init_kv_cache(batch, min(cache_len, cfg.local_window),
                             cfg.n_kv_heads, cfg.hd, cfg.cache_dtype)
    if btype == "ssd":
        return init_ssd_cache(batch, cfg.d_model, expand=cfg.ssm_expand,
                              headdim=cfg.ssm_headdim, d_state=cfg.ssm_state,
                              conv_width=cfg.conv_width, dtype=cfg.compute_dtype)
    if btype == "rglru":
        return init_rglru_cache(batch, cfg.rnn_width or cfg.d_model,
                                conv_width=cfg.conv_width, dtype=cfg.compute_dtype)
    raise ValueError(btype)


def init_caches(cfg: LMConfig, batch: int, cache_len: int) -> Dict:
    caches: Dict[str, Any] = {}
    if cfg.n_super > 0:
        one = {f"b{i}": _init_block_cache(cfg, t, batch, cache_len)
               for i, t in enumerate(cfg.block_pattern)}
        caches["scan"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_super,) + x.shape), one)
    if cfg.tail_types:
        caches["tail"] = [_init_block_cache(cfg, t, batch, cache_len)
                          for t in cfg.tail_types]
    return caches


# ------------------------------------------------------------------- forward
def _apply_block(cfg: LMConfig, p, x, btype: str, *, cache, pos_offset,
                 make_cache_len, mesh):
    """Returns (x, new_cache, aux, rows): ``rows`` is what the single-device
    MoE layer counts (``moe_apply_local``), None for any other block."""
    aux = jnp.zeros((), jnp.float32)
    rows = None
    eps = cfg.norm_eps
    if btype in ("dense", "local_attn", "moe"):
        window = cfg.local_window if btype == "local_attn" else None
        mcl = make_cache_len
        if btype == "local_attn" and mcl is not None:
            mcl = min(mcl, cfg.local_window)
        if cfg.kv_lora_rank:
            if cache is not None or mcl is not None:
                raise NotImplementedError("latent attention keeps no KV cache")
            x = x + mla_forward(
                p["attn"], rmsnorm(p["ln1"], x, eps), n_heads=cfg.n_heads,
                kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
                qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
                rope_theta=cfg.rope_theta, norm_eps=eps, impl=cfg.attn_impl,
                chunk=cfg.attn_chunk, unroll=cfg.unroll_scans, mesh=mesh)
            new_cache = None
        else:
            a, new_cache = attn_forward(
                p["attn"], rmsnorm(p["ln1"], x, eps),
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm, window=window,
                pos_offset=pos_offset, cache=cache, make_cache_len=mcl,
                cache_dtype=cfg.cache_dtype, impl=cfg.attn_impl,
                chunk=cfg.attn_chunk, unroll=cfg.unroll_scans, mesh=mesh)
            x = x + a
        h = rmsnorm(p["ln2"], x, eps)
        if btype == "moe":
            route = dict(top_k=cfg.top_k, scoring=cfg.moe_scoring,
                         renormalize=cfg.moe_renormalize,
                         routed_scale=cfg.moe_routed_scale)
            if mesh is not None and mesh.shape.get("model", 1) > 1:
                routed, aux = moe_apply_sharded(
                    p["moe"], h, mesh=mesh,
                    data_axes=tuple(a for a in ("pod", "data") if a in mesh.axis_names),
                    model_axis="model", ff_shard_axis=cfg.moe_ff_shard_axis,
                    capacity_factor=cfg.moe_capacity_factor, **route)
            else:
                routed, aux, rows = moe_apply_local(p["moe"], h, **route)
            y = routed
            if cfg.n_shared_experts:
                with jax.named_scope("moe/shared"):
                    y = y + mlp_forward(p["shared"], h)
        else:
            y = mlp_forward(p["mlp"], h)
        return x + y, new_cache, aux, rows
    if btype == "ssd":
        y, new_cache = ssd_forward(
            p["ssd"], rmsnorm(p["ln1"], x, eps), expand=cfg.ssm_expand,
            headdim=cfg.ssm_headdim, d_state=cfg.ssm_state,
            conv_width=cfg.conv_width, chunk=cfg.ssm_chunk, cache=cache,
            make_cache=make_cache_len is not None, unroll=cfg.unroll_scans)
        return x + y, new_cache, aux, rows
    if btype == "rglru":
        y, new_cache = rglru_forward(p["rec"], rmsnorm(p["ln1"], x, eps), cache=cache,
                                     make_cache=make_cache_len is not None)
        x = x + y
        x = x + mlp_forward(p["mlp"], rmsnorm(p["ln2"], x, eps))
        return x, new_cache, aux, rows
    raise ValueError(btype)


def _apply_super(cfg: LMConfig, p_sb, x, caches_sb, *, pos_offset,
                 make_cache_len, mesh):
    """Returns (x, new_caches, aux, rows): ``rows`` the MoE layers' counts
    stacked, (n, 2), or None where no block counts."""
    new_caches = {}
    aux_total = jnp.zeros((), jnp.float32)
    rows = []
    for i, btype in enumerate(cfg.block_pattern):
        c = caches_sb.get(f"b{i}") if caches_sb else None
        x, nc, aux, r = _apply_block(cfg, p_sb[f"b{i}"], x, btype, cache=c,
                                     pos_offset=pos_offset,
                                     make_cache_len=make_cache_len, mesh=mesh)
        aux_total = aux_total + aux
        if nc is not None:
            new_caches[f"b{i}"] = nc
        if r is not None:
            rows.append(r)
    return x, (new_caches or None), aux_total, (jnp.stack(rows) if rows else None)


def embed_inputs(cfg: LMConfig, params, batch) -> jnp.ndarray:
    if cfg.input_mode == "tokens":
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    elif cfg.input_mode == "embeds":
        x = batch["embeds"]
    elif cfg.input_mode == "prefix_embeds":
        parts = []
        if "prefix_embeds" in batch:
            parts.append(batch["prefix_embeds"].astype(cfg.compute_dtype))
        if "tokens" in batch:
            parts.append(jnp.take(params["embed"], batch["tokens"], axis=0))
        x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    else:
        raise ValueError(cfg.input_mode)
    return x.astype(cfg.compute_dtype)


def _remat(fn, remat: str):
    """``fn`` rematerialised as ``remat`` says: ``full`` keeps its inputs
    and the fused attention's output and log-sum-exp (``ATTN_RESIDUALS``)
    for the backward, ``dots`` its matmul outputs too.  The backward then
    recomputes q, k and v but not the attention forward; on the jnp
    attention paths nothing carries the name and ``full`` keeps only the
    inputs."""
    policies = jax.checkpoint_policies
    keep = policies.save_only_these_names(ATTN_RESIDUALS)
    if remat == "full":
        return jax.checkpoint(fn, policy=keep)
    if remat == "dots":
        return jax.checkpoint(fn, policy=policies.save_from_both_policies(
            policies.checkpoint_dots, keep))
    return fn


def forward(cfg: LMConfig, params, batch, *, caches=None, pos_offset=0,
            make_cache_len: Optional[int] = None, mesh=None,
            remat: Optional[str] = None, last_only: bool = False):
    """Returns (logits fp32 (B,S,V), new_caches or None, aux): ``aux["balance"]``
    the summed load-balance loss, and ``aux["moe_rows"]`` where single-device
    MoE layers ran: per MoE layer in order, the rows its held experts
    computed and the largest held expert's rows, (n_moe, 2) int32.
    ``last_only=True`` computes logits for the final position only (prefill
    memory saver: avoids materializing (B, S, V))."""
    remat = cfg.remat if remat is None else remat
    if remat != "none":
        tracing.count("remat.policy", 1, mode=remat, keeps=ATTN_RESIDUALS)
    if cfg.first_k_dense and (caches is not None or make_cache_len is not None):
        raise NotImplementedError("the leading dense layers keep no KV cache")
    x = embed_inputs(cfg, params, batch)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}
    rows: List[Any] = []

    def unrolled(x, aux_total, blocks, types, layer_caches):
        out_caches = []
        for p, btype, c in zip(blocks, types, layer_caches):
            block = _remat(lambda p, x, btype=btype, c=c: _apply_block(
                cfg, p, x, btype, cache=c, pos_offset=pos_offset,
                make_cache_len=make_cache_len, mesh=mesh), remat)
            x, nc, a, r = block(p, x)
            aux_total = aux_total + a
            out_caches.append(nc)
            if r is not None:
                rows.append(r[None])
        return x, aux_total, out_caches

    if cfg.first_k_dense:
        x, aux_total, _ = unrolled(x, aux_total, params["lead"],
                                   ("dense",) * cfg.first_k_dense,
                                   [None] * cfg.first_k_dense)

    if cfg.n_super > 0:
        scan_caches = caches.get("scan") if caches else None

        def body(carry, xs):
            x, aux = carry
            p_sb, c_sb = xs
            x, nc, a, r = _apply_super(cfg, p_sb, x, c_sb, pos_offset=pos_offset,
                                       make_cache_len=make_cache_len, mesh=mesh)
            return (x, aux + a), (nc, r)

        body = _remat(body, remat)
        if cfg.scan_layers:
            (x, aux_total), (nc_scan, r_scan) = jax.lax.scan(
                body, (x, aux_total), (params["scan"], scan_caches))
        else:
            # dry-run probe path: python loop so every layer's cost is in HLO
            outs = []
            for i in range(cfg.n_super):
                p_sb = jax.tree.map(lambda a: a[i], params["scan"])
                c_sb = (jax.tree.map(lambda a: a[i], scan_caches)
                        if scan_caches is not None else None)
                (x, aux_total), out = body((x, aux_total), (p_sb, c_sb))
                outs.append(out)
            nc_scan, r_scan = (jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
                               if outs else (None, None))
        if nc_scan is not None:
            new_caches["scan"] = nc_scan
        if r_scan is not None:           # (n_super, moe blocks per period, 2)
            rows.append(r_scan.reshape(-1, 2))

    if cfg.tail_types:
        tail_caches = (caches.get("tail") if caches else [None] * len(cfg.tail_types))
        x, aux_total, nc_tail = unrolled(x, aux_total, params["tail"], cfg.tail_types,
                                         tail_caches)
        if any(c is not None for c in nc_tail):
            new_caches["tail"] = nc_tail

    if last_only:
        x = x[:, -1:]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    with jax.named_scope("logits"):
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
    aux = {"balance": aux_total}
    if rows:
        aux["moe_rows"] = jnp.concatenate(rows)
    return logits, (new_caches or None), aux


def loss_fn(cfg: LMConfig, params, batch, *, mesh=None, aux_weight: float = 0.01,
            remat: Optional[str] = None):
    """Masked next-token cross entropy.  batch must carry ``targets`` (B,S)
    and ``loss_mask`` (B,S) aligned with the model's output positions.
    The metrics carry ``moe_rows`` where :func:`forward` gives it."""
    logits, _, aux = forward(cfg, params, batch, mesh=mesh, remat=remat)
    targets = batch["targets"]
    mask = batch["loss_mask"].astype(jnp.float32)
    with jax.named_scope("cross_entropy"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(nll) / denom
    total = loss + aux_weight * aux["balance"]
    metrics = {"loss": loss, "aux": aux["balance"], "tokens": jnp.sum(mask)}
    if "moe_rows" in aux:
        metrics["moe_rows"] = aux["moe_rows"]
    return total, metrics

"""Operations and bytes of the latent-attention MoE training cell, from
shapes and the program's routed-row counts alone (``bench/flops.py`` holds
the dense decoder's and the peak table).

The model FLOPs of one trained token are 6 times the matmul weights the
token uses here, plus 6 * layers * heads * seq * (d_qk + d_v) for the score
and value products (the PaLM paper's appendix B count, with the query/key
and value widths apart); recomputation for rematerialisation does not
count. A kernel's cost counts every call the step makes, recomputation
included: it is read against the kernel's device time.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def _widths(c: Dict) -> Dict[str, int]:
    return {"D": c["hidden_size"], "H": c["num_attention_heads"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "r": c["kv_lora_rank"]}


def matmul_params_per_token(c: Dict) -> float:
    """Weights one token multiplies through: every layer's latent-attention
    projections; the dense layers' MLP; per MoE layer the router, the shared
    experts and ``top_k * held / published`` of one routed expert (the
    expected share of its top-k that lands on the experts held here); the
    head's slice of the vocabulary. The embedding is a lookup."""
    w = _widths(c)
    D, H, qk = w["D"], w["H"], w["nope"] + w["rope"]
    attn = D * H * qk + D * (w["r"] + w["rope"]) + w["r"] * H * (w["nope"] + w["dv"]) \
        + H * w["dv"] * D
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    expert = 3 * D * c["moe_intermediate_size"]
    routed = c["num_experts_per_tok"] * c["n_routed_experts"] / c["published"]["n_routed_experts"]
    moe = (D * c["published"]["n_routed_experts"] + c["n_shared_experts"] * expert
           + routed * expert)
    return (c["num_hidden_layers"] * attn + n_dense * 3 * D * c["intermediate_size"]
            + n_moe * moe + D * c["vocab_size"])


def train_flops_per_token(c: Dict, seq: int) -> float:
    w = _widths(c)
    return (6.0 * matmul_params_per_token(c) + 6.0 * c["num_hidden_layers"] * w["H"] * seq
            * (w["nope"] + w["rope"] + w["dv"]))


def gmm_cost(rows: int, d_model: int, d_ff: int, held: int) -> Dict[str, float]:
    """The grouped matmuls of one MoE layer in one training step, for
    ``rows`` routed rows: each of the three expert matrices (gate and up
    D x F, down F x D) in the forward, the forward recomputed for the
    backward, and the backward's two products (the rows' gradient, a grouped
    matmul, and the matrices' gradient, a transposed grouped matmul). Bytes:
    each product reads its bfloat16 operands and writes its result once."""
    mm = 2.0 * rows * d_model * d_ff
    flops = 3 * 4 * mm
    weights = held * d_model * d_ff * BF16
    rows_in, rows_out = rows * d_model * BF16, rows * d_ff * BF16
    # gate, up: rows (D) x W -> rows (F); down: rows (F) x W -> rows (D)
    fwd = 3 * weights + 2 * (rows_in + rows_out) + (rows_out + rows_in)
    # per matrix: grad rows = grad out x W^T (reads grad out, W; writes grad
    # in); grad W = rows^T x grad out (reads both; writes W-sized)
    bwd = 3 * weights + 3 * weights + 2 * 3 * (rows_in + rows_out)
    return {"flops": flops, "bytes": 2.0 * fwd + bwd}


def attention_cost(batch: int, heads: int, seq: int, d_qk: int, d_v: int) -> Dict[str, float]:
    """The causal splash kernels of one layer in one training step: the
    forward, the forward recomputed with its residuals, and the fused
    backward (dq, dk, dv, with the scores recomputed), over the S(S+1)/2
    query-key pairs a causal mask leaves. Bytes: q, k, v read and o written
    once a forward (and the float32 log-sum-exp by the residual forward);
    the backward reads q, k, v, o, do and the log-sum-exp and writes dq,
    dk, dv."""
    bh = batch * heads
    pairs = seq * (seq + 1) / 2.0
    fwd = 2.0 * pairs * (d_qk + d_v)
    bwd = 2.0 * pairs * (3 * d_qk + 2 * d_v)
    qkv = seq * (2 * d_qk + d_v) * BF16
    o, lse = seq * d_v * BF16, seq * 4
    fwd_bytes = qkv + o
    bwd_bytes = qkv + 2 * o + lse + seq * (2 * d_qk + d_v) * BF16
    return {"flops": bh * (2 * fwd + bwd),
            "bytes": bh * (2 * fwd_bytes + lse + bwd_bytes)}


def roofline_s(cost: Dict[str, float], peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(cost["flops"] / peaks["bf16_flops_per_s"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])

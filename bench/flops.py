"""Operations and bytes of the benchmark's work, from shapes alone.

The counts belong to the work, not to whatever implements it, so a share
of a peak or a roofline is read from the same numerator on every PR.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks. A kind that is not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def dense_lm_matmul_params(d_model: int, n_layers: int, n_heads: int, n_kv_heads: int,
                           head_dim: int, d_ff: int, vocab_size: int) -> int:
    """Weights that enter a matmul: the attention and gated-MLP projections
    of every layer and the untied output head. The embedding is a lookup."""
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    mlp = 3 * d_model * d_ff
    return n_layers * (attn + mlp) + d_model * vocab_size


def dense_lm_train_flops_per_token(d_model: int, n_layers: int, n_heads: int,
                                   n_kv_heads: int, head_dim: int, d_ff: int,
                                   vocab_size: int, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6N for the
    matmul weights plus 12 * layers * heads * head_dim * seq for the score
    and value products (the PaLM paper's appendix B count). Recomputation
    for rematerialisation is not counted."""
    n = dense_lm_matmul_params(d_model, n_layers, n_heads, n_kv_heads, head_dim,
                               d_ff, vocab_size)
    return 6.0 * n + 12.0 * n_layers * n_heads * head_dim * seq


def knn_topk_cost(m: int, n: int, d: int, k: int) -> Dict[str, float]:
    """Fused distance and top-k of ``m`` queries against ``n`` float32 rows:
    the distance GEMM plus the norm terms; each input read once, the k
    distances and labels written once."""
    flops = 2.0 * m * n * d + 3.0 * m * n
    bytes_ = 4.0 * (m * d + n * d + n) + 8.0 * m * k
    return {"flops": flops, "bytes": bytes_}


def kmeans_assign_cost(n: int, d: int, k: int) -> Dict[str, float]:
    """Fused assignment and per-cluster partial sums of ``n`` float32 points
    against ``k`` centroids: distances to every centroid plus the sums; the
    points and centroids read once, sums, counts and the SSE written once."""
    flops = 2.0 * n * k * d + 1.0 * n * d
    bytes_ = 4.0 * (n * d + k * d) + 4.0 * (k * d + k + 1)
    return {"flops": flops, "bytes": bytes_}

"""jit'd public wrappers for the Pallas kernels.

Each op compiles its kernel with Mosaic on a TPU and runs it in interpret
mode on the CPU (the tests' platform; interpret mode executes the kernel
body faithfully for correctness validation).  Any other platform is an
error rather than a silent interpreter run.  The ops expose the
model-layer calling conventions.

``flash_attention_op`` is causal self-attention through JAX's Pallas
splash-attention kernels: one fused kernel forward and one backward (dq,
dk and dv together), so scores and probabilities never reach HBM.
``layers.attention.attn_forward`` selects it for training and prefill on a
single TPU (``attn_path``).  The forward names its output and log-sum-exp
``ATTN_RESIDUALS``, which ``models.lm``'s rematerialisation keeps, so the
backward does not run the forward kernel again.

``grouped_matmul`` multiplies row groups by their own matrices through
JAX's Pallas megablox kernels: the routed experts of
``layers.moe.moe_apply_local``, whose rows ``gather_rows_op`` and
``combine_rows_op`` move in and out (``moe_rows``), over the rows below
a count known at run time.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm
from jax.experimental.pallas.ops.tpu.splash_attention import (
    BlockSizes,
    CausalMask,
    MultiHeadMask,
    make_splash_mqa_single_device,
)

from .kmeans_assign import kmeans_assign
from .knn_topk import knn_topk
from .moe_rows import combine_rows, gather_rows
from .rglru_scan import rglru_scan
from .rmsnorm import rmsnorm
from .ssd_scan import ssd_scan


def _interpret() -> bool:
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted "
                           f"on CPU, not on {platform!r}")
    return platform == "cpu"


# ------------------------------------------------------------ flash attention
# Tiles of the two splash kernels, (q, kv): the forward, and one fused
# backward that computes dq beside dk/dv (3.64 ms a layer forward and
# backward against 4.45 ms for separate dq and dk/dv kernels at their best
# tiles). Swept over {256, 512, 1024}^2 on one TPU v5e at the benchmark's
# shape (B 4, K 8, G 2, S 2048, hd 128).
_FWD_BLOCKS = (512, 1024)
_BWD_BLOCKS = (1024, 1024)
_LANES = 128
# the name of the forward's output and log-sum-exp for ``jax.checkpoint``
# policies: the backward kernel's only residuals that the layer's own
# inputs do not give again
ATTN_RESIDUALS = "attn_residuals"


def _fit(block: int, seq_len: int) -> int:
    """The largest of block, block/2, ..., 128 that divides seq_len."""
    while seq_len % block:
        block //= 2
    return block


def _fills_lanes(width: int) -> bool:
    """A head width the kernels' tiles hold whole: 128 lanes or more, in
    steps of half a vector register (192 spans one and a half; a block
    that spans the full head dimension needs no further alignment)."""
    return width >= _LANES and width % (_LANES // 2) == 0


def flash_block_sizes(seq_len: int, head_dim: int,
                      head_dim_v: Optional[int] = None) -> Optional[BlockSizes]:
    """The splash kernels' tiles at this shape, or None where the fused
    path does not apply: the sequence must be a whole multiple of the TPU's
    128 lanes, and the query/key width and the value width (``head_dim``
    where not given) must each fill them (``_fills_lanes``)."""
    widths = (head_dim, head_dim if head_dim_v is None else head_dim_v)
    if seq_len % _LANES or not all(map(_fills_lanes, widths)):
        return None
    fq, fk = (_fit(b, seq_len) for b in _FWD_BLOCKS)
    bq, bk = (_fit(b, seq_len) for b in _BWD_BLOCKS)
    return BlockSizes(block_q=fq, block_kv=fk, block_kv_compute=fk,
                      block_q_dkv=bq, block_kv_dkv=bk, block_kv_dkv_compute=bk,
                      use_fused_bwd_kernel=True)


def flash_attention_op(q, k, v, scale: Optional[float] = None):
    """Causal self-attention, fused forward and backward.

    q: (B, K, G, S, hd); k: (B, K, S, hd); v: (B, K, S, hd_v) ->
    (B, K, G, S, hd_v) bfloat16; the value width may differ from the
    query/key width (latent attention: 192 and 128).
    Each (batch, KV head) pair is one MQA problem for the splash kernels:
    the scores a bfloat16 product with float32 accumulation, float32
    softmax statistics, and the value product in float32 (the forward
    widens each value tile to float32 and multiplies the float32
    probabilities by it); the output is rounded to bfloat16 once, and dq
    is summed over the backward's KV tiles from bfloat16 partials.  The
    output and the log-sum-exp carry the checkpoint name
    ``ATTN_RESIDUALS``.  ``scale`` (1/sqrt(hd) where not given) is applied
    to q in float32 before its one rounding to bfloat16."""
    B, K, G, S, hd = q.shape
    blocks = flash_block_sizes(S, hd, v.shape[-1])
    if blocks is None or k.shape[2] != S:
        raise ValueError(f"no fused attention for q {q.shape}, k {k.shape}, "
                         f"v {v.shape}")
    kernel = make_splash_mqa_single_device(
        MultiHeadMask([CausalMask((S, S))] * G), block_sizes=blocks,
        residual_checkpoint_name=ATTN_RESIDUALS, interpret=_interpret())
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    q = (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    return jax.vmap(jax.vmap(kernel))(q, k, v)


# ------------------------------------------------------------ grouped matmul
# Tiles (rows, contraction, output) of megablox's grouped matmul: the best
# of seven for the forward and backward of one MoE layer's held experts at
# 32,768 tokens, top-6 of 64, 8 held, D 2048, F 1408 on one TPU v5e (28.6 ms
# against 33.9 ms for jax.lax.ragged_dot and 36.2 ms at (512, 512, 128)).
_GMM_TILES = (512, 512, 256)


def grouped_matmul(x, w, group_sizes):
    """Rows grouped by their matrix: x (M, K) with ``group_sizes[g]`` rows
    for ``w[g]`` (G, K, N), in order, -> (M, N) in x's dtype (float32
    accumulation), through megablox's ``gmm`` kernel (its backward a
    ``gmm`` and a ``tgmm``). Rows past the groups' sum are left unwritten
    and are not read: the backward masks them by group, and the row
    kernels read only rows below the sum."""
    tm = _GMM_TILES[0]
    m = x.shape[0]
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = megablox_gmm(x, w, group_sizes, x.dtype, _GMM_TILES, None, None, False,
                       _interpret())
    return out[:m] if pad else out


# ------------------------------------------------------------ routed rows
def gather_rows_op(src, idx, count, scale=None, dot_with=None):
    return gather_rows(src, idx, count, scale, dot_with, interpret=_interpret())


def combine_rows_op(y, pos, w, count):
    return combine_rows(y, pos, w, count, interpret=_interpret())


# --------------------------------------------------------------------- others
def ssd_scan_op(x, dt, A, Bm, Cm, *, chunk: int = 128):
    return ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=_interpret())


def rglru_scan_op(log_a, b, h0=None):
    return rglru_scan(log_a, b, h0, interpret=_interpret())


def knn_topk_op(test_x, train_x, train_y, *, k: int = 5):
    return knn_topk(test_x, train_x, train_y, k=k, interpret=_interpret())


def kmeans_assign_op(x, centroids):
    return kmeans_assign(x, centroids, interpret=_interpret())


def rmsnorm_op(x, scale, *, eps: float = 1e-6):
    return rmsnorm(x, scale, eps=eps, interpret=_interpret())

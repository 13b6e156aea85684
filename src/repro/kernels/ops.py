"""jit'd public wrappers for the Pallas kernels.

Each op compiles its kernel with Mosaic on a TPU and runs it in interpret
mode on the CPU (the tests' platform; interpret mode executes the kernel
body faithfully for correctness validation).  Any other platform is an
error rather than a silent interpreter run.  The ops expose the
model-layer calling conventions.
``flash_attention_op`` additionally carries a custom_vjp whose backward
recomputes through the jnp reference — the kernel accelerates the forward
path while training remains differentiable.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from . import ref
from .flash_attention import flash_attention
from .kmeans_assign import kmeans_assign
from .knn_topk import knn_topk
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
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_op(q, k, v, causal: bool = True,
                       window: Optional[int] = None):
    """q: (B,H,Sq,d); k,v: (B,K,Skv,d) — kernel forward, reference backward."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=_interpret())


def _fa_fwd(q, k, v, causal, window):
    return flash_attention_op(q, k, v, causal, window), (q, k, v)


def _fa_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=causal,
                                                window=window), q, k, v)
    return vjp(g)


flash_attention_op.defvjp(_fa_fwd, _fa_bwd)


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

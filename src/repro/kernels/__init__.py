"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §7), each with a
pure-jnp oracle in ``ref.py`` and a jit'd wrapper in ``ops.py``:

* ``ops.flash_attention_op`` — causal GQA attention through JAX's Pallas
  splash kernels (fused forward, fused dq and dk/dv backward); the one
  kernel on the model's path, selected by ``layers.attention.attn_path``
  for training and prefill on a single TPU
* ``moe_rows``        — the routed experts' row gather and per-token
  weighted sum, over the rows below a count known at run time
* ``ssd_scan``        — Mamba-2 SSD chunked scan
* ``rglru_scan``      — RG-LRU linear recurrence
* ``knn_topk``        — fused distance + running top-k (paper's KNN_frag)
* ``kmeans_assign``   — fused assign + partial sums (paper's partial_sum)
* ``rmsnorm``         — fused norm

Validated in interpret mode on CPU; TPU is the target (BlockSpec VMEM
tiling, MXU-shaped dot_generals, accumulate-in-output grid patterns).
"""
from . import ops, ref  # noqa: F401
from .kmeans_assign import kmeans_assign  # noqa: F401
from .knn_topk import knn_topk  # noqa: F401
from .rglru_scan import rglru_scan  # noqa: F401
from .rmsnorm import rmsnorm  # noqa: F401
from .ssd_scan import ssd_scan  # noqa: F401

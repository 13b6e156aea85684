"""Fused distance + running top-k for the paper's ``KNN_frag`` hot loop.

Grid: (test-blocks, train-blocks); the train axis is the innermost
(sequential) dimension, and the output blocks — the running best
distances/labels for one test block, padded to one 128-lane tile — are
*revisited* across it (the standard TPU accumulate-in-output pattern).
Per step: one MXU matmul for the -2·X·Yᵀ term, then k selection passes.
Each pass takes the minimum over the running tile and the new block,
finds its first index with an iota mask (Pallas TPU has no dynamic gather
or scatter; the mask keeps everything vectorized), and writes column i of
the new running tile by another iota mask — no lane slice or concatenation
that is not tile-aligned.

Adaptation (DESIGN.md §3): the paper's R implementation leans on BLAS GEMM
+ R ``order()``; here distance and selection fuse in VMEM so candidate
distances never round-trip to HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e30  # plain python float: jnp constants would be captured as consts
LANES = 128  # running top-k tile width; k <= LANES
_HI = jax.lax.Precision.HIGHEST  # f32 distances: neighbour order must be exact


def _kernel(xsq_ref, x_ref, y_ref, ysq_ref, lab_ref, outd_ref, outl_ref,
            *, k: int, n_train: int, block_n: int):
    j = pl.program_id(1)
    m = x_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        outd_ref[...] = jnp.full((m, LANES), BIG, outd_ref.dtype)
        outl_ref[...] = jnp.zeros((m, LANES), outl_ref.dtype)

    x = x_ref[...].astype(jnp.float32)          # (m, d)
    y = y_ref[...].astype(jnp.float32)          # (bn, d)
    d2 = (xsq_ref[...]
          - 2.0 * jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                      precision=_HI,
                                      preferred_element_type=jnp.float32)
          + ysq_ref[...])                       # (m, bn)
    col = jax.lax.broadcasted_iota(jnp.int32, (m, block_n), 1)
    d2 = jnp.where(j * block_n + col < n_train, d2, BIG)
    labs = lab_ref[...]                         # (1, bn)

    # candidates: the running tile (index t) then this block (LANES + c);
    # the running tile holds earlier train indices, so a first-index tie
    # break keeps the reference's stable order
    run_d = outd_ref[...]                       # (m, LANES)
    run_l = outl_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (m, LANES), 1)
    new_d = jnp.full((m, LANES), BIG, jnp.float32)
    new_l = jnp.zeros((m, LANES), jnp.int32)
    none = LANES + block_n
    for i in range(k):                          # k selection passes
        best = jnp.minimum(jnp.min(run_d, axis=1, keepdims=True),
                           jnp.min(d2, axis=1, keepdims=True))   # (m, 1)
        arg = jnp.minimum(
            jnp.min(jnp.where(run_d == best, lane, none), axis=1, keepdims=True),
            jnp.min(jnp.where(d2 == best, LANES + col, none), axis=1,
                    keepdims=True))                              # (m, 1)
        hit_run = lane == arg
        hit_new = LANES + col == arg
        lab = (jnp.sum(jnp.where(hit_run, run_l, 0), axis=1, keepdims=True)
               + jnp.sum(jnp.where(hit_new, labs, 0), axis=1, keepdims=True))
        new_d = jnp.where(lane == i, best, new_d)
        new_l = jnp.where(lane == i, lab, new_l)
        run_d = jnp.where(hit_run, BIG, run_d)
        d2 = jnp.where(hit_new, BIG, d2)
    outd_ref[...] = new_d
    outl_ref[...] = new_l


@functools.partial(jax.jit, static_argnames=("k", "block_m", "block_n",
                                             "interpret"))
def knn_topk(test_x, train_x, train_y, *, k: int = 5, block_m: int = 128,
             block_n: int = 512, interpret: bool = False):
    """test_x: (m, d); train_x: (n, d); train_y: (n,) int32.
    Returns (dists (m, k) ascending, labels (m, k))."""
    if k > LANES:
        raise ValueError(f"k={k} exceeds the {LANES}-lane running tile")
    m, d = test_x.shape
    n = train_x.shape[0]
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    pad_m = (-m) % block_m
    pad_n = (-n) % block_n
    if pad_m:
        test_x = jnp.pad(test_x, ((0, pad_m), (0, 0)))
    if pad_n:
        train_x = jnp.pad(train_x, ((0, pad_n), (0, 0)))
        train_y = jnp.pad(train_y, (0, pad_n))
    xsq = jnp.sum(test_x.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    ysq = jnp.sum(train_x.astype(jnp.float32) ** 2, axis=1)[None, :]
    mp, np_ = m + pad_m, n + pad_n

    grid = (mp // block_m, np_ // block_n)
    outd, outl = pl.pallas_call(
        functools.partial(_kernel, k=k, n_train=n, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, LANES), jnp.float32),
            jax.ShapeDtypeStruct((mp, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(xsq, test_x, train_x, ysq, train_y.astype(jnp.int32)[None, :])
    return outd[:m, :k], outl[:m, :k]

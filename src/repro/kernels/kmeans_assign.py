"""Fused K-means assignment + partial sums (the paper's ``partial_sum``
task) as a Pallas TPU kernel.

Grid over point blocks (sequential); outputs (sums (k,d), counts (k,1),
sse (1,1) in SMEM) are revisited/accumulated across the grid.  The
distance matmul feeds the MXU; the one-hot assignment matrix immediately
contracts into the per-cluster sums (a second MXU matmul) so neither
distances nor assignments ever reach HBM — the kernel emits exactly the
paper's partial results (k·d + k + 1 floats) per fragment.

Layout: distances are built as (k, block_m), points on the lanes, so both
matmuls are plain NT/NN contractions, the argmin is a reduction over the
few centroid sublanes, and every vector is lane-dense.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST  # f32 distances: assignments must be exact


def _kernel(x_ref, c_ref, csq_ref, sums_ref, counts_ref, sse_ref,
            *, n_points: int, block_m: int):
    i = pl.program_id(0)
    kc = c_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        sse_ref[0, 0] = 0.0

    x = x_ref[...].astype(jnp.float32)                        # (m, d)
    c = c_ref[...].astype(jnp.float32)                        # (k, d)
    # distance without |x|^2 (constant per point for the argmin)
    half = (jax.lax.dot_general(c, x, (((1,), (1,)), ((), ())),
                                precision=_HI,
                                preferred_element_type=jnp.float32)
            - 0.5 * csq_ref[...])                             # (k, m)
    best = jnp.max(half, axis=0, keepdims=True)               # (1, m)
    row = jax.lax.broadcasted_iota(jnp.int32, (kc, block_m), 0)
    assign = jnp.min(jnp.where(half == best, row, kc), axis=0,
                     keepdims=True)                           # first argmax
    col = jax.lax.broadcasted_iota(jnp.int32, (1, block_m), 1)
    valid = i * block_m + col < n_points                      # (1, m)
    onehot = ((row == assign) & valid).astype(jnp.float32)    # (k, m)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x, (((1,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    counts_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)
    # padded rows of x are zero, so sum(x*x) needs no mask
    sse_ref[0, 0] += (jnp.sum(x * x)
                      - 2.0 * jnp.sum(jnp.where(valid, best, 0.0)))


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def kmeans_assign(x, centroids, *, block_m: int = 1024,
                  interpret: bool = False):
    """x: (n, d); centroids: (k, d).
    Returns (sums (k,d) f32, counts (k,) i32, sse scalar f32)."""
    n, d = x.shape
    k = centroids.shape[0]
    block_m = min(block_m, n)
    pad = (-n) % block_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    np_ = n + pad
    csq = jnp.sum(centroids.astype(jnp.float32) ** 2, axis=1, keepdims=True)

    grid = (np_ // block_m,)
    sums, counts, sse = pl.pallas_call(
        functools.partial(_kernel, n_points=n, block_m=block_m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, centroids, csq)
    return sums, counts[:, 0].astype(jnp.int32), sse[0, 0]

"""Routed-row movement for the drop-free experts (``layers.moe``): a row
gather and a per-token weighted sum whose work follows the number of rows
the held experts take, a count known only at run time, and not the static
bound ``M = N·min(k, E_l)`` of the buffer those rows live in.

* ``gather_rows(src, idx, count)``: row r is ``src[idx[r]]`` (times
  ``scale[r]``) for r < count. The grid covers ``cdiv(count, tm)`` row
  tiles; rows past the count in the last one are zeros, and later tiles
  are never written.
* ``combine_rows(y, pos, w, count)``: ``out[n] = sum_j [pos[n,j] < count]
  w[n,j] y[pos[n,j]]``, summed in float32 in the order j = 0..k-1. Only
  rows below the count are fetched; each token's row is written once.

Rows arrive one at a time by DMA from HBM, a tile's copies all in flight
on one semaphore. A DMA moves whole (8, 128) tiles of a 2-D array, and
such a tile holds 8 rows (16 in bfloat16), so the rows are first staged,
over the rows below the count only, as an (R, 1, W) array of 32-bit words
whose tiles hold one row each: bfloat16 rows as W = D/2 words, each the
bits of columns c and c + D/2 (the halves of the row side by side), other
rows as float32 (exact for both). Indices reach each tile as a block of
their own in SMEM; only the count is prefetched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_STAGE = 256             # rows a staging tile holds


def _tiles(count, tm: int):
    return (count[0] + tm - 1) // tm


def _count(count):
    return jnp.reshape(count, (1,)).astype(jnp.int32)


_HIGH = np.uint32(0xFFFF0000)


def _stage_kernel(count_ref, x_ref, o_ref):
    tm, _, w = o_ref.shape
    x = x_ref[...].astype(_F32)
    if o_ref.dtype == jnp.uint32:        # a bfloat16 is a float32's high half
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        x = (bits[:, :w] >> 16) | (bits[:, w:] & _HIGH)
    o_ref[...] = x.reshape(tm, 1, w)


def _f32(v):
    """Staged words (..., W) -> the rows' values (..., D) in float32."""
    if v.dtype != jnp.uint32:
        return v
    return jnp.concatenate([jax.lax.bitcast_convert_type(v << 16, _F32),
                            jax.lax.bitcast_convert_type(v & _HIGH, _F32)], axis=-1)


def _stage(x, count, tm: int, interpret: bool):
    """x (R, D) -> (R, 1, W) staged rows, those below ``count`` (1,)."""
    r, d = x.shape
    tm = min(tm, r)
    w, dtype = (d // 2, jnp.uint32) if x.dtype == jnp.bfloat16 else (d, _F32)
    return pl.pallas_call(
        _stage_kernel,
        out_shape=jax.ShapeDtypeStruct((r, 1, w), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(_tiles(count, tm),),
            in_specs=[pl.BlockSpec((tm, d), lambda i, c: (i, 0))],
            out_specs=pl.BlockSpec((tm, 1, w), lambda i, c: (i, 0, 0))),
        interpret=interpret, name="stage_rows",
    )(count, x)


def _gather_kernel(count_ref, idx_ref, src_ref, *refs, scaled: bool, dotted: bool):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    y_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dots_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    tm, d = out_ref.shape
    n = jnp.minimum(count_ref[0] - pl.program_id(0) * tm, tm)

    def start(r, c):
        pltpu.make_async_copy(src_ref.at[idx_ref[0, r]], buf.at[r], sem).start()
        return c

    def wait(r, c):
        pltpu.make_async_copy(src_ref.at[0], buf.at[0], sem).wait()
        return c

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    live = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0) < n
    rows = jnp.where(live, _f32(buf[...].reshape(tm, -1)), 0.0)
    # (1, tm) rows of per-row scalars meet (tm, 1) columns through an
    # (8, tm) transpose, a shape the TPU's lanes hold
    if dotted:
        dots = jnp.sum(jnp.where(live, rows * y_ref[...].astype(_F32), 0.0),
                       axis=1, keepdims=True)
        dots_ref[...] = jnp.broadcast_to(dots, (tm, 8)).T[:1]
    if scaled:
        rows = rows * jnp.broadcast_to(scale_ref[...], (8, tm)).T[:, :1]
    out_ref[...] = rows.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def gather_rows(src, idx, count, scale=None, dot_with=None, *, tm: int = 256,
                interpret: bool = False):
    """src (R, D), idx (M,) int32, count () int32 -> (M, D) in src's dtype:
    row r is ``src[idx[r]]``, times ``scale[r]`` (M,) in float32 where a
    scale is given, for r < count; rows past the count in its tile are
    zeros, later rows are never written. With ``dot_with`` (M, D), also
    the (M,) float32 ``<src[idx[r]], dot_with[r]>`` (unscaled), under the
    same rule."""
    m, d = idx.shape[0], src.shape[1]
    count = _count(count)
    tm = min(tm, m)
    staged = _stage(src, jnp.full((1,), src.shape[0], jnp.int32), _STAGE, interpret)
    row = pl.BlockSpec((1, tm), lambda i, c: (0, i))
    tile = pl.BlockSpec((tm, d), lambda i, c: (i, 0))
    args = [idx.reshape(1, m).astype(jnp.int32), staged]
    in_specs = [pl.BlockSpec((1, tm), lambda i, c: (0, i), memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    out_shape, out_specs = [jax.ShapeDtypeStruct((m, d), src.dtype)], [tile]
    if scale is not None:
        args.append(scale.reshape(1, m).astype(_F32))
        in_specs.append(row)
    if dot_with is not None:
        args.append(dot_with)
        in_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct((1, m), _F32))
        out_specs.append(row)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, scaled=scale is not None,
                          dotted=dot_with is not None),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(_tiles(count, tm),),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tm,) + staged.shape[1:], staged.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        interpret=interpret, name="gather_rows",
    )(count, *args)
    return (out[0], out[1][0]) if dot_with is not None else out[0]


def _combine_kernel(count_ref, live_ref, token_ref, row_ref, w_ref, rows_ref, out_ref,
                    buf, acc, sem):
    live = live_ref[pl.program_id(0)]

    def start(e, c):
        pltpu.make_async_copy(rows_ref.at[row_ref[0, e]], buf.at[e], sem).start()
        return c

    def wait(e, c):
        pltpu.make_async_copy(rows_ref.at[0], buf.at[0], sem).wait()
        return c

    def add(e, c):
        n = token_ref[0, e]
        acc[n] = acc[n] + w_ref[0, e] * _f32(buf[e])
        return c

    jax.lax.fori_loop(0, live, start, 0)
    acc[...] = jnp.zeros(acc.shape, _F32)
    jax.lax.fori_loop(0, live, wait, 0)
    jax.lax.fori_loop(0, live, add, 0)
    out_ref[...] = acc[...].reshape(out_ref.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def combine_rows(y, pos, w, count, *, tn: int = 128, interpret: bool = False):
    """y (M, D), pos (N, k) int32, w (N, k) or a scalar, count () int32 ->
    (N, D) in y's dtype: ``out[n] = sum_j [pos[n,j] < count] w[n,j] y[pos[n,j]]``,
    summed in float32 in the order j = 0..k-1. Rows of y at or past the
    count are never read.

    Each tile of ``tn`` tokens gets its assignments below the count as a
    list of its own, in token-major order (so each token's rows are added
    in the order of j), and loops over that list alone."""
    n, k = pos.shape
    m, d = y.shape
    count = _count(count)
    tn = min(tn, n)
    pad = -n % tn
    tiles, per_tile = (n + pad) // tn, tn * k
    pos = jnp.pad(pos.astype(jnp.int32), ((0, pad), (0, 0)), constant_values=m)
    w = jnp.pad(jnp.broadcast_to(w, (n, k)).astype(_F32), ((0, pad), (0, 0)))
    pos, w = pos.reshape(tiles, per_tile), w.reshape(tiles, per_tile)
    live = pos < count[0]
    first = jnp.argsort(~live, axis=1, stable=True).astype(jnp.int32)

    def listed(a):
        return jnp.take_along_axis(a, first, axis=1).reshape(tiles, 1, per_tile)

    staged = _stage(y, count, _STAGE, interpret)
    block = pl.BlockSpec((None, 1, per_tile), lambda i, c, t: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _combine_kernel,
        out_shape=jax.ShapeDtypeStruct((n + pad, d), y.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=[block, block, block, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tn, d), lambda i, c, t: (i, 0)),
            scratch_shapes=[pltpu.VMEM((per_tile,) + staged.shape[1:], staged.dtype),
                            pltpu.VMEM((tn, 1, d), _F32),
                            pltpu.SemaphoreType.DMA(())]),
        interpret=interpret, name="combine_rows",
    )(count, jnp.sum(live, axis=1, dtype=jnp.int32), (first // k).reshape(tiles, 1, per_tile),
      listed(pos), listed(w), staged)
    return out[:n] if pad else out

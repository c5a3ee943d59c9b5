"""Pallas TPU kernels: fused ELL SpMV + dot -- the CG denominator in the
matrix stream.

Every PCG iteration needs ``ap = A @ p`` *and* ``pap = dot(p, ap)``: unfused,
the dot is a second full HBM pass over ``p`` and ``ap`` right after the SpMV
wrote them.  Azul's PE computes the reduction while the matrix block streams
past; the TPU analogue is to emit per-row-tile dot partials from the SpMV
kernel itself, on the last width step, when the accumulated ``y`` tile is
complete and still VMEM-resident.  Each tile writes a lane-dense (k, 128)
block of per-lane partial sums; the wrapper sums them -- a deterministic,
tiny reduction.

Requires a square padded operator (``x.shape[-1] == rows_p``) -- the layout
the solvers run in (vectors padded to ``n_pad == rows_padded``), where the
row tile of ``x`` aligns with the row tile of ``y``.

Streaming and tiling match ``ell_spmv`` (lane-major (w, rows_p) planes,
``x[cols]`` gathered by XLA ahead of the kernel, grid = (ceil(rows_p / TM),
w / TW) with width innermost).  The multi-RHS variant (``ell_spmm_dot``)
amortizes the one matrix stream over k stacked vectors and emits per-RHS
dot partials.

The ``*_pfold_dot`` variants fold the CG search-direction update into the
same call: ``p = z + beta * p`` is computed by XLA ahead of the gather that
consumes it (one fused elementwise-plus-gather pass), so the iteration has
no standalone p-update op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ell_spmv import _Z, DEFAULT_TM, DEFAULT_TW, lane_major, row_tiles

__all__ = ["ell_spmv_dot", "ell_spmm_dot", "ell_spmv_pfold_dot",
           "ell_spmm_pfold_dot", "stream_rows_dot"]

LANES = 128


def lane_fold(v, width: int):
    """Sum the (k, T) tile ``v`` down to (k, width) by adding its
    lane-aligned column slabs (T is a multiple of ``width``): VPU adds
    only, no cross-lane reduction inside the kernel."""
    acc = v[:, :width]
    for c in range(1, v.shape[1] // width):
        acc = acc + v[:, c * width:(c + 1) * width]
    return acc


def masked_lane_sum(v, i, n: int, width: int):
    """Per-lane partial sums of tile ``i`` (``v`` is (k, T)), dropping the
    entries at or past the true length ``n`` -- the clipped tail of a
    ragged last tile holds garbage."""
    idx = i * v.shape[1] + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return lane_fold(jnp.where(idx < n, v, 0), width)


def partial_width(tm: int) -> int:
    """Lanes per tile of the partial-sum output: 128 when the tile is a
    lane multiple, else the whole (single, full-dimension) tile."""
    return LANES if tm % LANES == 0 else tm


def _rows_dot_kernel(v_ref, g_ref, xr_ref, y_ref, pp_ref, *, rows_p: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nw = pl.num_programs(1)
    part = jnp.sum(v_ref[...][None] * g_ref[...], axis=1)   # (k, TM)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = part

    @pl.when(j != 0)
    def _acc():
        y_ref[...] = y_ref[...] + part

    @pl.when(j == nw - 1)
    def _dot():
        # y tile is complete and still in VMEM: fold the dot partial here,
        # against the row-aligned tile of x -- no second pass over ap
        pp_ref[...] = masked_lane_sum(y_ref[...] * xr_ref[...], i, rows_p,
                                      pp_ref.shape[1])


@functools.partial(jax.jit, static_argnames=("tm", "tw", "interpret"))
def stream_rows_dot(cols, vals, xs, tm: int = DEFAULT_TM,
                    tw: int = DEFAULT_TW, interpret: bool = False):
    """(Y, pap) with Y = (A @ xs^T)^T (k, rows_p) and pap[j] = dot(xs[j],
    Y[j]) (k,), from one matrix stream.  ``xs`` is (k, rows_p)."""
    rows_p, w = cols.shape
    k = xs.shape[0]
    if xs.shape[1] != rows_p:
        raise ValueError(
            f"fused SpMV + dot needs a square padded operator: x {xs.shape} "
            f"vs rows {rows_p}")
    tm, tw, grid = row_tiles(rows_p, w, tm, tw)
    pw = partial_width(tm)
    vt, xg = lane_major(cols, vals, xs)
    y, partials = pl.pallas_call(
        functools.partial(_rows_dot_kernel, rows_p=rows_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tw, tm), lambda i, j: (j, i)),
            pl.BlockSpec((k, tw, tm), lambda i, j: (_Z, j, i)),
            pl.BlockSpec((k, tm), lambda i, j: (_Z, i)),
        ],
        out_specs=[
            pl.BlockSpec((k, tm), lambda i, j: (_Z, i)),
            pl.BlockSpec((k, pw), lambda i, j: (_Z, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, rows_p), vals.dtype),
            jax.ShapeDtypeStruct((k, grid[0] * pw), vals.dtype),
        ],
        interpret=interpret,
    )(vt, xg, xs)
    return y, jnp.sum(partials, axis=1)


@functools.partial(jax.jit, static_argnames=("tm", "tw", "interpret"))
def ell_spmv_dot(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    x: jnp.ndarray,
    tm: int = DEFAULT_TM,
    tw: int = DEFAULT_TW,
    interpret: bool = False,
):
    """Returns (y, pap) with y = A @ x and pap = dot(x, y), one matrix pass.

    A is padded ELL ((rows_p, W) cols/vals, padding vals == 0) and must be
    square in the padded layout: x.shape == (rows_p,).
    """
    y, pap = stream_rows_dot(cols, vals, x[None], tm=tm, tw=tw,
                             interpret=interpret)
    return y[0], pap[0]


@functools.partial(jax.jit, static_argnames=("tm", "tw", "interpret"))
def ell_spmm_dot(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    x: jnp.ndarray,
    tm: int = DEFAULT_TM,
    tw: int = DEFAULT_TW,
    interpret: bool = False,
):
    """Multi-RHS fused SpMM + dot: x is (rows_p, k) dense (kernel layout),
    returns (Y, pap) with Y = A @ X (rows_p, k) and pap[j] = dot(X[:, j],
    Y[:, j]) -- k per-RHS CG denominators from the one matrix stream."""
    if x.ndim != 2:
        raise ValueError(f"ell_spmm_dot expects x of shape (n, k), got {x.shape}")
    y, pap = stream_rows_dot(cols, vals, x.T, tm=tm, tw=tw,
                             interpret=interpret)
    return y.T, pap


@functools.partial(jax.jit, static_argnames=("tm", "tw", "interpret"))
def ell_spmv_pfold_dot(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    z: jnp.ndarray,
    p: jnp.ndarray,
    beta,
    tm: int = DEFAULT_TM,
    tw: int = DEFAULT_TW,
    interpret: bool = False,
):
    """Fused p-update + SpMV + dot: p' = z + beta*p, y = A @ p', pap =
    dot(p', y) -- one matrix stream, no separate p-update op.  Square
    padded operator as in ``ell_spmv_dot``; returns (p', y, pap)."""
    rows_p = cols.shape[0]
    if z.shape != (rows_p,) or p.shape != (rows_p,):
        raise ValueError(
            f"ell_spmv_pfold_dot needs square padded vectors: z {z.shape} / "
            f"p {p.shape} vs rows {rows_p}"
        )
    pn = z + jnp.asarray(beta, vals.dtype) * p
    y, pap = stream_rows_dot(cols, vals, pn[None], tm=tm, tw=tw,
                             interpret=interpret)
    return pn, y[0], pap[0]


@functools.partial(jax.jit, static_argnames=("tm", "tw", "interpret"))
def ell_spmm_pfold_dot(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    z: jnp.ndarray,
    p: jnp.ndarray,
    beta: jnp.ndarray,
    tm: int = DEFAULT_TM,
    tw: int = DEFAULT_TW,
    interpret: bool = False,
):
    """Multi-RHS p-fold: z/p are (rows_p, k) in kernel layout, beta (k,)
    per-RHS.  Returns (p', Y, pap) with p' = z + beta*p, Y = A @ p', and
    pap[j] = dot(p'[:, j], Y[:, j]) -- one matrix stream for everything."""
    if z.ndim != 2:
        raise ValueError(f"ell_spmm_pfold_dot expects (n, k) vectors, got {z.shape}")
    rows_p = cols.shape[0]
    if z.shape[0] != rows_p or p.shape != z.shape:
        raise ValueError(
            f"ell_spmm_pfold_dot needs square padded vectors: z {z.shape} / "
            f"p {p.shape} vs rows {rows_p}"
        )
    pn = z + jnp.reshape(jnp.asarray(beta, vals.dtype), (1, -1)) * p
    y, pap = stream_rows_dot(cols, vals, pn.T, tm=tm, tw=tw,
                             interpret=interpret)
    return pn, y.T, pap

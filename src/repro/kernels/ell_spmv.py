"""Pallas TPU kernel: ELLPACK SpMV/SpMM -- the per-tile hot loop of Azul.

Azul's PE streams its pinned matrix block once per solver iteration and
gathers x values as they arrive over the NoC.  On TPU the block lives in HBM
and is streamed through VMEM by the ``BlockSpec`` pipeline.

Lane-major stream: the padded ELL arrays are (rows_p, w) and the kernel
reads their (w, rows_p) transposes, so rows run along the 128-wide lane
axis and the ELL width along sublanes.  XLA keeps a narrow (rows_p, w)
array column-major on TPU, which makes the transpose a bitcast.  The
column gather ``x[cols]`` runs in XLA ahead of the kernel (Mosaic lowers
only 2-D gathers, and a whole x would not stay VMEM-resident at a million
rows); it yields lane-dense (k, w, rows_p) planes for k stacked vectors.
The kernel streams (vals, gathered x) tiles and reduces over the width:

    y[:, r] = sum_w vals[w, r] * xg[:, w, r]

Tiling:
  grid = (ceil(rows_p / TM), w / TW); the output row tile is revisited along
  the (inner) width axis and accumulated in VMEM, so arbitrary ELL widths
  stream without blowing the VMEM budget:
     VMEM ~ 2 * TM * TW * (1 + k) words + 2 * k * TM words (y).
  TM is a multiple of 128 (or the whole row count) and TW of 8 (or the whole
  width); a ragged last row tile is clipped by the pipeline.

For the MXU path on block-structured matrices use ``bcsr_spmm``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..obs.scopes import scope

__all__ = ["ell_spmv", "ell_spmm", "stream_rows"]

DEFAULT_TM = 8192
DEFAULT_TW = 128
# constant block index: an int32 even when x64 is on (Mosaic rejects the
# int64 a bare Python 0 becomes in an index map under jax_enable_x64)
_Z = np.int32(0)


def lane_major(cols, vals, xs):
    """(vals^T (w, rows_p), xs[:, cols^T] (k, w, rows_p)): the two planes
    the kernel streams.  ``xs`` is (k, m) -- k stacked vectors of any
    length m the column ids index into (the halo buffer on a tile)."""
    with scope("gather"):
        return vals.T, xs[:, cols.T]


def row_tiles(rows_p: int, w: int, tm: int, tw: int):
    """Clamp (tm, tw) to the shape and return (tm, tw, grid)."""
    tm = min(tm, rows_p)
    tw = min(tw, w)
    if w % tw:
        raise ValueError(f"ELL width {w} not divisible by tile {tw}")
    return tm, tw, (pl.cdiv(rows_p, tm), w // tw)


def _rows_kernel(v_ref, g_ref, y_ref):
    j = pl.program_id(1)
    part = jnp.sum(v_ref[...][None] * g_ref[...], axis=1)   # (k, TM)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = part

    @pl.when(j != 0)
    def _acc():
        y_ref[...] = y_ref[...] + part


@functools.partial(jax.jit, static_argnames=("tm", "tw", "interpret"))
def stream_rows(cols, vals, xs, tm: int = DEFAULT_TM, tw: int = DEFAULT_TW,
                interpret: bool = False):
    """Y = (A @ xs^T)^T for padded-ELL A and k stacked vectors ``xs`` (k, m):
    returns (k, rows_p).  The matrix streams through VMEM once per call
    while every tile is applied to all k vectors.  Padding entries must have
    vals == 0 (cols may be anything in-bounds)."""
    rows_p, w = cols.shape
    k = xs.shape[0]
    tm, tw, grid = row_tiles(rows_p, w, tm, tw)
    vt, xg = lane_major(cols, vals, xs)
    return pl.pallas_call(
        _rows_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tw, tm), lambda i, j: (j, i)),
            pl.BlockSpec((k, tw, tm), lambda i, j: (_Z, j, i)),
        ],
        out_specs=pl.BlockSpec((k, tm), lambda i, j: (_Z, i)),
        out_shape=jax.ShapeDtypeStruct((k, rows_p), vals.dtype),
        interpret=interpret,
    )(vt, xg)


@functools.partial(jax.jit, static_argnames=("tm", "tw", "interpret"))
def ell_spmv(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    x: jnp.ndarray,
    tm: int = DEFAULT_TM,
    tw: int = DEFAULT_TW,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = A @ x, A in padded ELL ((rows_p, W) cols/vals).  Padding entries
    must have vals == 0 (cols may be anything in-bounds)."""
    return stream_rows(cols, vals, x[None], tm=tm, tw=tw, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("tm", "tw", "interpret"))
def ell_spmm(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    x: jnp.ndarray,
    tm: int = DEFAULT_TM,
    tw: int = DEFAULT_TW,
    interpret: bool = False,
) -> jnp.ndarray:
    """Y = A @ X for padded-ELL A and dense X of shape (n, k) -- the batched
    multi-RHS SpMV.  The matrix block streams through VMEM exactly once per
    call while every tile is applied to all k vectors, so the arithmetic
    intensity grows ~k-fold over ``ell_spmv`` at the same matrix traffic
    (the regime batched solver workloads live in).  Returns (rows_p, k).
    Padding entries must have vals == 0."""
    if x.ndim != 2:
        raise ValueError(f"ell_spmm expects x of shape (n, k), got {x.shape}")
    return stream_rows(cols, vals, x.T, tm=tm, tw=tw, interpret=interpret).T

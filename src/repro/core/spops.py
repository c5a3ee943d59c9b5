"""Single-device sparse ops on packed formats (pure jax.numpy).

These are the *functional* definitions of the engine's math; the Pallas
kernels in ``repro.kernels`` implement the same contracts with explicit VMEM
tiling and are verified against these (plus numpy/scipy) in tests.  The
distributed engine composes these per-tile ops under ``shard_map``.
The matvecs run under the ``matvec`` scope and their gathers of x by
column id under ``gather`` (``repro.obs.scopes``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.scopes import scope
from .formats import ELL, BCSR, HYB, SELL
from .levels import LevelSchedule

__all__ = [
    "spmv_ell",
    "spmv_ell_padded",
    "spmm_ell_padded",
    "spmv_sell_flat",
    "spmm_sell_flat",
    "spmv_hyb_padded",
    "spmm_hyb_padded",
    "spmv_bcsr",
    "spmv_bcsr_padded",
    "spmm_bcsr_padded",
    "sptrsv_ell",
    "sptrsv_ell_unrolled",
    "extract_diag_ell",
]


def spmv_ell(m: ELL, x: jnp.ndarray) -> jnp.ndarray:
    """y = A @ x for ELLPACK A; returns the true (n_rows,) result."""
    return spmv_ell_padded(m.cols, m.vals, x)[: m.n_rows]


@scope("matvec")
def spmv_ell_padded(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Padded-row SpMV: (rows_p, w) gather + row-sum.  Padding vals are 0 so
    padded slots contribute nothing; padded cols point at 0 which is always
    in-bounds."""
    with scope("gather"):
        xg = x[cols]
    return jnp.sum(vals * xg, axis=1)


@scope("matvec")
def spmm_ell_padded(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Batched multi-RHS SpMV in the solvers' stacked layout: x is (k, n),
    returns (k, rows_p).  One gather of the matrix serves all k vectors --
    x[:, cols] is (k, rows_p, w), weighted by the shared (rows_p, w) vals."""
    with scope("gather"):
        xg = x[:, cols]
    return jnp.sum(vals * xg, axis=-1)


@scope("matvec")
def spmv_sell_flat(m: SELL, x: jnp.ndarray) -> jnp.ndarray:
    """Padded-row SpMV over sliced-ELL flat storage: one gather of x per
    stored entry, then a segment-sum by row id.  Returns (rows_padded,)
    (padded rows reduce only their own 0.0 padding entries)."""
    with scope("gather"):
        xg = x[m.cols]
    return jax.ops.segment_sum(
        m.vals * xg, m.rows, num_segments=m.rows_padded
    )


@scope("matvec")
def spmm_sell_flat(m: SELL, x: jnp.ndarray) -> jnp.ndarray:
    """Multi-RHS sliced-ELL SpMV in the solvers' stacked layout: x is
    (k, n_pad), returns (k, rows_padded).  One matrix stream serves all k
    (the segment reduction runs over the leading entry axis)."""
    with scope("gather"):
        xg = x[:, m.cols]
    contrib = m.vals * xg                       # (k, n_stored)
    return jax.ops.segment_sum(
        contrib.T, m.rows, num_segments=m.rows_padded
    ).T


@scope("matvec")
def spmv_hyb_padded(m: HYB, x: jnp.ndarray) -> jnp.ndarray:
    """HYB SpMV: the regular ELL-core gather + row-sum, then a COO
    scatter-add of the spill tail.  Returns (rows_padded,)."""
    with scope("gather"):
        xg = x[m.cols]
    y = jnp.sum(m.vals * xg, axis=1)
    with scope("gather"):
        xt = x[m.tail_cols]
    return y.at[m.tail_rows].add(m.tail_vals * xt)


@scope("matvec")
def spmm_hyb_padded(m: HYB, x: jnp.ndarray) -> jnp.ndarray:
    """Multi-RHS HYB SpMV: x is (k, n_pad), returns (k, rows_padded)."""
    with scope("gather"):
        xg = x[:, m.cols]
    y = jnp.sum(m.vals * xg, axis=-1)
    with scope("gather"):
        xt = x[:, m.tail_cols]
    return y.at[:, m.tail_rows].add(m.tail_vals * xt)


@scope("matvec")
def spmv_bcsr(m: BCSR, x: jnp.ndarray) -> jnp.ndarray:
    """y = A @ x for BCSR A (dense (bm, bn) blocks -> MXU-shaped einsum)."""
    nbc = (m.n_cols + m.bn - 1) // m.bn
    x_pad = jnp.zeros((nbc * m.bn,), x.dtype).at[: m.n_cols].set(x)
    xb = x_pad.reshape(nbc, m.bn)
    with scope("gather"):
        xg = xb[m.block_cols]                  # (nbr, width, bn)
    y = jnp.einsum("iwmn,iwn->im", m.blocks, xg)  # (nbr, bm)
    return y.reshape(-1)[: m.n_rows]


@scope("matvec")
def spmv_bcsr_padded(m: BCSR, x: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """BCSR SpMV on padded engine vectors: x is (n_pad,), returns (n_pad,).
    x re-embeds into the (nbc*bn,) block layout, blocks apply as dense
    (bm, bn) fmas, and the (nbr*bm,) result re-embeds into n_pad."""
    nbc = (m.n_cols + m.bn - 1) // m.bn
    x_blk = jnp.zeros((nbc * m.bn,), x.dtype).at[: m.n_cols].set(x[: m.n_cols])
    with scope("gather"):
        xg = x_blk.reshape(nbc, m.bn)[m.block_cols]  # (nbr, width, bn)
    y = jnp.einsum("iwmn,iwn->im", m.blocks, xg).reshape(-1)
    nbr_rows = y.shape[0]
    if nbr_rows >= n_pad:
        return y[:n_pad]
    return jnp.zeros((n_pad,), y.dtype).at[:nbr_rows].set(y)


@scope("matvec")
def spmm_bcsr_padded(m: BCSR, x: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """Multi-RHS BCSR SpMV: x is (k, n_pad), returns (k, n_pad) -- one
    block stream for all k (the einsum carries the batch axis)."""
    nbc = (m.n_cols + m.bn - 1) // m.bn
    k = x.shape[0]
    x_blk = jnp.zeros((k, nbc * m.bn), x.dtype).at[:, : m.n_cols].set(
        x[:, : m.n_cols])
    with scope("gather"):
        xg = x_blk.reshape(k, nbc, m.bn)[:, m.block_cols]  # (k, nbr, w, bn)
    y = jnp.einsum("iwmn,kiwn->kim", m.blocks, xg).reshape(k, -1)
    nbr_rows = y.shape[1]
    if nbr_rows >= n_pad:
        return y[:, :n_pad]
    return jnp.zeros((k, n_pad), y.dtype).at[:, :nbr_rows].set(y)


def extract_diag_ell(m: ELL) -> jnp.ndarray:
    """Diagonal of a square ELL matrix (0.0 where absent)."""
    r = jnp.arange(m.rows_padded)[:, None]
    is_diag = (m.cols == r) & (m.vals != 0)
    return jnp.sum(jnp.where(is_diag, m.vals, 0.0), axis=1)[: m.n_rows]


def sptrsv_ell(m: ELL, sched: LevelSchedule, b: jnp.ndarray) -> jnp.ndarray:
    """Solve L x = b for lower-triangular L in ELL form, via the wavefront
    schedule.  ``lax.scan`` over levels; each level solves all of its rows in
    one vector step:

        x[r] = (b[r] - sum_{c<r} L[r,c] x[c]) / L[r,c==r]

    Rows in a level never depend on each other (schedule invariant), so the
    gather of x inside a level sees only values finalized by prior levels.
    """
    n = m.n_rows
    if sched.n != n:
        raise ValueError("schedule/matrix size mismatch")
    diag = extract_diag_ell(m)
    diag = jnp.where(diag == 0, 1.0, diag)  # padded rows / graceful degenerate
    b_pad = jnp.zeros((m.rows_padded,), b.dtype).at[:n].set(b)

    # x carries one extra slot (index n) that absorbs padded scatter/gather.
    x0 = jnp.zeros((n + 1,), b.dtype)
    cols, vals = m.cols, m.vals

    def level_step(x, level_rows):
        # level_rows: (max_width,) row ids, padded with n (dropped on scatter)
        lrows = jnp.minimum(level_rows, m.rows_padded - 1)
        c = cols[lrows]                     # (W, w)
        v = vals[lrows]                     # (W, w)
        off_mask = c != lrows[:, None]      # exclude the diagonal entry
        contrib = jnp.sum(jnp.where(off_mask, v, 0.0) * x[jnp.minimum(c, n)], axis=1)
        rhs = b_pad[lrows] - contrib
        xr = rhs / diag[jnp.minimum(level_rows, n - 1)] if n else rhs
        x = x.at[level_rows].set(xr, mode="drop")
        return x, None

    x, _ = jax.lax.scan(level_step, x0, sched.rows)
    return x[:n]


def sptrsv_ell_unrolled(m: ELL, sched: LevelSchedule, b: jnp.ndarray) -> jnp.ndarray:
    """The trace-time-unrolled wavefront baseline of :func:`sptrsv_ell`:
    one Python-loop slice of the identical per-level arithmetic per level,
    so the traced graph grows LINEARLY with the level count.

    This exists only to benchmark what ``lax.scan`` over the padded level
    structure buys: ``plan()``/trace wall time at thousands of levels
    (``benchmarks/bench_sptrsv.py`` records scan-vs-unrolled growth under
    the regression gate).  Under ``jax.jit`` results are bitwise identical
    to the scan -- same level body, same order; eager execution can differ
    by an ulp (op-by-op dispatch fuses the level body differently than the
    compiled scan)."""
    n = m.n_rows
    if sched.n != n:
        raise ValueError("schedule/matrix size mismatch")
    diag = extract_diag_ell(m)
    diag = jnp.where(diag == 0, 1.0, diag)
    b_pad = jnp.zeros((m.rows_padded,), b.dtype).at[:n].set(b)
    x = jnp.zeros((n + 1,), b.dtype)
    cols, vals = m.cols, m.vals

    for level_rows in np.asarray(sched.rows):
        lrows = jnp.minimum(level_rows, m.rows_padded - 1)
        c = cols[lrows]
        v = vals[lrows]
        off_mask = c != lrows[:, None]
        contrib = jnp.sum(jnp.where(off_mask, v, 0.0) * x[jnp.minimum(c, n)],
                          axis=1)
        rhs = b_pad[lrows] - contrib
        xr = rhs / diag[jnp.minimum(level_rows, n - 1)] if n else rhs
        x = x.at[level_rows].set(xr, mode="drop")
    return x[:n]

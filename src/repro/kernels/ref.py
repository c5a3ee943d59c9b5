"""Pure-jnp oracles for every Pallas kernel (the "Python testbench" of the
paper's functional-verification methodology).  Each function is the exact
mathematical contract its kernel must match; tests assert allclose across
shape/dtype sweeps with the kernels running in interpret mode on CPU.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..obs.scopes import scope

__all__ = [
    "ell_spmv_ref", "ell_spmm_ref", "bcsr_spmm_ref",
    "sptrsv_level_step_ref", "sptrsv_solve_dot_ref", "axpy_dot_ref",
    "ell_spmv_dot_ref", "ell_spmm_dot_ref", "cg_update_ref",
    "ell_spmv_pfold_dot_ref", "ell_spmm_pfold_dot_ref",
]


def ell_spmv_ref(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """y[r] = sum_k vals[r, k] * x[cols[r, k]].  Padding: vals == 0."""
    with scope("gather"):
        xg = x[cols]
    return jnp.sum(vals * xg, axis=1)


def ell_spmm_ref(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Multi-RHS ELL SpMM: x is (n, k) dense, returns (rows_p, k).

    Y[r, :] = sum_w vals[r, w] * x[cols[r, w], :] -- one matrix read shared
    by all k right-hand sides."""
    with scope("gather"):
        xg = x[cols]
    return jnp.sum(vals[..., None] * xg, axis=1)


def bcsr_spmm_ref(block_cols: jnp.ndarray, blocks: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Block-sparse (BCSR) times multi-RHS dense.

    block_cols: (nbr, w) int32
    blocks:     (nbr, w, bm, bn)
    x:          (nbc * bn, R)
    returns     (nbr * bm, R)
    """
    nbr, w, bm, bn = blocks.shape
    xr = x.reshape(-1, bn, x.shape[-1])          # (nbc, bn, R)
    with scope("gather"):
        xg = xr[block_cols]                      # (nbr, w, bn, R)
    y = jnp.einsum("iwmn,iwnr->imr", blocks, xg)
    return y.reshape(nbr * bm, x.shape[-1])


def sptrsv_level_step_ref(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    diag: jnp.ndarray,
    b: jnp.ndarray,
    x: jnp.ndarray,
    level_rows: jnp.ndarray,
) -> jnp.ndarray:
    """One wavefront of the level-scheduled triangular solve.

    For each r in level_rows (padded with an out-of-range id == x.size - 1
    sentinel slot):  x_new[r] = (b[r] - sum_{c != r} L[r,c] x[c]) / diag[r].
    Returns the scattered-updated x (x has one trailing sentinel slot).
    """
    n = x.shape[0] - 1
    rows_p = cols.shape[0]
    lr = jnp.minimum(level_rows, rows_p - 1)
    c = cols[lr]
    v = vals[lr]
    off = jnp.where(c != lr[:, None], v, 0.0)
    contrib = jnp.sum(off * x[jnp.minimum(c, n)], axis=1)
    rhs = b[lr] - contrib
    xr = rhs / diag[jnp.minimum(level_rows, n - 1)]
    return x.at[level_rows].set(xr, mode="drop")


def sptrsv_solve_dot_ref(
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    dinv: jnp.ndarray,
    b: jnp.ndarray,
    sched_rows: jnp.ndarray,
    wdot: jnp.ndarray,
    n_rows: int,
):
    """Whole level-scheduled lower solve plus dot(wdot, x), the contract of
    the fused ``sptrsv_solve_dot`` kernel.

    cols/vals: (rows_p, w) padded ELL of L; dinv: (rows_p,) inverse diagonal
    (1.0 in padded rows); b/wdot: (rows_p,); sched_rows: (n_levels, W) row
    ids padded with a sentinel >= n_rows.  Returns (x (rows_p,), pp scalar).
    """
    import jax

    rows_p = cols.shape[0]
    x0 = jnp.zeros((rows_p + 1,), vals.dtype)

    def level_step(x, level_rows):
        lr = jnp.minimum(level_rows, rows_p - 1)
        c = cols[lr]
        v = vals[lr]
        off = jnp.where(c != lr[:, None], v, 0.0)
        contrib = jnp.sum(off * x[c], axis=1)
        xr = (b[lr] - contrib) * dinv[lr]
        xr = jnp.where(level_rows < n_rows, xr, 0.0)
        sc = jnp.minimum(level_rows, rows_p)       # sentinel -> absorber slot
        return x.at[sc].add(xr), None

    x, _ = jax.lax.scan(level_step, x0, sched_rows)
    x = x[:rows_p]
    return x, jnp.sum(wdot * x)


def axpy_dot_ref(a, x: jnp.ndarray, y: jnp.ndarray):
    """Fused z = y + a*x ; returns (z, dot(z, z)) -- one CG pipeline stage."""
    z = y + a * x
    return z, jnp.sum(z * z)


def ell_spmv_dot_ref(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray):
    """Fused SpMV + dot: (y, pap) = (A @ x, dot(x, y)) -- square padded
    operator, x.shape == (rows_p,)."""
    with scope("gather"):
        xg = x[cols]
    y = jnp.sum(vals * xg, axis=1)
    with scope("reduce"):
        return y, jnp.sum(x * y)


def ell_spmm_dot_ref(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray):
    """Multi-RHS fused SpMM + dot in kernel layout: x (rows_p, k) dense ->
    (Y, pap) with Y = A @ X (rows_p, k), pap[j] = dot(X[:, j], Y[:, j])."""
    with scope("gather"):
        xg = x[cols]
    y = jnp.sum(vals[..., None] * xg, axis=1)
    with scope("reduce"):
        return y, jnp.sum(x * y, axis=0)


def ell_spmv_pfold_dot_ref(cols, vals, z, p, beta):
    """p-fold contract: p' = z + beta*p computed at gather time, then
    (p', y, pap) = (p', A @ p', dot(p', y)) from the one matrix stream."""
    with scope("gather"):
        pn = z + beta * p
        pg = pn[cols]
    y = jnp.sum(vals * pg, axis=1)
    with scope("reduce"):
        return pn, y, jnp.sum(pn * y)


def ell_spmm_pfold_dot_ref(cols, vals, z, p, beta):
    """Multi-RHS p-fold in kernel layout: z/p (rows_p, k), beta (k,).
    Returns (p', Y, pap) with pap[j] = dot(p'[:, j], Y[:, j])."""
    with scope("gather"):
        pn = z + jnp.reshape(beta, (1, -1)) * p
        pg = pn[cols]
    y = jnp.sum(vals[..., None] * pg, axis=1)
    with scope("reduce"):
        return pn, y, jnp.sum(pn * y, axis=0)


def cg_update_ref(alpha, x, r, p, ap, dinv=None):
    """One-pass CG update contract (solvers' dot convention: scalars for
    (n,) vectors, (k, 1) for (k, n) batches):

        x' = x + alpha p;  r' = r - alpha ap;  z = dinv r' (or r');
        rr = dot(r', r');  rz = dot(r', z).
    """
    xo = x + alpha * p
    ro = r - alpha * ap
    kd = ro.ndim > 1
    rr = jnp.sum(ro * ro, axis=-1, keepdims=kd)
    if dinv is None:
        return xo, ro, ro, rr, rr
    z = ro * dinv
    rz = jnp.sum(ro * z, axis=-1, keepdims=kd)
    return xo, ro, z, rr, rz

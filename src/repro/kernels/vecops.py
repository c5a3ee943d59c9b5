"""Pallas TPU kernels: fused CG vector-op pipeline stages.

Each CG iteration runs a handful of length-n vector ops (axpys, dots,
preconditioner scaling).  Unfused, every op streams the vectors HBM->VMEM
again; the memory roofline term is 2-3x larger than necessary.

``axpy_dot`` is the original two-op fusion (z = y + a*x with dot(z, z)).
``cg_update`` is the generalized one-pass CG update the solvers actually
need:

    x' = x + alpha * p
    r' = r - alpha * ap
    z  = dinv * r'            (Jacobi psolve; identity when dinv is None)
    rr = dot(r', r')          (residual norm for the trace)
    rz = dot(r', z)           (the next beta's numerator)

Five vector reads, three writes (two without ``dinv``: z is r'), both dots
emitted as per-tile partials in the same pass -- vs. five separate XLA ops
re-streaming everything.  Vectors run as (k, n) rows (a single vector is
k = 1), tiled along n in lane-dense (k, TN) blocks; each tile writes its dot
partials as a (k, 128) block of per-lane sums that the wrapper adds up.
Arbitrary n: the last tile is clipped by the pipeline and masked (an iota
against the true ``n``) so the dot partials stay exact.

grid = (ceil(n / TN),); VMEM ~ 2 * (5 reads + 3 writes) * k * TN words.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ell_spmv import _Z
from .spmv_dot import masked_lane_sum, partial_width

__all__ = ["axpy_dot", "cg_update"]

DEFAULT_TN = 8192


def _axpy_dot_kernel(a_ref, x_ref, y_ref, z_ref, p_ref, *, n):
    z = y_ref[...] + a_ref[...] * x_ref[...]
    z_ref[...] = z
    p_ref[...] = masked_lane_sum(z * z, pl.program_id(0), n, p_ref.shape[1])


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def axpy_dot(
    a: jnp.ndarray,
    x: jnp.ndarray,
    y: jnp.ndarray,
    tn: int = DEFAULT_TN,
    interpret: bool = False,
):
    """Returns (z, zz) with z = y + a*x and zz = dot(z, z)."""
    (n,) = x.shape
    tn = min(tn, n)
    pw = partial_width(tn)
    nt = pl.cdiv(n, tn)
    vec = lambda: pl.BlockSpec((1, tn), lambda i: (_Z, i))
    z, partials = pl.pallas_call(
        functools.partial(_axpy_dot_kernel, n=n),
        grid=(nt,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (_Z, _Z)), vec(), vec()],
        out_specs=[vec(), pl.BlockSpec((1, pw), lambda i: (_Z, i))],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), x.dtype),
            jax.ShapeDtypeStruct((1, nt * pw), x.dtype),
        ],
        interpret=interpret,
    )(jnp.reshape(a, (1, 1)).astype(x.dtype), x[None], y[None])
    return z[0], jnp.sum(partials)


# ---------------------------------------------------------------------------
# fused CG update: x', r', z and both dots in one pass
# ---------------------------------------------------------------------------


def _cg_update_kernel(a_ref, x_ref, r_ref, p_ref, ap_ref, *rest, n, jacobi):
    i = pl.program_id(0)
    a = a_ref[...]                       # (k, 1) per-RHS alphas
    if jacobi:
        d_ref, xo_ref, ro_ref, zo_ref, rr_ref, rz_ref = rest
    else:
        xo_ref, ro_ref, rr_ref = rest
    xo_ref[...] = x_ref[...] + a * p_ref[...]
    ro = r_ref[...] - a * ap_ref[...]    # (k, TN)
    ro_ref[...] = ro
    width = rr_ref.shape[1]
    rr_ref[...] = masked_lane_sum(ro * ro, i, n, width)
    if jacobi:
        # identity-psolve plans (IC(0), unpreconditioned CG) skip this
        # branch: no dinv stream, no z write -- z is r'
        z = ro * d_ref[...]              # (1, TN) dinv broadcasts over k
        zo_ref[...] = z
        rz_ref[...] = masked_lane_sum(ro * z, i, n, width)


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def cg_update(
    alpha,
    x: jnp.ndarray,
    r: jnp.ndarray,
    p: jnp.ndarray,
    ap: jnp.ndarray,
    dinv: jnp.ndarray | None = None,
    tn: int = DEFAULT_TN,
    interpret: bool = False,
):
    """One-pass CG update (see module docstring).

    ``x``/``r``/``p``/``ap``: (n,) or batched (k, n); ``alpha``: scalar or
    (k, 1); ``dinv``: (n,) Jacobi inverse diagonal or None (identity
    psolve -- z comes back equal to r', and the kernel skips the dinv
    stream and the z write entirely instead of multiplying by ones).
    Returns (x', r', z, rr, rz) with rr/rz following the solvers' dot
    convention: () scalars for (n,) vectors, (k, 1) for batches.
    """
    n = x.shape[-1]
    batched = x.ndim == 2
    dt = r.dtype
    jacobi = dinv is not None
    rows = lambda v: jnp.asarray(v, dt).reshape(-1, n)
    x, r, p, ap = (rows(v) for v in (x, r, p, ap))
    k = x.shape[0]
    tn = min(tn, n)
    pw = partial_width(tn)
    nt = pl.cdiv(n, tn)
    a_arr = jnp.broadcast_to(jnp.reshape(jnp.asarray(alpha, dt), (-1, 1)), (k, 1))
    vec = lambda: pl.BlockSpec((k, tn), lambda i: (_Z, i))
    part = lambda: pl.BlockSpec((k, pw), lambda i: (_Z, i))
    vshape = jax.ShapeDtypeStruct((k, n), dt)
    pshape = jax.ShapeDtypeStruct((k, nt * pw), dt)
    dspec = (pl.BlockSpec((1, tn), lambda i: (_Z, i)),) if jacobi else ()
    outs = pl.pallas_call(
        functools.partial(_cg_update_kernel, n=n, jacobi=jacobi),
        grid=(nt,),
        in_specs=[pl.BlockSpec((k, 1), lambda i: (_Z, _Z)),
                  vec(), vec(), vec(), vec(), *dspec],
        out_specs=([vec(), vec(), vec(), part(), part()] if jacobi
                   else [vec(), vec(), part()]),
        out_shape=([vshape] * 3 + [pshape] * 2 if jacobi
                   else [vshape] * 2 + [pshape]),
        interpret=interpret,
    )(a_arr, x, r, p, ap, *((rows(dinv),) if jacobi else ()))
    if jacobi:
        xo, ro, zo, rr, rz = outs
        rr = jnp.sum(rr, axis=1, keepdims=True)
        rz = jnp.sum(rz, axis=1, keepdims=True)
    else:
        xo, ro, rr = outs
        zo = ro
        rr = rz = jnp.sum(rr, axis=1, keepdims=True)
    if batched:
        return xo, ro, zo, rr, rz
    return xo[0], ro[0], zo[0], rr[0, 0], rz[0, 0]

"""jit'd public wrappers over the Pallas kernels.

Each op picks the Pallas kernel when it is applicable on the current
backend (TPU, or interpret mode for CPU validation) and otherwise falls
back to the jnp oracle in ``ref.py`` -- the two are allclose-verified in
tests, so the choice is purely a performance/backend decision.

``backend_mode(mode)``: "auto" (TPU -> compiled kernel, CPU -> jnp),
"interpret" (kernel body in Python -- CI validation), "never".  The initial
mode can be set with the ``REPRO_KERNEL_MODE`` environment variable (used
by the CI bench smoke job to exercise kernels on CPU runners).

Each op runs under the ``repro.obs.scopes`` scope of its layer (the ELL
and BCSR streams ``matvec``, the triangular solves ``precond``, the vector
updates ``update``), on the kernel and the jnp path alike.

Tile selection: explicit tile args always win; otherwise the wrappers
consult the autotune cache (``autotune.py``, populated by
``bench_kernels --autotune``) for this op/shape/dtype/backend, and finally
fall back to the kernel defaults: lane-axis tiles (rows, vector length) are
multiples of 128 sized to a VMEM budget, or the whole axis when it is
short; ELL width tiles are divisors of the width in multiples of 8.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ..obs.scopes import scope
from . import autotune, ref
from .ell_spmv import ell_spmv as _ell_spmv_pallas
from .ell_spmv import ell_spmm as _ell_spmm_pallas
from .ell_spmv import DEFAULT_TM, DEFAULT_TW
from .bcsr_spmm import bcsr_spmm as _bcsr_spmm_pallas
from .spmv_dot import ell_spmv_dot as _ell_spmv_dot_pallas
from .spmv_dot import ell_spmm_dot as _ell_spmm_dot_pallas
from .spmv_dot import ell_spmv_pfold_dot as _ell_spmv_pfold_dot_pallas
from .spmv_dot import ell_spmm_pfold_dot as _ell_spmm_pfold_dot_pallas
from .sptrsv import sptrsv_level_step as _sptrsv_step_pallas
from .sptrsv import sptrsv_solve_dot as _sptrsv_solve_dot_pallas
from .sptrsv import DEFAULT_TL
from .vecops import axpy_dot as _axpy_dot_pallas
from .vecops import cg_update as _cg_update_pallas
from .vecops import DEFAULT_TN
from .spmv_dot import LANES

__all__ = [
    "ell_spmv", "ell_spmm", "ell_spmv_dot", "ell_spmm_dot", "bcsr_spmm",
    "ell_spmv_pfold_dot", "ell_spmm_pfold_dot",
    "sptrsv_level_step", "sptrsv_solve_dot", "sptrsv_solve_pack",
    "axpy_dot", "cg_update",
    "backend_mode", "kernels_active", "kernel_usable",
]

_MODE = os.environ.get("REPRO_KERNEL_MODE", "auto")
if _MODE not in ("auto", "interpret", "never"):
    _MODE = "auto"


def backend_mode(mode: str | None = None) -> str:
    """Get/set the global kernel dispatch mode ('auto'|'interpret'|'never')."""
    global _MODE
    if mode is not None:
        if mode not in ("auto", "interpret", "never"):
            raise ValueError(mode)
        _MODE = mode
    return _MODE


def _dispatch() -> tuple[bool, bool]:
    """-> (use_kernel, interpret)."""
    if _MODE == "never":
        return False, False
    if _MODE == "interpret":
        return True, True
    on_tpu = jax.default_backend() == "tpu"
    return on_tpu, False


def kernels_active() -> bool:
    """True when ops dispatch to Pallas kernels (compiled or interpret)."""
    return _dispatch()[0]


# Kernels the TPU compiler refuses at real sizes (an AOT compile for v5e
# rejects their rank-1 level blocks and in-kernel 1-D gathers); the main-path
# kernels that do compile are guarded by tests/test_tpu_aot.py.  These run
# in interpret mode only, for semantic validation.
UNCOMPILABLE = frozenset({"sptrsv_level_step", "sptrsv_solve_dot"})


def kernel_usable(op: str) -> bool:
    """True when ``op`` dispatches to a kernel that can actually run here:
    kernels are active and either interpreted or compilable."""
    use, interp = _dispatch()
    return use and (interp or op not in UNCOMPILABLE)


def _fit(total: int, pref: int, quantum: int = 1) -> int:
    """Largest divisor of ``total`` that is <= pref (preferring multiples of
    ``quantum``) -- clamps a preferred tile to a valid one for the shape."""
    pref = max(1, min(pref, total))
    for d in range(pref, 0, -1):
        if total % d == 0 and d % quantum == 0:
            return d
    for d in range(pref, 0, -1):
        if total % d == 0:
            return d
    return total


# VMEM the picked row/vector tiles may fill (double-buffered): half of the
# 16 MiB scoped default on TPU v5e, leaving room for the compiler
VMEM_BUDGET = 8 << 20


def _lane_tile(total: int, pref: int, words_per_lane: int = 1,
               itemsize: int = 4) -> int:
    """Tile along a lane axis of length ``total``: the whole axis when it
    fits ``pref``, else a multiple of 128 no larger than ``pref`` nor than
    the VMEM budget allows at ``words_per_lane`` words per position (the
    last tile may be ragged: kernels clip and mask it)."""
    cap = max(LANES, VMEM_BUDGET // (words_per_lane * itemsize))
    pref = min(pref, cap)
    if total <= pref:
        return total
    return max(LANES, pref // LANES * LANES)


def _tiles_2d(op: str, cols, dtype, tm, tw, k: int = 1):
    """Resolve (tm, tw) for an ELL-shaped kernel streaming k vectors.
    Explicit args pass through untouched (callers pin tiles deliberately,
    e.g. for VMEM budgets or autotune candidates); missing args come from
    the autotune cache, else the defaults: tw a divisor of the width in
    multiples of 8, tm a lane tile sized to the VMEM budget."""
    rows_p, w = cols.shape
    hit = None
    if tm is None or tw is None:
        hit = autotune.lookup(op, (rows_p, w), dtype) or {}
    if tw is None:
        tw = _fit(w, hit.get("tw") or DEFAULT_TW, 8)
    if tm is None:
        # vals + k gathered planes (tw words each), y + x row tiles
        words = 2 * (tw * (1 + k) + 2 * k)
        tm = _lane_tile(rows_p, hit.get("tm") or DEFAULT_TM, words,
                        jnp.dtype(dtype).itemsize)
    return tm, tw


@scope("matvec")
def ell_spmv(cols, vals, x, tm: int | None = None, tw: int | None = None):
    use, interp = _dispatch()
    if use:
        tm, tw = _tiles_2d("ell_spmv", cols, vals.dtype, tm, tw)
        return _ell_spmv_pallas(cols, vals, x, tm=tm, tw=tw, interpret=interp)
    return ref.ell_spmv_ref(cols, vals, x)


@scope("matvec")
def ell_spmm(cols, vals, x, tm: int | None = None, tw: int | None = None):
    """Multi-RHS SpMM; x is (n, k) dense, one matrix stream for all k."""
    use, interp = _dispatch()
    if use:
        tm, tw = _tiles_2d("ell_spmm", cols, vals.dtype, tm, tw, x.shape[1])
        return _ell_spmm_pallas(cols, vals, x, tm=tm, tw=tw, interpret=interp)
    return ref.ell_spmm_ref(cols, vals, x)


@scope("matvec")
def ell_spmv_dot(cols, vals, x, tm: int | None = None, tw: int | None = None):
    """Fused SpMV + dot: (y, pap) = (A @ x, dot(x, y)) in one matrix pass."""
    use, interp = _dispatch()
    if use:
        tm, tw = _tiles_2d("ell_spmv_dot", cols, vals.dtype, tm, tw)
        return _ell_spmv_dot_pallas(cols, vals, x, tm=tm, tw=tw, interpret=interp)
    return ref.ell_spmv_dot_ref(cols, vals, x)


@scope("matvec")
def ell_spmm_dot(cols, vals, x, tm: int | None = None, tw: int | None = None):
    """Multi-RHS fused SpMM + dot; x (n, k) -> (Y (n, k), pap (k,))."""
    use, interp = _dispatch()
    if use:
        tm, tw = _tiles_2d("ell_spmm_dot", cols, vals.dtype, tm, tw,
                            x.shape[1])
        return _ell_spmm_dot_pallas(cols, vals, x, tm=tm, tw=tw, interpret=interp)
    return ref.ell_spmm_dot_ref(cols, vals, x)


@scope("matvec")
def ell_spmv_pfold_dot(cols, vals, z, p, beta,
                       tm: int | None = None, tw: int | None = None):
    """p-fold SpMV + dot: p' = z + beta*p at gather time, y = A @ p',
    pap = dot(p', y) -- kills the separate 3n p-update stream."""
    use, interp = _dispatch()
    if use:
        tm, tw = _tiles_2d("ell_spmv_pfold_dot", cols, vals.dtype, tm, tw)
        return _ell_spmv_pfold_dot_pallas(cols, vals, z, p, beta,
                                          tm=tm, tw=tw, interpret=interp)
    return ref.ell_spmv_pfold_dot_ref(cols, vals, z, p, beta)


@scope("matvec")
def ell_spmm_pfold_dot(cols, vals, z, p, beta,
                       tm: int | None = None, tw: int | None = None):
    """Multi-RHS p-fold (kernel layout (n, k), beta (k,))."""
    use, interp = _dispatch()
    if use:
        tm, tw = _tiles_2d("ell_spmm_pfold_dot", cols, vals.dtype, tm, tw,
                            z.shape[1])
        return _ell_spmm_pfold_dot_pallas(cols, vals, z, p, beta,
                                          tm=tm, tw=tw, interpret=interp)
    return ref.ell_spmm_pfold_dot_ref(cols, vals, z, p, beta)


@scope("matvec")
def bcsr_spmm(block_cols, blocks, x, nbc: int | None = None):
    """Block-sparse x dense multi-RHS (the MXU path); ``nbc`` (static)
    asserts x is exactly (nbc*bn, R) -- see ``bcsr_spmm.bcsr_spmm``."""
    use, interp = _dispatch()
    if use:
        return _bcsr_spmm_pallas(block_cols, blocks, x, interpret=interp,
                                 nbc=nbc)
    if nbc is not None and x.shape[0] != nbc * blocks.shape[3]:
        raise ValueError(
            f"x shape {x.shape} incompatible with nbc={nbc}, "
            f"bn={blocks.shape[3]}: expected ({nbc * blocks.shape[3]}, R)")
    return ref.bcsr_spmm_ref(block_cols, blocks, x)


@scope("precond")
def sptrsv_level_step(cols, vals, diag, b, x, level_rows, tl: int | None = None):
    """Level wavefront: gathers rows, runs the kernel (or ref), scatters."""
    use, interp = _dispatch()
    if not use:
        return ref.sptrsv_level_step_ref(cols, vals, diag, b, x, level_rows)
    n = x.shape[0] - 1
    rows_p = cols.shape[0]
    wl = level_rows.shape[0]
    if tl is None:
        hit = autotune.lookup("sptrsv_level_step", (wl, cols.shape[1]), vals.dtype) or {}
        tl = _fit(wl, hit.get("tl") or DEFAULT_TL, 8)
    lr = jnp.minimum(level_rows, rows_p - 1)
    xr = _sptrsv_step_pallas(
        cols[lr],
        vals[lr],
        lr,
        b[lr],
        diag[jnp.minimum(level_rows, n - 1)],
        x,
        tl=tl,
        interpret=interp,
    )
    return x.at[level_rows].set(xr, mode="drop")


def sptrsv_solve_pack(cols, vals, dinv, sched_rows, n_rows: int) -> dict:
    """Pre-gather the call-invariant kernel inputs of ``sptrsv_solve_dot``
    (the factor rows per level, the clamped/scatter row-id planes, the
    padding mask and the per-level inverse diagonal).  These are
    O(n_levels * W * w) gathers -- loop-invariant for a fixed factor, so
    callers that run the solve inside a scan/while_loop (the IC(0)
    substrates: twice per PCG iteration) must build the pack ONCE and pass
    it via ``pack=`` instead of re-gathering the factor every iteration."""
    rows_p = cols.shape[0]
    lr_g = jnp.minimum(sched_rows, rows_p - 1)     # (L, W) gather-safe ids
    return {
        "cols_l": cols[lr_g],
        "vals_l": vals[lr_g],
        "lr_g": lr_g,
        "lr_s": jnp.minimum(sched_rows, rows_p),   # sentinel -> absorber
        "mask": (sched_rows < n_rows).astype(vals.dtype),
        "dinv_l": dinv[lr_g],
        # constant zero dot-weight plane for wdot=None calls (the IC(0)
        # L-solve): avoids materializing + gathering an n-word zeros
        # vector every call on the solver hot loop
        "wdot0": jnp.zeros(sched_rows.shape, vals.dtype),
        "rows_p": rows_p,
    }


@scope("precond")
def sptrsv_solve_dot(cols, vals, dinv, b, sched_rows, wdot=None,
                     n_rows: int | None = None, tl: int | None = None,
                     pack: dict | None = None):
    """Whole level-scheduled lower solve in ONE kernel launch, with
    dot(wdot, x) emitted in-stream as rows solve (see ``sptrsv.py``).

    cols/vals: (rows_p, w) padded ELL; dinv: (rows_p,) inverse diagonal;
    b/wdot: (rows_p,); sched_rows: (n_levels, W) padded with a sentinel
    >= ``n_rows`` (default rows_p).  Returns (x (rows_p,), dot(wdot, x)).
    The reference path runs the identical per-level arithmetic as a scan;
    the kernel keeps x VMEM-resident across every wavefront instead of
    round-tripping it per level.  ``pack``: optional pre-gathered factor
    planes from :func:`sptrsv_solve_pack` (hoists the loop-invariant
    gathers out of solver loops); only the per-call b/wdot gathers remain.
    """
    rows_p, w = cols.shape
    n_rows = rows_p if n_rows is None else n_rows
    use, interp = _dispatch()
    if not use:
        if wdot is None:
            wdot = jnp.zeros((rows_p,), vals.dtype)
        return ref.sptrsv_solve_dot_ref(cols, vals, dinv, b, sched_rows,
                                        wdot, n_rows)
    nl, wl = sched_rows.shape
    if tl is None:
        hit = autotune.lookup("sptrsv_solve_dot", (nl, wl, w), vals.dtype) or {}
        tl = _fit(wl, hit.get("tl") or DEFAULT_TL, 8)
    if pack is None:
        pack = sptrsv_solve_pack(cols, vals, dinv, sched_rows, n_rows)
    lr_g = pack["lr_g"]
    w_l = pack["wdot0"] if wdot is None else wdot[lr_g]
    x, pp = _sptrsv_solve_dot_pallas(
        pack["cols_l"], pack["vals_l"], lr_g, pack["lr_s"],
        b[lr_g], pack["dinv_l"], w_l, pack["mask"],
        rows_p=pack["rows_p"], tl=tl, interpret=interp,
    )
    return x, pp


@scope("update")
def axpy_dot(a, x, y, tn: int | None = None):
    use, interp = _dispatch()
    if use:
        if tn is None:
            hit = autotune.lookup("axpy_dot", x.shape, x.dtype) or {}
            tn = _lane_tile(x.shape[0], hit.get("tn") or DEFAULT_TN, 6,
                            x.dtype.itemsize)
        return _axpy_dot_pallas(a, x, y, tn=tn, interpret=interp)
    return ref.axpy_dot_ref(a, x, y)


@scope("update")
def cg_update(alpha, x, r, p, ap, dinv=None, tn: int | None = None):
    """One-pass CG update (see ``vecops.cg_update``): handles arbitrary n
    via masked tail tiles, (k, n) batches via per-RHS alphas."""
    use, interp = _dispatch()
    if use:
        if tn is None:
            hit = autotune.lookup("cg_update", x.shape, r.dtype) or {}
            k = x.shape[0] if x.ndim == 2 else 1
            # 5 reads + 3 writes per RHS, double-buffered
            tn = _lane_tile(x.shape[-1], hit.get("tn") or DEFAULT_TN,
                            16 * k, r.dtype.itemsize)
        return _cg_update_pallas(alpha, x, r, p, ap, dinv, tn=tn, interpret=interp)
    return ref.cg_update_ref(alpha, x, r, p, ap, dinv)

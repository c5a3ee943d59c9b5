"""AzulEngine: the paper's accelerator as a distributed JAX program.

The engine is the public API of the reproduction.  Given a sparse SPD (or
lower-triangular) matrix, it

  1. runs the static "task compiler" (partition + ELL packing + level
     schedules + preconditioner factorization) on the host -- the paper's
     one-time preprocessing that Azul offloads to a compiler;
  2. pins the resulting blocks *device-resident* on the mesh (the analogue
     of Azul's SRAM-pinned matrix blocks: after ``device_put`` the matrix
     never crosses ICI again -- verified by the roofline collective parse:
     only vector shards move);
  3. exposes ``spmv`` / ``build_sptrsv`` / ``solve`` as jit-compiled
     ``shard_map`` programs whose only cross-device traffic is the vector
     halo exchange.

Layouts (2D mode, the default -- see partition.plan_2d):
  * matrix blocks: stacked (pr*pc, br, w) ELL, sharded on the leading axis
    over all mesh axes -> tile (i, j) owns block A[I=i, J=j];
  * vectors: (n_pad,) contiguously sharded over all mesh axes ("L_row":
    tile (i, j) holds subsegment q = i*pc + j of length u);
  * SpMV = mesh_transpose (L_row -> L_col, one u-shard ppermute)
         + x_J assembly along the row axes
         + local ELL kernel
         + psum_scatter of y partials along the col axis (br bytes).
    Per-tile traffic ~ n/pc, vs. the full-n all_gather of the 1D plan.

1D mode is the bandwidth-hungry baseline (what a cache-less GPU run looks
like): vectors fully sharded, SpMV assembles the whole x on every tile.
It exists so benchmarks can report the paper's "Azul vs. naive" delta.

Communication plans (``layout`` knob): the x assembly step runs in one of
two layouts.  ``"dense"`` is the blanket ``all_gather`` above.  ``"halo"``
runs the structure-compiled pull schedule of :mod:`repro.core.commplan`:
at engine build the host computes which remote u-shards each tile's stored
nonzeros actually reference, takes the union as a bounded ``ppermute`` hop
sequence, and rewrites the tile's ELL columns into the compact halo buffer
-- NoC bytes then scale with the halo instead of with the block size
(Azul's sparsity-driven NoC traffic).  ``"auto"`` (default) picks halo
exactly when the compiled plan moves strictly fewer shard-words than the
all_gather; unstructured matrices fall back to dense automatically.
``reorder="rcm"`` composes a bandwidth-reducing reverse Cuthill-McKee
permutation into the partition (vectors permute on embed / un-permute on
extract) so halos shrink before the plan is cut, and ``balance="nnz"``
now also applies to 2D row blocks (prefix-sum boundaries + a pad2g
embedding; collectives stay shape-uniform).

Batched multi-RHS: ``spmv``/``solve`` also take stacked (k, n) inputs.  The
batch axis is *replicated* in the sharding spec (P(None, axes)) so matrix
blocks stay device-resident and untouched; only (k, u) stacked vector
shards traverse the NoC (one message per hop regardless of k), and the
per-tile compute switches to the multi-RHS ``spmm`` path that amortizes the
single matrix stream over all k right-hand sides.

Fused hot path: the engine threads a solver *substrate*
(:mod:`repro.core.substrate`) through the solve programs -- fused Pallas
kernels (SpMV with the CG denominator emitted in the matrix stream;
one-pass x/r/z update with both dots) locally, and a collective-fused
shard substrate (single stacked psum for [rr, rz]) under ``shard_map``.
The ``fused`` knob ("auto" default / True / False) applies wherever the
method/preconditioner pair supports it -- a capability lookup against
:mod:`repro.core.registry`, not a hard-coded ladder; unsupported
combinations fall back to the reference path.

Plan/execute API: the public solve surface is ``engine.plan(spec)`` -- a
frozen :class:`repro.core.plan.SolveSpec` lowered ONCE into a compiled
:class:`repro.core.plan.SolvePlan` (jitted program + operand buffers +
substrate info), cached spec-keyed in ``engine.plans``.  The legacy
``engine.solve(**knobs)`` survives as a thin deprecated shim over that
cache: identical results, one DeprecationWarning per process.
"""

from __future__ import annotations

import hashlib
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import commplan, noc, registry
from .formats import CSR, pad_to
from .levels import build_schedule
from .multigrid import MGHierarchy
from .multigrid import build as build_mg
from .partition import (padded_layout_1d, permute_csr, plan_1d, plan_2d,
                        rcm_permutation, tile_csr)
from ..obs import REGISTRY as _OBS
from ..obs.scopes import scope
from .plan import PlanCache, SolvePlan, SolveSpec, canonicalize, warn_deprecated
from .precond import ic0 as host_ic0
from .solvers import ensure_status
from .spops import spmm_ell_padded, spmv_ell_padded
from .stencil import Stencil, stencil_diag, stencil_matvec
from .substrate import (format_stream_ops, fused_ic0_local_substrate,
                        fused_local_substrate, fused_shard_ic0_substrate,
                        fused_shard_substrate)

__all__ = ["AzulEngine", "local_sptrsv"]

_M_STAGED_COPIES = _OBS.counter(
    "repro_solve_staged_copies_total",
    "vectors the engine staged through a host padding or permutation copy "
    "('in': to_device_vec, 'out': from_device_vec); an unpadded, "
    "unpermuted vector goes straight across and is not counted",
    ("phase",))


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the solver programs
    emit psum'd scalars whose replication the checker cannot always prove."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# local (per-tile) triangular solve on raw stacked arrays
# ---------------------------------------------------------------------------


def local_sptrsv(cols, vals, diag_inv, b, sched_rows):
    """Level-scheduled lower solve on one tile's (rows_p, w) ELL block.

    cols/vals: (rows_p, w); diag_inv: (rows_p,) (1.0 in padded rows);
    b: (rows_p,); sched_rows: (n_levels, W) row ids padded with >= rows_p.
    Returns x: (rows_p,).  Runs identically on every tile (SPMD) -- tiles
    holding a dummy schedule produce zeros, which the caller masks.
    """
    rows_p = cols.shape[0]
    x0 = jnp.zeros((rows_p + 1,), vals.dtype)
    sched_rows = jnp.minimum(sched_rows, rows_p)  # sentinel -> absorber slot

    def level_step(x, level_rows):
        lr = jnp.minimum(level_rows, rows_p - 1)
        c = cols[lr]
        v = vals[lr]
        off = jnp.where(c != lr[:, None], v, jnp.zeros_like(v))
        contrib = jnp.sum(off * x[jnp.minimum(c, rows_p)], axis=1)
        xr = (b[lr] - contrib) * diag_inv[lr]
        return x.at[level_rows].set(xr, mode="drop"), None

    x, _ = lax.scan(level_step, x0, sched_rows)
    return x[:rows_p]


def _ell_block_apply(cols_loc, vals_loc, xj):
    """The per-tile ELL gather-and-reduce on one (1, rows, w) block shard:
    Pallas kernels when active, the jnp reference otherwise.  ``xj`` is the
    assembled x buffer in solver layout ((m,) or (k, m)); kernel calls
    transpose batched inputs to the (m, k) kernel layout."""
    from ..kernels import ops
    if xj.ndim == 2:                              # (k, bc) stacked
        if ops.kernels_active():                  # Pallas path (TPU)
            return ops.ell_spmm(cols_loc[0], vals_loc[0], xj.T).T
        return spmm_ell_padded(cols_loc[0], vals_loc[0], xj)
    if ops.kernels_active():
        return ops.ell_spmv(cols_loc[0], vals_loc[0], xj)
    return spmv_ell_padded(cols_loc[0], vals_loc[0], xj)


def _host_diag(m: CSR, r0: int, r1: int) -> np.ndarray:
    """Diagonal entries of rows [r0, r1) (0.0 where absent), host side.

    Vectorized: one boolean compare over the row range's nnz slice instead
    of the former per-entry Python loop -- this is a task-compiler hot spot
    (called per engine build and per SpTRSV compile; the loop was O(nnz)
    interpreted bytecode, ~two orders of magnitude slower at suite sizes).
    """
    indptr = np.asarray(m.indptr)
    lo, hi = int(indptr[r0]), int(indptr[r1])
    rows = np.repeat(np.arange(r0, r1), np.diff(indptr[r0 : r1 + 1]))
    idx = np.asarray(m.indices)[lo:hi]
    sel = idx == rows
    d = np.zeros(r1 - r0, dtype=np.float64)
    d[rows[sel] - r0] = np.asarray(m.data)[lo:hi][sel]
    return d


def _csr_fingerprint(m: CSR) -> tuple:
    """Content-based cache key for a host CSR matrix.  ``id()`` keys are
    unsafe here: CPython reuses addresses after GC, so a *fresh* matrix
    could silently hit a stale compiled entry."""
    h = hashlib.sha1()
    for a in (m.indptr, m.indices, m.data):
        h.update(np.ascontiguousarray(a).tobytes())
    return (tuple(m.shape), h.hexdigest())


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


class AzulEngine:
    """Distributed sparse iterative-solver engine (see module docstring).

    Parameters
    ----------
    a : CSR | Stencil | MGHierarchy
        square sparse matrix (host side), a matrix-free stencil, or a
        stored matrix with its multigrid levels (``repro.core.multigrid``:
        one device, no reorder; needed by ``precond="mg"``)
    mesh : jax.sharding.Mesh | None
        None -> single-device mode (plain jnp ops; oracle/test path).
    mode : "2d" | "1d"           partition layout (2d = Azul NoC pattern)
    row_axes / col_axes :        mesh axis names of the tile grid; default
                                 ("data",) x ("model",); multi-pod solvers
                                 pass row_axes=("pod", "data").
    precond : "jacobi" | "block_ic0" | "none"
    fused : "auto" | True | False
        Fused-kernel hot path (see module docstring).  "auto"/True enable
        it wherever the method/preconditioner support it; False forces the
        reference op-per-line path everywhere.  Per-solve override:
        ``solve(..., fused=...)``.
    layout : "auto" | "halo" | "dense"
        Distributed communication layout (see module docstring): "auto"
        runs the compiled halo pull schedule wherever it moves fewer bytes
        than the dense collectives; per-plan override via
        ``SolveSpec(layout=...)``.
    reorder : "none" | "rcm"
        Bandwidth-reducing row/column reordering composed into the
        partition (build-time: the matrix is repacked under the
        permutation; vector I/O round-trips it transparently).
    format : "auto" | "ell" | "sell" | "hyb" | "bcsr" | "stencil"
        Operator storage format (local engines).  "auto" runs the
        per-matrix format autotuner (``kernels.autotune.choose_format``:
        modeled matrix-stream words over the row-length distribution,
        persisted in the autotune cache) -- uniform-row matrices stay on
        padded ELL, skewed/power-law matrices pick sliced-ELL or HYB.
        Explicit names pin the format; "bcsr" is explicit-only (block
        structure is a caller assertion).  Distributed engines are "ell"
        (sharding and halo remap are phrased over the padded ELL blocks);
        matrix-free :class:`~repro.core.stencil.Stencil` operators are
        "stencil".  Per-plan override via ``SolveSpec(format=...)``.
    """

    def __init__(
        self,
        a: CSR | Stencil,
        mesh: Mesh | None = None,
        mode: str = "2d",
        row_axes=("data",),
        col_axes=("model",),
        precond: str = "jacobi",
        balance: str = "nnz",
        dtype=np.float32,
        row_pad: int = 8,
        width_pad: int = 8,
        fused="auto",
        layout: str = "auto",
        reorder: str = "none",
        format: str = "auto",
    ):
        if a.shape[0] != a.shape[1]:
            raise ValueError("engine expects a square matrix")
        if fused not in ("auto", True, False):
            raise ValueError(f"fused must be 'auto', True or False, got {fused!r}")
        if layout not in ("auto", "halo", "dense"):
            raise ValueError(
                f"layout must be 'auto', 'halo' or 'dense', got {layout!r}")
        if reorder not in ("none", "rcm"):
            raise ValueError(f"reorder must be 'none' or 'rcm', got {reorder!r}")
        if layout == "halo" and mesh is None:
            raise ValueError("layout='halo' needs a mesh (no NoC locally)")
        if format not in ("auto", "ell", "sell", "hyb", "bcsr", "stencil"):
            raise ValueError(
                "format must be 'auto', 'ell', 'sell', 'hyb', 'bcsr' or "
                f"'stencil', got {format!r}")
        hierarchy = None
        if isinstance(a, MGHierarchy):
            if mesh is not None:
                raise ValueError(
                    "an MGHierarchy runs on one device (the distributed "
                    "partition shards one matrix, not its coarse levels)")
            if reorder != "none":
                raise ValueError(
                    "reorder needs a plain CSR; the hierarchy's f2c maps "
                    "index the operator's own row order")
            if format == "stencil":
                raise ValueError(
                    "format='stencil' conflicts with an MGHierarchy: its "
                    "levels are stored matrices")
            hierarchy, a = a, a.levels[0]
        needs_levels = registry.get_precond(precond).hierarchy
        if needs_levels and hierarchy is None:
            raise ValueError(
                f"precond {precond!r} needs the operator's multigrid "
                "levels: build the engine from a repro.core.multigrid."
                "MGHierarchy (stored, one device)")
        is_stencil = isinstance(a, Stencil)
        if is_stencil:
            if mesh is not None:
                raise ValueError(
                    "matrix-free stencil operators are local-only (the "
                    "distributed partition shards stored nonzeros)")
            if reorder != "none":
                raise ValueError(
                    "reorder needs a stored matrix; stencil operators have "
                    "a fixed grid ordering")
            if registry.get_precond(precond).factorized:
                raise ValueError(
                    f"precond {precond!r} needs stored nonzeros to factor; "
                    "stencil engines support 'jacobi' or 'identity'")
            if format not in ("auto", "stencil"):
                raise ValueError(
                    f"format={format!r} conflicts with a matrix-free "
                    "stencil operator")
        elif format == "stencil":
            raise ValueError("format='stencil' needs a Stencil operator")
        if mesh is not None and format not in ("auto", "ell"):
            raise ValueError(
                f"format={format!r} is not supported in distributed mode "
                "(sharding and halo remap are phrased over padded ELL)")
        self.fused = fused
        self.layout = layout
        self.reorder = reorder
        self._row_perm = None          # global row/col permutation (reorder)
        self._row_iperm = None
        if reorder == "rcm":
            self._row_perm = rcm_permutation(a)
            self._row_iperm = np.empty_like(self._row_perm)
            self._row_iperm[self._row_perm] = np.arange(a.shape[0])
            a = permute_csr(a, self._row_perm)
        self.a = a                     # the engine's working (reordered) matrix
        self.n = a.shape[0]
        self.mesh = mesh
        self.mode = mode if mesh is not None else "local"
        self.row_axes = (row_axes,) if isinstance(row_axes, str) else tuple(row_axes)
        self.col_axes = (col_axes,) if isinstance(col_axes, str) else tuple(col_axes)
        self.precond = precond
        self.dtype = dtype
        self._row_pad = row_pad
        self._width_pad = width_pad
        self._pad2g = None             # padded->global row map (1d / nnz-2d)
        self.comm_plan = None          # compiled halo schedule (dist modes)
        self._cols_halo_dev = None     # lazily device_put halo-remapped cols
        self._vals_split_dev = None    # lazily split interior/frontier vals
        self._imask_dev = None         # lazily device_put interior mask
        self._compiled: dict = {}      # spmv/spmm programs (vector ops)
        self._trsv_cache: dict = {}
        self.stencil = a if is_stencil else None
        self._mg = None                # V-cycle operands (precond="mg")
        self.format = format           # the knob; format_choice = resolved
        self.format_choice = "ell"     # per-matrix decision (local builds)
        self.format_words = None       # modeled words/matvec behind it
        self._fmt_objs: dict = {}      # lazily built SELL/HYB/BCSR operands
        # spec-keyed compiled solve plans (see repro.core.plan): replaces
        # the former hand-rolled (method, iters, precond, ...) key tuples
        self.plans = PlanCache()
        # populated by every plan execution: method, fused flag, substrate
        # kind, and (post-solve) the per-RHS iteration counts
        self.last_solve_info: dict = {}
        registry.get_precond(precond)  # fail fast on unknown preconditioner

        if self.mode == "local":
            if is_stencil:
                self._build_local_stencil()
            else:
                self._build_local()
            if needs_levels:
                self._mg = build_mg(hierarchy, self.dtype)
        else:
            self.pr = int(np.prod([mesh.shape[ax] for ax in self.row_axes]))
            self.pc = int(np.prod([mesh.shape[ax] for ax in self.col_axes]))
            self._all_axes = self.row_axes + self.col_axes
            self._vec_spec = P(self._all_axes)
            # batched (k, n_pad) layout: batch replicated, vector sharded --
            # matrix blocks stay put, only stacked vector shards move.
            self._bvec_spec = P(None, self._all_axes)
            self._blk_spec = P(self._all_axes, None, None)
            if self.mode == "2d":
                self._build_2d(balance)
            elif self.mode == "1d":
                self._build_1d(balance)
            else:
                raise ValueError(f"unknown mode {mode!r}")

    # -- construction -------------------------------------------------------

    def _build_local(self):
        from .formats import ell_from_csr

        self.ell = ell_from_csr(
            self.a, width_pad=self._width_pad, row_pad=self._row_pad, dtype=self.dtype
        )
        self.n_pad = self.ell.rows_padded
        dg = _host_diag(self.a, 0, self.n)
        dg[dg == 0] = 1.0
        di = np.zeros(self.n_pad, self.dtype)
        di[: self.n] = 1.0 / dg
        self._dinv_pad = jnp.asarray(di)
        if self.precond == "block_ic0":
            self._ic0 = host_ic0(self.a, dtype=self.dtype)
        # per-matrix format decision (the task compiler's storage leg):
        # "auto" consults the autotuner's modeled-words ranking (cached by
        # row-stats fingerprint); explicit knobs pin.  The padded ELL above
        # always builds -- it backs spmv(), injectable plans and IC(0).
        from ..kernels import autotune
        if self.format == "auto":
            self.format_choice, self.format_words = autotune.choose_format(
                self.a, dtype=self.dtype, slice_height=self._row_pad,
                row_pad=self._row_pad)
        else:
            self.format_choice = self.format
            self.format_words = autotune.modeled_format_words(
                self.a, slice_height=self._row_pad, row_pad=self._row_pad)

    def _build_local_stencil(self):
        """Matrix-free local build: no stored nonzeros, no ELL pack -- the
        operator is its coefficient-generating matvec.  Device state is
        O(n): just the padded inverse diagonal (the stencil diagonal is a
        known constant)."""
        self.ell = None
        self.n_pad = pad_to(max(self.n, 1), self._row_pad)
        di = np.zeros(self.n_pad, self.dtype)
        di[: self.n] = 1.0 / stencil_diag(self.stencil)
        self._dinv_pad = jnp.asarray(di)
        self.format_choice = "stencil"

    def _format_obj(self, fmt: str):
        """The device operand container for a non-ELL stored format, built
        on FIRST use and cached: plans that stay on ELL never pay the
        second packing."""
        obj = self._fmt_objs.get(fmt)
        if obj is not None:
            return obj
        from .formats import bcsr_from_csr, hyb_from_csr, sell_from_csr
        if fmt == "sell":
            obj = sell_from_csr(self.a, slice_height=self._row_pad,
                                row_pad=self._row_pad, dtype=self.dtype)
            assert obj.rows_padded == self.n_pad
        elif fmt == "hyb":
            obj = hyb_from_csr(self.a, row_pad=self._row_pad,
                               dtype=self.dtype)
            assert obj.rows_padded == self.n_pad
        elif fmt == "bcsr":
            obj = bcsr_from_csr(self.a, bm=self._row_pad, bn=self._row_pad,
                                dtype=self.dtype)
        else:
            raise ValueError(f"no format container for {fmt!r}")
        self._fmt_objs[fmt] = obj
        return obj

    def _put(self, x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, spec))

    def _build_2d(self, balance):
        plan = plan_2d(
            self.a, self.pr, self.pc, width_pad=self._width_pad,
            row_pad=self._row_pad, dtype=self.dtype, balance=balance,
        )
        self.partition_plan = plan   # the static task-compiler output
        self.n_pad = plan.n_padded
        self.br = plan.block_rows
        self.bc = plan.block_cols
        self.u = self.n_pad // (self.pr * self.pc)
        self._pad2g = plan.pad2g     # None for uniform row blocks

        # the static pull schedule: which remote u-shards each tile's
        # stored structure references (commplan module docstring)
        self.comm_plan = commplan.compile_comm_plan_2d(
            np.asarray(plan.cols), np.asarray(plan.vals), self.pr, self.pc,
            self.u, itemsize=np.dtype(self.dtype).itemsize,
        )
        self.cols = self._put(plan.cols, self._blk_spec)
        self.vals = self._put(plan.vals, self._blk_spec)
        if plan.pad2g is None:
            segs = [
                (min(q * self.u, self.n), min((q + 1) * self.u, self.n))
                for q in range(self.pr * self.pc)
            ]
        else:
            # tile (i, j)'s u-shard sits inside row block i at local
            # offset j*u; valid rows clip at the block's true extent
            offs = plan.row_offsets
            segs = []
            for i in range(self.pr):
                for j in range(self.pc):
                    r0 = min(int(offs[i]) + j * self.u, int(offs[i + 1]))
                    r1 = min(int(offs[i]) + (j + 1) * self.u, int(offs[i + 1]))
                    segs.append((r0, r1))
        self._setup_diag_and_precond(seg_ranges=segs, pad2g=plan.pad2g)

    def _build_1d(self, balance):
        parts = self.pr * self.pc
        plan = plan_1d(
            self.a, parts, balance=balance, width_pad=self._width_pad,
            row_pad=self._row_pad, dtype=self.dtype,
        )
        self.partition_plan = plan   # the static task-compiler output
        self.n_pad = plan.n_padded
        self.u = plan.rows_per_tile

        # global cols -> padded tile layout (tile t, local r) = t*u + r
        offs = plan.row_offsets
        cols_pad, pad2g = padded_layout_1d(plan)
        self._pad2g = pad2g

        self.comm_plan = commplan.compile_comm_plan_1d(
            cols_pad, np.asarray(plan.vals), self.u, parts,
            itemsize=np.dtype(self.dtype).itemsize,
        )
        self._cols_pad_host = cols_pad
        self.cols = self._put(cols_pad, self._blk_spec)
        self.vals = self._put(plan.vals, self._blk_spec)
        segs = [(int(offs[t]), int(offs[t + 1])) for t in range(parts)]
        self._setup_diag_and_precond(seg_ranges=segs, pad2g=pad2g)

    def _setup_diag_and_precond(self, seg_ranges, pad2g):
        dg_g = _host_diag(self.a, 0, self.n)
        dg_g[dg_g == 0] = 1.0
        di = np.zeros(self.n_pad, self.dtype)
        if pad2g is None:
            di[: self.n] = 1.0 / dg_g
        else:
            valid = pad2g < self.n
            di[valid] = 1.0 / dg_g[pad2g[valid]]
        self._dinv_pad = self._put(di, self._vec_spec)

        if self.precond == "block_ic0":
            rows_p, l_pack, u_pack = self._prep_precond_blocks(seg_ranges)
            s3 = P(self._all_axes, None, None)
            s2 = P(self._all_axes, None)
            self._pc_rows_p = rows_p
            self._pc_l = tuple(
                self._put(x, s) for x, s in zip(l_pack, (s3, s3, s2, s3))
            )
            self._pc_u = tuple(
                self._put(x, s) for x, s in zip(u_pack, (s3, s3, s2, s3))
            )
            ks = np.asarray([max(r1 - r0, 1) for r0, r1 in seg_ranges], np.int32)
            self._pc_k = self._put(ks, P(self._all_axes))

    def _prep_precond_blocks(self, seg_ranges):
        """Factor every vector segment's diagonal block (block-Jacobi IC(0));
        falls back to point-Jacobi (L = sqrt(D)) for blocks whose IC(0)
        pivots fail.  Returns stacked, commonly-padded factor arrays."""
        segs = len(seg_ranges)
        facs = []
        for (r0, r1) in seg_ranges:
            if r1 <= r0:
                facs.append(None)
                continue
            blk = tile_csr(self.a, r0, r1, r0, r1)
            try:
                facs.append(host_ic0(blk, dtype=self.dtype))
            except ValueError:
                facs.append(None)
        max_seg = max((r1 - r0 for r0, r1 in seg_ranges), default=1)
        rows_p = max(
            [pad_to(max(max_seg, 1), self._row_pad)]
            + [max(f.ell_l.rows_padded, f.ell_u_rev.rows_padded) for f in facs if f]
        )
        w = max([max(f.ell_l.width, f.ell_u_rev.width) for f in facs if f] + [1])
        nl = max([max(f.sched_l.n_levels, f.sched_u_rev.n_levels) for f in facs if f] + [1])
        wl = max([max(f.sched_l.max_width, f.sched_u_rev.max_width) for f in facs if f] + [8])

        def pack(get_ell, get_sched):
            cols = np.zeros((segs, rows_p, w), np.int32)
            vals = np.zeros((segs, rows_p, w), self.dtype)
            dinv = np.ones((segs, rows_p), self.dtype)
            rows = np.full((segs, nl, wl), rows_p, np.int32)
            for s, f in enumerate(facs):
                r0, r1 = seg_ranges[s]
                k = r1 - r0
                if f is None:
                    if k <= 0:
                        continue
                    dsqrt = np.sqrt(np.maximum(_host_diag(self.a, r0, r1), 1e-30))
                    cols[s, :k, 0] = np.arange(k)
                    vals[s, :k, 0] = dsqrt
                    dinv[s, :k] = 1.0 / dsqrt
                    # schedule: all rows in one level (diagonal solve)
                    nrows_lv = min(k, nl * wl)
                    flat = rows[s].reshape(-1)
                    flat[:nrows_lv] = np.arange(nrows_lv)
                    rows[s] = flat.reshape(nl, wl)
                    continue
                e, sc = get_ell(f), get_sched(f)
                rp, ww = e.cols.shape
                cols[s, :rp, :ww] = np.asarray(e.cols)
                vals[s, :rp, :ww] = np.asarray(e.vals)
                dd = np.zeros(rows_p, np.float64)
                rpm = min(rp, rows_p)
                ee_cols = np.asarray(e.cols)[:rpm]
                ee_vals = np.asarray(e.vals)[:rpm]
                hit = (ee_cols == np.arange(rpm)[:, None]) & (ee_vals != 0)
                has = hit.any(axis=1)
                dd[:rpm][has] = ee_vals[np.arange(rpm)[has], np.argmax(hit, axis=1)[has]]
                dinv[s] = np.where(dd == 0, 1.0, 1.0 / np.where(dd == 0, 1.0, dd))
                sr = np.asarray(sc.rows)
                sr = np.where(sr >= sc.n, rows_p, sr)
                rows[s, : sr.shape[0], : sr.shape[1]] = sr
            return cols, vals, dinv, rows

        return (
            rows_p,
            pack(lambda f: f.ell_l, lambda f: f.sched_l),
            pack(lambda f: f.ell_u_rev, lambda f: f.sched_u_rev),
        )

    # -- vector embedding ---------------------------------------------------

    def to_device_vec(self, v: np.ndarray) -> jnp.ndarray:
        """Embed a global (n,) -- or batched (k, n) -- vector into the padded
        device layout.  Batched vectors shard the trailing (vector) axis and
        replicate the batch axis, so k RHS share one set of matrix blocks.
        With ``reorder`` active the engine's row permutation applies here
        (and inverts in :meth:`from_device_vec`), so callers always speak
        the original ordering.

        Where the layout is the identity (no permutation, no pad2g map,
        ``n_pad == n``) the vector goes to the device as it is, after one
        ``astype`` if its dtype is not the engine's.  Otherwise it is built
        into a fresh zero-filled host buffer, never reused across calls (on
        the CPU backend a device array may alias its host memory), and
        counted in ``repro_solve_staged_copies_total{phase="in"}``."""
        v = np.asarray(v)
        if (self._row_perm is None and self._pad2g is None
                and self.n_pad == self.n and v.shape[-1] == self.n):
            out = v if v.dtype == self.dtype else v.astype(self.dtype)
        else:
            _M_STAGED_COPIES.inc(phase="in")
            if self._row_perm is not None:
                v = v[..., self._row_perm]
            out = np.zeros(v.shape[:-1] + (self.n_pad,), self.dtype)
            if self._pad2g is not None:
                valid = self._pad2g < self.n
                out[..., valid] = v[..., self._pad2g[valid]]
            else:
                out[..., : self.n] = v
        if self.mesh is None:
            return jnp.asarray(out)
        spec = self._bvec_spec if v.ndim == 2 else self._vec_spec
        return self._put(out, spec)

    def from_device_vec(self, v: jnp.ndarray) -> np.ndarray:
        """Extract the global (n,) / (k, n) vector from the padded layout
        (a view where only trailing padding goes; a pad2g map or the row
        permutation copies, counted in
        ``repro_solve_staged_copies_total{phase="out"}``)."""
        v = np.asarray(v)
        if self._pad2g is not None or self._row_iperm is not None:
            _M_STAGED_COPIES.inc(phase="out")
        if self._pad2g is not None:
            out = np.zeros(v.shape[:-1] + (self.n,), self.dtype)
            valid = self._pad2g < self.n
            out[..., self._pad2g[valid]] = v[..., valid]
        else:
            out = v[..., : self.n]
        if self._row_iperm is not None:
            out = out[..., self._row_iperm]
        return out

    # -- distributed program builders ---------------------------------------

    def _mk_matvec(self, layout: str = "dense") -> Callable:
        """Returns mv(x_loc, cols_loc, vals_loc) -> y_loc with collectives
        inside; cols/vals arrive as the (1, rows, w) local shard.

        ``x_loc`` is the (u,) vector shard or the batch-stacked (k, u)
        shard; the batch axis rides every NoC hop intact (``vec_axis``)
        while the local compute switches to the multi-RHS ``spmm`` kernel,
        amortizing the one matrix stream over all k vectors.

        ``layout="dense"`` assembles x with a blanket ``all_gather``;
        ``layout="halo"`` runs the compiled pull schedule instead (the
        caller must pass the halo-remapped ``cols_halo`` blocks): the x
        buffer is ``concat([own shard, pulled shards...])`` -- same values
        in the gather slots the structure references, so results are
        bit-identical to the dense layout while moving only halo bytes."""
        row_axes, col_axes, mode = self.row_axes, self.col_axes, self.mode
        col_axis = col_axes[0] if len(col_axes) == 1 else col_axes
        deltas = self.comm_plan.deltas if layout == "halo" else ()

        _local = _ell_block_apply

        def _pull(x_loc, axes, va):
            # the halo buffer: own shard at slot 0, then one bounded
            # ppermute per scheduled hop (commplan's static pull order)
            shards = [x_loc] + [noc.pull_shard(x_loc, axes, d) for d in deltas]
            return jnp.concatenate(shards, axis=va)

        if mode == "2d":
            def mv(x_loc, cols_loc, vals_loc):
                va = x_loc.ndim - 1
                with scope("halo"):
                    xc = noc.mesh_transpose(x_loc, row_axes, col_axes)
                    if layout == "halo":
                        xj = _pull(xc, row_axes, va)      # (..., (1+H)u)
                    else:
                        xj = noc.gather_along(xc, row_axes, vec_axis=va)
                yp = _local(cols_loc, vals_loc, xj)               # (..., br)
                with scope("halo"):
                    return noc.reduce_scatter_along(yp, col_axis, vec_axis=va)
            return mv

        all_axes = self._all_axes

        def mv1d(x_loc, cols_loc, vals_loc):
            va = x_loc.ndim - 1
            with scope("halo"):
                if layout == "halo":
                    xg = _pull(x_loc, all_axes, va)      # (..., (1+H)u)
                else:
                    xg = noc.gather_along(x_loc, all_axes, vec_axis=va)
            return _local(cols_loc, vals_loc, xg)                # (..., u)
        return mv1d

    def _dot(self):
        axes = self._all_axes

        @scope("reduce")
        def dot(u, v):
            # last-axis reduce (keepdims when batched) + psum: per-RHS
            # scalars arrive as (k, 1), broadcastable back onto (k, u).
            return lax.psum(jnp.sum(u * v, axis=-1, keepdims=u.ndim > 1), axes)
        return dot

    def _dot2(self):
        """N stacked dots, ONE collective (pipelined-CG reduction fusion).
        Accepts flat ``(a1, b1, a2, b2, ...)`` pairs and psums the stacked
        partials once; the pipelined recurrence rides its whole per-
        iteration reduction load ([gamma, delta, rr]) on a single call."""
        axes = self._all_axes

        @scope("reduce")
        def dot2(*vs):
            kd = vs[0].ndim > 1
            return lax.psum(
                jnp.stack([jnp.sum(a * b, axis=-1, keepdims=kd)
                           for a, b in zip(vs[::2], vs[1::2])]),
                axes,
            )
        return dot2

    def _mk_matvec_split(self):
        """The communication-hiding SpMV as a ``(start, finish)`` pair
        (halo layout only; see ``commplan`` on the interior/frontier
        split).

        ``start(x_loc)`` issues the communication for x -- the 2d mesh
        transpose plus the compiled ``ppermute`` pull schedule -- and
        returns the in-flight halo tuple ``(own, pulled...)``.
        ``finish(halo, cols_loc, vi_loc, vf_loc)`` computes

            y = A_interior @ [own, 0...] + A_frontier @ [own, pulled...]

        The interior pass has NO data dependence on the pulled shards, so
        the latency-hiding scheduler is free to stream it while the
        permutes fly; ``vi``/``vf`` zero complementary row sets of the
        same val blocks, so by SpMV linearity the sum is value-identical
        to the single-pass halo SpMV.  The pipelined solver calls
        ``start`` on the NEXT iteration's operand at the tail of each
        step, putting the whole update/reduction/psolve tail between
        issue and use (double-buffered halo)."""
        row_axes, col_axes, mode = self.row_axes, self.col_axes, self.mode
        col_axis = col_axes[0] if len(col_axes) == 1 else col_axes
        deltas = self.comm_plan.deltas
        pull_axes = row_axes if mode == "2d" else self._all_axes

        @scope("halo")
        def start(x_loc):
            xc = (noc.mesh_transpose(x_loc, row_axes, col_axes)
                  if mode == "2d" else x_loc)
            return (xc,) + tuple(
                noc.pull_shard(xc, pull_axes, d) for d in deltas
            )

        @scope("matvec")
        def finish(halo, cols_loc, vi_loc, vf_loc):
            xc, pulled = halo[0], halo[1:]
            va = xc.ndim - 1
            with scope("halo"):
                x_int = jnp.concatenate(
                    [xc] + [jnp.zeros_like(s) for s in pulled], axis=va)
                x_ext = jnp.concatenate([xc, *pulled], axis=va)
            y = (_ell_block_apply(cols_loc, vi_loc, x_int)
                 + _ell_block_apply(cols_loc, vf_loc, x_ext))
            if mode == "2d":
                with scope("halo"):
                    return noc.reduce_scatter_along(y, col_axis, vec_axis=va)
            return y

        return start, finish

    def _split_vals(self):
        """Interior/frontier val blocks for the overlap lowering,
        device-put on FIRST use: the split doubles the val footprint, so
        dense plans and non-overlapping methods never pay it.  Each block
        keeps the full ELL shape with the complementary row set zeroed
        (``comm_plan.interior_mask``)."""
        if self._vals_split_dev is None:
            vals = np.asarray(self.partition_plan.vals)
            mask = self.comm_plan.interior_mask[:, :, None]
            vi = np.where(mask, vals, 0).astype(vals.dtype)
            vf = np.where(mask, 0, vals).astype(vals.dtype)
            self._vals_split_dev = (self._put(vi, self._blk_spec),
                                    self._put(vf, self._blk_spec))
        return self._vals_split_dev

    def _interior_mask_dev(self):
        """The (tiles, rows_p) interior-row mask as a device operand
        (injectable overlap plans recompute the interior/frontier val
        split in-program from it)."""
        if self._imask_dev is None:
            self._imask_dev = self._put(self.comm_plan.interior_mask,
                                        P(self._all_axes, None))
        return self._imask_dev

    # -- fault-injection surface --------------------------------------------

    def vals_template(self) -> np.ndarray:
        """Host copy of the packed matrix value buffer in the layout the
        compiled programs consume -- (rows, w) local ELL or (tiles,
        rows_p, w) stacked dist blocks.  Corrupt a copy (see
        ``repro.ft.inject``) and hand it to an injectable plan:
        ``plan(b, vals=corrupted)``."""
        if self.stencil is not None:
            raise ValueError("matrix-free stencil engines store no values "
                             "(coefficients are generated in-kernel)")
        if self.mode == "local":
            return np.array(self.ell.vals)
        return np.array(self.partition_plan.vals)

    def cols_template(self) -> np.ndarray:
        """Host copy of the packed ELL column indices matching
        ``vals_template`` (padded-global ids locally and in 1d mode)."""
        if self.stencil is not None:
            raise ValueError("matrix-free stencil engines store no columns "
                             "(structure is implicit in the grid)")
        if self.mode == "local":
            return np.array(self.ell.cols)
        if self.mode == "1d":
            return np.array(self._cols_pad_host)
        return np.array(self.partition_plan.cols)

    def halo_entry_mask(self) -> np.ndarray:
        """Boolean mask over ``vals_template()`` marking stored entries
        whose contribution depends on REMOTE vector shards -- the words a
        dropped or corrupted halo exchange poisons.  1d mode classifies
        per entry (global column outside the tile's own u-shard); 2d mode
        uses the comm plan's frontier rows (every stored entry of a row
        whose structure references any remote shard)."""
        if self.mode == "local":
            raise ValueError("halo faults need a distributed engine "
                             "(single-device engines have no exchange)")
        vals = self.vals_template()
        if self.mode == "1d":
            cols = self.cols_template()
            tiles = np.arange(cols.shape[0])[:, None, None]
            return ((cols // self.u) != tiles) & (vals != 0)
        imask = (self.comm_plan.interior_mask
                 if self.comm_plan is not None else None)
        if imask is None:
            return vals != 0
        return (~imask[:, :, None]) & (vals != 0)

    def vals_operand(self, vals=None):
        """Device operand for an injectable plan's ``vals`` argument: the
        engine's clean resident buffer when None, else a device_put of the
        caller's host buffer (shape-checked against the packed layout)."""
        if self.stencil is not None:
            raise ValueError("matrix-free stencil engines store no values "
                             "(no injectable surface)")
        if vals is None:
            return (jnp.asarray(self.ell.vals) if self.mode == "local"
                    else self.vals)
        vals = np.asarray(vals, dtype=self.dtype)
        want = ((np.asarray(self.ell.vals).shape if self.mode == "local"
                 else np.asarray(self.partition_plan.vals).shape))
        if vals.shape != want:
            raise ValueError(
                f"vals must match the packed value-buffer shape {want}, "
                f"got {vals.shape}")
        if self.mode == "local":
            return jnp.asarray(vals)
        return self._put(vals, self._blk_spec)

    # -- public ops ---------------------------------------------------------

    def spmv(self, x) -> np.ndarray:
        """y = A @ x on *global* vectors (host convenience wrapper).

        ``x`` may be (n,) or batch-stacked (k, n); the batched call runs the
        multi-RHS SpMM path (one matrix stream for all k) and returns (k, n).
        """
        x = np.asarray(x)
        if self.mode == "local":
            if self.stencil is not None:
                xd = jnp.asarray(self.to_device_vec(x))
                y = stencil_matvec(self.stencil, xd, self.n_pad)
                return self.from_device_vec(np.asarray(y))
            if self._row_perm is None:
                xd = jnp.asarray(x, self.dtype)
                if x.ndim == 2:
                    return np.asarray(
                        spmm_ell_padded(self.ell.cols, self.ell.vals, xd)[..., : self.n]
                    )
                from .spops import spmv_ell
                return np.asarray(spmv_ell(self.ell, xd))
            xd = self.to_device_vec(x)      # applies the row permutation
            if x.ndim == 2:
                y = spmm_ell_padded(self.ell.cols, self.ell.vals, xd)
            else:
                y = spmv_ell_padded(self.ell.cols, self.ell.vals, xd)
            return self.from_device_vec(y)
        layout = self._op_layout()
        key = ("spmm" if x.ndim == 2 else "spmv", layout)
        if key not in self._compiled:
            mv = self._mk_matvec(layout)
            vec = self._bvec_spec if x.ndim == 2 else self._vec_spec
            blk = self._blk_spec
            f = _shard_map(
                mv, mesh=self.mesh, in_specs=(vec, blk, blk), out_specs=vec,
            )
            self._compiled[key] = jax.jit(f)
        cols = self._halo_cols() if layout == "halo" else self.cols
        y = self._compiled[key](self.to_device_vec(x), cols, self.vals)
        return self.from_device_vec(y)

    def _halo_cols(self) -> jnp.ndarray:
        """The halo-remapped column blocks, device-put on FIRST use: a
        dense-only engine never pays the duplicate index footprint (the
        halo cols are a full copy of the ELL column array)."""
        if self._cols_halo_dev is None:
            self._cols_halo_dev = self._put(self.comm_plan.cols_halo,
                                            self._blk_spec)
        return self._cols_halo_dev

    def _op_layout(self) -> str:
        """The communication layout the engine-level ops (``spmv``) run:
        the engine knob resolved against the compiled comm plan ("auto" =
        halo exactly where it moves fewer bytes)."""
        if self.mode == "local" or self.comm_plan is None:
            return "dense"
        if self.layout == "auto":
            return "halo" if self.comm_plan.use_halo else "dense"
        return self.layout

    def _resolve_fused(self, method: str, fused) -> bool:
        """Map the tri-state knob to a concrete bool for this method: a
        capability lookup against the solver/precond registry ("auto" and
        True mean "fused wherever this method/preconditioner/mode triple
        registers support")."""
        sdef = registry.get_solver(method)
        pdef = registry.get_precond(self.precond)
        knob = self.fused if fused is None else fused
        return registry.resolve_fused(sdef, pdef, self.mode == "local", knob)

    def substrate_kind(self, method: str = "pcg", fused=None) -> str:
        """The substrate a plan for ``method`` will run on: "reference",
        "fused", "fused_ic0", "fused_shard" or "fused_shard_ic0".  Tests
        and the launch driver use this to assert path selection without
        re-deriving the dispatch rules."""
        sdef = registry.get_solver(method)
        pdef = registry.get_precond(self.precond)
        use = self._resolve_fused(method, fused)
        return registry.substrate_kind(sdef, pdef, self.mode == "local", use)

    # -- plan/execute API ---------------------------------------------------

    def plan(self, spec: SolveSpec | None = None, **kwargs) -> SolvePlan:
        """Lower a :class:`SolveSpec` into a compiled :class:`SolvePlan`.

        The spec is canonicalized against this engine (registry-validated
        method, engine preconditioner, resolved fused bool, tolerance
        fields nulled on fixed-iteration methods) and looked up in the
        spec-keyed ``self.plans`` cache -- equal configurations lower and
        compile exactly once; executing the returned plan never re-resolves
        dispatch.  ``plan(method="pcg", iters=100)`` is shorthand for
        ``plan(SolveSpec(method="pcg", iters=100))``."""
        if spec is None:
            spec = SolveSpec(**kwargs)
        spec = canonicalize(spec, self)
        from ..kernels import ops

        # the kernel dispatch mode is trace-relevant global state: a plan
        # traced under interpret kernels must not serve an "auto" run
        return self.plans.get(spec, self._lower, env=(ops.backend_mode(),))

    def _lower(self, spec: SolveSpec) -> SolvePlan:
        """Lower one canonical spec: pick the substrate by capability
        lookup, build the (local or shard_map) program, jit it once."""
        sdef = registry.get_solver(spec.method)
        pdef = registry.get_precond(self.precond)
        local = self.mode == "local"
        kind = registry.substrate_kind(sdef, pdef, local, spec.fused)
        cell = [0]  # trace counter: incremented when jax (re)traces
        fn = (self._lower_local if local else self._lower_dist)(
            spec, sdef, kind, cell
        )
        info = {
            "method": spec.method,
            "precond": spec.precond,
            "fused": spec.fused,
            "substrate": kind,
            "batch": spec.batch,
            "layout": spec.layout,
            "reorder": spec.reorder,
            "format": spec.format,
        }
        _OBS.counter(
            "repro_plan_format_total",
            "plans lowered by operator storage format", ("format",),
        ).inc(format=spec.format)
        if self.comm_plan is not None:
            # the modeled NoC record: halo width + bytes/iteration of the
            # layout this plan actually lowered to (and the alternative),
            # plus the overlap model and whether THIS plan lowered the
            # split communication-hiding matvec
            noc_model = self.comm_plan.model()
            noc_model["plan"] = spec.layout
            noc_model["comm_overlap"] = self._overlaps(sdef, spec, kind)
            info["noc"] = noc_model
            g = _OBS.gauge(
                "repro_plan_noc_bytes_per_iter",
                "modeled NoC bytes per solver iteration by comm layout",
                ("layout",))
            for lay in ("halo", "dense"):
                v = noc_model.get(f"bytes_per_iter_{lay}")
                if v is not None:
                    g.set(float(v), layout=lay)
        _OBS.gauge(
            "repro_engine_device_bytes",
            "device-resident operator footprint of the last-planned engine",
        ).set(float(self.device_bytes()))
        return SolvePlan(self, spec, fn, info, cell)

    @staticmethod
    def _overlaps(sdef, spec: SolveSpec, kind: str) -> bool:
        """Whether a plan lowers the split communication-hiding matvec:
        the method's recurrence must consume it (``comm_overlap``), the
        layout must be the compiled pull schedule, and the lowering must
        build a shard substrate to hang ``matvec_start``/``finish`` on."""
        return (sdef.comm_overlap and spec.layout == "halo"
                and kind in ("fused_shard", "fused_shard_ic0"))

    def _lower_local(self, spec: SolveSpec, sdef, kind: str, cell: list):
        """Single-device program: padded-ELL closures + fused substrate
        per the resolved kind, jitted (one trace per plan).

        Injectable plans take the packed value buffer as a runtime operand
        (the fault-injection surface -- ``plan(b, vals=corrupted)``)
        instead of closing over it as a trace constant; the substrate and
        matvec closures rebuild from the operand inside the trace, so one
        compiled program serves clean and corrupted operators alike.  The
        preconditioner operands (diagonal, IC(0) factors) stay clean --
        faults target the streamed matrix."""
        ell = self.ell
        dinv = self._dinv_pad
        eff = registry.effective_precond(sdef, self.precond, local=True)
        psolve = eff.local_apply(self)

        # non-ELL formats stream the operator through their own
        # (matvec, fold) pair -- ONE closure pair shared by the fused
        # substrate and the reference matvec, so fused == reference stays
        # bitwise per format.  Injectable plans are canonicalized to
        # "ell" (the runtime vals operand is ELL-shaped), so the runtime
        # rebuild below never meets a format stream.
        stream = None
        if spec.format != "ell":
            fobj = (self.stencil if spec.format == "stencil"
                    else self._format_obj(spec.format))
            stream = format_stream_ops(fobj, spec.format, self.n_pad)

        def build_ctx(vals):
            sub = None
            if kind == "fused_ic0":
                sub = fused_ic0_local_substrate(
                    None if ell is None else ell.cols, vals, self._ic0,
                    self.n, self.n_pad, stream_ops=stream)
            elif kind == "fused":
                sub = fused_local_substrate(
                    None if ell is None else ell.cols, vals,
                    dinv=dinv if eff.uses_dinv else None, stream_ops=stream,
                )

            if stream is not None:
                mv = stream[0]
            else:
                def mv(x):
                    if x.ndim == 2:
                        return spmm_ell_padded(ell.cols, vals, x)
                    return spmv_ell_padded(ell.cols, vals, x)

            return registry.SolveContext(
                matvec=mv, psolve=psolve, dinv=dinv, substrate=sub,
                iters=spec.iters, tol=spec.tol, max_iters=spec.max_iters,
                guard=spec.guard,
            )

        if spec.injectable:
            def prog(b_pad, x0_pad, vals_rt):
                cell[0] += 1
                res = ensure_status(
                    sdef.run(build_ctx(vals_rt), b_pad, x0_pad), b_pad)
                return (res.x, res.res_norms, res.iters, res.status,
                        res.bad_iter)

            return jax.jit(prog)

        ctx = build_ctx(None if ell is None else ell.vals)

        def prog(b_pad, x0_pad):
            cell[0] += 1
            res = ensure_status(sdef.run(ctx, b_pad, x0_pad), b_pad)
            return res.x, res.res_norms, res.iters, res.status, res.bad_iter

        return jax.jit(prog)

    def _lower_dist(self, spec: SolveSpec, sdef, kind: str, cell: list):
        """Distributed ``shard_map`` program: NoC matvec closure, per-tile
        preconditioner from the registry capability flags, collective-fused
        shard substrate per the resolved kind."""
        batched = spec.batch is not None
        # the NoC matvec closure lowers on the spec's resolved layout:
        # "halo" runs the compiled pull schedule over the halo-remapped
        # column blocks, "dense" the blanket collectives -- bit-identical
        # values, structurally different traffic
        mv = self._mk_matvec(spec.layout)
        dot = self._dot()
        dot2 = self._dot2()
        mesh = self.mesh
        vec, blk = self._vec_spec, self._blk_spec
        io_vec = self._bvec_spec if batched else vec
        s3 = P(self._all_axes, None, None)
        s2 = P(self._all_axes, None)
        cols = self._halo_cols() if spec.layout == "halo" else self.cols
        vals = self.vals
        eff = registry.effective_precond(sdef, self.precond, local=False)

        extra_args: tuple = ()
        extra_specs: tuple = ()
        if eff.uses_dinv:
            extra_args = (self._dinv_pad,)
            extra_specs = (vec,)
        elif eff.factorized:
            extra_args = self._pc_l + self._pc_u + (self._pc_k,)
            extra_specs = (s3, s3, s2, s3, s3, s3, s2, s3, vec)

        # communication hiding: the split val blocks ride as the LAST two
        # operands (the precond operand indices above stay stable) and the
        # shard substrate grows matvec_start/finish over them.  Injectable
        # plans instead carry the interior-row mask and recompute the
        # split in-program from the runtime vals operand (the host split
        # would bake the clean values back in).
        overlap = self._overlaps(sdef, spec, kind)
        if overlap:
            mv_start, mv_finish = self._mk_matvec_split()
            if spec.injectable:
                extra_args = extra_args + (self._interior_mask_dev(),)
                extra_specs = extra_specs + (P(self._all_axes, None),)
            else:
                vi_dev, vf_dev = self._split_vals()
                extra_args = extra_args + (vi_dev, vf_dev)
                extra_specs = extra_specs + (blk, blk)

        psum_axes = self._all_axes

        def prog(b_loc, x0_loc, cols_loc, vals_loc, *extra):
            amv = lambda x: mv(x, cols_loc, vals_loc)
            dinv_loc = extra[0] if eff.uses_dinv else None
            if eff.factorized:
                lc, lv, ldi, lr, uc, uv, udi, ur = (a[0] for a in extra[:8])
                k = extra[8][0]  # true block size of this tile

                def flip_k(z):
                    # reverse the first k entries in-place (padded tail
                    # stays zero): z_rev[i] = z[k-1-i] for i < k.
                    idx = k - 1 - jnp.arange(z.shape[0])
                    ok = idx >= 0
                    return jnp.where(
                        ok, z[jnp.clip(idx, 0, z.shape[0] - 1)], 0.0
                    )

                def ps1(r_loc):
                    rows_p = lc.shape[0]
                    bb = jnp.zeros((rows_p,), r_loc.dtype)
                    bb = bb.at[: r_loc.shape[0]].set(r_loc)
                    zp = local_sptrsv(lc, lv, ldi, bb, lr)
                    z = local_sptrsv(uc, uv, udi, flip_k(zp), ur)
                    return flip_k(z)[: r_loc.shape[0]]

                def ps(r_loc):
                    # batched (k, u) shard: the factors are shared, so
                    # the two triangular solves vmap over the batch.
                    return jax.vmap(ps1)(r_loc) if r_loc.ndim == 2 else ps1(r_loc)
            elif eff.uses_dinv:
                ps = lambda r: r * dinv_loc
            else:
                ps = lambda r: r
            sub = None
            if kind == "fused_shard":
                # collective-fused shard substrate: one stacked psum
                # carries [rr, rz]; the local update is the one-pass
                # cg_update kernel on this tile's vector shard.
                sub = fused_shard_substrate(
                    amv, dinv_loc, lambda s: lax.psum(s, psum_axes)
                )
            elif kind == "fused_shard_ic0":
                # same collective fusion with the per-tile block-IC(0)
                # triangular solves as the (collective-free) psolve
                sub = fused_shard_ic0_substrate(
                    amv, ps, lambda s: lax.psum(s, psum_axes)
                )
            if overlap:
                if spec.injectable:
                    mask_loc = extra[-1][..., None]
                    vi_loc = jnp.where(mask_loc, vals_loc, 0)
                    vf_loc = jnp.where(mask_loc, 0, vals_loc)
                else:
                    vi_loc, vf_loc = extra[-2], extra[-1]
                sub = sub._replace(
                    matvec_start=mv_start,
                    matvec_finish=lambda h: mv_finish(h, cols_loc, vi_loc,
                                                      vf_loc),
                )
            ctx = registry.SolveContext(
                matvec=amv, psolve=ps, dinv=dinv_loc, dot=dot, dot2=dot2,
                substrate=sub, iters=spec.iters, tol=spec.tol,
                max_iters=spec.max_iters, guard=spec.guard,
            )
            res = ensure_status(sdef.run(ctx, b_loc, x0_loc), b_loc)
            # status/bad_iter derive from psum'd reduction slots, so they
            # are replicated across tiles -- P() outputs like iters
            return res.x, res.res_norms, res.iters, res.status, res.bad_iter

        f = _shard_map(
            prog, mesh=mesh,
            in_specs=(io_vec, io_vec, blk, blk) + extra_specs,
            out_specs=(io_vec, P(), P(), P(), P()),
        )

        if spec.injectable:
            def outer(b, x0, vals_rt):
                cell[0] += 1
                return f(b, x0, cols, vals_rt, *extra_args)
        else:
            def outer(b, x0):
                cell[0] += 1
                return f(b, x0, cols, vals, *extra_args)

        return jax.jit(outer)

    # -- legacy kwargs surface (deprecated shim over the plan cache) --------

    def solve(self, b, method: str = "pcg", iters: int = 200, x0=None,
              fused=None, tol: float = 1e-8, max_iters: int | None = None):
        """DEPRECATED: build a :class:`SolveSpec` and use :meth:`plan`.

        Thin shim kept for compatibility: it builds the equivalent spec,
        hits the spec-keyed plan cache, and executes -- bit-identical to
        calling the plan directly (``b`` may be (n,) or stacked (k, n); for
        tolerance methods per-RHS iteration counts land in
        ``self.last_solve_info["iters"]``).  Emits one DeprecationWarning
        per process."""
        warn_deprecated(
            "AzulEngine.solve",
            "AzulEngine.solve(**knobs) is deprecated: build a SolveSpec "
            "and use AzulEngine.plan(spec) (see README 'The plan/execute "
            "API').",
        )
        b = np.asarray(b)
        # no knob resolution here: canonicalize() owns the engine-knob
        # deference ('auto'/None -> engine.fused), the shim just spells
        # the kwargs as a spec
        spec = SolveSpec(
            method=method, iters=iters, tol=tol, max_iters=max_iters,
            batch=b.shape[0] if b.ndim == 2 else None,
            fused="auto" if fused is None else fused,
        )
        return self.plan(spec)(b, x0=x0)

    def device_bytes(self) -> int:
        """Device-resident footprint of this engine's operator state in
        bytes: matrix blocks (packed ELL cols/vals), preconditioner
        buffers (inverse diagonal, IC(0) factor planes, multigrid
        levels).  The serving layer's operator registry charges this
        against its memory budget for admission/eviction decisions.  Plan programs/executables are
        not counted (they are XLA-owned and tiny next to the operands)."""
        total = 0
        seen: set[int] = set()
        for attr in ("ell", "cols", "vals", "_dinv_pad", "_ic0", "_fmt_objs",
                     "_mg"):
            obj = getattr(self, attr, None)
            if obj is None:
                continue
            for leaf in jax.tree_util.tree_leaves(obj):
                nb = getattr(leaf, "nbytes", None)
                if nb is None or id(leaf) in seen:
                    continue
                seen.add(id(leaf))
                total += int(nb)
        return total

    # -- distributed SpTRSV (2D block-stage forward substitution) -----------

    def build_sptrsv(self, l_csr: CSR):
        """Compile a distributed lower-triangular solve for ``l_csr`` on this
        engine's mesh (square 2D grids).  Returns fn: b_global -> x_global.

        Execution = pr block stages of Azul-style wavefronts: at stage I the
        tiles of block-row I apply their pinned L_IJ against already-solved
        x_J fragments (local SpMV + psum across the row), the diagonal tile
        runs its *local level-scheduled* solve (fine-grained wavefronts
        inside the block), and the solved x_I is broadcast down column I --
        three NoC messages per stage, the paper's task dataflow made static.
        """
        if self.mode != "2d" or self.pr != self.pc:
            raise ValueError("distributed SpTRSV needs a square 2d engine")
        if self._row_perm is not None:
            raise ValueError(
                "distributed SpTRSV needs reorder='none': the engine's "
                "permutation would destroy triangularity of l_csr"
            )
        if self._pad2g is not None:
            raise ValueError(
                "distributed SpTRSV needs uniform row blocks (the engine's "
                "nnz-balanced 2d embedding shifts block boundaries) -- "
                "build the engine with balance='rows'"
            )
        key = _csr_fingerprint(l_csr)
        if key in self._trsv_cache:
            return self._trsv_cache[key]

        mesh = self.mesh
        pr, pc, u = self.pr, self.pc, self.u
        plan = plan_2d(l_csr, pr, pc, width_pad=self._width_pad,
                       row_pad=self._row_pad, dtype=self.dtype)
        if plan.n_padded != self.n_pad:
            raise ValueError("triangular matrix padding mismatch with engine")
        br = plan.block_rows

        # per-tile level schedule of its own block (real only on diagonal)
        scheds = []
        nl_max, wl_max = 1, 8
        nl = l_csr.shape[0]
        for i in range(pr):
            for j in range(pc):
                if i == j:
                    r0, r1 = min(i * br, nl), min((i + 1) * br, nl)
                    if r1 > r0:
                        blk = tile_csr(l_csr, r0, r1, r0, r1)
                        sc = build_schedule(blk)
                        scheds.append(sc)
                        nl_max = max(nl_max, sc.n_levels)
                        wl_max = max(wl_max, sc.max_width)
                        continue
                scheds.append(None)
        rows = np.full((pr * pc, nl_max, wl_max), br, np.int32)
        for t, sc in enumerate(scheds):
            if sc is None:
                continue
            sr = np.asarray(sc.rows)
            sr = np.where(sr >= sc.n, br, sr)
            rows[t, : sr.shape[0], : sr.shape[1]] = sr

        # per-tile diag inverse of its own block (meaningful on diagonal)
        dloc = np.ones((pr * pc, br), self.dtype)
        dg = np.ones(self.n_pad, np.float64)
        dg[: nl] = _host_diag(l_csr, 0, nl)
        dg[dg == 0] = 1.0
        for i in range(pr):
            dloc[i * pc + i] = (1.0 / dg[i * br : (i + 1) * br]).astype(self.dtype)

        s3 = P(self._all_axes, None, None)
        s2 = P(self._all_axes, None)
        cols_d = self._put(plan.cols, s3)
        vals_d = self._put(plan.vals, s3)
        rows_d = self._put(rows, s3)
        dinv_d = self._put(dloc, s2)

        row_axes, col_axes = self.row_axes, self.col_axes
        all_axes = self._all_axes

        def prog(b_loc, cols, vals, rows, dinv):
            cols, vals, rows, dinv = cols[0], vals[0], rows[0], dinv[0]
            ri = lax.axis_index(row_axes)
            ci = lax.axis_index(col_axes)
            b_row = noc.gather_along(b_loc, col_axes)        # (br,) = b_I
            x_col = jnp.zeros((br,), vals.dtype)             # known x_J (ours)
            out = jnp.zeros((u,), vals.dtype)

            def stage(carry, i_stage):
                x_col, out = carry
                part = spmv_ell_padded(cols, vals, x_col)    # L_iJ x_J
                s = lax.psum(part, col_axes)                 # row-combine
                rhs = b_row - s
                xi = local_sptrsv(cols, vals, dinv, rhs, rows)
                mine = (ri == i_stage) & (ci == i_stage)
                x_i = lax.psum(
                    jnp.where(mine, xi, jnp.zeros_like(xi)), all_axes
                )
                x_col = jnp.where(ci == i_stage, x_i, x_col)
                seg = lax.dynamic_slice(x_i, (ci * u,), (u,))
                out = jnp.where(ri == i_stage, seg, out)
                return (x_col, out), None

            (x_col, out), _ = lax.scan(stage, (x_col, out), jnp.arange(pr))
            return out

        vec = self._vec_spec
        f = _shard_map(
            prog, mesh=mesh,
            in_specs=(vec, s3, s3, s3, s2),
            out_specs=vec,
        )
        fn_dev = jax.jit(lambda b: f(b, cols_d, vals_d, rows_d, dinv_d))

        def solve(b_global):
            bd = self.to_device_vec(np.asarray(b_global))
            return self.from_device_vec(fn_dev(bd))

        solve.device_fn = fn_dev
        self._trsv_cache[key] = solve
        return solve

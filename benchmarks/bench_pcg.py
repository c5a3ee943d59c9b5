"""Paper §IV analogue: end-to-end PCG on the SuiteSparse-analog suite.

Per matrix x preconditioner: iterations to 1e-8 relative residual, wall
time per iteration, sustained GF/s (2*nnz + 10n flops/iter), and the
functional-verification check against numpy (paper's "matching a sample
Python implementation").

``--batch-sizes 1,4,16`` adds the multi-RHS sweep: per batch size k, one
batched (k, n) solve vs k sequential single-RHS solves, reporting per-RHS
throughput (the amortize-the-matrix-stream payoff of the batched path):

    PYTHONPATH=src python -m benchmarks.bench_pcg --batch-sizes 1,4,16

``--fused-compare`` times the fused solver-iteration hot path against the
reference op-per-line path on the same matrices (plus the modeled
vector-HBM traffic from ``substrate.modeled_vector_traffic``), and
``--json FILE`` writes the whole run as a machine-readable payload -- the
perf-trajectory record CI archives per commit (see also
``benchmarks.run --json``).

Everything here runs through the plan/execute API: each configuration is a
frozen ``SolveSpec`` lowered once via ``engine.plan(spec)`` and the
compiled ``SolvePlan`` is executed for the timed repeats -- so the
benchmark exercises exactly the program production serving runs, and the
tolerance section plots the bounded convergence-trace ring ``pcg_tol``
plans now return (ASCII log-residual sparkline + downsampled points in the
JSON payload).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np
import scipy.sparse as sp

from repro.core.engine import AzulEngine
from repro.core.plan import SolveSpec
from repro.core.substrate import modeled_ic0_traffic, modeled_vector_traffic
from repro.data.matrices import suite

NOC_GRIDS_2D = ((2, 2), (4, 1), (4, 2))
NOC_PARTS_1D = (4, 8)

_SPARK = "▁▂▃▄▅▆▇█"


def sparkline(trace, iters: int | None = None, width: int = 48) -> str:
    """ASCII log-residual curve of a convergence trace (the plot the
    bounded ``pcg_tol`` ring buffer exists for).  ``iters`` truncates to
    the real trace (the ring tail-fills past the stopping iteration)."""
    t = np.asarray(trace, dtype=float).ravel()
    if iters is not None:
        t = t[: int(iters) + 1]
    t = np.log10(np.maximum(np.abs(t), 1e-300))
    if t.size > width:
        idx = np.linspace(0, t.size - 1, width).round().astype(int)
        t = t[idx]
    lo, hi = float(t.min()), float(t.max())
    span = (hi - lo) or 1.0
    levels = ((t - lo) / span * (len(_SPARK) - 1)).round().astype(int)
    return "".join(_SPARK[lv] for lv in levels)


def _trace_points(trace, iters: int, width: int = 32) -> list[float]:
    """Downsample a convergence trace for the JSON payload (<= width
    points, endpoints kept)."""
    t = np.asarray(trace, dtype=float).ravel()[: int(iters) + 1]
    if t.size > width:
        idx = np.linspace(0, t.size - 1, width).round().astype(int)
        t = t[idx]
    return [float(v) for v in t]


def run() -> list[tuple[str, float, str]]:
    rows = []
    rng = np.random.default_rng(0)
    for name, m in suite("small").items():
        a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
        x_true = rng.standard_normal(m.shape[0])
        b = a @ x_true
        bn = np.linalg.norm(b)
        for pc in ("jacobi", "block_ic0"):
            eng = AzulEngine(m, mesh=None, precond=pc, dtype=np.float64)
            # convergence: fixed-iteration plans, find iters to 1e-8
            x, norms = eng.plan(SolveSpec(method="pcg", iters=200))(b)
            rel = norms / bn
            hit = np.argmax(rel < 1e-8) if (rel < 1e-8).any() else len(rel)
            plan50 = eng.plan(SolveSpec(method="pcg", iters=50))
            plan50(b)                        # warm: compile outside the clock
            t0 = time.perf_counter()
            plan50(b)
            dt = (time.perf_counter() - t0) / 50
            flops = 2 * m.nnz + 10 * m.shape[0]
            err = float(np.abs(x - x_true).max())
            rows.append((
                f"pcg_{name}_{pc}", dt * 1e6,
                f"iters_to_1e8={int(hit)} GF/s={flops/dt/1e9:.3f} "
                f"verify_maxerr={err:.2e}",
            ))
    return rows


def run_fused_compare(
    iters: int = 60, matrices=("lap2d_32", "banded_1k", "rspd_1k"),
) -> tuple[list[tuple[str, float, str]], list[dict]]:
    """Fused solver-iteration hot path vs the reference op-per-line path.

    Per matrix: per-iteration wall time for both paths, the residual-trace
    agreement (they run the same recurrence, reassociated), and the modeled
    vector-HBM traffic reduction the fusion buys at this matrix's ELL
    width.  On CPU the fused path runs the fused jnp composition (or
    interpret-mode kernel bodies under ``REPRO_KERNEL_MODE=interpret``);
    compiled-kernel timings come from TPU runs of the same entry point.
    """
    rows, payload = [], []
    rng = np.random.default_rng(0)
    mats = suite("small")
    for name in matrices:
        m = mats[name]
        a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
        b = a @ rng.standard_normal(m.shape[0])
        eng = AzulEngine(m, mesh=None, precond="jacobi", dtype=np.float64)

        def timed(fused):
            plan = eng.plan(SolveSpec(method="pcg", iters=iters, fused=fused))
            plan(b)                                                # warm jit
            t0 = time.perf_counter()
            x, norms = plan(b)
            return (time.perf_counter() - t0) / iters, x, norms

        dt_f, x_f, n_f = timed(True)
        dt_u, x_u, n_u = timed(False)
        trace_diff = float(np.abs((n_f - n_u) / (np.abs(n_u) + 1e-300)).max())
        model = modeled_vector_traffic(eng.ell.width)
        rows.append((
            f"pcg_fused_{name}", dt_f * 1e6,
            f"unfused_us={dt_u * 1e6:.1f} speedup={dt_u / dt_f:.2f}x "
            f"trace_reldiff={trace_diff:.2e} "
            f"modeled_traffic_reduction={model['reduction']:.2f}x",
        ))
        payload.append({
            "matrix": name,
            "n": int(m.shape[0]),
            "nnz": int(m.nnz),
            "ell_width": int(eng.ell.width),
            "iters": int(iters),
            "us_per_iter_fused": round(dt_f * 1e6, 3),
            "us_per_iter_unfused": round(dt_u * 1e6, 3),
            "speedup": round(dt_u / dt_f, 4),
            "trace_rel_maxdiff": trace_diff,
            "x_maxdiff": float(np.abs(x_f - x_u).max()),
            "modeled_traffic": model,
        })
    return rows, payload


def run_batch_sweep(batch_sizes, iters: int = 60,
                    matrices=("lap2d_32", "rspd_1k")):
    """Multi-RHS sweep: batched (k, n) PCG vs k sequential solves.
    Returns (csv_rows, json_payload)."""
    rows, payload = [], []
    rng = np.random.default_rng(0)
    mats = suite("small")
    for name in matrices:
        m = mats[name]
        a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
        eng = AzulEngine(m, mesh=None, precond="jacobi", dtype=np.float64)
        x_true = rng.standard_normal((max(batch_sizes), m.shape[0]))
        b_all = x_true @ a.T
        for k in batch_sizes:
            b = b_all[:k]
            # batched: one stacked plan execution
            bplan = eng.plan(SolveSpec(method="pcg", iters=iters, batch=k))
            bplan(b)                                         # warm the jit
            t0 = time.perf_counter()
            xb, _ = bplan(b)
            dt_batch = time.perf_counter() - t0
            # sequential baseline: k executions of the single-RHS plan
            splan = eng.plan(SolveSpec(method="pcg", iters=iters))
            splan(b[0])
            t0 = time.perf_counter()
            x_seq = []
            for i in range(k):
                xi, _ = splan(b[i])
                x_seq.append(xi)
            dt_seq = time.perf_counter() - t0
            # verify batched against the sequential solves (same algorithm,
            # same iteration count) -- NOT against x_true, which a fixed-
            # iteration PCG need not have reached yet
            err = float(np.abs(xb - np.stack(x_seq)).max())
            rows.append((
                f"pcg_batch_{name}_k{k}", dt_batch / k * 1e6,
                f"rhs_per_s={k/dt_batch:.2f} seq_rhs_per_s={k/dt_seq:.2f} "
                f"speedup={dt_seq/dt_batch:.2f}x batch_vs_seq_maxerr={err:.2e}",
            ))
            payload.append({
                "matrix": name,
                "k": int(k),
                "iters": int(iters),
                "us_per_iter_per_rhs": round(dt_batch / k / iters * 1e6, 3),
                "rhs_per_s_batched": round(k / dt_batch, 4),
                "rhs_per_s_sequential": round(k / dt_seq, 4),
                "speedup_vs_sequential": round(dt_seq / dt_batch, 4),
                "batch_vs_seq_maxerr": err,
            })
    return rows, payload


def run_tol_solves(
    tol: float = 1e-8, max_iters: int = 400,
    matrices=("lap2d_32", "banded_1k"),
    preconds=("jacobi", "block_ic0"),
) -> tuple[list[tuple[str, float, str]], list[dict]]:
    """Tolerance-stopped solves, fused vs reference: the CI regression
    gate's primary signal.  Iteration counts are *discrete* -- any change
    to the recurrence, the preconditioner factorization, or the stopping
    test moves them, so the gate compares them exactly (timings only get a
    generous cross-machine ratio).  Also records the per-path substrate,
    the modeled IC(0) traffic at this matrix's level counts, and the
    bounded convergence trace the tolerance plans carry (downsampled
    points in the payload; the driver plots the sparkline)."""
    rows, payload = [], []
    rng = np.random.default_rng(0)
    mats = suite("small")
    for name in matrices:
        m = mats[name]
        a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
        b = a @ rng.standard_normal(m.shape[0])
        for pc in preconds:
            eng = AzulEngine(m, mesh=None, precond=pc, dtype=np.float64)

            def timed(fused):
                plan = eng.plan(SolveSpec(method="pcg_tol", tol=tol,
                                          max_iters=max_iters, fused=fused))
                plan(b)                                     # warm jit
                t0 = time.perf_counter()
                x, norms = plan(b)
                dt = time.perf_counter() - t0
                return dt, x, int(np.asarray(plan.last_iters)), \
                    plan.info["substrate"], norms

            dt_f, x_f, it_f, sub_f, trace_f = timed(True)
            dt_u, x_u, it_u, _, _ = timed(False)
            entry = {
                "matrix": name,
                "precond": pc,
                "n": int(m.shape[0]),
                "tol": tol,
                "substrate_fused": sub_f,
                "iters_fused": it_f,
                "iters_reference": it_u,
                "iters_match": it_f == it_u,
                "x_maxdiff": float(np.abs(x_f - x_u).max()),
                "us_per_iter_fused": round(dt_f / max(it_f, 1) * 1e6, 3),
                "us_per_iter_unfused": round(dt_u / max(it_u, 1) * 1e6, 3),
                # the bounded trace ring (tolerance-mode convergence plot)
                "trace_points": _trace_points(trace_f, it_f),
                "trace_spark": sparkline(trace_f, it_f),
            }
            if pc == "block_ic0":
                f = eng._ic0
                entry["modeled_ic0_traffic"] = modeled_ic0_traffic(
                    eng.ell.width, f.sched_l.n_levels, f.sched_u_rev.n_levels
                )
            payload.append(entry)
            rows.append((
                f"pcg_tol_{name}_{pc}", dt_f / max(it_f, 1) * 1e6,
                f"substrate={sub_f} iters={it_f} iters_ref={it_u} "
                f"x_maxdiff={entry['x_maxdiff']:.2e}",
            ))
    return rows, payload


def run_pipelined_solves(
    tol: float = 1e-8, max_iters: int = 400,
    matrices=("lap2d_32", "banded_1k"),
    preconds=("jacobi", "block_ic0"),
) -> tuple[list[tuple[str, float, str]], list[dict]]:
    """Pipelined vs standard PCG in tolerance mode: the PR 6 promotion's
    regression record.  Per (matrix, precond): iteration counts of BOTH
    methods (discrete -- gated exactly, like ``tol_solves``), the solution
    agreement between the two recurrences, the trace-head check (the
    pipelined r0 comes from the stacked init reduction and must equal
    ``||b||`` -- the injected-reduction bug regression), and the structural
    reduction count the method exists for: ONE stacked all-reduce per
    iteration against standard PCG's two."""
    rows, payload = [], []
    rng = np.random.default_rng(0)
    mats = suite("small")
    for name in matrices:
        m = mats[name]
        a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
        b = a @ rng.standard_normal(m.shape[0])
        bn = float(np.linalg.norm(b))
        for pc in preconds:
            eng = AzulEngine(m, mesh=None, precond=pc, dtype=np.float64)

            def timed(method):
                plan = eng.plan(SolveSpec(method=method, tol=tol,
                                          max_iters=max_iters))
                plan(b)                                     # warm jit
                t0 = time.perf_counter()
                x, norms = plan(b)
                dt = time.perf_counter() - t0
                return dt, x, int(np.asarray(plan.last_iters)), norms

            dt_p, x_p, it_p, trace_p = timed("pcg_pipelined_tol")
            dt_s, x_s, it_s, _ = timed("pcg_tol")
            entry = {
                "matrix": name,
                "precond": pc,
                "n": int(m.shape[0]),
                "tol": tol,
                "iters_pipelined": it_p,
                "iters_pcg": it_s,
                "x_vs_pcg_maxdiff": float(np.abs(x_p - x_s).max()),
                # trace head = ||b||: the stacked init reduction's rr slot
                "r0_reldiff": abs(float(np.asarray(trace_p)[0]) - bn) / bn,
                # the communication structure, not a measurement: the
                # stacked 3-way pdots is ONE collective; standard PCG
                # carries two dependent reductions per iteration
                "reductions_per_iter_pipelined": 1,
                "reductions_per_iter_pcg": 2,
                "us_per_iter_pipelined": round(dt_p / max(it_p, 1) * 1e6, 3),
                "us_per_iter_pcg": round(dt_s / max(it_s, 1) * 1e6, 3),
                "trace_points": _trace_points(trace_p, it_p),
                "trace_spark": sparkline(trace_p, it_p),
            }
            payload.append(entry)
            rows.append((
                f"pcg_pipelined_{name}_{pc}", dt_p / max(it_p, 1) * 1e6,
                f"iters={it_p} iters_pcg={it_s} "
                f"x_vs_pcg_maxdiff={entry['x_vs_pcg_maxdiff']:.2e} "
                f"r0_reldiff={entry['r0_reldiff']:.2e}",
            ))
    return rows, payload


def run_noc_plans(
    matrices=("lap2d_32", "banded_1k", "rspd_1k"),
    reorders=("none", "rcm"),
) -> tuple[list[tuple[str, float, str]], list[dict]]:
    """Modeled NoC traffic of the compiled communication plans.

    Pure host-side compilation (NumPy partition + comm-plan compile, no
    devices needed -- exactly what the engine build runs), so the record is
    deterministic and the regression gate compares it exactly: the plan
    choice (halo vs dense fallback), the halo width, and the modeled
    bytes/iteration of both layouts per (matrix, reorder, mode, grid).  A
    config that used to cut a halo plan and now falls back to dense is a
    traffic regression the gate fails on."""
    from repro.core.commplan import compile_comm_plan_1d, compile_comm_plan_2d
    from repro.core.partition import (padded_layout_1d, permute_csr, plan_1d,
                                      plan_2d, rcm_permutation)

    rows, payload = [], []
    mats = suite("small")
    for name in matrices:
        base = mats[name]
        for reorder in reorders:
            m = (permute_csr(base, rcm_permutation(base))
                 if reorder == "rcm" else base)
            plans = []
            for (pr, pc) in NOC_GRIDS_2D:
                p = plan_2d(m, pr, pc, dtype=np.float64, balance="nnz")
                u = p.n_padded // (pr * pc)
                cp = compile_comm_plan_2d(np.asarray(p.cols),
                                          np.asarray(p.vals), pr, pc, u,
                                          itemsize=8)
                plans.append((f"{pr}x{pc}", "2d", cp))
            for parts in NOC_PARTS_1D:
                p = plan_1d(m, parts, balance="nnz", dtype=np.float64)
                cols_pad, _ = padded_layout_1d(p)   # the engine's layout
                cp = compile_comm_plan_1d(cols_pad, np.asarray(p.vals),
                                          p.rows_per_tile, parts, itemsize=8)
                plans.append((f"{parts}", "1d", cp))
            for grid, mode, cp in plans:
                model = cp.model()
                payload.append({"matrix": name, "reorder": reorder,
                                "mode": mode, "grid": grid, **model})
                # these are traffic-model rows, not timings: the numeric
                # CSV column carries 0.0 (no wall time was measured) and
                # every modeled quantity lives, labeled, in the derived
                # string -- nothing masquerades as microseconds
                rows.append((
                    f"noc_{name}_{reorder}_{mode}_{grid}", 0.0,
                    f"plan={model['plan']} halo_width={model['halo_width']} "
                    f"bytes_per_iter_halo={model['bytes_per_iter_halo']} "
                    f"bytes_per_iter_dense={model['bytes_per_iter_dense']} "
                    f"reduction={model['reduction']}x",
                ))
    return rows, payload



def run_guarded_solves(
    tol: float = 1e-8, max_iters: int = 400,
    matrices=("lap2d_32",),
    methods=("pcg_tol", "pcg_pipelined_tol"),
) -> tuple[list[tuple[str, float, str]], list[dict]]:
    """Guarded solves: the fault-tolerance layer's regression record.  Per
    (matrix, method):

    * the clean solve's iteration count and status;
    * its per-iteration timing, against the baseline's;
    * ``collectives_guarded`` counted from the lowered HLO -- guards read
      reduction slots the iteration already computed, so the count is
      pinned to the baseline's (locally 0; the 4-device halo counts are
      asserted in tests/test_faults.py);
    * ``detects_indefinite`` -- an injectable plan handed values with a
      negated diagonal entry must report ``breakdown`` (the end-to-end
      detection probe, exercising the same program the clean run timed).

    That a clean guarded solve is bitwise the bare recurrence is a test
    (``tests/test_guards.py``), not a record.
    """
    rows, payload = [], []
    rng = np.random.default_rng(0)
    mats = suite("small")
    for name in matrices:
        m = mats[name]
        a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
        b = a @ rng.standard_normal(m.shape[0])
        eng = AzulEngine(m, mesh=None, precond="jacobi", dtype=np.float64)
        for method in methods:
            plan = eng.plan(SolveSpec(method=method, tol=tol,
                                      max_iters=max_iters))
            plan(b)                                         # warm jit
            t0 = time.perf_counter()
            plan(b)
            dt_g = time.perf_counter() - t0
            it_g = int(np.asarray(plan.last_iters))
            ops = plan.hlo_summary()["count_by_op"]
            coll_g = int(ops.get("all-reduce", 0)
                         + ops.get("collective-permute", 0))

            # detection probe: negate one diagonal entry through the
            # injectable value operand -- A stops being SPD, the guards
            # must say so (breakdown), and x must come back finite
            pi = eng.plan(SolveSpec(method=method, tol=tol,
                                    max_iters=max_iters, injectable=True))
            vbad = eng.vals_template()
            cols = eng.cols_template()
            row = 1
            slot = int(np.where(cols[row] == row)[0][0])
            vbad[row, slot] *= -1000.0
            x_bad, _ = pi(b, vals=vbad)
            detected = pi.last_status_names == "breakdown"

            entry = {
                "matrix": name,
                "method": method,
                "precond": "jacobi",
                "n": int(m.shape[0]),
                "tol": tol,
                "iters_guarded": it_g,
                "status_clean": plan.last_status_names,
                "collectives_guarded": coll_g,
                "detects_indefinite": bool(detected),
                "bad_x_finite": bool(np.isfinite(x_bad).all()),
                "us_per_iter_guarded": round(dt_g / max(it_g, 1) * 1e6, 3),
            }
            payload.append(entry)
            rows.append((
                f"guarded_{name}_{method}", dt_g / max(it_g, 1) * 1e6,
                f"iters={it_g} collectives={coll_g} "
                f"detects_indefinite={detected}",
            ))
    return rows, payload


def run_formats(
    matrices=("skew_1k", "rmat_1k"), tol: float = 1e-8, max_iters: int = 400,
    repeats: int = 3, wall_gate=("skew_1k",),
) -> tuple[list[tuple[str, float, str]], list[dict]]:
    """Storage-format portfolio on skewed/power-law matrices: the record
    ROADMAP item 4a exists for.

    Per matrix: the autotuner's chosen format, the modeled matrix-stream
    words of every candidate (host-deterministic -- gated exactly), and the
    A/B the portfolio must win: the autotuned solve vs the same solve
    forced to padded ELL.  ``beats_ell_modeled`` is a pure model statement;
    ``beats_ell_wall`` is measured (min of ``repeats`` interleaved runs) --
    both are gated on the skewed matrices, where global-width padding
    streams mostly zeros.  Correctness rides along: tolerance-mode
    iteration counts match ELL's exactly (same recurrence, reassociated
    reductions), and the fused path is bitwise-identical to the reference
    path ON the chosen format.

    The whole A/B runs with kernel dispatch forced off (compiled XLA for
    BOTH arms): under ``REPRO_KERNEL_MODE=interpret`` the ELL arm would
    otherwise pay interpret-mode Pallas cost the compact formats (XLA
    segment ops) never see, inflating the wall win ~1000x.  Forcing one
    substrate class makes the measured speedup the storage-format effect
    alone, and makes the smoke-CI record match a bare local run."""
    from repro.kernels import ops
    from repro.kernels.autotune import choose_format, modeled_format_words

    rows, payload = [], []
    rng = np.random.default_rng(0)
    mats = suite("small")
    prev_mode = ops.backend_mode()
    ops.backend_mode("never")
    try:
        for name in matrices:
            rows_n, entry = _format_ab(
                mats[name], name, rng, tol, max_iters, repeats, wall_gate,
                choose_format, modeled_format_words)
            payload.append(entry)
            rows.append(rows_n)
    finally:
        ops.backend_mode(prev_mode)
    return rows, payload


def _format_ab(m, name, rng, tol, max_iters, repeats, wall_gate,
               choose_format, modeled_format_words):
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    b = a @ rng.standard_normal(m.shape[0])
    words = modeled_format_words(m)
    chosen, _ = choose_format(m, dtype=np.float64, use_cache=False)

    def arm(fmt):
        eng = AzulEngine(m, mesh=None, precond="jacobi",
                         dtype=np.float64, format=fmt)
        plan = eng.plan(SolveSpec(method="pcg_tol", tol=tol,
                                  max_iters=max_iters))
        plan(b)                                         # warm jit
        return eng, plan

    eng_a, plan_a = arm("auto")
    eng_e, plan_e = arm("ell")
    dts_a, dts_e = [], []
    x_a = x_e = None
    for _ in range(repeats):                # interleave against noise
        t0 = time.perf_counter()
        x_a, _ = plan_a(b)
        dts_a.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        x_e, _ = plan_e(b)
        dts_e.append(time.perf_counter() - t0)
    dt_a, dt_e = min(dts_a), min(dts_e)
    it_a = int(np.asarray(plan_a.last_iters))
    it_e = int(np.asarray(plan_e.last_iters))
    # fused == reference bitwise, on the chosen format
    plan_r = eng_a.plan(SolveSpec(method="pcg_tol", tol=tol,
                                  max_iters=max_iters, fused=False))
    x_r, _ = plan_r(b)
    entry = {
        "kind": "format_autotune",
        "matrix": name,
        "n": int(m.shape[0]),
        "nnz": int(m.nnz),
        "chosen_format": eng_a.format_choice,
        "modeled_words": {k: int(v) for k, v in words.items()},
        "modeled_reduction_vs_ell": round(
            words["ell"] / max(words[chosen], 1), 3),
        "beats_ell_modeled": bool(words[chosen] < words["ell"]),
        "beats_ell_wall": bool(dt_a < dt_e),
        # the hub-row matrix's ~2x wall win is machine-robust and gated
        # exactly; power-law wins are real but thin on CPU (the padded
        # width is smaller), so they stay recorded-not-gated
        "wall_gated": name in wall_gate,
        "wall_speedup_vs_ell": round(dt_e / dt_a, 4),
        "iters_auto": it_a,
        "iters_ell": it_e,
        "iters_match": it_a == it_e,
        "x_vs_ell_maxdiff": float(np.abs(x_a - x_e).max()),
        "fused_matches_reference": bool(np.array_equal(x_a, x_r)),
        "us_per_iter_auto": round(dt_a / max(it_a, 1) * 1e6, 3),
        "us_per_iter_ell": round(dt_e / max(it_e, 1) * 1e6, 3),
    }
    row = (
        f"format_{name}", dt_a / max(it_a, 1) * 1e6,
        f"chosen={entry['chosen_format']} "
        f"modeled_reduction={entry['modeled_reduction_vs_ell']}x "
        f"wall_speedup={entry['wall_speedup_vs_ell']}x "
        f"iters={it_a}=={it_e} "
        f"fused_bitwise={entry['fused_matches_reference']}",
    )
    return row, entry


def run_plan_scaling(
    levels=(128, 1024),
) -> tuple[list[tuple[str, float, str]], list[dict]]:
    """Compile scaling of the SpTRSV wavefront (ROADMAP item 4c): plan-time
    (jit trace + StableHLO lower) of the ``lax.scan`` wavefront vs the
    trace-time-unrolled per-level baseline, on a bidiagonal system whose
    level count equals n.  The scan emits O(1) traced statements regardless
    of level count, the unrolled loop O(levels); the gate asserts the scan
    stays far sublinear at ~1000 levels (``scan_sublinear_vs_unrolled``)."""
    import jax
    import jax.numpy as jnp

    from repro.core.formats import csr_from_scipy, ell_from_csr
    from repro.core.levels import build_schedule
    from repro.core.spops import sptrsv_ell, sptrsv_ell_unrolled

    def trace_lower_s(fn, e, sched, b):
        f = jax.jit(lambda bb: fn(e, sched, bb))
        t0 = time.perf_counter()
        f.lower(b)
        return time.perf_counter() - t0

    per_level = []
    for nlev in levels:
        l = (sp.eye(nlev) * 2.0
             + sp.diags([-1.0], [-1], shape=(nlev, nlev))).tocsr()
        m = csr_from_scipy(l)
        e = ell_from_csr(m, dtype=np.float64)
        sched = build_schedule(m)
        b = jnp.asarray(np.ones(nlev))
        per_level.append({
            "levels": int(sched.n_levels),
            "plan_s_scan": round(trace_lower_s(sptrsv_ell, e, sched, b), 4),
            "plan_s_unrolled": round(
                trace_lower_s(sptrsv_ell_unrolled, e, sched, b), 4),
        })
    lo, hi = per_level[0], per_level[-1]
    growth_scan = hi["plan_s_scan"] / max(lo["plan_s_scan"], 1e-9)
    growth_unr = hi["plan_s_unrolled"] / max(lo["plan_s_unrolled"], 1e-9)
    entry = {
        "kind": "plan_scaling",
        "matrix": f"bidiag_{hi['levels']}",
        "points": per_level,
        "growth_scan": round(growth_scan, 3),
        "growth_unrolled": round(growth_unr, 3),
        # robust across machines: at ~1000 levels the scan's plan time must
        # sit far below the unrolled baseline's (linear growth vs flat)
        "scan_sublinear_vs_unrolled": bool(
            hi["plan_s_scan"] < hi["plan_s_unrolled"] / 4.0
            and growth_scan < growth_unr),
    }
    rows = [(
        "sptrsv_plan_scaling", hi["plan_s_scan"] * 1e6,
        f"levels={hi['levels']} scan_s={hi['plan_s_scan']} "
        f"unrolled_s={hi['plan_s_unrolled']} "
        f"sublinear={entry['scan_sublinear_vs_unrolled']}",
    )]
    return rows, [entry]


def run_observability(
    iters: int = 60, repeats: int = 5, matrix: str = "lap2d_32",
) -> tuple[list[tuple[str, float, str]], list[dict]]:
    """Instrumented-vs-bare overhead of the ``repro.obs`` subsystem.

    The obs contract has two halves, both measured here on the same warm
    plan:

    * **bitwise identity** -- recording is host-side only, so an
      instrumented solve returns the exact bits of a bare
      (``obs.disabled()``) one;
    * **bounded overhead** -- per-execution cost of the always-on metrics
      (one span, a histogram observe, a couple of counter bumps) must stay
      a rounding error next to the solve itself.  Both arms take the min
      of ``repeats`` interleaved runs so scheduler noise cannot fake (or
      mask) a regression; the gate bounds ``overhead_ratio``
      (``check_regression --obs-overhead``, default 1.05).

    Also records the exposition surface: required metric families present
    in a live Prometheus render, and the span kinds sitting in the ring.
    """
    from repro import obs

    rng = np.random.default_rng(0)
    m = suite("small")[matrix]
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    b = a @ rng.standard_normal(m.shape[0])
    eng = AzulEngine(m, mesh=None, precond="jacobi", dtype=np.float64)
    plan = eng.plan(SolveSpec(method="pcg", iters=iters))
    plan(b)                                                 # warm jit

    def one(instrumented: bool):
        if instrumented:
            t0 = time.perf_counter()
            x, _ = plan(b)
            return time.perf_counter() - t0, x
        with obs.disabled():
            t0 = time.perf_counter()
            x, _ = plan(b)
            return time.perf_counter() - t0, x

    dts_on, dts_off = [], []
    x_on = x_off = None
    for _ in range(repeats):
        dt, x_on = one(True)
        dts_on.append(dt)
        dt, x_off = one(False)
        dts_off.append(dt)
    dt_on, dt_off = min(dts_on), min(dts_off)

    text = obs.render_prometheus()
    required = ("repro_solve_executions_total", "repro_solve_seconds",
                "repro_plan_cache_misses_total", "repro_plan_build_seconds")
    span_counts = obs.TRACER.counts()
    entry = {
        "matrix": matrix,
        "method": "pcg",
        "n": int(m.shape[0]),
        "iters": int(iters),
        "repeats": int(repeats),
        "us_per_iter_instrumented": round(dt_on / iters * 1e6, 3),
        "us_per_iter_bare": round(dt_off / iters * 1e6, 3),
        "overhead_ratio": round(dt_on / dt_off, 4),
        "bitwise_identical": bool(np.array_equal(x_on, x_off)),
        "required_families_present": all(f"\n{f}" in "\n" + text
                                         for f in required),
        "span_kinds_present": sorted(
            k for k in ("solve", "plan_build") if span_counts.get(k)),
        "span_counts": {k: int(v) for k, v in span_counts.items()},
        "metric_families": int(len(obs.REGISTRY.families())),
    }
    rows = [(
        f"obs_overhead_{matrix}", dt_on / iters * 1e6,
        f"bare_us={dt_off / iters * 1e6:.1f} "
        f"overhead={entry['overhead_ratio']:.3f}x "
        f"bitwise={entry['bitwise_identical']} "
        f"families={entry['metric_families']}",
    )]
    return rows, [entry]


def collect_json(fused_payload, batch_payload, tol_payload=None,
                 noc_payload=None, pipelined_payload=None,
                 guarded_payload=None, serving_payload=None,
                 observability_payload=None, formats_payload=None) -> dict:
    """Assemble the machine-readable perf-trajectory record (BENCH_pcg.json
    schema: see README "Performance").  v2 added the tolerance-solve section
    (fused-vs-reference iteration counts, the regression gate's exact-match
    signal); v3 added the comm-plan section (modeled NoC bytes/iteration,
    halo-vs-dense plan choice per partition -- host-deterministic, gated
    exactly); v4 adds the pipelined section (pipelined-vs-standard PCG
    iteration counts, reduction structure, the r0 trace-head regression)
    and the comm-overlap fields on the noc_plans entries; v5 adds the
    guarded section (guarded timings, the pinned collective count, the
    indefinite-detection probe);
    v6 adds the serving section (SolveService load-generator runs:
    open/closed-loop p50/p99 latency, throughput vs offered load,
    zero-retrace steady state -- see ``benchmarks/bench_serve.py``); v7
    adds the observability section (``repro.obs`` instrumented-vs-bare
    overhead ratio, bitwise-identity flag, exposition-surface presence --
    see ``run_observability``); v8 adds the formats section (per-matrix
    storage-format autotuner record: chosen format, modeled stream words
    per candidate, autotuned-vs-ELL wall/model A/B, and the SpTRSV
    plan-scaling scan-vs-unrolled record -- see ``run_formats`` /
    ``run_plan_scaling``)."""
    import jax

    from repro.kernels import ops

    return {
        "schema": "bench_pcg/v8",
        "backend": jax.default_backend(),
        "kernel_mode": ops.backend_mode(),
        "x64": bool(jax.config.jax_enable_x64),
        "fused_vs_unfused": fused_payload,
        "batch_sweep": batch_payload,
        "tol_solves": tol_payload or [],
        "noc_plans": noc_payload or [],
        "pipelined": pipelined_payload or [],
        "guarded": guarded_payload or [],
        "serving": serving_payload or [],
        "observability": observability_payload or [],
        "formats": formats_payload or [],
    }


def main(argv=None) -> int:
    import jax

    jax.config.update("jax_enable_x64", True)  # match run.py: verify at f64
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-sizes", default="",
                    help="comma-separated multi-RHS sweep, e.g. 1,4,16")
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--skip-convergence", action="store_true",
                    help="only run the batch sweep / fused compare")
    ap.add_argument("--fused-compare", action="store_true",
                    help="time the fused hot path vs the reference path")
    ap.add_argument("--matrices", default="lap2d_32,banded_1k,rspd_1k",
                    help="suite matrices for --fused-compare")
    ap.add_argument("--json", default="",
                    help="write the machine-readable payload to this file")
    args = ap.parse_args(argv)

    rows = [] if args.skip_convergence else run()
    fused_payload, batch_payload, tol_payload = [], [], []
    noc_payload, pipe_payload, guarded_payload = [], [], []
    obs_payload, formats_payload = [], []
    if args.fused_compare or args.json:
        mats = tuple(s for s in args.matrices.split(",") if s)
        frows, fused_payload = run_fused_compare(iters=args.iters, matrices=mats)
        rows += frows
        trows, tol_payload = run_tol_solves(
            matrices=tuple(m for m in mats if m in suite("small"))
        )
        rows += trows
        prows, pipe_payload = run_pipelined_solves(
            matrices=tuple(m for m in mats if m in suite("small"))
        )
        rows += prows
        grows, guarded_payload = run_guarded_solves(
            matrices=tuple(m for m in mats if m in suite("small"))[:1]
        )
        rows += grows
        nrows, noc_payload = run_noc_plans(
            matrices=tuple(m for m in mats if m in suite("small"))
        )
        rows += nrows
        orows, obs_payload = run_observability(
            iters=args.iters,
            matrix=next(m for m in mats if m in suite("small")),
        )
        rows += orows
        krows, formats_payload = run_formats()
        rows += krows
        srows, scaling_payload = run_plan_scaling()
        rows += srows
        formats_payload += scaling_payload
    if args.batch_sizes:
        ks = [int(x) for x in args.batch_sizes.split(",")]
        brows, batch_payload = run_batch_sweep(ks, iters=args.iters)
        rows += brows
    for r in rows:
        print(",".join(str(x) for x in r))
    for e in tol_payload:
        # tolerance-mode convergence, plotted from the bounded trace ring
        print(f"# pcg_tol {e['matrix']}/{e['precond']} "
              f"({e['iters_fused']} iters): {e['trace_spark']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(collect_json(fused_payload, batch_payload, tol_payload,
                                   noc_payload, pipe_payload,
                                   guarded_payload,
                                   observability_payload=obs_payload,
                                   formats_payload=formats_payload),
                      f, indent=1)
        print(f"# wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

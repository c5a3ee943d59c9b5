"""CI perf-regression gate over the BENCH_pcg.json trajectory.

    PYTHONPATH=src python -m benchmarks.check_regression \
        --current BENCH_pcg.json --baseline benchmarks/BENCH_baseline.json

Compares the bench-smoke payload just produced against the committed
baseline and exits non-zero on regression, so the ``bench-smoke`` CI job
*enforces* the perf trajectory instead of merely archiving it.  What is
compared, and how strictly, follows what is actually stable across
machines:

* **Iteration counts** (``tol_solves``): exact match, fused and reference,
  plus the fused/reference agreement flags.  Iteration counts are discrete
  and deterministic -- any drift means the recurrence, preconditioner, or
  stopping test changed behaviour.
* **Numeric equivalence fields** (``trace_rel_maxdiff``, ``x_maxdiff``,
  ``batch_vs_seq_maxerr``): absolute thresholds.  The fused path must stay
  numerically indistinguishable from the reference oracle.
* **Modeled traffic** (``modeled_traffic`` / ``modeled_ic0_traffic``):
  exact match -- the model only moves when someone changes the fusion
  itself, which should be a deliberate, baseline-updating act.
* **Communication plans** (``noc_plans``): exact match on the plan choice,
  halo width, modeled bytes/iteration and the comm-overlap fields
  (interior nnz fraction, hidden/exposed gather words, overlap
  efficiency) per (matrix, reorder, mode, grid).  The comm-plan compile is
  pure host NumPy, so any drift is a real behaviour change; in particular
  a **dense fallback where a halo plan previously applied** (halo ->
  dense) is flagged as a halo-plan regression -- the partition/reordering
  stopped producing a halo sparse enough to pay.
* **Pipelined PCG** (``pipelined``): iteration counts of the pipelined
  and standard tolerance solves (exact), the per-iteration reduction
  structure (exact -- 1 stacked collective vs 2), the r0 trace-head
  agreement with ``||b||`` and the solution agreement between the two
  recurrences (absolute thresholds).
* **Guarded solves** (``guarded``): the fault-tolerance layer's contract.
  The iteration count, clean status and lowered collective count match the
  baseline exactly (guards may not add collectives), ``detects_indefinite``
  must stay True (the end-to-end detection probe), and the per-iteration
  timing rides the timing-ratio gate.  That a clean guarded solve is
  bitwise the bare recurrence is asserted by ``tests/test_guards.py``.
* **Serving** (``serving``): the always-on ``SolveService`` contract per
  load point (matrix, mode, offered load / concurrency).  ``completed``,
  ``rejected`` and ``errors`` (non-converged statuses) match exactly, and
  ``retraces`` must stay 0 -- the compile-free steady-state guarantee of
  the continuous-batching loop.  Latency quantiles (``p50_ms``/``p99_ms``/
  ``mean_ms``) ride the generous timing-ratio gate, like every other
  wall-clock field.
* **Observability** (``observability``): the ``repro.obs`` subsystem's
  contract.  ``bitwise_identical`` must stay True (metrics recording is
  host-side only -- an instrumented solve returns the exact bits of a bare
  one), ``required_families_present`` must stay True (the Prometheus
  exposition keeps its core metric families), and the instrumented/bare
  per-iteration timing ratio from the SAME RUN is bounded by
  ``--obs-overhead`` (default 1.05 -- the always-on instrumentation may
  cost at most 5%).  The instrumented timing also rides the generous
  cross-run timing gate.
* **Formats** (``formats``): the storage-format portfolio's contract.
  For ``format_autotune`` entries: the autotuner's ``chosen_format`` and
  the modeled per-candidate stream words match exactly (the model is pure
  host arithmetic over row statistics -- drift means the model or the
  heuristic changed), ``beats_ell_modeled``, ``iters_match`` and
  ``fused_matches_reference`` must stay True, and on ``wall_gated``
  entries (the hub-row skewed matrix, where the win is ~2x and
  machine-robust) ``beats_ell_wall`` must stay True.  For the
  ``plan_scaling`` entry: ``scan_sublinear_vs_unrolled`` must stay True --
  the ``lax.scan`` SpTRSV wavefront's plan (trace+lower) time at ~1000
  levels stays far below the unrolled baseline's.
* **Timings** (``us_per_iter*``): within ``--timing-ratio`` (default 10x)
  of baseline.  Interpret-mode CPU timings are noisy and machine-dependent;
  the generous ratio still catches order-of-magnitude regressions (an
  accidentally-unfused hot path, a jit cache miss per iteration).
* **Coverage**: every baseline entry must still be present (dropping a
  benchmark silently is itself a regression).

Every compared path is produced through the plan/execute API
(``engine.plan(SolveSpec(...))`` -- see ``benchmarks.bench_pcg``), so the
gate pins the *plan* surface: substrate selection, iteration counts and
numeric equivalence of the compiled ``SolvePlan`` programs.  The v2
payload additionally carries optional ``trace_points``/``trace_spark``
fields (tolerance-mode convergence from the bounded trace ring); they are
informational and not gate-checked.

Escape hatch -- when a change legitimately moves the trajectory (better
preconditioner => fewer iterations, new traffic model), refresh and commit
the baseline:

    python -m benchmarks.check_regression --current BENCH_pcg.json \
        --baseline benchmarks/BENCH_baseline.json --update-baseline
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys

EQUIV_TOL = 1e-8     # fused-vs-reference agreement fields (f64 payloads)
# pipelined vs standard PCG run DIFFERENT recurrences to the same relative
# tolerance: both solutions sit within tol of truth but not bitwise of each
# other, so their agreement bound is looser than EQUIV_TOL (observed
# ~1e-12 on the smoke suite; 1e-6 leaves conditioning headroom)
PIPE_X_TOL = 1e-6


def _index(entries: list[dict], keys: tuple[str, ...]) -> dict:
    return {tuple(e.get(k) for k in keys): e for e in entries}


class Gate:
    def __init__(self, timing_ratio: float, obs_overhead: float = 1.05):
        self.ratio = timing_ratio
        self.obs_overhead = obs_overhead
        self.failures: list[str] = []
        self.checks = 0

    def fail(self, msg: str) -> None:
        self.failures.append(msg)

    def exact(self, where: str, field: str, cur, base) -> None:
        self.checks += 1
        if cur != base:
            self.fail(f"{where}: {field} changed {base!r} -> {cur!r} "
                      f"(exact-match field)")

    def leq(self, where: str, field: str, cur, limit) -> None:
        self.checks += 1
        if cur is None or not (cur <= limit):
            self.fail(f"{where}: {field} = {cur!r} exceeds {limit}")

    def timing(self, where: str, field: str, cur, base) -> None:
        self.checks += 1
        if cur is None or base is None:
            self.fail(f"{where}: {field} missing ({base!r} -> {cur!r})")
            return
        if base > 0 and cur > base * self.ratio:
            unit = "ms" if field.endswith("_ms") else "us"
            self.fail(f"{where}: {field} regressed {base:.1f} -> {cur:.1f} "
                      f"{unit} (> {self.ratio:.0f}x baseline)")

    def section(self, name: str, keys: tuple[str, ...], cur: list, base: list):
        """Pair up entries; every baseline entry must exist in current."""
        ci, bi = _index(cur, keys), _index(base, keys)
        for k, be in bi.items():
            ce = ci.get(k)
            if ce is None:
                self.fail(f"{name}{list(k)}: entry missing from current payload")
                continue
            yield f"{name}{list(k)}", ce, be


#: every gate-checked payload section, in check order
SECTIONS = ("tol_solves", "fused_vs_unfused", "batch_sweep", "noc_plans",
            "guarded", "pipelined", "serving", "observability", "formats")


def check(cur: dict, base: dict, timing_ratio: float = 10.0,
          obs_overhead: float = 1.05,
          sections: tuple[str, ...] | None = None) -> Gate:
    g = Gate(timing_ratio, obs_overhead)
    g.exact("payload", "schema", cur.get("schema"), base.get("schema"))
    want = set(SECTIONS if sections is None else sections)

    def _skip(name: str) -> bool:
        return name not in want

    for where, ce, be in () if _skip("tol_solves") else g.section(
                                   "tol_solves", ("matrix", "precond"),
                                   cur.get("tol_solves", []),
                                   base.get("tol_solves", [])):
        g.exact(where, "iters_fused", ce.get("iters_fused"), be.get("iters_fused"))
        g.exact(where, "iters_reference", ce.get("iters_reference"),
                be.get("iters_reference"))
        g.exact(where, "iters_match", ce.get("iters_match"), True)
        g.exact(where, "substrate_fused", ce.get("substrate_fused"),
                be.get("substrate_fused"))
        g.leq(where, "x_maxdiff", ce.get("x_maxdiff"), EQUIV_TOL)
        if "modeled_ic0_traffic" in be:
            g.exact(where, "modeled_ic0_traffic", ce.get("modeled_ic0_traffic"),
                    be.get("modeled_ic0_traffic"))
        g.timing(where, "us_per_iter_fused", ce.get("us_per_iter_fused"),
                 be.get("us_per_iter_fused"))

    for where, ce, be in () if _skip("fused_vs_unfused") else g.section(
                                   "fused_vs_unfused", ("matrix",),
                                   cur.get("fused_vs_unfused", []),
                                   base.get("fused_vs_unfused", [])):
        g.leq(where, "trace_rel_maxdiff", ce.get("trace_rel_maxdiff"), EQUIV_TOL)
        g.leq(where, "x_maxdiff", ce.get("x_maxdiff"), EQUIV_TOL)
        g.exact(where, "modeled_traffic", ce.get("modeled_traffic"),
                be.get("modeled_traffic"))
        g.timing(where, "us_per_iter_fused", ce.get("us_per_iter_fused"),
                 be.get("us_per_iter_fused"))
        g.timing(where, "us_per_iter_unfused", ce.get("us_per_iter_unfused"),
                 be.get("us_per_iter_unfused"))

    for where, ce, be in () if _skip("batch_sweep") else g.section(
                                   "batch_sweep", ("matrix", "k"),
                                   cur.get("batch_sweep", []),
                                   base.get("batch_sweep", [])):
        g.leq(where, "batch_vs_seq_maxerr", ce.get("batch_vs_seq_maxerr"),
              EQUIV_TOL)
        g.timing(where, "us_per_iter_per_rhs", ce.get("us_per_iter_per_rhs"),
                 be.get("us_per_iter_per_rhs"))

    for where, ce, be in () if _skip("noc_plans") else g.section(
                                   "noc_plans",
                                   ("matrix", "reorder", "mode", "grid"),
                                   cur.get("noc_plans", []),
                                   base.get("noc_plans", [])):
        g.checks += 1
        if be.get("plan") == "halo" and ce.get("plan") == "dense":
            g.fail(f"{where}: halo-plan regression -- dense fallback where "
                   "a halo plan previously applied (the compiled pull "
                   "schedule no longer beats the all-gather)")
        else:
            g.exact(where, "plan", ce.get("plan"), be.get("plan"))
        for field in ("halo_width", "gather_words_halo", "gather_words_dense",
                      "bytes_per_iter_halo", "bytes_per_iter_dense",
                      "interior_frac_nnz", "overlap_interior_words",
                      "overlap_hidden_words", "overlap_exposed_words",
                      "overlap_efficiency"):
            g.exact(where, field, ce.get(field), be.get(field))

    for where, ce, be in () if _skip("guarded") else g.section(
                                   "guarded", ("matrix", "method"),
                                   cur.get("guarded", []),
                                   base.get("guarded", [])):
        g.exact(where, "iters_guarded", ce.get("iters_guarded"),
                be.get("iters_guarded"))
        g.exact(where, "status_clean", ce.get("status_clean"),
                be.get("status_clean"))
        # zero-extra-collectives invariant: the guards read reduction slots
        # the iteration already computed, so the lowered program's
        # collective count is pinned to the baseline's
        g.exact(where, "collectives_guarded", ce.get("collectives_guarded"),
                be.get("collectives_guarded"))
        g.exact(where, "detects_indefinite",
                ce.get("detects_indefinite"), True)
        g.exact(where, "bad_x_finite", ce.get("bad_x_finite"), True)
        g.timing(where, "us_per_iter_guarded", ce.get("us_per_iter_guarded"),
                 be.get("us_per_iter_guarded"))

    for where, ce, be in () if _skip("pipelined") else g.section(
                                   "pipelined", ("matrix", "precond"),
                                   cur.get("pipelined", []),
                                   base.get("pipelined", [])):
        g.exact(where, "iters_pipelined", ce.get("iters_pipelined"),
                be.get("iters_pipelined"))
        g.exact(where, "iters_pcg", ce.get("iters_pcg"), be.get("iters_pcg"))
        g.exact(where, "reductions_per_iter_pipelined",
                ce.get("reductions_per_iter_pipelined"), 1)
        g.exact(where, "reductions_per_iter_pcg",
                ce.get("reductions_per_iter_pcg"), 2)
        g.leq(where, "r0_reldiff", ce.get("r0_reldiff"), EQUIV_TOL)
        g.leq(where, "x_vs_pcg_maxdiff", ce.get("x_vs_pcg_maxdiff"),
              PIPE_X_TOL)
        g.timing(where, "us_per_iter_pipelined",
                 ce.get("us_per_iter_pipelined"),
                 be.get("us_per_iter_pipelined"))

    for where, ce, be in () if _skip("serving") else g.section(
                                   "serving",
                                   ("matrix", "mode", "offered_rps",
                                    "concurrency"),
                                   cur.get("serving", []),
                                   base.get("serving", [])):
        for field in ("method", "requests", "chunk", "max_batch",
                      "completed", "rejected", "errors"):
            g.exact(where, field, ce.get(field), be.get(field))
        # the compile-free steady-state contract: warm-pool plans trace
        # once; any retrace means the service re-entered the compiler
        g.exact(where, "retraces", ce.get("retraces"), 0)
        for field in ("p50_ms", "p99_ms", "mean_ms"):
            g.timing(where, field, ce.get(field), be.get(field))

    for where, ce, be in () if _skip("observability") else g.section(
                                   "observability", ("matrix",),
                                   cur.get("observability", []),
                                   base.get("observability", [])):
        # host-side-only recording: instrumented bits == bare bits, always
        g.exact(where, "bitwise_identical", ce.get("bitwise_identical"),
                True)
        g.exact(where, "required_families_present",
                ce.get("required_families_present"), True)
        g.exact(where, "method", ce.get("method"), be.get("method"))
        # overhead vs the bare arm, same machine/run
        g.leq(where, "overhead_ratio", ce.get("overhead_ratio"),
              g.obs_overhead)
        g.timing(where, "us_per_iter_instrumented",
                 ce.get("us_per_iter_instrumented"),
                 be.get("us_per_iter_instrumented"))

    for where, ce, be in () if _skip("formats") else g.section(
                                   "formats", ("kind", "matrix"),
                                   cur.get("formats", []),
                                   base.get("formats", [])):
        if be.get("kind") == "plan_scaling":
            g.exact(where, "scan_sublinear_vs_unrolled",
                    ce.get("scan_sublinear_vs_unrolled"), True)
            # the scan's plan time is the thing item 4c bought; bound it by
            # the cross-machine timing ratio like every wall-clock field
            g.checks += 1
            cs = (ce.get("points") or [{}])[-1].get("plan_s_scan")
            bs = (be.get("points") or [{}])[-1].get("plan_s_scan")
            if cs is None or bs is None:
                g.fail(f"{where}: plan_s_scan missing ({bs!r} -> {cs!r})")
            elif bs > 0 and cs > bs * g.ratio:
                g.fail(f"{where}: plan_s_scan regressed {bs:.3f} -> {cs:.3f} "
                       f"s (> {g.ratio:.0f}x baseline)")
            continue
        # format_autotune entries: the decision and its model, exactly
        g.exact(where, "chosen_format", ce.get("chosen_format"),
                be.get("chosen_format"))
        g.exact(where, "modeled_words", ce.get("modeled_words"),
                be.get("modeled_words"))
        g.exact(where, "modeled_reduction_vs_ell",
                ce.get("modeled_reduction_vs_ell"),
                be.get("modeled_reduction_vs_ell"))
        g.exact(where, "beats_ell_modeled", ce.get("beats_ell_modeled"), True)
        g.exact(where, "iters_auto", ce.get("iters_auto"),
                be.get("iters_auto"))
        g.exact(where, "iters_ell", ce.get("iters_ell"), be.get("iters_ell"))
        g.exact(where, "iters_match", ce.get("iters_match"), True)
        g.exact(where, "fused_matches_reference",
                ce.get("fused_matches_reference"), True)
        if be.get("wall_gated"):
            g.exact(where, "beats_ell_wall", ce.get("beats_ell_wall"), True)
        g.leq(where, "x_vs_ell_maxdiff", ce.get("x_vs_ell_maxdiff"),
              EQUIV_TOL)
        g.timing(where, "us_per_iter_auto", ce.get("us_per_iter_auto"),
                 be.get("us_per_iter_auto"))
    return g


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--current", required=True,
                    help="freshly produced BENCH_pcg.json")
    ap.add_argument("--baseline", required=True,
                    help="committed baseline payload")
    ap.add_argument("--timing-ratio", type=float, default=10.0,
                    help="allowed current/baseline timing ratio (generous: "
                         "interpret-mode CPU timings are machine-dependent)")
    ap.add_argument("--obs-overhead", type=float, default=1.05,
                    help="allowed instrumented/bare per-iteration timing "
                         "ratio within ONE payload (the repro.obs always-on "
                         "instrumentation budget)")
    ap.add_argument("--sections", default="",
                    help="comma-separated subset of payload sections to "
                         "gate (default: all); e.g. the serve-smoke CI job "
                         "produces a serving-only payload and passes "
                         "--sections serving")
    ap.add_argument("--update-baseline", action="store_true",
                    help="overwrite the baseline with the current payload "
                         "(the documented escape hatch for intentional "
                         "trajectory changes) and exit 0")
    args = ap.parse_args(argv)

    if args.update_baseline:
        # refuse to install a baseline the gate could never check against:
        # an empty/truncated payload would make every future run vacuously
        # pass (the gate iterates baseline entries)
        with open(args.current) as f:
            cur = json.load(f)
        problems = []
        if cur.get("schema") != "bench_pcg/v8":
            problems.append(f"unexpected schema {cur.get('schema')!r}")
        for section in ("fused_vs_unfused", "tol_solves", "noc_plans",
                        "pipelined", "guarded", "serving", "observability",
                        "formats"):
            if not cur.get(section):
                problems.append(f"section {section!r} is empty/missing")
        if problems:
            print("refusing to update baseline from a degenerate payload:")
            for msg in problems:
                print(f"  - {msg}")
            return 1
        shutil.copyfile(args.current, args.baseline)
        print(f"baseline updated: {args.current} -> {args.baseline}")
        return 0

    with open(args.current) as f:
        cur = json.load(f)
    with open(args.baseline) as f:
        base = json.load(f)
    sections = None
    if args.sections:
        sections = tuple(s for s in args.sections.split(",") if s)
        unknown = [s for s in sections if s not in SECTIONS]
        if unknown:
            print(f"unknown --sections {unknown}; known: {list(SECTIONS)}")
            return 2
    g = check(cur, base, timing_ratio=args.timing_ratio,
              obs_overhead=args.obs_overhead, sections=sections)
    if g.failures:
        print(f"PERF REGRESSION: {len(g.failures)} failure(s) "
              f"({g.checks} checks):")
        for msg in g.failures:
            print(f"  - {msg}")
        print("intentional change?  re-baseline with --update-baseline and "
              "commit the result (see README).")
        return 1
    print(f"perf gate OK: {g.checks} checks against {args.baseline}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

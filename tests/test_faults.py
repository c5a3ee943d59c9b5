"""Fault-injection matrix and recovery paths: deterministic injector units,
injected faults detected through the guarded/injectable plans, the chunked
SolveRestartManager reconverging after rollback, checkpoint corruption
recovery, and the deadline/degradation serving paths.  The distributed half
(halo faults + HLO collective-count identity) runs in a subprocess on a
forced host-device mesh."""

import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

from repro.checkpoint import CorruptCheckpointError, save, restore
from repro.core import AzulEngine, SolveSpec
from repro.data.matrices import laplacian_2d
from repro.ft import (
    FaultInjector,
    FaultSpec,
    FTSolveReport,
    SolveRestartManager,
    StepTimer,
    corrupt_vals,
)
from repro.serve import SolveRequestError, SolveServer

pytestmark = pytest.mark.faults

TOL = 1e-8


def _setup(n=16):
    m = laplacian_2d(n)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    eng = AzulEngine(m, precond="jacobi", dtype=np.float64)
    x_true = np.random.default_rng(0).standard_normal(m.shape[0])
    return eng, a @ x_true, x_true


def _spec(method="pcg_tol", max_iters=400):
    return SolveSpec(method=method, tol=TOL, max_iters=max_iters)


# -- injector units ----------------------------------------------------------


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(kind="gamma_ray")
    with pytest.raises(ValueError, match="count"):
        FaultSpec(count=0)
    with pytest.raises(ValueError, match="iteration"):
        FaultSpec(iteration=-1)


def test_corrupt_vals_deterministic_and_seeded():
    eng, _, _ = _setup(8)
    clean = eng.vals_template()
    a = corrupt_vals(clean, FaultSpec(kind="nan", seed=7, count=3))
    b = corrupt_vals(clean, FaultSpec(kind="nan", seed=7, count=3))
    c = corrupt_vals(clean, FaultSpec(kind="nan", seed=8, count=3))
    assert np.array_equal(a, b, equal_nan=True)       # same seed, same words
    assert not np.array_equal(a, c, equal_nan=True)   # seed moves the fault
    assert int(np.sum(np.isnan(a))) == 3
    assert not np.isnan(clean).any()                  # input untouched


def test_corrupt_vals_bitflip_is_silent_and_involutive():
    eng, _, _ = _setup(8)
    clean = eng.vals_template()
    spec = FaultSpec(kind="bitflip", seed=3, count=2, bit=62)
    bad = corrupt_vals(clean, spec)
    diff = bad != clean
    assert int(diff.sum()) == 2
    assert not np.isnan(bad).any()    # silent: never NaN (Inf is possible
    #                                   when the flip lands on a [1,2) word)
    # XOR is its own inverse: flipping the same words again restores bits
    assert corrupt_vals(bad, spec).tobytes() == clean.tobytes()


def test_corrupt_vals_delay_is_identity():
    eng, _, _ = _setup(8)
    clean = eng.vals_template()
    assert corrupt_vals(clean, FaultSpec(kind="delay")) is clean


def test_halo_kinds_need_distributed_engine():
    eng, _, _ = _setup(8)
    with pytest.raises(ValueError, match="halo"):
        corrupt_vals(eng.vals_template(), FaultSpec(kind="halo_drop"))
    with pytest.raises(ValueError, match="halo"):
        FaultInjector(eng, FaultSpec(kind="halo_perturb"))


def test_injector_schedule_transient_vs_persistent():
    eng, _, _ = _setup(8)
    tr = FaultInjector(eng, FaultSpec(kind="nan", iteration=30))
    assert not tr.fires_in(0, 25)
    assert tr.fires_in(25, 50)
    assert not tr.fires_in(50, 75)          # transient: only its own chunk
    assert tr.vals_for(25, 50) is not None
    tr.restart()
    assert tr.vals_for(25, 50) is None      # SEU gone after recovery
    pe = FaultInjector(eng, FaultSpec(kind="nan", iteration=30,
                                      transient=False))
    assert not pe.fires_in(0, 25)
    assert pe.fires_in(25, 50) and pe.fires_in(50, 75)   # stuck-at
    pe.restart()
    assert pe.vals_for(50, 75) is not None  # restart does not clear it


# -- local fault matrix: detection + reconvergence ---------------------------


@pytest.mark.parametrize("method", ("pcg_tol", "pcg_pipelined_tol"))
@pytest.mark.parametrize("kind", ("nan", "bitflip"))
def test_injected_fault_detected_and_reconverges(method, kind):
    """The core matrix: a scheduled transient fault mid-solve is detected
    (guards or the true-residual audit), rolled back, and the solve still
    reaches the CLEAN tolerance."""
    eng, b, x_true = _setup()
    mgr = SolveRestartManager(eng, _spec(method), chunk=20)
    inj = FaultInjector(eng, FaultSpec(kind=kind, iteration=25, seed=1))
    rep = mgr.solve(b, injector=inj)
    assert isinstance(rep, FTSolveReport)
    assert inj.fired >= 1
    assert rep.restarts >= 1
    assert len(rep.faults) >= 1
    assert rep.faults[0]["label"] in (
        "breakdown", "diverged", "stagnated", "silent_corruption",
        "nonfinite_x")
    assert rep.status == "converged"
    assert rep.rel_residual <= SolveRestartManager.TRUE_RESIDUAL_SLACK * TOL
    assert np.allclose(rep.x, x_true, atol=1e-5)


def test_clean_chunked_solve_converges_without_restarts():
    eng, b, x_true = _setup()
    mgr = SolveRestartManager(eng, _spec(), chunk=20)
    rep = mgr.solve(b)
    assert rep.status == "converged"
    assert rep.restarts == 0 and rep.faults == []
    assert rep.resumed_from is None
    assert np.allclose(rep.x, x_true, atol=1e-5)


def test_persistent_fault_exhausts_restarts():
    """A stuck-at fault survives every rollback: the manager gives up after
    max_restarts recoveries and reports the fault label, not converged."""
    eng, b, _ = _setup()
    mgr = SolveRestartManager(eng, _spec(), chunk=20, max_restarts=2)
    inj = FaultInjector(eng, FaultSpec(kind="nan", iteration=0,
                                       transient=False))
    rep = mgr.solve(b, injector=inj)
    assert rep.status != "converged"
    assert rep.status in ("breakdown", "diverged", "stagnated",
                          "silent_corruption", "nonfinite_x")
    assert rep.restarts == 3                # max_restarts + the give-up try
    assert len(rep.faults) == 3


def test_restart_manager_requires_tolerance_method():
    eng, _, _ = _setup(8)
    with pytest.raises(ValueError, match="tolerance"):
        SolveRestartManager(eng, SolveSpec(method="pcg", iters=50))


def test_checkpointed_solve_resumes_and_recovers(tmp_path):
    """Checkpoints make recovery durable: a faulted solve with a checkpoint
    dir reconverges, and a FRESH manager on the same directory resumes from
    the persisted iterate instead of starting over."""
    eng, b, x_true = _setup()
    ck = str(tmp_path / "ck")
    mgr = SolveRestartManager(eng, _spec(), chunk=20, checkpoint_dir=ck)
    inj = FaultInjector(eng, FaultSpec(kind="nan", iteration=45, seed=2))
    rep = mgr.solve(b, injector=inj)
    assert rep.status == "converged" and rep.restarts >= 1
    assert np.allclose(rep.x, x_true, atol=1e-5)
    # process death after the solve: a new manager sees the checkpoints
    mgr2 = SolveRestartManager(eng, _spec(), chunk=20, checkpoint_dir=ck)
    rep2 = mgr2.solve(b)
    assert rep2.resumed_from is not None and rep2.resumed_from > 0
    assert rep2.status == "converged"
    assert rep2.iterations <= rep.iterations   # warm start did not regress


def test_delay_fault_lands_in_straggler_report():
    """A delayed chunk carries no numeric corruption -- the solve stays
    clean -- but the StepTimer flags the slow chunk."""
    eng, b, _ = _setup()
    mgr = SolveRestartManager(eng, _spec(), chunk=5,
                              timer=StepTimer(deadline_factor=2.0))
    inj = FaultInjector(eng, FaultSpec(kind="delay", iteration=40,
                                       delay_s=0.4))
    rep = mgr.solve(b, injector=inj)
    assert rep.status == "converged"
    assert rep.restarts == 0                 # no numeric fault to recover
    assert inj.fired == 1
    assert len(rep.straggler_chunks) >= 1


# -- checkpoint corruption recovery ------------------------------------------


def _tree(val, k):
    return {"x": np.full(32, float(val)), "k": np.int64(k)}


def test_restore_falls_back_past_corrupted_leaf(tmp_path):
    d = str(tmp_path / "ck")
    save(_tree(1.0, 10), d, 10)
    save(_tree(2.0, 20), d, 20)
    # flip bytes in the newest step's data leaf; its manifest stays valid
    leaf = os.path.join(d, "step_00000020", "x_.npy")
    if not os.path.exists(leaf):
        leaf = next(os.path.join(d, "step_00000020", f)
                    for f in os.listdir(os.path.join(d, "step_00000020"))
                    if f.endswith(".npy") and f.startswith("x"))
    with open(leaf, "r+b") as f:
        f.seek(-8, os.SEEK_END)
        f.write(b"\xff" * 8)
    # explicit load of the damaged step must fail loudly ...
    with pytest.raises(CorruptCheckpointError):
        restore(_tree(0.0, 0), d, step=20)
    # ... and the unpinned restore silently falls back to the older step
    tree, step = restore(_tree(0.0, 0), d)
    assert step == 10
    assert float(tree["x"][0]) == 1.0 and int(tree["k"]) == 10


def test_restore_skips_torn_manifest(tmp_path):
    d = str(tmp_path / "ck")
    save(_tree(1.0, 10), d, 10)
    save(_tree(2.0, 20), d, 20)
    man = os.path.join(d, "step_00000020", "manifest.json")
    with open(man, "r+") as f:            # simulate a torn write
        f.truncate(17)
    tree, step = restore(_tree(0.0, 0), d)
    assert step == 10 and float(tree["x"][0]) == 1.0


def test_restore_raises_when_all_steps_corrupt(tmp_path):
    d = str(tmp_path / "ck")
    save(_tree(1.0, 10), d, 10)
    with open(os.path.join(d, "step_00000010", "manifest.json"), "r+") as f:
        f.truncate(3)
    with pytest.raises(FileNotFoundError):
        restore(_tree(0.0, 0), d)


# -- serving: validation, deadlines, degradation -----------------------------


def test_submit_validation_rejects_without_enqueueing():
    eng, b, _ = _setup(8)
    srv = SolveServer(eng, method="pcg_tol", tol=TOL, max_iters=200)
    n = eng.n
    cases = [
        (dict(b=object()), "rhs_not_array"),
        (dict(b=np.zeros((n, 2))), "rhs_shape"),
        (dict(b=np.zeros(n + 1)), "rhs_shape"),
        (dict(b=np.zeros(n, dtype=np.complex128)), "rhs_dtype"),
        (dict(b=np.full(n, np.nan)), "rhs_nonfinite"),
        (dict(b=np.zeros(n), deadline=-1.0), "deadline"),
    ]
    for kw, reason in cases:
        with pytest.raises(SolveRequestError) as ei:
            srv.submit(**kw)
        assert ei.value.reason == reason
    assert srv.stats["rejected"] == len(cases)
    assert srv.pending() == 0               # nothing poisoned the queue
    # a valid request still goes through after the rejections
    rid = srv.submit(b)
    out = srv.step()[rid]
    assert out.status == "converged"
    assert 0 <= out.rel_residual <= TOL * 1.01


def test_deadline_zero_returns_best_effort():
    """deadline=0 expires at the first chunk boundary: the request resolves
    with its best-effort iterate and status deadline_exceeded while the
    no-deadline lane in the SAME batch runs to convergence."""
    eng, b, x_true = _setup()
    srv = SolveServer(eng, method="pcg_tol", tol=TOL, max_iters=400,
                      deadline_chunk=10)
    r_dead = srv.submit(b, deadline=0.0)
    r_free = srv.submit(b)
    out = srv.step()
    dead, free = out[r_dead], out[r_free]
    assert dead.status == "deadline_exceeded"
    assert 0 < dead.iters < free.iters       # partial but real progress
    assert np.isfinite(dead.x).all()
    assert dead.rel_residual > 0
    assert free.status == "converged"
    assert np.allclose(free.x, x_true, atol=1e-5)
    assert srv.stats["deadline_exceeded"] == 1
    assert srv.stats["deadline_batches"] == 1


def test_generous_deadline_converges():
    eng, b, x_true = _setup()
    srv = SolveServer(eng, method="pcg_tol", tol=TOL, max_iters=400,
                      deadline_chunk=25)
    rid = srv.submit(b, deadline=120.0)
    out = srv.step()[rid]
    assert out.status == "converged"
    assert out.rel_residual <= TOL * 1.01
    assert np.allclose(out.x, x_true, atol=1e-5)
    assert srv.stats["deadline_exceeded"] == 0


class _ExplodingPlan:
    """Stands in for a fused plan whose compiled program fails at runtime."""

    info = {"fused": True}
    traces = 1

    def __init__(self):
        self.calls = 0

    def compile(self):
        return None                     # compiled fine; fails when run

    def __call__(self, batch, x0=None, vals=None):
        self.calls += 1
        raise RuntimeError("fused kernel fault")


def test_fused_failure_degrades_to_reference_substrate():
    eng, b, x_true = _setup()
    srv = SolveServer(eng, max_batch=1, method="pcg_tol", tol=TOL,
                      max_iters=400)
    boom = _ExplodingPlan()
    srv._plans[1] = boom                     # poison the fused bucket plan
    rid = srv.submit(b)
    out = srv.step()[rid]
    assert boom.calls == 1                   # fused path WAS attempted
    assert srv.stats["degraded_batches"] == 1
    assert out.status == "converged"         # reference substrate answered
    assert np.allclose(out.x, x_true, atol=1e-5)


# -- distributed half: halo faults + collective-count identity ---------------

_DIST_SCRIPT = r"""
import numpy as np
import scipy.sparse as sp
from repro.core.engine import AzulEngine
from repro.core.plan import SolveSpec
from repro.data.matrices import laplacian_2d
from repro.ft.inject import FaultInjector, FaultSpec
from repro.launch.mesh import make_mesh

m = laplacian_2d(16)
n = m.shape[0]
A = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
rng = np.random.default_rng(1)
xt = rng.standard_normal(n); b = A @ xt

mesh = make_mesh((4, 1), ("data", "model"))
eng = AzulEngine(m, mesh=mesh, mode="1d", precond="jacobi", dtype=np.float64)
assert eng.comm_plan.use_halo

mask = eng.halo_entry_mask()
assert mask.shape == eng.vals_template().shape
assert mask.any(), "banded 1d partition must have frontier entries"

for method in ("pcg_tol", "pcg_pipelined_tol"):
    plan = eng.plan(SolveSpec(method=method, tol=1e-8, max_iters=400,
                              layout="halo", injectable=True))
    # clean operand through the injectable program: converges
    x, _ = plan(b, vals=eng.vals_template())
    assert plan.last_status_names == "converged", (method, "clean")
    assert np.allclose(np.asarray(x), xt, atol=1e-5), (method, "clean x")

    # dropped NoC message: remote-referencing words zeroed -> the operator
    # is no longer the assembled A; detection = guards or residual audit
    for kind in ("halo_drop", "halo_perturb"):
        inj = FaultInjector(eng, FaultSpec(kind=kind, seed=2, count=4))
        xb, nb = plan(b, vals=inj._corrupt)
        sname = plan.last_status_names
        rel_claim = float(np.asarray(nb)[int(np.asarray(plan.last_iters))]
                          / np.linalg.norm(b))
        rel_true = float(np.linalg.norm(b - eng.spmv(np.asarray(xb)))
                         / np.linalg.norm(b))
        detected = (sname in ("breakdown", "diverged", "stagnated")
                    or not np.isfinite(np.asarray(xb)).all()
                    or rel_true > 100.0 * max(rel_claim, 1e-8))
        assert detected, (method, kind, sname, rel_claim, rel_true)

# guards and injectable value operands add ZERO collectives: guarded and
# unguarded halo programs carry identical all_reduce / collective_permute
# counts, and the PR 6 invariants (pipelined ar==2, pcg ar==4) still hold
def collectives(plan):
    ops = plan.hlo_summary()["count_by_op"]
    return (int(ops.get("all-reduce", 0)),
            int(ops.get("collective-permute", 0)),
            int(ops.get("all-gather", 0)))

for method, want_ar in (("pcg_pipelined", 2), ("pcg", 4)):
    cg = collectives(eng.plan(SolveSpec(method=method, iters=60,
                                        layout="halo", guard=True)))
    cu = collectives(eng.plan(SolveSpec(method=method, iters=60,
                                        layout="halo", guard=False)))
    assert cg == cu, (method, "guard added collectives", cg, cu)
    assert cg[0] == want_ar, (method, cg)
    assert cg[2] == 0, (method, "all_gather crept in")

print("FAULT_DIST_OK")
"""


@pytest.mark.slow
@pytest.mark.dist
def test_halo_faults_and_collective_identity_multidevice():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    env["JAX_ENABLE_X64"] = "1"
    r = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=560,
    )
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "FAULT_DIST_OK" in r.stdout

"""SolveService (the always-on serving management plane): continuous
batching joins at chunk boundaries bitwise-identically to solo solves,
steady state never re-enters the compiler, the operator registry
admits/evicts/reloads under a memory budget, and admission control
rejects with structured reasons.  The legacy ``SolveServer`` shim stays
pinned to the plan surface."""

import warnings

import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import AzulEngine, SolveSpec
from repro.core.plan import _reset_deprecation_warnings
from repro.data.matrices import laplacian_2d
from repro.serve import (
    SolveRequestError,
    SolveServer,
    SolveService,
)
from repro.serve.service import _Pending

TOL = 1e-8


def _csr(m):
    return sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)


def _rhs(n, seed=0):
    return np.random.default_rng(seed).standard_normal(n)


def _service(n=8, chunk=8, max_batch=4, tol=TOL, name="lap", **kw):
    m = laplacian_2d(n)
    svc = SolveService(max_batch=max_batch, chunk=chunk, **kw)
    svc.register_operator(name, m, method="pcg_tol", tol=tol, iters=400,
                          precond="jacobi", dtype=np.float64)
    return svc, m


def _all_pool_plans(svc):
    for op in svc._operators.values():
        for pool in op.pools.values():
            yield from pool.values()


# -- continuous batching: the bitwise mid-stream join invariant --------------


def test_midstream_join_bitwise_identical_to_solo():
    """A request that arrives while another solve is mid-flight joins at
    the next chunk boundary and produces the EXACT bits -- solution and
    residual trace -- of a solo solve of the same RHS."""
    m = laplacian_2d(8)
    n = m.shape[0]
    b_a, b_b = _rhs(n, 1), _rhs(n, 2)

    solo, _ = _service(8)
    rid = solo.submit(b_b)
    ref = solo.drain()[rid]
    assert ref.status == "converged"

    svc, _ = _service(8)
    ra = svc.submit(b_a)
    svc.tick()
    svc.tick()
    assert svc.active() == 1          # a genuinely mid-solve
    rb = svc.submit(b_b)              # joins at the next chunk boundary
    done = svc.drain()
    assert done[ra].status == "converged"
    got = done[rb]
    assert got.status == "converged"
    assert got.iters == ref.iters
    assert np.array_equal(got.x, ref.x)                    # bitwise
    assert np.array_equal(got.res_norms, ref.res_norms)    # bitwise
    assert svc.stats["rebuckets"] >= 1     # the cohort actually changed


def test_midstream_join_bitwise_multi_operator_and_zero_retraces():
    """The invariant holds with several tenants resident: traffic on
    operator A cannot perturb a solve on operator B, and the whole run
    never retraces any pool plan."""
    ma, mb = laplacian_2d(8), laplacian_2d(9)
    b_a = _rhs(ma.shape[0], 3)
    b_b = _rhs(mb.shape[0], 4)

    solo = SolveService(max_batch=4, chunk=8)
    solo.register_operator("B", mb, method="pcg_tol", tol=TOL, iters=400)
    rid = solo.submit(b_b, "B")
    ref = solo.drain()[rid]

    svc = SolveService(max_batch=4, chunk=8)
    svc.register_operator("A", ma, method="pcg_tol", tol=TOL, iters=400)
    svc.register_operator("B", mb, method="pcg_tol", tol=TOL, iters=400)
    ra = svc.submit(b_a, "A")
    svc.tick()                       # A mid-flight on its own lanes
    rb = svc.submit(b_b, "B")        # B joins while A keeps chunking
    done = svc.drain()
    assert done[ra].operator == "A" and done[rb].operator == "B"
    assert np.array_equal(done[rb].x, ref.x)
    assert np.array_equal(done[rb].res_norms, ref.res_norms)
    # compile-free steady state, both tenants
    for plan in _all_pool_plans(svc):
        assert plan.traces == 1


def test_steady_state_100_requests_zero_retraces():
    """The acceptance run: 100 requests stream through one operator with
    continuous re-bucketing, and every warm-pool plan traced exactly
    once -- the service never re-enters the compiler in steady state."""
    svc, m = _service(8, chunk=25, max_batch=8, tol=1e-6, queue_max=None)
    n = m.shape[0]
    rhs = np.random.default_rng(5).standard_normal((16, n))
    ids = [svc.submit(rhs[i % 16]) for i in range(100)]
    done = svc.drain()
    assert len(done) == 100
    assert all(done[r].status == "converged" for r in ids)
    plans = list(_all_pool_plans(svc))
    assert plans, "warm pool unexpectedly empty"
    for plan in plans:
        assert plan.traces == 1            # ZERO retraces, asserted
    # the pool stays bucket-bounded: at most one cb plan per power-of-two
    # bucket <= max_batch, not one per cohort
    assert svc.stats["plans"] <= 4
    assert svc.stats["chunks"] > len(plans)    # plans are genuinely reused
    a = _csr(m)
    for rid in ids[:5]:
        r = np.linalg.norm(rhs[ids.index(rid) % 16] - a @ done[rid].x)
        assert r <= 1e-6 * np.linalg.norm(rhs[ids.index(rid) % 16]) * 10


# -- admission control / backpressure ----------------------------------------


def test_structured_rejects():
    svc, m = _service(8, queue_max=2)
    n = m.shape[0]
    with pytest.raises(SolveRequestError) as ei:
        svc.submit(_rhs(n), "nope")
    assert ei.value.reason == "operator_unknown"
    with pytest.raises(SolveRequestError) as ei:
        svc.submit(_rhs(n + 1))
    assert ei.value.reason == "rhs_shape"
    bad = _rhs(n)
    bad[3] = np.nan
    with pytest.raises(SolveRequestError) as ei:
        svc.submit(bad)
    assert ei.value.reason == "rhs_nonfinite"
    with pytest.raises(SolveRequestError) as ei:
        svc.submit(_rhs(n), tol=-1.0)
    assert ei.value.reason == "tol"
    with pytest.raises(SolveRequestError) as ei:
        svc.submit(_rhs(n), max_iters=0)
    assert ei.value.reason == "max_iters"
    with pytest.raises(SolveRequestError) as ei:
        svc.submit(_rhs(n), deadline=-0.5)
    assert ei.value.reason == "deadline"
    svc.submit(_rhs(n))
    svc.submit(_rhs(n))
    with pytest.raises(SolveRequestError) as ei:
        svc.submit(_rhs(n))               # bounded queue pushes back
    assert ei.value.reason == "queue_full"
    assert svc.pending() == 2             # rejected request never enqueued
    assert svc.stats["rejected"] == 7
    assert svc.stats["rejects"]["queue_full"] == 1
    assert svc.stats["rejects"]["operator_unknown"] == 1
    svc.drain()


def test_admission_order_ages_old_low_priority_work():
    def mk(rid, pr, t, dl=None):
        return _Pending(rid=rid, op="o", b=None, tol=None, max_iters=None,
                        deadline=dl, priority=pr, t_submit=t)

    old_low = mk(0, 0.0, 0.0)          # waited 10s -> effective 10
    new_high = mk(1, 5.0, 9.5)         # effective 5.5
    new_deadline = mk(2, 0.0, 9.5, dl=1.0)   # deadline boost -> 1.5
    order = SolveService._admission_order(
        [new_deadline, new_high, old_low], now=10.0, aging=1.0)
    assert [p.rid for p in order] == [0, 1, 2]
    # aging disabled: raw priority wins, FIFO ties
    order = SolveService._admission_order(
        [old_low, new_high, new_deadline], now=10.0, aging=None)
    assert [p.rid for p in order] == [1, 2, 0]


def test_per_request_tol_and_max_iters_never_add_plans():
    """Per-request completion targets are host-side: a loose-tol and a
    tight-tol request share the SAME warm plan (no new compile)."""
    svc, m = _service(8, chunk=8)
    n = m.shape[0]
    r1 = svc.submit(_rhs(n, 7), tol=1e-3)
    loose = svc.drain()[r1]
    plans_after = svc.stats["plans"]
    r2 = svc.submit(_rhs(n, 7), tol=1e-11)
    tight = svc.drain()[r2]
    assert svc.stats["plans"] == plans_after     # no new plan for new tol
    assert loose.status == tight.status == "converged"
    assert loose.iters < tight.iters
    assert loose.rel_residual <= 1e-3
    assert tight.rel_residual <= 1e-11
    # per-request budget: tol=0 never converges host-side, the cap lands
    # at the first chunk boundary >= max_iters
    r3 = svc.submit(_rhs(n, 7), tol=0.0, max_iters=5)
    capped = svc.drain()[r3]
    assert capped.status == "maxiter"
    assert capped.iters >= 5
    assert svc.stats["plans"] == plans_after


def test_deadline_on_the_continuous_path():
    svc, m = _service(8, chunk=8)
    rid = svc.submit(_rhs(m.shape[0]), tol=1e-20, deadline=0.0)
    out = svc.drain()[rid]
    assert out.status == "deadline_exceeded"
    assert out.iters >= svc.chunk          # got at least one chunk of work
    assert svc.stats["deadline_exceeded"] == 1


# -- operator registry: memory accounting, LRU eviction, reload --------------


def test_lru_eviction_and_lazy_reload():
    big, small = laplacian_2d(10), laplacian_2d(6)
    svc = SolveService(max_batch=2, chunk=8)
    i_big = svc.register_operator("big", big, method="pcg_tol", tol=TOL,
                                  iters=400)
    # budget exactly the big operator: registering the small one must
    # evict "big" (idle, rebuildable) rather than reject
    svc.memory_limit = i_big.bytes
    svc.register_operator("small", small, method="pcg_tol", tol=TOL,
                          iters=400)
    snap = svc.operators()
    assert not snap["big"].resident and snap["small"].resident
    assert snap["big"].evictable            # host matrix kept
    assert svc.stats["evictions"] == 1
    assert svc.resident_bytes() <= svc.memory_limit
    # traffic to the evicted operator re-materializes it from the host
    # matrix (and evicts the other idle tenant to make room)
    rid = svc.submit(_rhs(big.shape[0], 9), "big")
    out = svc.drain()[rid]
    assert out.status == "converged"
    assert svc.stats["reloads"] == 1
    assert svc.operators()["big"].resident


def test_over_memory_reject_when_nothing_evictable():
    m = laplacian_2d(8)
    eng = AzulEngine(m, precond="jacobi", dtype=np.float64)
    svc = SolveService(max_batch=2, chunk=8,
                       memory_limit=int(eng.device_bytes()))
    # engine-registered operator: pinned (no host matrix to rebuild from)
    svc.register_operator("pinned", engine=eng,
                          spec=SolveSpec(method="pcg_tol", tol=TOL,
                                         iters=400))
    assert not svc.operators()["pinned"].evictable
    with pytest.raises(SolveRequestError) as ei:
        svc.register_operator("more", laplacian_2d(6), method="pcg_tol",
                              tol=TOL, iters=400)
    assert ei.value.reason == "over_memory"
    assert "more" not in svc.operators()


def test_unregister_refuses_busy_operator():
    svc, m = _service(8)
    rid = svc.submit(_rhs(m.shape[0]))
    svc.tick()
    assert svc.active() == 1
    with pytest.raises(ValueError, match="busy"):
        svc.unregister_operator("lap")
    svc.drain()
    svc.unregister_operator("lap")
    assert svc.operators() == {}
    assert rid is not None


# -- degradation and fixed-iteration methods on the continuous path ----------


class _BoomPlan:
    """Fused-plan double that explodes on execution (traces stays 1)."""

    info = {"fused": True}
    traces = 1

    def __init__(self):
        self.calls = 0

    def compile(self):
        return None                     # compiled fine; fails when run

    def __call__(self, batch, x0=None):
        self.calls += 1
        raise RuntimeError("injected fused-kernel failure")


def test_degrades_to_reference_chunks_on_fused_failure():
    svc, m = _service(8, chunk=8)
    rid = svc.submit(_rhs(m.shape[0], 11))
    boom = _BoomPlan()
    svc._operators["lap"].pools["cb"][1] = boom   # poison bucket-1 chunks
    out = svc.drain()[rid]
    assert out.status == "converged"              # answered by cb_ref
    assert boom.calls >= 1
    assert svc.stats["degraded_batches"] >= 1
    a = _csr(m)
    b = _rhs(m.shape[0], 11)
    assert np.linalg.norm(b - a @ out.x) <= TOL * np.linalg.norm(b) * 10


def test_compile_failure_propagates_not_degraded(monkeypatch):
    """A fused plan whose program cannot be lowered or compiled is a broken
    program, not a runtime fault: the error reaches the caller and no
    batch is counted as degraded (a reference retry would hide it)."""
    from repro.kernels import ops

    svc, m = _service(8, chunk=8)
    # compiled (non-interpret) Pallas kernels cannot lower on the CPU
    # backend: the fused chunk plan fails while it is being built
    monkeypatch.setattr(ops, "_dispatch", lambda: (True, False))
    svc.submit(_rhs(m.shape[0], 5))
    with pytest.raises(Exception, match="(?i)interpret"):
        svc.drain()
    assert svc.stats["degraded_batches"] == 0


def test_fixed_iteration_method_serves_in_chunks():
    m = laplacian_2d(8)
    svc = SolveService(max_batch=2, chunk=10)
    svc.register_operator("lap", m, method="pcg", iters=30, precond="jacobi",
                          dtype=np.float64)
    b = _rhs(m.shape[0], 13)
    rid = svc.submit(b)
    out = svc.drain()[rid]
    assert out.status == "maxiter"        # budget-terminated, healthy
    assert out.iters == -1                # fixed-iter contract (no target)
    assert np.all(np.isfinite(out.x))
    assert out.res_norms.shape[0] == 31   # 3 chunks of 10, head + deltas
    a = _csr(m)
    assert (np.linalg.norm(b - a @ out.x)
            < 1e-3 * np.linalg.norm(b))   # 30 PCG iters genuinely happened


# -- the deprecated SolveServer shim -----------------------------------------


def test_solve_server_shim_warns_and_stays_on_the_plan_surface():
    m = laplacian_2d(8)
    eng = AzulEngine(m, precond="jacobi", dtype=np.float64)
    spec = SolveSpec(method="pcg_tol", tol=TOL, max_iters=200)
    _reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        srv = SolveServer(eng, spec=spec)
    assert any(issubclass(w.category, DeprecationWarning)
               and "SolveService" in str(w.message) for w in rec)
    b = _rhs(m.shape[0], 17)
    rid = srv.submit(b)
    out = srv.step()[rid]
    # bit-identical to executing the batch-1 plan directly: the shim adds
    # management, never math
    from dataclasses import replace
    plan = eng.plan(replace(srv._op.cspec, batch=1))
    x, norms = plan(b[None])
    assert np.array_equal(out.x, np.asarray(x)[0])
    assert out.status == "converged"


# -- stats: the legacy dict shape, now a write-through registry view ---------


def test_stats_is_the_exact_legacy_dict_shape():
    """``SolveService.stats`` became a write-through view over the obs
    registry; to every reader it must stay EXACTLY the legacy dict --
    same keys, same initial values, plain-dict equality -- and every bump
    must land in ``repro_serve_events_total`` under this service's
    label."""
    from repro import obs

    svc, m = _service(8)
    legacy = {
        "requests": 0, "batches": 0, "padded_rhs": 0, "plans": 0,
        "rejected": 0, "degraded_batches": 0, "deadline_batches": 0,
        "deadline_exceeded": 0, "straggler_chunks": [],
        "ticks": 0, "chunks": 0, "admitted": 0, "completed": 0,
        "rebuckets": 0, "padded_lanes": 0, "queue_peak": 0,
        "evictions": 0, "reloads": 0, "rejects": {},
    }
    assert dict(svc.stats) == legacy
    assert isinstance(svc.stats, dict)            # readers see a dict
    assert isinstance(svc.stats["rejects"], dict)

    rid = svc.submit(_rhs(m.shape[0], 23))
    out = svc.drain()[rid]
    assert out.status == "converged"
    assert svc.stats["requests"] == 1
    assert svc.stats["completed"] == 1
    assert svc.stats["ticks"] >= 1

    ev = obs.REGISTRY.get("repro_serve_events_total")
    svc_label = svc._obs_label
    for key in ("requests", "completed", "ticks", "chunks"):
        assert ev.value(service=svc_label, event=key) == svc.stats[key], key
    # structured rejection mirrors into repro_serve_rejects_total
    with pytest.raises(SolveRequestError):
        svc.submit(np.ones(3))                    # wrong length
    assert svc.stats["rejected"] == 1
    reason = next(iter(svc.stats["rejects"]))
    rj = obs.REGISTRY.get("repro_serve_rejects_total")
    assert rj.value(service=svc_label, reason=reason) == 1
    # gauges track residency and queue high-water
    assert (obs.REGISTRY.get("repro_serve_resident_bytes")
            .value(service=svc_label)) == svc.resident_bytes()
    assert (obs.REGISTRY.get("repro_serve_queue_peak")
            .value(service=svc_label)) == svc.stats["queue_peak"]

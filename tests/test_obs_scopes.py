"""Layer names from inside the program: the device program's scopes, the
instruction -> scope map, the solve's staging spans and transfer counters.

1. **Scopes.**  Every instruction of a compiled plan maps to a scope of the
   closed vocabulary or to ``other``; the gather, the CG update, the
   solver loop, the halo exchange and the dot ``psum``s map to theirs
   (stored and matrix-free local plans here; the 4-device halo plan in a
   subprocess, ``dist`` marker).  The v5e compile is checked in
   ``tests/test_tpu_aot.py``.
2. **The map** is parsed lazily (on read, or when its plan is freed) and
   calls a name two executables disagree on ``other``.
3. **Spans and counters.**  A solve is a ``solve`` span whose
   ``stage_in`` / ``execute`` / ``stage_out`` children share its request
   id; the byte counters equal the transferred arrays' ``nbytes`` (a
   call without x0 sends b alone); ``repro_solve_staged_copies_total``
   counts only vectors a padded or permuted layout copies on the host;
   ``SolvePlan.compile()`` feeds ``plan_compile``.
"""

import gc
import os
import re
import subprocess
import sys
import weakref

import numpy as np
import pytest

from repro import obs
from repro.core import AzulEngine, SolveSpec
from repro.core.stencil import lap3d_stencil
from repro.data.matrices import laplacian_2d
from repro.obs import scopes
from repro.obs.scopes import OTHER, VOCABULARY

SPEC = SolveSpec(method="pcg_tol", tol=1e-5, max_iters=300)
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\b"
                   r"(fusion|custom-call|gather|while|all-reduce|"
                   r"collective-permute|reduce-scatter|all-gather)\(")


def _engine(kind):
    a = laplacian_2d(16) if kind == "stored" else lap3d_stencil(8)
    return AzulEngine(a, precond="jacobi", dtype=np.float32)


def _ops(text):
    """[(instruction, opcode)] of the fusions, custom calls, gathers, loops
    and collectives in a compiled module's text."""
    out = []
    for line in text.splitlines():
        m = _LINE.match(line)
        if m:
            out.append((m.group(1), m.group(2)))
    return out


# -- the scope of an op_name --------------------------------------------------


@pytest.mark.parametrize("op_name, want", [
    ("jit(prog)/control/while/body/jit(stream_rows)/gather/gather", "gather"),
    ("jit(prog)/control/while/body/update/jit(cg_update)/pallas_call",
     "update"),
    ("jit(prog)/control/while", "control"),
    ("jit(prog)/control/while/body/matvec/gather", "matvec"),  # primitive
    ("jit(prog)/reduce_sum", OTHER),
    ("", OTHER),
])
def test_innermost_vocabulary_element_names_the_op(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_scope_refuses_names_outside_the_vocabulary():
    with pytest.raises(ValueError):
        scopes.scope("kernels")


# -- the instruction -> scope map ---------------------------------------------


class _Exe:
    def __init__(self, text):
        self.text = text
        self.printed = 0

    def as_text(self):
        self.printed += 1
        return self.text


_TEXT = """HloModule m
%f (p: f32[]) -> f32[] {
  ROOT %add.1 = f32[] add(%p, %p), metadata={op_name="jit(f)/update/add"}
}
ENTRY %main (a: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %copy.2 = f32[] copy(%a)
  ROOT %fusion.3 = f32[] fusion(%copy.2), kind=kLoop, calls=%f, metadata={op_name="jit(f)/control/gather/add" source_line=3}
}
"""


def test_map_is_parsed_on_read_not_on_register():
    m = scopes.ScopeMap()
    owner, exe = type("Plan", (), {})(), _Exe(_TEXT)
    m.register(owner, exe)
    assert exe.printed == 0
    assert m.mapping() == {"add.1": "update", "a": OTHER, "copy.2": OTHER,
                           "fusion.3": "gather"}
    m.mapping()
    assert exe.printed == 1


def test_map_is_parsed_when_the_owner_dies_and_lets_go():
    m = scopes.ScopeMap()
    Plan = type("Plan", (), {})
    owner, exe = Plan(), _Exe(_TEXT)
    m.register(owner, exe)
    gone = weakref.ref(exe)
    del owner, exe
    gc.collect()
    assert gone() is None                       # parsed, then released
    assert m.mapping()["fusion.3"] == "gather"


def test_a_name_two_executables_disagree_on_is_other():
    m = scopes.ScopeMap()
    Plan = type("Plan", (), {})
    keep = [Plan(), Plan()]
    m.register(keep[0], _Exe(_TEXT))
    m.register(keep[1], _Exe(_TEXT.replace("/gather/add", "/halo/add")))
    got = m.mapping()
    assert got["fusion.3"] == OTHER and got["add.1"] == "update"


# -- scopes on compiled plans -------------------------------------------------


@pytest.fixture
def own_scopes(monkeypatch):
    """A scope map of this test's plans alone: executables other tests of
    the process compiled reuse instruction names with other scopes, which
    the process-wide map calls ``other``."""
    from repro.core import plan as plan_module

    m = scopes.ScopeMap()
    monkeypatch.setattr(plan_module, "_SCOPES", m)
    return m


@pytest.mark.parametrize("kind", ["stored", "stencil"])
def test_every_instruction_of_a_plan_has_a_scope(kind, own_scopes):
    plan = _engine(kind).plan(SPEC)
    text = plan.compile().as_text()
    found = own_scopes.mapping()
    ops = _ops(text)
    assert ops
    named = {}
    for name, opcode in ops:
        assert found[name] in VOCABULARY + (OTHER,)
        named.setdefault(opcode, set()).add(found[name])
    assert named["while"] == {"control"}
    seen = {found[n] for n, _ in ops}
    assert {"matvec", "update", "reduce", "control"} <= seen
    if kind == "stored":
        assert named["gather"] == {"gather"}    # the x[cols] gather
    else:
        assert "gather" not in named and "gather" not in seen


_DIST_SCRIPT = """
import re
import numpy as np
from repro.core import AzulEngine, SolveSpec
from repro.data.matrices import laplacian_2d
from repro.launch.mesh import make_mesh
from repro.obs.scopes import SCOPES

eng = AzulEngine(laplacian_2d(16), mesh=make_mesh((2, 2), ("data", "model")),
                 precond="jacobi", dtype=np.float32)
plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-5, max_iters=200,
                          layout="halo"))
assert plan.spec.layout == "halo"
text = plan.compile().as_text()
found = SCOPES.mapping()
seen = {}
for line in text.splitlines():
    m = re.match(r"\\s*(?:ROOT\\s+)?%?([^\\s=]+) = .*?\\b(all-reduce|"
                 r"collective-permute|reduce-scatter)\\(", line)
    if m:
        seen.setdefault(m.group(2), set()).add(found[m.group(1)])
assert seen == {"collective-permute": {"halo"}, "reduce-scatter": {"halo"},
                "all-reduce": {"reduce"}}, seen
print("SCOPES_DIST_OK")
"""


@pytest.mark.dist
def test_halo_plan_collectives_carry_halo_and_reduce():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)), timeout=560)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "SCOPES_DIST_OK" in r.stdout


# -- spans and counters of a solve --------------------------------------------


def _counter(name):
    fam = obs.REGISTRY.get(name)
    return 0.0 if fam is None else sum(c.value for _, c in fam.samples())


def _stage_counts():
    fam = obs.REGISTRY.get("repro_solve_stage_seconds")
    return {k[0]: c.count for k, c in fam.samples()}


@pytest.mark.parametrize("batch", [None, 3])
def test_solve_stages_nest_under_solve_and_count_bytes(batch):
    eng = _engine("stored")
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-5, max_iters=300,
                              batch=batch))
    plan.compile()
    shape = (eng.n,) if batch is None else (batch, eng.n)
    b = np.random.default_rng(0).standard_normal(shape)
    h2d0 = _counter("repro_solve_h2d_bytes_total")
    d2h0 = _counter("repro_solve_d2h_bytes_total")
    stages0 = _stage_counts()
    obs.TRACER.clear()
    plan(b)
    # what the call hands over: the padded b alone (with no x0 given the
    # plan passes its resident zero guess); what it takes back: every
    # output of the program
    args = (eng.to_device_vec(b), eng.to_device_vec(np.zeros(shape)))
    outs = plan.compile()(*args)
    assert _counter("repro_solve_h2d_bytes_total") - h2d0 == args[0].nbytes
    assert _counter("repro_solve_d2h_bytes_total") - d2h0 == \
        sum(o.nbytes for o in outs)
    stages = _stage_counts()
    assert {k: stages[k] - stages0.get(k, 0) for k in stages} == \
        {"in": 1, "out": 1}
    spans = {s.name: s for s in obs.TRACER.spans()}
    solve = spans["solve"]
    assert solve.parent is None and solve.request == solve.id
    kids = [spans[f"solve.{k}"] for k in ("stage_in", "execute", "stage_out")]
    for k in kids:
        assert k.parent == solve.id and k.request == solve.id
        assert solve.start <= k.start <= k.end <= solve.end
    assert kids[0].end <= kids[1].start and kids[1].end <= kids[2].start


def _staging_engine(kind):
    if kind == "rcm":
        return AzulEngine(laplacian_2d(16), precond="jacobi",
                          dtype=np.float32, reorder="rcm")
    if kind == "padded":                     # n = 225, n_pad = 232
        return AzulEngine(laplacian_2d(15), precond="jacobi",
                          dtype=np.float32)
    return _engine(kind)


@pytest.mark.parametrize("kind", ["stored", "stencil", "rcm", "padded"])
def test_h2d_counts_one_padded_vector_per_call_without_x0(kind):
    eng = _staging_engine(kind)
    plan = eng.plan(SPEC)
    plan.compile()
    b = np.random.default_rng(0).standard_normal(eng.n)
    vec = eng.n_pad * np.dtype(np.float32).itemsize
    h2d0 = _counter("repro_solve_h2d_bytes_total")
    for _ in range(3):
        plan(b)
    assert _counter("repro_solve_h2d_bytes_total") - h2d0 == 3 * vec
    plan(b, x0=np.zeros(eng.n))              # an explicit x0 is sent
    assert _counter("repro_solve_h2d_bytes_total") - h2d0 == 5 * vec


@pytest.mark.parametrize("kind, copies_in, copies_out", [
    ("stored", 0, 0), ("stencil", 0, 0),
    ("rcm", 1, 1),                           # permuted in and out
    ("padded", 1, 0),                        # out only slices the padding
])
def test_staged_copies_count_padded_or_permuted_vectors(kind, copies_in,
                                                        copies_out):
    eng = _staging_engine(kind)
    plan = eng.plan(SPEC)
    plan.compile()
    fam = obs.REGISTRY.get("repro_solve_staged_copies_total")
    b = np.random.default_rng(0).standard_normal(eng.n)
    in0, out0 = fam.value(phase="in"), fam.value(phase="out")
    plan(b)
    plan(b)
    assert fam.value(phase="in") - in0 == 2 * copies_in
    assert fam.value(phase="out") - out0 == 2 * copies_out
    plan(b, x0=np.zeros(eng.n))
    assert fam.value(phase="in") - in0 == 4 * copies_in


def test_bridge_names_spans_repro_dot_name(monkeypatch):
    import jax

    names = []

    class Ann:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    plan = _engine("stored").plan(SPEC)
    plan.compile()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    prev = obs.set_jax_bridge(True)
    try:
        plan(np.ones(plan.engine.n))
    finally:
        obs.set_jax_bridge(prev)
    assert names == ["repro.solve", "repro.solve.stage_in",
                     "repro.solve.execute", "repro.solve.stage_out"]


def test_compile_feeds_plan_compile_once():
    fam = obs.REGISTRY.get("repro_plan_compile_seconds")

    def count():
        return sum(c.count for _, c in fam.samples())

    plan = _engine("stencil").plan(SolveSpec(method="pcg_tol", tol=1e-4,
                                             max_iters=123))
    before = count()
    obs.TRACER.clear()
    plan.compile()
    assert count() - before == 1
    assert [s.kind for s in obs.TRACER.spans()] == ["plan_compile"]
    plan.compile()
    plan(np.ones(plan.engine.n))
    assert count() - before == 1
    assert obs.TRACER.counts().get("plan_compile") == 1

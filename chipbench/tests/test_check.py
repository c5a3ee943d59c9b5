"""The check that decides ``correct``, on hand-made answers, and the seeded
reservoir that keeps the x of a sample of them."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import tiny


def _answer(b, x, status="converged", rnorm_share=0.5e-5, pool=None):
    return harness.Answer(
        b=b, t_due=float(b), t_done=float(b) + 0.5, iters=10, status=status,
        rnorm=rnorm_share * float(np.linalg.norm(pool[b].astype(np.float64))),
        x=x)


@pytest.fixture
def solved():
    """A tiny stored cell, its pool and the exact float64 solutions."""
    cell = tiny.cell("poisson2d_1024.solo")
    pool = cell.rhs.make(cell.cfg, cell.operator,
                         np.random.default_rng([5, 0]), 4)
    a = cell.operator.scipy_csr(cell.cfg)
    import scipy.sparse.linalg as spla

    xs = [spla.spsolve(a.tocsc(), b.astype(np.float64)) for b in pool]
    return cell, pool, xs


def _check(cell, pool, answers):
    w = harness.Window(t0=0.0, t1=10.0, answers=answers)
    return harness.check(harness.Run(
        cell=cell, seed=1, seconds=10.0, setup_s=0.0, window=w, pool=pool,
        device={}, work={}, peaks={}))


def test_sound_answers_are_correct(solved):
    cell, pool, xs = solved
    v = _check(cell, pool, [_answer(i, x, pool=pool) for i, x in enumerate(xs)])
    assert v["correct"] and v["failed"] == 0 and v["checked"] == 4
    assert list(v["checks"]) == ["max_rel_residual",
                                 "max_recursive_rel_residual",
                                 "not_converged", "unanswered"]


@pytest.mark.parametrize("fault, number", [
    ("status", "not_converged"),
    ("recursive", "max_recursive_rel_residual"),
    ("unanswered", "unanswered"),
    ("residual", "max_rel_residual"),
])
def test_one_bad_answer_is_not_correct(solved, fault, number):
    cell, pool, xs = solved
    answers = [_answer(i, x, pool=pool) for i, x in enumerate(xs)]
    if fault == "status":
        answers[1].status = "max_iters"
    elif fault == "recursive":
        answers[1].rnorm *= 100.0
    elif fault == "unanswered":
        answers[1] = harness.Answer(b=1, t_due=1.0)
    else:
        answers[1].x = answers[1].x + 1.0
    v = _check(cell, pool, answers)
    assert v["correct"] is False and v["failed"] == 1
    c = v["checks"][number]
    assert c["value"] > c["limit"]


def test_answer_dropped_by_the_sample_is_answered(solved):
    # an answer whose x the reservoir dropped is not "unanswered"
    cell, pool, xs = solved
    answers = [_answer(i, x, pool=pool) for i, x in enumerate(xs)]
    answers[2].x = None
    v = _check(cell, pool, answers)
    assert v["correct"] and v["checked"] == 3
    assert v["checks"]["unanswered"]["value"] == 0


def _offer_all(size, count, seed):
    s = harness.Sample(size, np.random.default_rng([seed, 2]))
    answers = [harness.Answer(b=0, t_due=0.0, t_done=1.0, x=np.zeros(2))
               for _ in range(count)]
    for a in answers:
        s.offer(a)
    return [i for i, a in enumerate(answers) if a.x is not None]


@pytest.mark.parametrize("count", [3, 16, 200])
def test_sample_holds_at_most_its_size(count):
    kept = _offer_all(16, count, seed=2**40 + 3)
    assert len(kept) == min(16, count)


def test_sample_is_drawn_from_the_seed():
    assert _offer_all(8, 500, 11) == _offer_all(8, 500, 11)
    assert _offer_all(8, 500, 11) != _offer_all(8, 500, 12)
    # every answer stands the same chance: late ones are kept too
    assert max(_offer_all(8, 500, 11)) > 250

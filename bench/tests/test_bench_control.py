"""The check that decides `correct`, shown to fail, at a few thousand
bodies on the CPU: the bfloat16 control in the program's place comes out
not correct, and so does a run with its timed path broken underneath —
a step that leaves the session's state unchanged, half of the sources
left out, every answer 10% off where it is produced.  (No cell spans chips,
so there is no exchange to leave out.)"""
import numpy as np
import pytest

from benchtools import CPU_KNOBS, cells, harness, run, small_root

import control  # noqa: E402
from repro.core.api import FMMSession  # noqa: E402

CELLS = cells()
SEEDS = (1, 2, 2 ** 31 + 3)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_every_limit(root, cell):
    for seed in SEEDS:
        out = control.control_run(cell, seed, 0.0, root=root,
                                  require_tpu=False, cache=None,
                                  log=lambda msg: None)
        assert out["correct"] is False and out["failed"] >= 1, (cell, seed)
        assert all(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_program_readings_pass(root, cell):
    """The readings of the lower end: one session, many seeds, each call
    within the limit."""
    got = control.program_readings(cell, list(SEEDS), calls=2, root=root,
                                   cache=None, session_kw=CPU_KNOBS,
                                   require_tpu=False, log=lambda msg: None)
    limit = harness.load_cell(root, cell)[2]["limits"]["scaled_rms_error"]
    assert sorted(got) == sorted(SEEDS)
    for errs in got.values():
        assert len(errs) == 2
        assert all(0 < e <= limit and 0 < rel for e, rel in errs)
    assert got[SEEDS[0]] != got[SEEDS[1]]


def _unchanged_step(mp):
    mp.setattr(FMMSession, "step", lambda self, new_x, new_q=None: None)


def _half_sources(mp):
    plan, step = FMMSession.from_points.__func__, FMMSession.step

    def halve(q):
        q = np.array(q, np.float64)
        q[::2] = 0.0
        return q

    mp.setattr(FMMSession, "from_points", classmethod(
        lambda cls, x, q, *a, **kw: plan(cls, x, halve(q), *a, **kw)))
    mp.setattr(FMMSession, "step", lambda self, new_x, new_q=None: step(
        self, new_x, None if new_q is None else halve(new_q)))


def _altered_answer(mp):
    evaluate = FMMSession.evaluate
    mp.setattr(FMMSession, "evaluate", lambda self: evaluate(self) * 1.1)


FAULTS = {"unchanged_step": _unchanged_step, "half_sources": _half_sources,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS
                                        for f in FAULTS])
def test_fault_is_not_correct(root, monkeypatch, cell, fault):
    assert run(root, cell)["correct"] is True
    FAULTS[fault](monkeypatch)
    out = run(root, cell)
    assert out["correct"] is False and out["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in out["checks"].values())

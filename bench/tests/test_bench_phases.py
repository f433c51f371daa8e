"""The readers of the program's own names and counts: the fused evaluate's
device phases (`FMMSession.device_phases`) summed from a trace recorded on
one TPU v5e, and the counters of one matvec call (`call_counters`) on the
CPU at a few thousand bodies.

`fixtures/trace_sphere_scoped.json` is the first call of a
`sphere-1m.matvec` window (`trace_reduce.py --trim --keep fusion`, with
`--max-ops` at the call's 88,479 device events), less the 86,792 events
nested inside a loop's op (mostly the M2L loop's body), whose time the
loop's op already holds; `.phases.json` beside it is `device_phases()` of
the same run for the ops it holds."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchtools import CPU_KNOBS, ROOT, harness

import trace_reduce  # noqa: E402
import workload  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
TRACE = FIXTURES / "trace_sphere_scoped.json"
PHASE_MAP = FIXTURES / "trace_sphere_scoped.phases.json"
DEVICE = {"upward_device_ms": "fmm.upward", "m2l_device_ms": "fmm.m2l",
          "downward_device_ms": "fmm.downward",
          "p2p_scatter_device_ms": "fmm.p2p_scatter"}
COUNTED = ("m2l_live_share", "p2p_tile_live_share", "h2d_mb_per_call")


class _Program:
    """A session of a program that names no phases and counts nothing."""
    class geometry:
        x0, q0 = np.zeros((1, 3)), np.zeros(1)

    def step(self, x, q):
        pass

    def evaluate(self):
        pass


@pytest.fixture(scope="module")
def traced():
    events = json.loads(TRACE.read_text())
    return trace_reduce.reduce(events), json.loads(PHASE_MAP.read_text())


@pytest.fixture(scope="module")
def session():
    spec = harness.load_spec(ROOT)
    cfg = json.loads((ROOT / spec["configs"][0]["file"]).read_text())
    deploy = dict(cfg["deployment"], bodies=2000)
    x, q = workload.bodies(deploy, 7)
    make = harness.program_session(ROOT, CPU_KNOBS)
    sess = make(x, q, deploy, None)
    sess.evaluate()
    return sess


@pytest.mark.parametrize("metric", sorted(DEVICE))
def test_device_phase_readers_on_the_chip_trace(traced, metric):
    s, phases = traced
    mod = harness.load_reader(ROOT, metric)

    class Session:
        def device_phases(self):
            return phases

    ops = mod.prepare(Session())
    assert ops == {op for op, ph in phases.items() if ph == DEVICE[metric]}
    want = sum(v for op, v in s["ops"].items() if phases.get(op)
               == DEVICE[metric])
    run = harness.Run(cell={}, config={}, mix={}, evaluate_s=[1.0, 1.0],
                      trace=s, prepared={metric: ops})
    assert want > 0
    assert mod.read(run) == pytest.approx(want / 2 * 1e3, rel=1e-12)


def test_phases_cover_the_traced_device_time(traced):
    """The fixture's top-level ops of the fused evaluate, by phase, add up
    to its device busy time (no op of the window double counted)."""
    s, phases = traced
    named = sum(v for op, v in s["ops"].items() if op in phases)
    assert 0.97 * s["busy_s"] < named <= s["busy_s"] * (1 + 1e-9)
    assert set(phases.values()) >= set(DEVICE.values())
    p2p = harness.load_reader(ROOT, "p2p_ms").KERNEL
    assert {phases[op] for op in s["ops"] if p2p in op} == {"fmm.p2p"}


@pytest.mark.parametrize("metric", sorted(DEVICE) + list(COUNTED))
def test_readers_report_nothing_for_a_program_without_names(metric):
    """On a program that names no phases and counts none of these, the
    readers return None and do not raise."""
    mod = harness.load_reader(ROOT, metric)
    run = harness.Run(cell={}, config={}, mix={}, evaluate_s=[1.0],
                      trace={"ops": {"fusion.1": 1.0}, "busy_s": 1.0,
                             "window_s": 1.0})
    run.prepared[metric] = mod.prepare(_Program())
    assert mod.read(run) is None


def test_counted_readers_on_a_matvec_call(session):
    eng = session.engine
    read = {}
    for m in COUNTED:
        mod = harness.load_reader(ROOT, m)
        read[m] = mod.read(harness.Run(cell={}, config={}, mix={},
                                       prepared={m: mod.prepare(session)}))
    mask = eng.tables.m2l["mask"]
    assert read["m2l_live_share"] == np.count_nonzero(mask) / len(mask)
    assert read["p2p_tile_live_share"] == (eng._stream["n_live_tiles"]
                                           / eng._stream["n_tiles"])
    P, nmax = eng.tables.n_parts, eng.tables.n_bodies_max
    assert read["h2d_mb_per_call"] == P * nmax * (12 + 4) / 1e6
    assert all(0 < v <= 1 for k, v in read.items() if "share" in k)
    from repro import obs
    assert not obs.enabled()                # off again after the call

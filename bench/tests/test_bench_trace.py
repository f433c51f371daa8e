"""`bench/trace_reduce.py` on a trimmed profiler trace recorded on one TPU
v5e (`fixtures/trace_sphere.json`: the first ops of a `sphere-1m.matvec`
window, with the benchmark's host annotations), checked against sums
made independently here."""
import json
from pathlib import Path

import pytest

from benchtools import BENCH, ROOT, harness

import trace_reduce  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_sphere.json"
P2P = harness.load_reader(ROOT, "p2p_ms").KERNEL


@pytest.fixture(scope="module")
def events():
    return json.loads(FIXTURE.read_text())


def _sweep_busy(intervals):
    """Covered length by a sweep over the sorted end points."""
    marks = sorted([(a, 1) for a, b in intervals]
                   + [(b, -1) for a, b in intervals])
    depth, last, busy = 0, None, 0.0
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_reduce_matches_independent_sums(events):
    s = trace_reduce.reduce(events)
    win = next(e for e in events if e["name"] == trace_reduce.WINDOW)
    w0, w1 = win["start_ns"], win["start_ns"] + win["dur_ns"]
    dev = [e for e in events if e["plane"].startswith("/device:")]
    assert len(dev) > 100 and s["n_devices"] == 1
    clipped = [(max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
               for e in dev]
    clipped = [(a, b) for a, b in clipped if b > a]
    assert s["window_s"] == pytest.approx(win["dur_ns"] * 1e-9, rel=1e-12)
    assert s["busy_s"] == pytest.approx(_sweep_busy(clipped) * 1e-9,
                                        rel=1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert sum(s["ops"].values()) == pytest.approx(
        sum(b - a for a, b in clipped) * 1e-9, rel=1e-9)
    assert sum(g for _, g in s["gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)
    names = {e["name"] for e in events if e["plane"] == "/host:CPU"}
    assert all(n in names for n, _ in s["gaps"])
    assert [g for _, g in s["gaps"]] == sorted(
        (g for _, g in s["gaps"]), reverse=True)


def test_stream_kernel_is_found(events):
    s = trace_reduce.reduce(events)
    want = sum(e["dur_ns"] for e in events
               if e["plane"].startswith("/device:") and P2P in e["name"])
    assert want > 0
    assert trace_reduce.op_seconds(s, P2P) == pytest.approx(want * 1e-9,
                                                            rel=1e-9)


def test_breakdown_and_readers(events):
    s = trace_reduce.reduce(events)
    b = trace_reduce.breakdown(s)
    assert set(b) == {"device_ops", "idle_gaps"}
    for rows in b.values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and v > 0 for n, v in rows)
    run = harness.Run(cell={}, config={}, mix={}, evaluate_s=[1.0],
                      trace=s, prepared={"p2p_gpairs_s": 10 ** 9})
    read = {m: harness.load_reader(ROOT, m).read
            for m in ("idle_share", "p2p_ms", "p2p_gpairs_s",
                      "evaluate_other_device_ms")}
    assert read["idle_share"](run) == pytest.approx(
        100 * (1 - s["busy_s"] / s["window_s"]))
    p2p = trace_reduce.op_seconds(s, P2P)
    assert read["p2p_ms"](run) == pytest.approx(p2p * 1e3)
    assert read["p2p_gpairs_s"](run) == pytest.approx(1 / p2p)
    assert read["evaluate_other_device_ms"](run) == pytest.approx(
        (s["busy_s"] - p2p) * 1e3)


def test_trace_without_device_ops_is_an_error(events):
    host = [e for e in events if e["plane"] == "/host:CPU"]
    with pytest.raises(ValueError, match="no device op"):
        trace_reduce.reduce(host)
    with pytest.raises(ValueError, match="one 'bench.window'"):
        trace_reduce.reduce([e for e in events
                             if e["name"] != trace_reduce.WINDOW])

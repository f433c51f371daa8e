"""Flight recorder (repro.obs): tracer, metrics, report surfaces.

In-process: span nesting/ordering, the disabled-mode zero-allocation pin,
spans as profiler annotations, metrics-registry isolation, the non-raising stats
surfaces and the one-launch guarantee under tracing.  The 4-device exchange
probe (wire bytes == rank-aggregated `GeometryPlan.bytes_matrix`, finite
`model_drift` per protocol) runs in a subprocess so this process keeps a
single device.
"""
import json
import os
import subprocess
import sys
import textwrap
import tracemalloc

import numpy as np
import pytest

from repro import obs


def _toy_points(n=300, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, 3)), rng.uniform(-1, 1, n)


# ------------------------------------------------------------- tracer -----
def test_span_nesting_and_ordering():
    tr = obs.configure(enabled=True)
    with obs.span("outer", {"k": 1}):
        with obs.span("inner.a"):
            pass
        with obs.span("inner.b"):
            pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner.a", "inner.b", "outer"]
    outer = spans[2]
    assert outer.attrs == {"k": 1}
    assert spans[0].parent == outer.sid == spans[1].parent
    assert outer.parent == -1
    assert spans[0].sid < spans[1].sid          # monotonic ids
    for s in spans:
        assert s.t1_ns >= s.t0_ns >= 0
    # children are contained in the parent interval
    assert outer.t0_ns <= spans[0].t0_ns and spans[1].t1_ns <= outer.t1_ns


def test_span_set_merges_attrs_and_summary_aggregates():
    tr = obs.configure(enabled=True)
    for i in range(3):
        with obs.span("work", {"i": i}) as sp:
            sp.set({"extra": i * 10})
    assert tr.spans("work")[1].attrs == {"i": 1, "extra": 10}
    summ = tr.summary()
    assert summ["work"]["count"] == 3
    assert summ["work"]["total_s"] >= summ["work"]["max_s"] > 0
    assert summ["work"]["mean_s"] == pytest.approx(
        summ["work"]["total_s"] / 3)


def test_events_record_instants_with_parent_span():
    tr = obs.configure(enabled=True)
    with obs.span("phase") as sp:
        obs.event("probe", {"x": 1})
    evs = [e for e in tr.events if isinstance(e, dict)]
    assert len(evs) == 1 and evs[0]["name"] == "probe"
    assert evs[0]["parent"] == sp.sid
    assert evs[0]["attrs"] == {"x": 1}


def test_ring_drop_bounds_memory():
    tr = obs.configure(enabled=True, max_events=100)
    for i in range(500):
        obs.event("e")
    assert len(tr.events) <= 100
    assert tr.dropped >= 400


def test_disabled_mode_is_zero_allocation():
    """The overhead pin: with tracing off, span/event/counter calls on a hot
    loop must not allocate (NULL_SPAN singleton, early-return helpers)."""
    obs.configure(enabled=False)
    d = {"n": 7}                     # pre-built attrs, as the contract asks

    def hot(iters):
        for _ in iters:
            with obs.span("hot.loop", d):
                pass
            obs.event("hot.event", d)
            obs.counter_add("hot.counter")
            obs.observe("hot.hist", 1.0)

    import itertools
    hot(itertools.repeat(None, 100))            # warm any lazy init
    it = itertools.repeat(None, 10_000)
    tracemalloc.start()
    hot(it)
    current, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # 10k iterations x 4 calls: anything per-iteration would be >100 KB;
    # allow small constant noise from the tracemalloc machinery itself
    assert peak < 8192, f"disabled obs hot path allocated {peak} bytes"


def test_span_is_a_profiler_annotation(tmp_path):
    """An enabled span is `repro.<name>` on the host line of a
    `jax.profiler` trace, the clock the device ops are timed on; its own
    record stays for `summary()`."""
    import jax
    from jax.profiler import ProfileData
    tr = obs.configure(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("outer"):
            with obs.span("inner"):
                jax.numpy.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        host[ev.name] = (ev.start_ns, ev.duration_ns)
    assert set(host) == {"repro.outer", "repro.inner"}
    (o0, od), (i0, idur) = host["repro.outer"], host["repro.inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od
    assert [s.name for s in tr.spans()] == ["inner", "outer"]


def test_tracer_disable_keeps_history_reset_drops_it():
    tr = obs.configure(enabled=True)
    with obs.span("kept"):
        pass
    obs.configure(enabled=False)
    assert not obs.enabled()
    assert obs.get_tracer() is tr and len(tr.spans("kept")) == 1
    obs.reset()
    assert obs.get_tracer() is None


# ------------------------------------------------------------- metrics ----
def test_metrics_counters_gauges_histograms():
    obs.configure(enabled=True)
    obs.counter_add("c", 2)
    obs.counter_add("c")
    obs.gauge_set("g", 4.5)
    for v in (1.0, 3.0):
        obs.observe("h", v)
    snap = obs.metrics_snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 4.5
    h = snap["histograms"]["h"]
    assert (h["count"], h["sum"], h["min"], h["max"], h["mean"]) == \
        (2, 4.0, 1.0, 3.0, 2.0)


def test_metrics_disabled_records_nothing():
    obs.configure(enabled=False)
    obs.counter_add("never")
    assert obs.metrics_snapshot()["counters"] == {}


def test_metrics_family_conflict_raises():
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter_add("name")
    with pytest.raises(ValueError):
        reg.gauge_set("name", 1.0)


def test_metrics_reset_isolation():
    """The autouse fixture calls obs.reset(); a prior test's counters must
    never be visible (this test relies on the fixture having run)."""
    assert obs.metrics_snapshot()["counters"] == {}
    obs.configure(enabled=True)
    obs.counter_add("leaky")
    obs.reset()
    assert obs.metrics_snapshot()["counters"] == {}


# ----------------------------------------------------- session surfaces ---
def test_meshless_exchange_stats_is_structured_not_raising():
    from repro.core.api import FMMSession
    x, q = _toy_points()
    sess = FMMSession.from_points(x, q, nparts=4, engine=False)
    st = sess.exchange_stats                     # pre-PR-8: RuntimeError
    assert st["enabled"] is False
    assert "reason" in st and st["n_rounds"] == 0
    assert st["protocol"] == "bulk"


def test_meshless_report_structure():
    from repro.core.api import FMMSession
    obs.configure(enabled=True)
    x, q = _toy_points()
    sess = FMMSession.from_points(x, q, nparts=4, engine=False)
    sess.evaluate()
    rep = sess.report()
    assert rep["obs"]["enabled"] is True
    assert "session.evaluate" in rep["timings"]
    assert "plan.geometry" in rep["timings"]
    assert rep["metrics"]["counters"]["session.evaluations"] == 1
    assert rep["exchange"] == {"enabled": False, "protocols": {}}
    assert rep["launches"] == {"enabled": False}
    assert rep["memo"]["misses"] >= 0
    assert rep["geometry"]["bytes_matrix_total"] == \
        int(sess.geometry.bytes_matrix.sum())
    json.dumps(rep)                              # report must be exportable


def test_traced_fused_evaluate_still_one_entry_launch():
    """Tracing must not break the one-launch guarantee: spans never wait
    for the device, and the fused entry still compiles to ONE entry
    computation."""
    from repro.analysis.hlo_walk import count_entry_launches
    from repro.core.api import FMMSession
    from repro.core.engine import ExecutableCache
    obs.configure(enabled=True)
    x, q = _toy_points(400, seed=2)
    sess = FMMSession.from_points(x, q, nparts=4, engine=True, fused=True,
                                  use_kernels=False,
                                  exe_cache=ExecutableCache())
    sess.evaluate()
    sess.evaluate()
    rep = sess.report()
    la = rep["launches"]["evaluate"]
    assert la["entry_computations"] == 1
    assert la["calls"] == 2
    assert rep["exe_cache"]["misses"] == 1       # one compile, ever
    assert rep["metrics"]["counters"]["exe_cache.misses"] == 1
    assert rep["metrics"]["counters"]["engine.fused_launches"] == 2
    assert "exe_cache.compile" in rep["timings"]
    assert "engine.fused_evaluate" in rep["timings"]


def test_plan_geometry_spans_nest_under_plan():
    from repro.core.api import PartitionSpec, plan_geometry
    tr = obs.configure(enabled=True)
    x, q = _toy_points()
    plan_geometry(x, q, PartitionSpec(nparts=4))
    parent = tr.spans("plan.geometry")[0]
    for sub in ("plan.partition", "plan.trees", "plan.lets",
                "plan.receivers"):
        sp = tr.spans(sub)
        assert len(sp) == 1 and sp[0].parent == parent.sid
    assert parent.attrs["nparts"] == 4


# ----------------------------------------- 4-device exchange probes -------
_PROBE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    from repro import obs
    obs.configure(enabled=True)
    from repro.core.api import FMMSession, PartitionSpec, plan_geometry
    from repro.launch.mesh import host_device_mesh

    mesh = host_device_mesh(4)
    rng = np.random.default_rng(3)
    x = rng.uniform(0, 1, (800, 3)); x[:, 0] *= 4.0
    q = rng.uniform(-1, 1, 800)
    geo = plan_geometry(x, q, PartitionSpec(nparts=8, method="morton",
                                            ncrit=64))
    sess = FMMSession(geo, mesh=mesh, dist_protocol="bulk")
    rep = sess.report(measure_exchange=True, reps=2)

    lay = sess.dist.layout
    inter = int(sum(int(geo.bytes_matrix[i, j])
                    for i in range(len(lay.part_rank))
                    for j in range(len(lay.part_rank))
                    if lay.part_rank[i] != lay.part_rank[j]))
    out = {"inter_rank_bytes": inter,
           "rank_bytes_sum": int(lay.rank_bytes.sum()),
           "protocols": {}}
    for name, st in rep["exchange"]["protocols"].items():
        out["protocols"][name] = {
            "delivered_bytes": int(st["delivered_bytes"]),
            "moved_bytes": int(st["moved_bytes"]),
            "model_drift": float(st["model_drift"]),
            "measured_s": float(st["measured_s"]),
            "loggp_s": float(st["loggp_s"]),
            "n_rounds": int(st["n_rounds"]),
            "round_wire_bytes": [r["wire_bytes"] for r in st["rounds"]]}
    out["probe_events"] = sum(
        1 for e in obs.get_tracer().events
        if isinstance(e, dict) and e["name"] == "dist.exchange_probe")
    print(json.dumps(out))
""").strip()


@pytest.fixture(scope="module")
def probe_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _PROBE_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("protocol", ["bulk", "grain", "hsdx"])
def test_exchange_probe_wire_bytes_match_bytes_matrix(probe_results,
                                                      protocol):
    """The probe's delivered bytes must equal the inter-rank aggregation of
    `GeometryPlan.bytes_matrix` — the paper's byte accounting, measured."""
    st = probe_results["protocols"][protocol]
    assert st["delivered_bytes"] == probe_results["inter_rank_bytes"]
    assert st["delivered_bytes"] == probe_results["rank_bytes_sum"]
    # every round's wire payload is accounted (moved >= delivered; relays
    # count per hop)
    assert st["moved_bytes"] >= st["delivered_bytes"]
    assert len(st["round_wire_bytes"]) == st["n_rounds"]


@pytest.mark.parametrize("protocol", ["bulk", "grain", "hsdx"])
def test_exchange_probe_model_drift(probe_results, protocol):
    st = probe_results["protocols"][protocol]
    assert np.isfinite(st["model_drift"]) and st["model_drift"] > 0
    assert st["measured_s"] > 0 and st["loggp_s"] > 0
    assert st["model_drift"] == pytest.approx(
        st["measured_s"] / st["loggp_s"])


def test_exchange_probe_emitted_events(probe_results):
    assert probe_results["probe_events"] == 3    # one per protocol

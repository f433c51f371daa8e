"""Fused megakernel + AOT executable cache (repro.core.engine.fused /
repro.core.engine.exe_cache): warm evaluate()/step() pinned at exactly ONE
entry-computation launch, fused numerics pinned against the per-phase
engine, shape-class keying pinned hit/miss-exact, and the donation-vs-
residency contract (DeviceMemo views must never be donated) regression.

Compilation economics shape this module: every distinct shape-class key is
an XLA compile, so the tests share one module-scoped session + private
cache and then *count* cache traffic instead of recompiling per test."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.hlo_walk import count_entry_launches
from repro.core.api import (FMMSession, PartitionSpec, execute_geometry,
                            plan_geometry)
from repro.core.distributions import make_distribution
from repro.core.engine import (DeviceEngine, ExecutableCache,
                               default_fused_enabled)
from repro.core.engine import fused as fused_mod
from repro.core.engine.exe_cache import CompiledEntry

RTOL, ATOL = 1e-6, 2e-5         # x64 engine tolerances (test_engine.py)
F32_RTOL, F32_ATOL = 1e-4, 1e-4  # non-x64 fused path: device f32 accumulation


def _problem(n=700, seed=11, qseed=12, dist="sphere"):
    x = make_distribution(dist, n, seed=seed)
    q = np.random.default_rng(qseed).uniform(-1, 1, n)
    return x, q


@pytest.fixture(scope="module")
def shared():
    """One compiled fused session + its private cache, shared module-wide so
    launch/cache counters are asserted against known traffic instead of
    paying one XLA compile per test."""
    x, q = _problem()
    spec = PartitionSpec(nparts=3, ncrit=48)
    cache = ExecutableCache()
    sess = FMMSession.from_points(x, q, spec, engine=True, fused=True,
                                  use_kernels=False, exe_cache=cache)
    return {"x": x, "q": q, "spec": spec, "cache": cache, "sess": sess}


# ------------------------------------------------------------- numerics ----
def test_fused_matches_reference_f32(shared):
    """Non-x64 fused evaluate accumulates in device f32 — marginally looser
    than the per-phase host-f64 path, but must still track the reference
    executor at f32-accumulation tolerances."""
    ref = execute_geometry(shared["sess"].geometry)
    phi = shared["sess"].evaluate()
    np.testing.assert_allclose(phi, ref, rtol=F32_RTOL, atol=F32_ATOL)


def test_fused_matches_per_phase_x64():
    """With x64 the fused composite inlines the SAME traced kernels the
    per-phase engine launches one by one, accumulating in device f64 — it
    must match at the tight engine tolerances."""
    x, q = _problem(n=500, seed=21, qseed=22)
    geo = plan_geometry(x, q, PartitionSpec(nparts=3, ncrit=48))
    jax.config.update("jax_enable_x64", True)
    try:
        per_phase = DeviceEngine(geo, use_kernels=False, fused=False)
        fused = DeviceEngine(geo, use_kernels=False, fused=True,
                             exe_cache=ExecutableCache())
        want = np.asarray(per_phase.evaluate_device())
        got_dev = fused.evaluate_device()
        assert isinstance(got_dev, jax.Array)
        assert got_dev.shape == (geo.n,) and got_dev.dtype == jnp.float64
    finally:
        jax.config.update("jax_enable_x64", False)
    np.testing.assert_allclose(np.asarray(got_dev), want, rtol=RTOL,
                               atol=ATOL)


# -------------------------------------------------------- launch counting --
def test_fused_warm_evaluate_is_one_launch(shared):
    """Warm fused evaluate: exactly one dispatch through one executable
    whose compiled HLO holds exactly one ENTRY computation; the donated
    payload handle from the previous call is consumed (aliased storage)."""
    sess = shared["sess"]
    eng = sess.engine
    sess.evaluate()                       # ensure warm
    x_prev = eng._x_pad                   # previous launch's threaded output
    n_before = len(eng.launch_log)
    sess.evaluate()
    launches = eng.launch_log[n_before:]
    assert [kind for kind, _ in launches] == ["evaluate"]
    entry, _ = eng._entries[("evaluate", False)]
    assert count_entry_launches(entry.hlo_text) == 1
    assert entry.calls >= 2
    # donation really happened: the old handle's buffer was given to XLA
    assert x_prev.is_deleted()
    assert not eng._x_pad.is_deleted()


def test_fused_step_within_slack_is_one_launch(shared):
    """A within-slack step through the fused session is one dispatch of the
    step executable (restack + drift + changed fused into one donated entry
    computation), no rebuilds, and the following evaluate matches the
    per-phase engine stepped identically."""
    sess = shared["sess"]
    sess.evaluate()
    eng = sess.engine
    rng = np.random.default_rng(31)
    eps = float(sess.geometry.slack.min()) / 4
    new_x = shared["x"] + rng.uniform(-eps, eps, shared["x"].shape)

    n_before = len(eng.launch_log)
    rep = sess.step(new_x)
    assert rep.rebuilt == ()
    steps = [e for e in eng.launch_log[n_before:] if e[0] == "step"]
    assert len(steps) == 1
    entry, _ = eng._entries[("step", False)]
    assert count_entry_launches(entry.hlo_text) == 1

    pp = FMMSession.from_points(shared["x"], shared["q"], shared["spec"],
                                engine=True, fused=False, use_kernels=False)
    pp.evaluate()
    assert pp.step(new_x).rebuilt == ()
    np.testing.assert_allclose(sess.evaluate(), pp.evaluate(),
                               rtol=F32_RTOL, atol=F32_ATOL)


# --------------------------------------------------- shape-class caching ---
def test_second_same_shape_class_geometry_zero_compiles(shared):
    """A new geometry over byte-identical points shares the shape class —
    its session must be served from the executable cache with ZERO XLA
    compilations (the miss counter is the compilation meter)."""
    cache = shared["cache"]
    shared["sess"].evaluate()             # ensure the evaluate entry exists
    stats0 = cache.stats()
    sess2 = FMMSession.from_points(shared["x"].copy(), shared["q"].copy(),
                                   shared["spec"], engine=True, fused=True,
                                   use_kernels=False, exe_cache=cache)
    phi2 = sess2.evaluate()
    assert cache.misses == stats0["misses"]          # zero recompiles
    assert cache.hits == stats0["hits"] + 1          # one served resolution
    assert sess2.exe_cache_stats["misses"] == cache.misses
    # served-from-cache executable still computes the right answer
    np.testing.assert_allclose(phi2, execute_geometry(sess2.geometry),
                               rtol=F32_RTOL, atol=F32_ATOL)


def test_m2l_live_count_is_a_value_not_a_shape(shared):
    """Geometries whose M2L tables differ only in their live row count
    (one pair fewer here, masked as padding) keep one shape class: the
    count is a 0-d table, so they share one executable."""
    import dataclasses
    from repro.core.engine.schedules import shape_class_digest
    t = shared["sess"].engine.tables
    m2l = dict(t.m2l)
    n_live = int(m2l["n_live"])
    assert m2l["n_live"].shape == () and m2l["n_live"].dtype == np.int32
    assert 0 < n_live <= len(m2l["mask"])
    m2l["mask"] = m2l["mask"].copy()
    m2l["mask"][n_live - 1] = 0.0
    m2l["n_live"] = np.asarray(n_live - 1, np.int32)
    fewer = dataclasses.replace(t, m2l=m2l)

    def digest(tables):
        return shape_class_digest(
            {k: jnp.asarray(v) for k, v in
             fused_mod.flatten_eval_tables(tables).items()})
    assert digest(fewer) == digest(t)


def test_different_shape_class_geometry_compiles(shared):
    """Changing the partition count changes the stacked envelope shapes —
    a genuinely new shape class must MISS (one new compilation)."""
    cache = shared["cache"]
    misses0 = cache.misses
    sess = FMMSession.from_points(shared["x"], shared["q"],
                                  PartitionSpec(nparts=2, ncrit=48),
                                  engine=True, fused=True,
                                  use_kernels=False, exe_cache=cache)
    sess.evaluate()
    assert cache.misses == misses0 + 1


def test_executable_key_sensitivity():
    """The shape-class key must separate every compilation-relevant static
    and nothing else: theta buckets at 1/16 resolution, x64, backend,
    padded-dim digest, kernel statics."""
    kw = dict(n=100, n_parts=4, p=4, theta=0.5, x64=False, backend="cpu",
              use_kernels=False, interpret=None, block_ts=())
    base = fused_mod.executable_key("evaluate", "digest0", **kw)
    assert base == fused_mod.executable_key("evaluate", "digest0", **kw)
    assert base != fused_mod.executable_key("step", "digest0", **kw)
    assert base != fused_mod.executable_key("evaluate", "digest1", **kw)
    for field, value in [("n", 101), ("n_parts", 5), ("p", 6),
                         ("theta", 0.6), ("x64", True), ("backend", "tpu"),
                         ("use_kernels", True), ("block_ts", (128,))]:
        assert base != fused_mod.executable_key(
            "evaluate", "digest0", **{**kw, field: value}), field
    # thetas within one 1/16 bucket share the executable (same MAC geometry
    # class for compilation purposes; the tables carry the actual pairs)
    assert fused_mod.theta_bucket(0.5) == fused_mod.theta_bucket(0.52)
    assert base == fused_mod.executable_key("evaluate", "digest0",
                                            **{**kw, "theta": 0.52})


def test_exe_cache_lru_eviction_and_counters():
    """Pure cache semantics: LRU order refreshed on hit, eviction at the
    bound, counters exact, undersized bound rejected."""
    cache = ExecutableCache(maxsize=2)
    made = []

    def compiler(tag):
        def fn():
            made.append(tag)
            return object()       # stands in for jax.stages.Compiled
        return fn

    a = cache.get_or_compile("a", compiler("a"))
    cache.get_or_compile("b", compiler("b"))
    assert cache.get_or_compile("a", compiler("a2")) is a   # hit, no build
    cache.get_or_compile("c", compiler("c"))                # evicts LRU = b
    assert made == ["a", "b", "c"]
    assert "b" not in cache and "a" in cache and "c" in cache
    assert len(cache) == 2
    assert cache.stats() == {"hits": 1, "misses": 3, "evictions": 1,
                             "size": 2, "maxsize": 2}
    cache.get_or_compile("b", compiler("b2"))   # must recompile after evict
    assert made[-1] == "b2"
    assert isinstance(cache.get_or_compile("b", compiler("x")), CompiledEntry)
    with pytest.raises(ValueError, match="maxsize"):
        ExecutableCache(maxsize=0)


# ------------------------------------------------------ donation contract --
def test_donation_guard_rejects_memo_resident_view(shared):
    """DeviceMemo views are shared read-only state; donating one would let
    XLA delete a buffer every other consumer still reads.  `_donatable`
    must refuse them (the engine.fused donation-vs-residency contract
    documented at fmm.device_hook)."""
    eng = shared["sess"].engine
    view = eng._aa(eng.tables.up.tables["leaves"])    # memo-resident view
    assert eng.memo.is_resident(view)
    with pytest.raises(TypeError, match="donate"):
        eng._donatable(view)
    # host arrays upload as fresh copies — always donatable
    out = eng._donatable(np.zeros((4, 3)), jnp.float32)
    assert isinstance(out, jax.Array) and not eng.memo.is_resident(out)


def test_fused_interpret_smoke():
    """The Pallas kernel route INSIDE the fused composite (interpret mode,
    the CPU CI stand-in): bucketed P2P runs through p2p_pallas tiles instead
    of the jnp reference, AOT-lowered and compiled like any other entry —
    and still matches the reference executor."""
    x, q = _problem(n=260, seed=41, qseed=42)
    geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=32))
    eng = DeviceEngine(geo, use_kernels=True, interpret=True, fused=True,
                       exe_cache=ExecutableCache())
    phi = eng.evaluate()
    assert count_entry_launches(eng._entries[("evaluate", False)][0]
                                .hlo_text) == 1
    np.testing.assert_allclose(phi, execute_geometry(geo),
                               rtol=F32_RTOL, atol=F32_ATOL)


def test_fused_default_off_on_cpu():
    """CPU backends keep the per-phase engine default (its counters are
    pinned byte-exactly elsewhere); fused stays opt-in there."""
    if jax.default_backend() == "cpu":
        assert default_fused_enabled() is False
        x, q = _problem(n=200)
        geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=48))
        assert DeviceEngine(geo, use_kernels=False).fused is False
    else:
        assert default_fused_enabled() is True

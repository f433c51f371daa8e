"""Device evaluation engine (repro.core.engine): batched multi-tree upward
pass, segment-summed M2L, Pallas-bucketed P2P, and engine-backed session
dispatch — all pinned against the per-partition reference executors.

Tolerances: the engine's segment-summed M2L accumulates the same f32 terms
as the reference's per-plan scatters in a single launch, so sums regroup —
rtol 1e-6 with a small atol absorbs the f32 reassociation (the batched
upward pass itself is bitwise-identical, pinned below)."""
import warnings

import numpy as np
import pytest

import repro.core.api as api
import repro.core.fmm as fmm
from repro.core.api import (FMMSession, PartitionSpec, execute_geometry,
                            plan_geometry, sync_host_multipoles)
from repro.core.distributions import make_distribution
from repro.core.engine import DeviceEngine, build_batched_upward, stack_bodies
from repro.core.engine.upward import batched_upward
from repro.core.fmm import direct_potential, upward_pass
from repro.core.multipole import get_operators
from repro.core.tree import build_tree

RTOL, ATOL = 1e-6, 2e-5


def _problem(n=1500, seed=5, qseed=6, dist="sphere"):
    x = make_distribution(dist, n, seed=seed)
    q = np.random.default_rng(qseed).uniform(-1, 1, n)
    return x, q


def _clustered_problem():
    """Duplicated sites -> >= 3 of 8 morton partitions empty (inf/-inf
    sentinel boxes)."""
    pts = np.array([[.1, .1, .1], [.8, .2, .3], [.3, .9, .5],
                    [.6, .6, .9], [.9, .9, .1]])
    x = np.repeat(pts, 60, axis=0)
    q = np.random.default_rng(1).uniform(-1, 1, len(x))
    return x, q


# ------------------------------------------------- batched upward pass -----
def test_batched_upward_bitwise_matches_per_partition():
    """One vmapped launch over stacked schedules must reproduce every
    partition's per-tree upward_pass exactly (same traced closures, padding
    rows contribute exactly 0)."""
    x, q = _problem(n=1200, dist="plummer")
    geo = plan_geometry(x, q, PartitionSpec(nparts=4, ncrit=48))
    sched = build_batched_upward(geo.trees, geo.scheds)
    xp, qp = stack_bodies(geo.trees, sched.n_bodies_max)
    M = np.asarray(batched_upward(get_operators(geo.p), xp, qp, sched))
    for j, t in enumerate(geo.trees):
        ref = geo.Ms[j]
        np.testing.assert_array_equal(M[j, :ref.shape[0]], ref)
        assert not M[j, ref.shape[0]:].any()       # padding rows exactly 0


# --------------------------------------------------- engine vs reference ---
@pytest.mark.parametrize("method,nparts", [("orb", 5), ("morton", 4)])
def test_engine_allclose_reference(method, nparts):
    x, q = _problem()
    geo = plan_geometry(x, q, PartitionSpec(nparts=nparts, method=method,
                                            ncrit=48))
    ref = execute_geometry(geo)
    phi = DeviceEngine(geo, use_kernels=False).evaluate()
    np.testing.assert_allclose(phi, ref, rtol=RTOL, atol=ATOL)
    d = direct_potential(x, q)
    assert np.linalg.norm(phi - d) / np.linalg.norm(d) < 3e-3


def test_engine_with_empty_partitions_matches_reference():
    x, q = _clustered_problem()
    geo = plan_geometry(x, q, PartitionSpec(nparts=8, method="morton",
                                            ncrit=64))
    empty = [p for p in range(8) if len(geo.owners[p]) == 0]
    assert len(empty) >= 3
    for p in empty:                                # inf/-inf sentinel boxes
        assert np.all(geo.boxes[p, 0] == np.inf)
        assert np.all(geo.boxes[p, 1] == -np.inf)
    phi = DeviceEngine(geo, use_kernels=False).evaluate()
    np.testing.assert_allclose(phi, execute_geometry(geo), rtol=RTOL,
                               atol=ATOL)


def test_engine_single_partition_matches_reference():
    x, q = _problem(n=400, dist="cube")
    geo = plan_geometry(x, q, PartitionSpec(nparts=1, ncrit=32))
    phi = DeviceEngine(geo, use_kernels=False).evaluate()
    np.testing.assert_allclose(phi, execute_geometry(geo), rtol=RTOL,
                               atol=ATOL)


# ------------------------------------------- session dispatch / stepping ---
def test_session_engine_dispatch_matches_reference_session():
    x, q = _problem(n=1200)
    spec = PartitionSpec(nparts=4, ncrit=48)
    phi_ref = FMMSession.from_points(x, q, spec, engine=False).potentials().phi
    sess = FMMSession.from_points(x, q, spec, engine=True, use_kernels=False)
    res = sess.potentials("hsdx")
    np.testing.assert_allclose(res.phi, phi_ref, rtol=RTOL, atol=ATOL)
    # protocol sweep still serves every protocol from the one evaluation
    sweep = sess.sweep()
    assert all(sweep[p].phi is res.phi for p in sweep)
    # the engine rides the session memo: one transfer meter for both paths
    assert sess.engine.memo is sess.memo


def test_engine_step_zero_multipole_transfers(monkeypatch):
    """Acceptance criterion: after warmup, a within-slack step issues no
    per-partition host transfers — revalidation is ONE batched device launch
    fed by a single new_x upload (+3 one-time frozen tables on the first
    step), the restacked device payload is reused for evaluation, and zero
    host upward_pass calls / multipole uploads happen."""
    x, q = _problem()
    sess = FMMSession.from_points(x, q, PartitionSpec(nparts=4, ncrit=48),
                                  engine=True, use_kernels=False)
    phi0 = sess.evaluate()
    eng = sess.engine
    misses0 = eng.memo.misses
    assert np.array_equal(sess.evaluate(), phi0)   # warm: zero transfers
    assert eng.memo.misses == misses0

    eps = float(sess.geometry.slack.min())
    assert eps > 0
    rng = np.random.default_rng(0)
    x1 = x + rng.uniform(-eps / 4, eps / 4, size=x.shape)
    calls = []
    real = api.upward_pass
    monkeypatch.setattr(api, "upward_pass",
                        lambda *a, **k: calls.append(a) or real(*a, **k))
    rep = sess.step(x1)
    assert rep.rebuilt == () and len(rep.refreshed) == 4
    assert calls == []                  # no host multipole recompute
    assert sess.geometry.Ms_stale == (0, 1, 2, 3)
    phi1 = sess.potentials("hsdx").phi
    assert eng.payload_refreshes == 1
    # first step: new_x + the three one-time revalidation tables (x_ref
    # envelope and the orig->flat gather pair) — NOTHING per-partition, and
    # evaluation reuses the device-restacked payload with zero extra uploads
    assert eng.memo.misses == misses0 + 4
    assert calls == []

    # steady state: each further within-slack step uploads exactly new_x
    misses1 = eng.memo.misses
    x2 = x1 + rng.uniform(-eps / 8, eps / 8, size=x.shape)
    rep2 = sess.step(x2)
    assert rep2.rebuilt == () and len(rep2.refreshed) == 4
    phi2 = sess.potentials("hsdx").phi
    assert eng.memo.misses == misses1 + 1
    assert calls == []

    ref = FMMSession.from_points(x, q, PartitionSpec(nparts=4, ncrit=48),
                                 engine=False)
    ref.step(x1)
    np.testing.assert_allclose(phi1, ref.potentials("hsdx").phi,
                               rtol=RTOL, atol=ATOL)
    ref.step(x2)
    np.testing.assert_allclose(phi2, ref.potentials("hsdx").phi,
                               rtol=RTOL, atol=ATOL)


def test_engine_step_with_charge_change_falls_back_to_host_revalidation():
    """The single-upload device revalidation path is position-only; a step
    that also rebinds charges must still agree with the reference session."""
    x, q = _problem(n=1000)
    spec = PartitionSpec(nparts=4, ncrit=48)
    sess = FMMSession.from_points(x, q, spec, engine=True, use_kernels=False)
    ref = FMMSession.from_points(x, q, spec, engine=False)
    sess.potentials()
    ref.potentials()
    eps = float(sess.geometry.slack.min())
    rng = np.random.default_rng(2)
    x1 = x + rng.uniform(-eps / 4, eps / 4, size=x.shape)
    q1 = q * 1.25
    rep = sess.step(x1, q1)
    ref.step(x1, q1)
    assert rep.rebuilt == ()
    np.testing.assert_allclose(sess.potentials("hsdx").phi,
                               ref.potentials("hsdx").phi, rtol=RTOL,
                               atol=ATOL)


# ------------------------------------------------ x64 device accumulation --
def test_engine_x64_device_accumulation_matches_reference():
    """Acceptance criterion: with x64 enabled the engine's segment sums stay
    on device and return ONE (N,) float64 device array matching the host-
    accumulated reference within the engine tolerances."""
    import jax
    import jax.numpy as jnp
    x, q = _problem(n=900)
    geo = plan_geometry(x, q, PartitionSpec(nparts=3, ncrit=48))
    ref = execute_geometry(geo)
    jax.config.update("jax_enable_x64", True)
    try:
        eng = DeviceEngine(geo, use_kernels=False)
        phi_dev = eng.evaluate_device()
        assert isinstance(phi_dev, jax.Array)
        assert phi_dev.shape == (geo.n,) and phi_dev.dtype == jnp.float64
        phi = eng.evaluate()               # same path, host boundary only
    finally:
        jax.config.update("jax_enable_x64", False)
    np.testing.assert_allclose(np.asarray(phi_dev), ref, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(phi, ref, rtol=RTOL, atol=ATOL)


def test_evaluate_device_requires_x64():
    x, q = _problem(n=300, dist="cube")
    geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=48))
    eng = DeviceEngine(geo, use_kernels=False)
    with pytest.raises(RuntimeError, match="x64"):
        eng.evaluate_device()


def test_engine_step_rebuild_syncs_host_mirrors():
    """A beyond-slack step after deferred refreshes must fill the host
    multipole mirrors before re-extracting LETs, then match the eagerly
    stepped reference session."""
    x, q = _problem()
    spec = PartitionSpec(nparts=4, ncrit=48)
    sess = FMMSession.from_points(x, q, spec, engine=True, use_kernels=False)
    ref = FMMSession.from_points(x, q, spec, engine=False)
    sess.potentials()
    ref.potentials()

    eps = float(sess.geometry.slack.min())
    rng = np.random.default_rng(0)
    x1 = x + rng.uniform(-eps / 4, eps / 4, size=x.shape)
    sess.step(x1)
    ref.step(x1)
    assert sess.geometry.Ms_stale != ()

    x2 = x1.copy()
    mover = 1
    x2[sess.geometry.owners[mover]] += np.array([0.15, -0.1, 0.2])
    rep = sess.step(x2)
    assert rep.rebuilt == (mover,)
    assert sess.geometry.Ms_stale == ()            # rebuild synced everything
    ref.step(x2)
    np.testing.assert_allclose(sess.potentials("hsdx").phi,
                               ref.potentials("hsdx").phi, rtol=RTOL,
                               atol=ATOL)
    d = direct_potential(x2, q)
    phi = sess.potentials("hsdx").phi
    assert np.linalg.norm(phi - d) / np.linalg.norm(d) < 3e-3


def test_reference_path_on_deferred_geometry_syncs_lazily():
    """Turning the engine off after deferred steps must transparently fill
    the host mirrors (sync_host_multipoles) and agree with an eager
    reference session."""
    x, q = _problem(n=1000)
    spec = PartitionSpec(nparts=4, ncrit=48)
    sess = FMMSession.from_points(x, q, spec, engine=True, use_kernels=False)
    sess.potentials()
    eps = float(sess.geometry.slack.min())
    x1 = x + np.random.default_rng(0).uniform(-eps / 4, eps / 4, size=x.shape)
    sess.step(x1)
    assert sess.geometry.Ms_stale != ()
    sess.engine_enabled = False                    # force reference dispatch
    phi = sess.evaluate()
    assert sess.geometry.Ms_stale == ()            # lazily synced
    ref = FMMSession.from_points(x, q, spec, engine=False)
    ref.step(x1)
    np.testing.assert_allclose(phi, ref.evaluate(), rtol=RTOL, atol=ATOL)


def test_sync_host_multipoles_idempotent_noop_when_fresh():
    x, q = _problem(n=400)
    geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=48))
    Ms_before = [None if M is None else M.copy() for M in geo.Ms]
    sync_host_multipoles(geo)
    for a, b in zip(geo.Ms, Ms_before):
        if a is not None:
            np.testing.assert_array_equal(a, b)


# -------------------------------------------------- pallas interpret smoke -
def test_engine_pallas_interpret_smoke():
    """Toy-size engine with the Pallas bucketed P2P path in interpret mode
    (what CPU CI runners can exercise; TPU runs compile the same kernels)."""
    x, q = _problem(n=300, dist="cube")
    geo = plan_geometry(x, q, PartitionSpec(nparts=3, ncrit=32))
    ref = execute_geometry(geo)
    phi = DeviceEngine(geo, use_kernels=True, interpret=True).evaluate()
    np.testing.assert_allclose(phi, ref, rtol=RTOL, atol=ATOL)


def test_p2p_autotune_cache_keyed_by_bucket_shape():
    from repro.kernels import p2p as kp
    kp._BLOCK_CACHE.clear()
    b1 = kp.best_block_t(64, 7, 32, interpret=True)
    b2 = kp.best_block_t(64, 7, 32, interpret=True)
    assert b1 == b2 and list(kp._BLOCK_CACHE) == [(64, 7, 32)]
    assert b1 in kp.BLOCK_CANDIDATES
    kp.best_block_t(128, 3, 32, interpret=True)
    assert len(kp._BLOCK_CACHE) == 2
    # same (S, n_pairs) with a different target width is a distinct class
    kp.best_block_t(64, 7, 512, interpret=True)
    assert len(kp._BLOCK_CACHE) == 3
    # the heuristic never exceeds its VMEM budget even when no candidate
    # covers T: S=1024 forces the last *fitting* candidate, not an overflow
    assert kp.best_block_t(1024, 2, 512, interpret=True) == 128
    # autotuned choices must produce identical numerics
    rng = np.random.default_rng(0)
    q = rng.uniform(-1, 1, (2, 64)).astype(np.float32)
    xs = rng.uniform(-1, 1, (2, 64, 3)).astype(np.float32)
    xt = rng.uniform(-1, 1, (2, 40, 3)).astype(np.float32)
    got = np.asarray(kp.p2p_pallas(q, xs, xt, interpret=True, block_t=256))
    ref = np.asarray(kp.p2p_pallas(q, xs, xt, interpret=True, block_t=128))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)


# ----------------------------------------------- deprecated use_pallas -----
def test_session_rejects_conflicting_kernel_flags():
    x, q = _problem(n=150, dist="cube")
    geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=48))
    with pytest.raises(ValueError, match="use_kernels only"):
        FMMSession(geo, use_kernels=True, use_pallas=False)


def test_use_pallas_flag_warns_once_and_is_honored():
    x, q = _problem(n=150, dist="cube")
    fmm._USE_PALLAS_WARNED.clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        p1 = fmm.fmm_potential(x, q, ncrit=64, use_pallas=True)
        p2 = fmm.fmm_potential(x, q, ncrit=64, use_pallas=True)
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)
           and "use_pallas" in str(w.message)]
    assert len(dep) == 1                           # once per call site
    assert "use_kernels" in str(dep[0].message)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_allclose(
        p1, fmm.fmm_potential(x, q, ncrit=64, use_kernels=True), rtol=2e-5,
        atol=2e-6)


# The hypothesis property sweep lives in test_engine_property.py (module-
# level importorskip would skip this whole file where hypothesis is absent).


def test_m2l_accumulate_chunked_matches_one_shot(monkeypatch):
    """Deployment-size far fields run M2L in fixed chunks of pairs; a
    chunked accumulation (ragged last chunk included) must give the
    one-shot sum up to f32 scatter order."""
    import jax.numpy as jnp
    from repro.core.engine import m2l as m2l_mod
    from repro.core.engine.m2l import m2l_accumulate
    ops = get_operators(4)
    rng = np.random.default_rng(3)
    C, n = 40, 1000
    M = jnp.asarray(rng.normal(size=(C, ops.nk)), jnp.float32)
    src = jnp.asarray(rng.integers(0, C, n))
    tgt = jnp.asarray(rng.integers(0, C, n))
    d = jnp.asarray(rng.normal(size=(n, 3)) + 4.0, jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    L0 = jnp.zeros_like(M)
    whole = np.asarray(m2l_accumulate(ops, M, L0, src, tgt, d, mask))
    monkeypatch.setattr(m2l_mod, "M2L_CHUNK", 128)
    chunked = np.asarray(m2l_accumulate(ops, M, L0, src, tgt, d, mask))
    np.testing.assert_allclose(chunked, whole, rtol=1e-5,
                               atol=1e-6 * np.abs(whole).max())


@pytest.mark.parametrize("n_live", [50, 256, 300, 512],
                         ids=["inside_first_chunk", "whole_chunks",
                              "ragged_last_chunk", "fills_table"])
def test_m2l_accumulate_runs_live_chunks_only(monkeypatch, n_live):
    """Given the table's live row count (traced), the loop runs only the
    chunks that hold live rows: the same sums as the whole-table loop over
    masked padding, and rows past those chunks never reach L, even with
    mask 1 and operators ~1e16 — so their steps are skipped, not masked."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import m2l as m2l_mod
    from repro.core.engine.m2l import m2l_accumulate, m2l_rows
    monkeypatch.setattr(m2l_mod, "M2L_CHUNK", 128)
    ops = get_operators(4)
    rng = np.random.default_rng(7)
    C, n = 40, 512                               # four chunks
    M = jnp.asarray(rng.normal(size=(C, ops.nk)), jnp.float32)
    L0 = jnp.zeros_like(M)
    src = rng.integers(0, C, n)
    tgt = rng.integers(0, C, n)
    d = (rng.normal(size=(n, 3)) + 4.0).astype(np.float32)
    mask = np.ones(n, np.float32)
    # padding as build_engine_tables makes it: row 0 repeated, mask 0
    src[n_live:], tgt[n_live:], d[n_live:], mask[n_live:] = \
        src[0], tgt[0], d[0], 0.0
    whole = np.asarray(m2l_accumulate(ops, M, L0, src, tgt, d, mask))
    live = jax.jit(lambda s, t, dd, m, k: m2l_accumulate(
        ops, M, L0, s, t, dd, m, k))
    np.testing.assert_array_equal(
        np.asarray(live(src, tgt, d, mask, jnp.int32(n_live))), whole)

    end = m2l_rows(n_live, n)                    # rows the loop runs
    assert end == -(-n_live // 128) * 128
    mask[end:], d[end:] = 1.0, d[end:] * 1e-3
    np.testing.assert_array_equal(
        np.asarray(live(src, tgt, d, mask, jnp.int32(n_live))), whole)
    if end < n:                                  # they would, if run
        poisoned = np.asarray(m2l_accumulate(ops, M, L0, src, tgt, d, mask))
        assert np.abs(poisoned - whole).max() > 1e6 * np.abs(whole).max()


def test_p2p_rows_vals_chunked_matches_one_shot(monkeypatch):
    """The sharded engine's jnp near field runs gathered rows in chunks;
    rows are independent, so chunking (ragged last chunk included) must
    not change a value beyond f32 rounding."""
    import jax.numpy as jnp
    from repro.core.engine import p2p as p2p_mod
    from repro.core.engine.p2p import p2p_rows_vals
    rng = np.random.default_rng(4)
    N, B, wt, ws = 300, 77, 16, 8
    x = jnp.asarray(rng.uniform(size=(N, 3)), jnp.float32)
    q = jnp.asarray(rng.uniform(-1, 1, N), jnp.float32)
    t_idx = jnp.asarray(rng.integers(0, N, (B, wt)))
    s_idx = jnp.asarray(rng.integers(0, N, (B, ws)))
    s_valid = jnp.asarray(rng.integers(0, 2, (B, ws)).astype(bool))
    mask = jnp.asarray(rng.integers(0, 2, B), jnp.float32)
    whole = np.asarray(p2p_rows_vals(x, x, q, t_idx, s_idx, s_valid, mask))
    monkeypatch.setattr(p2p_mod, "P2P_ROW_CHUNK", 16)
    chunked = np.asarray(p2p_rows_vals(x, x, q, t_idx, s_idx, s_valid, mask))
    assert chunked.shape == (B, wt)
    np.testing.assert_allclose(chunked, whole, rtol=1e-6, atol=1e-6)

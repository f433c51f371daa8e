"""Streaming P2P megakernel (repro.kernels.p2p_stream + the engine's
unified stream tables): interpret-mode BITWISE parity against the gathered
`p2p_pallas` kernel on identical slabs, stream-table invariants (ragged
width classes, dead padding tiles, partial tails, contiguity fallback),
engine equivalence stream-vs-gathered on both dispatch routes, the
donation-vs-residency contract for the stream index tables, and the warm
fused streaming evaluate pinned at exactly ONE entry-computation launch."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.hlo_walk import count_entry_launches
from repro.core.api import (FMMSession, PartitionSpec, execute_geometry,
                            plan_geometry)
from repro.core.distributions import make_distribution
from repro.core.engine import (DeviceEngine, ExecutableCache,
                               build_p2p_stream_tables, default_p2p_stream)
from repro.core.engine.schedules import stream_out_index
from repro.core.engine.p2p import (p2p_stream_gathered, p2p_stream_vals,
                                   stream_payload)
from repro.kernels.p2p import p2p_pallas
from repro.kernels.p2p_stream import p2p_stream, src_width

RTOL, ATOL = 1e-6, 2e-5          # x64 engine tolerances
F32_RTOL, F32_ATOL = 1e-4, 1e-4


def _problem(n=600, seed=5, qseed=6, dist="sphere"):
    """Boundary distribution: surface-heavy leaves give ragged source width
    classes (the paper's boundary-distribution regime, and the stress case
    for the unified stream table)."""
    x = make_distribution(dist, n, seed=seed)
    q = np.random.default_rng(qseed).uniform(-1, 1, n)
    return x, q


def _stream_fixture(n=500, nparts=3, ncrit=32, block_t=128):
    x, q = _problem(n=n)
    geo = plan_geometry(x, q, PartitionSpec(nparts=nparts, ncrit=ncrit))
    eng = DeviceEngine(geo, use_kernels=False, fused=False, p2p_stream=False)
    stream = build_p2p_stream_tables(eng.tables.p2p_buckets, block_t)
    assert stream is not None
    payload = np.asarray(stream_payload(
        jnp.asarray(eng._x_pad), jnp.asarray(eng._q_pad), stream["pad"]))
    return geo, eng, stream, payload


# --------------------------------------------------------- bitwise parity --
def test_stream_kernel_bitwise_vs_gathered_pallas():
    """The pinned tentpole invariant: the streaming kernel (in-kernel slab
    DMA, double-buffered pipeline, interpret mode) is BITWISE-equal to
    `p2p_pallas` run on the very slabs the DMAs would fetch — the gather
    moved into the kernel must change no bit of the result.  The geometry
    provides ragged width classes, partial target tails (tgt_len < block_t)
    and dead padding tiles."""
    _, _, stream, payload = _stream_fixture()
    meta, trange = stream["meta"].reshape(-1, 4), stream["tgt_range"]
    bt, smax = stream["block_t"], stream["smax"]
    ws = src_width(smax)
    live = meta[:, 2] > meta[:, 1]
    assert live.any() and (~live).any()          # dead padding tiles exist
    assert (trange[1, live] - trange[0, live] < bt).any()   # partial tails
    assert len({int(r) for r in meta[live, 2] - meta[live, 1]}) > 1
    assert (meta[live, 1] > 0).any()             # unaligned source runs
    assert (trange[0, live] > meta[live, 3]).any()   # unaligned target runs
    assert np.all(meta[:, [0, 3]] % 128 == 0)    # every DMA tile-aligned

    out = np.asarray(p2p_stream(jnp.asarray(stream["meta"]),
                                jnp.asarray(payload),
                                block_t=bt, smax=smax, n_buffers=2,
                                interpret=True))

    # gathered reference: identical slab values through p2p_pallas
    m = meta[live]
    lanes = np.arange(ws)
    in_run = (lanes[None, :] >= m[:, 1:2]) & (lanes[None, :] < m[:, 2:3])
    qs = np.where(in_run, payload[3][m[:, 0:1] + lanes[None, :]], 0.0)
    xs = payload[:3, m[:, 0:1] + lanes[None, :]].transpose(1, 2, 0)
    xt = payload[:3, m[:, 3:4] + np.arange(bt)[None, :]].transpose(1, 2, 0)
    ref = np.asarray(p2p_pallas(jnp.asarray(qs, jnp.float32),
                                jnp.asarray(xs), jnp.asarray(xt),
                                interpret=True, block_t=bt))
    assert np.array_equal(out[live].view(np.uint32), ref.view(np.uint32))
    assert np.all(out[~live] == 0.0)             # dead tiles: exact zeros

    # pipeline depth must not change a single bit either
    out3 = np.asarray(p2p_stream(jnp.asarray(stream["meta"]),
                                 jnp.asarray(payload),
                                 block_t=bt, smax=smax, n_buffers=3,
                                 interpret=True))
    assert np.array_equal(out3.view(np.uint32), out.view(np.uint32))


def test_stream_gathered_xla_path_matches_kernel():
    """`p2p_stream_gathered` (the use_kernels=False streaming route) runs
    the same tile expression on the same slabs — allclose to the kernel at
    f32 tolerances (reduction order may differ across XLA programs)."""
    _, _, stream, payload = _stream_fixture()
    kern = np.asarray(p2p_stream(jnp.asarray(stream["meta"]),
                                 jnp.asarray(payload),
                                 block_t=stream["block_t"],
                                 smax=stream["smax"], interpret=True))
    xla = np.asarray(p2p_stream_gathered(jnp.asarray(stream["meta"]),
                                         jnp.asarray(payload),
                                         block_t=stream["block_t"],
                                         smax=stream["smax"]))
    np.testing.assert_allclose(xla, kern, rtol=F32_RTOL, atol=F32_ATOL)


# ------------------------------------------------------- table invariants --
def test_stream_tables_cover_exactly_the_bucket_work():
    """Every live (tile, lane) must map to exactly the target-body slots the
    gathered buckets cover, with identical multiplicity — the accumulation
    is a scatter-add, so coverage equality IS value equality."""
    _, eng, stream, _ = _stream_fixture()
    out_idx, out_valid = stream_out_index(
        stream["meta"], stream["tgt_range"], stream["block_t"])
    got = {}
    for k in out_idx[out_valid]:
        got[int(k)] = got.get(int(k), 0) + 1
    want = {}
    for b in eng.tables.p2p_buckets:
        live = b["mask"] != 0.0
        for r in np.nonzero(live)[0]:
            for t in b["t_idx"][r][b["t_valid"][r]]:
                want[int(t)] = want.get(int(t), 0) + 1
    assert got == want


def test_stream_tables_fallback_on_non_contiguous_rows():
    """A bucket whose source ids are not a contiguous run (synthetic: a
    permuted gather) must refuse the stream path — correctness never
    depends on the fast path."""
    _, eng, _, _ = _stream_fixture()
    buckets = [dict(b) for b in eng.tables.p2p_buckets]
    b0 = buckets[0]
    s_idx = b0["s_idx"].copy()
    r = int(np.nonzero(b0["mask"] != 0.0)[0][0])
    if b0["s_valid"][r].sum() >= 2:
        s_idx[r, [0, 1]] = s_idx[r, [1, 0]]      # break the run
    else:
        s_idx[r, 0] += 7
    b0["s_idx"] = s_idx
    assert build_p2p_stream_tables(tuple(buckets), 128) is None
    assert build_p2p_stream_tables((), 128) is None   # no near field at all


def test_engine_falls_back_to_gathered_buckets(monkeypatch):
    """An engine asked to stream a geometry that cannot stream must fall
    back to the gathered buckets and still produce the right answer."""
    from repro.core import engine as eng_mod
    x, q = _problem(n=300)
    geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=32))
    monkeypatch.setattr(eng_mod, "build_p2p_stream_tables",
                        lambda buckets, bt: None)
    eng = DeviceEngine(geo, use_kernels=False, fused=False, p2p_stream=True)
    with pytest.warns(RuntimeWarning, match="gathered buckets"):
        phi = eng.evaluate()
    assert eng.p2p_stream is False and eng._stream is None
    np.testing.assert_allclose(phi, execute_geometry(geo),
                               rtol=F32_RTOL, atol=F32_ATOL)


# --------------------------------------------------- engine equivalence ----
def test_stream_engine_matches_gathered_engine_x64():
    """Per-phase engine, stream vs gathered near field, x64 device f64
    accumulation: tight-tolerance equivalence on both dispatch routes
    (XLA slab program and interpret-mode kernel)."""
    x, q = _problem(n=500, seed=15, qseed=16)
    geo = plan_geometry(x, q, PartitionSpec(nparts=3, ncrit=48))
    jax.config.update("jax_enable_x64", True)
    try:
        want = np.asarray(DeviceEngine(geo, use_kernels=False, fused=False,
                                       p2p_stream=False).evaluate_device())
        got_xla = np.asarray(DeviceEngine(geo, use_kernels=False, fused=False,
                                          p2p_stream=True).evaluate_device())
        got_kern = np.asarray(DeviceEngine(geo, use_kernels=True,
                                           interpret=True, fused=False,
                                           p2p_stream=True).evaluate_device())
    finally:
        jax.config.update("jax_enable_x64", False)
    np.testing.assert_allclose(got_xla, want, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(got_kern, want, rtol=RTOL, atol=ATOL)


def test_stream_session_matches_reference():
    """FMMSession(p2p_stream=True) end to end against the reference
    executor — the knob threads through api -> engine -> schedules."""
    x, q = _problem(n=400, seed=25, qseed=26)
    sess = FMMSession.from_points(x, q, PartitionSpec(nparts=2, ncrit=48),
                                  engine=True, use_kernels=False,
                                  fused=False, p2p_stream=True)
    assert sess.engine.p2p_stream is True
    np.testing.assert_allclose(sess.evaluate(),
                               execute_geometry(sess.geometry),
                               rtol=F32_RTOL, atol=F32_ATOL)


# ------------------------------------------------------- fused streaming ---
@pytest.fixture(scope="module")
def fused_stream():
    """One compiled fused streaming session + private cache, shared
    module-wide (every distinct shape class is an XLA compile)."""
    x, q = _problem(n=500, seed=35, qseed=36)
    spec = PartitionSpec(nparts=3, ncrit=48)
    cache = ExecutableCache()
    sess = FMMSession.from_points(x, q, spec, engine=True, fused=True,
                                  use_kernels=False, p2p_stream=True,
                                  exe_cache=cache)
    return {"x": x, "q": q, "spec": spec, "cache": cache, "sess": sess}


def test_warm_fused_stream_evaluate_is_one_launch(fused_stream):
    """Streaming near field inside the fused composite: warm evaluate stays
    exactly ONE entry-computation launch, the executable key carries the
    kernel variant, and the numerics still track the reference."""
    sess = fused_stream["sess"]
    phi = sess.evaluate()
    np.testing.assert_allclose(phi, execute_geometry(sess.geometry),
                               rtol=F32_RTOL, atol=F32_ATOL)
    eng = sess.engine
    n_before = len(eng.launch_log)
    eng.evaluate()                    # warm: second dispatch, same entry
    launches = eng.launch_log[n_before:]
    assert [kind for kind, _ in launches] == ["evaluate"]
    entry, tabs = eng._entries[("evaluate", False)]
    assert count_entry_launches(entry.hlo_text) == 1
    assert entry.calls >= 2
    assert entry.key[-1] == "stream"  # p2p_impl recorded in the shape key
    assert eng._stream is not None
    # no per-bucket gather tables were uploaded on the stream path
    assert "p2ps_meta" in tabs
    assert not any(k.startswith("p2p0") for k in tabs)


def test_second_stream_geometry_zero_recompiles(fused_stream):
    """A second same-shape-class geometry on the streaming path must be
    served from the executable cache with zero XLA compilations — the
    stream tables are part of the shape-class digest, so byte-identical
    points share the class."""
    cache = fused_stream["cache"]
    fused_stream["sess"].evaluate()
    stats0 = cache.stats()
    sess2 = FMMSession.from_points(
        fused_stream["x"].copy(), fused_stream["q"].copy(),
        fused_stream["spec"], engine=True, fused=True, use_kernels=False,
        p2p_stream=True, exe_cache=cache)
    phi2 = sess2.evaluate()
    assert cache.misses == stats0["misses"]
    assert cache.hits == stats0["hits"] + 1
    np.testing.assert_allclose(phi2, execute_geometry(sess2.geometry),
                               rtol=F32_RTOL, atol=F32_ATOL)


# ------------------------------------------------------ donation contract --
def test_stream_tables_never_donated(fused_stream):
    """The stream meta/index tables are DeviceMemo-resident frozen state —
    `_donatable` must refuse them exactly like every other index table
    (the engine.fused donation-vs-residency contract)."""
    eng = fused_stream["sess"].engine
    eng.evaluate()
    view = eng._aa(eng._stream["meta"])
    assert eng.memo.is_resident(view)
    with pytest.raises(TypeError, match="donate"):
        eng._donatable(view)
    view2 = eng._aa(eng._stream["tgt_range"])
    with pytest.raises(TypeError, match="donate"):
        eng._donatable(view2)


def test_stream_obs_counters():
    """The DMA-tile/launch counters and the p2p.stream span land in the
    flight recorder when enabled."""
    from repro import obs
    x, q = _problem(n=300, seed=45, qseed=46)
    geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=32))
    tr = obs.configure(enabled=True)
    try:
        obs.reset()
        eng = DeviceEngine(geo, use_kernels=False, fused=False,
                           p2p_stream=True)
        eng.evaluate()
        counters = obs.metrics_snapshot()["counters"]
        assert counters.get("p2p.stream.launches", 0) == 1
        assert counters.get("p2p.stream.builds", 0) == 1
        live = eng._stream["n_live_tiles"]
        assert counters.get("p2p.stream.tiles", 0) == live
        assert counters.get("p2p.stream.dma_tiles", 0) == 2 * live
        assert tr.spans("engine.p2p_stream")
    finally:
        obs.configure(enabled=False)


def test_default_p2p_stream_off_cpu():
    if jax.default_backend() == "cpu":
        assert default_p2p_stream() is False
        x, q = _problem(n=200)
        geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=48))
        assert DeviceEngine(geo, use_kernels=False).p2p_stream is False


def test_stream_kernel_chunked_table_bitwise(monkeypatch):
    """The tile table reaches SMEM one chunk at a time, and the slab
    pipeline restarts at each chunk: any chunking must give the same bits
    as one chunk over the whole table."""
    from repro.kernels import p2p_stream as stream_mod
    _, _, stream, payload = _stream_fixture()
    meta, pay = jnp.asarray(stream["meta"]), jnp.asarray(payload)
    kw = dict(block_t=stream["block_t"], smax=stream["smax"], interpret=True)
    monkeypatch.setattr(stream_mod, "STREAM_CHUNK", stream["n_tiles"])
    whole = np.asarray(p2p_stream(meta, pay, **kw))
    for chunk, nb in ((64, 2), (256, 3)):
        assert stream["n_tiles"] > chunk
        monkeypatch.setattr(stream_mod, "STREAM_CHUNK", chunk)
        part = np.asarray(p2p_stream(meta, pay, n_buffers=nb, **kw))
        assert np.array_equal(part.view(np.uint32), whole.view(np.uint32))


def test_measure_stream_warms_each_candidate_once():
    """The autotune sweep times every candidate several times; only its
    first measurement compiles and warms it, and the tables built for the
    sweep's other tilings are dropped once the choice is made."""
    x, q = _problem(n=400)
    geo = plan_geometry(x, q, PartitionSpec(nparts=2, ncrit=32))
    eng = DeviceEngine(geo, use_kernels=True, fused=False, p2p_stream=True)
    times = [eng._measure_stream(128, nb) for nb in (2, 2, 3)]
    assert all(np.isfinite(t) and t > 0 for t in times)
    assert eng._stream_warm == {(128, 2), (128, 3)}
    assert set(eng._stream_candidates) == {128}
    eng._stream_tables()
    assert eng._stream_warm == set() and eng._stream_candidates == {}

"""What the program tells a profile about itself: the fused evaluate's
device phases by name (`jax.named_scope` -> `hlo_walk.op_phases` ->
`FMMSession.device_phases`), the step's and the engine's host spans, and
the counters of padding and uploads, on toy sessions on the CPU."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.analysis.hlo_walk import (op_phases, parse_computations,
                                     top_level_ops)
from repro.core.api import FMMSession, PartitionSpec
from repro.core.distributions import make_distribution
from repro.core.engine import ExecutableCache
from repro.core.engine.m2l import M2L_CHUNK, m2l_rows

PHASES = {"fmm.upward", "fmm.m2l", "fmm.downward", "fmm.p2p",
          "fmm.p2p_scatter", "fmm.reorder"}
# instructions that carry the program's metadata but run no work of a phase
PLUMBING = (" parameter(", " tuple(", " get-tuple-element(", " bitcast(")


def _toy(p2p_stream: bool) -> FMMSession:
    x = make_distribution("sphere", 700, seed=11)
    q = np.random.default_rng(12).uniform(-1, 1, 700)
    return FMMSession.from_points(
        x, q, PartitionSpec(nparts=3, ncrit=48), engine=True, fused=True,
        use_kernels=False, p2p_stream=p2p_stream,
        exe_cache=ExecutableCache())


@pytest.fixture(scope="module", params=[False, True],
                ids=["gathered", "stream"])
def warm(request):
    sess = _toy(request.param)
    assert sess.device_phases() == {}          # nothing compiled yet
    sess.evaluate()
    return sess


def _hlo(sess) -> str:
    (entry, _tabs), = sess.engine._entries.values()
    return entry.hlo_text


def test_op_phases_nested_scopes_loop_and_scatter():
    def f(x, idx):
        with jax.named_scope("fmm.upward"):
            a = jnp.cos(x) + 1
        with jax.named_scope("fmm.m2l"):
            a = jax.lax.fori_loop(
                0, 5, lambda i, v: v.at[i].add(jnp.sin(v[i + 1])), a)
        with jax.named_scope("fmm.p2p"), jax.named_scope("other"):
            b = a * 2
            with jax.named_scope("fmm.p2p_scatter"):
                c = jnp.zeros(16).at[idx].add(b)
        return c[::-1]                      # no phase

    text = jax.jit(f).lower(jnp.ones(16), jnp.arange(16)).compile().as_text()
    ph = op_phases(text)
    assert set(ph.values()) == {"fmm.upward", "fmm.m2l", "fmm.p2p",
                                "fmm.p2p_scatter"}
    comps = parse_computations(text)

    def named(op):
        return {ln.split(" = ")[0].split()[-1].lstrip("%"): ln
                for lines in comps.values() for ln in lines
                if f" {op}(" in ln}

    (loop, loop_line), = named("while").items()
    assert ph[loop] == "fmm.m2l"
    assert {ph[s] for s in named("scatter")} == {"fmm.p2p_scatter"}
    assert all(r not in ph for r in named("reverse"))
    # the loop body's ops are timed inside the loop's op, not beside it
    body = re.search(r"body=%?([\w.\-]+)", loop_line).group(1)
    body_ops = {ln.split(" = ")[0].split()[-1].lstrip("%")
                for ln in comps[body]}
    assert {ph.get(n) for n in body_ops} >= {"fmm.m2l"}
    top = top_level_ops(text)
    assert top and not body_ops & top
    assert top_level_ops("") == set() and op_phases("") == {}


def test_fused_program_names_every_phase(warm):
    """Every instruction of the fused evaluate that carries the program's
    metadata belongs to an `fmm.*` phase, apart from plumbing."""
    text = _hlo(warm)
    ph = op_phases(text)
    want = PHASES | ({"fmm.m2p"} if warm.engine.tables.m2p["b"].shape[0]
                     else set())
    assert set(ph.values()) == want
    loose = [ln.strip() for ln in text.splitlines()
             if 'op_name="jit(fused)' in ln and " = " in ln
             and not any(k in ln for k in PLUMBING)
             and ln.strip().split(" = ")[0].split()[-1].lstrip("%")
             not in ph]
    assert loose == []


def test_device_phases_is_the_top_level_map(warm):
    dp = warm.device_phases()
    text = _hlo(warm)
    assert dp and set(dp) <= top_level_ops(text)
    assert dp == {k: v for k, v in op_phases(text).items() if k in dp}
    assert PHASES <= set(dp.values())


def test_counters_of_a_matvec_call(warm):
    """One call of a boundary solver's matvec (fresh charges, then the
    fused evaluate): the M2L rows and stream tiles the launch runs, and
    the payload it uploads, counted where they happen."""
    eng = warm.engine
    geo = warm.geometry
    q2 = -np.asarray(geo.q0)
    obs.configure(enabled=True)
    warm.step(geo.x0, q2)
    warm.evaluate()
    c = obs.metrics_snapshot()["counters"]
    mask = eng.tables.m2l["mask"]
    assert c["m2l.pairs_live"] == np.count_nonzero(mask) > 0
    n_live = int(eng.tables.m2l["n_live"])
    assert c["m2l.pairs_padded"] == m2l_rows(n_live, len(mask)) == len(mask)
    P, nmax = eng.tables.n_parts, eng.tables.n_bodies_max
    x_pad = np.zeros((P, nmax, 3), np.float32)
    q_pad = np.zeros((P, nmax), np.float32)
    assert c["engine.h2d_bytes"] == x_pad.nbytes + q_pad.nbytes
    if eng._stream is not None:
        assert c["p2p.stream.tiles"] == eng._stream["n_live_tiles"]
        assert c["p2p.stream.grid_tiles"] == eng._stream["n_tiles"]
    else:
        assert "p2p.stream.grid_tiles" not in c
    assert m2l_rows(M2L_CHUNK + 1, M2L_CHUNK + 1) == 2 * M2L_CHUNK


@pytest.mark.parametrize("n_live,n,rows", [
    (100, M2L_CHUNK, M2L_CHUNK),
    (M2L_CHUNK + 1, 4 * M2L_CHUNK, 2 * M2L_CHUNK),
    (4 * M2L_CHUNK, 4 * M2L_CHUNK, 4 * M2L_CHUNK)],
    ids=["one_chunk_table", "ragged_live_count", "full_envelope"])
def test_m2l_rows_counts_the_rows_the_loop_runs(n_live, n, rows):
    """One rule for the loop's trip count and the `m2l.pairs_padded`
    counter: a table of one chunk runs whole, a longer one only the
    chunks that hold its live rows."""
    assert m2l_rows(n_live, n) == rows
    assert m2l_rows(jnp.int32(n_live), n) == rows      # traced, as the loop


def test_counters_of_a_multi_chunk_table(monkeypatch):
    """With chunks smaller than the toy's M2L table, the counter reads
    the live chunks the fused loop runs, and the sums stay the same."""
    from repro.core.engine import m2l as m2l_mod
    whole = _toy(p2p_stream=False)
    want = whole.evaluate()
    monkeypatch.setattr(m2l_mod, "M2L_CHUNK", 4)
    sess = _toy(p2p_stream=False)
    m2l = sess.engine.tables.m2l
    n_live, n = int(m2l["n_live"]), len(m2l["mask"])
    assert 4 < n_live <= n - 4                   # a chunk or more skipped
    obs.configure(enabled=True)
    phi = sess.evaluate()
    c = obs.metrics_snapshot()["counters"]
    assert c["m2l.pairs_live"] == n_live
    assert c["m2l.pairs_padded"] == -(-n_live // 4) * 4
    np.testing.assert_allclose(phi, want, rtol=1e-5, atol=1e-5)


def test_step_and_engine_spans(warm):
    """The host stages of a step and the evaluate's upload and fetch are
    spans of their own (each a `repro.*` profiler annotation)."""
    geo = warm.geometry
    tr = obs.configure(enabled=True)
    warm.step(geo.x0, -np.asarray(geo.q0))
    warm.evaluate()
    names = [s.name for s in tr.spans()]
    for name in ("step.check", "step.drift", "step.advance", "step.restack",
                 "engine.upload", "engine.fetch"):
        assert names.count(name) == 1, name
    step = tr.spans("session.step")[0]
    assert {s.parent for s in tr.spans() if s.name.startswith("step.")} \
        == {step.sid}

"""The benchmark harness on the CPU, at a few thousand bodies: the result
line, lookup by file name, traffic made from the seed alone, the float64
reference, and the refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchtools import BENCH, ROOT, cells, harness, run, small_root

import reference  # noqa: E402
import workload  # noqa: E402

SPEC = harness.load_spec(ROOT)
CELLS = cells()
CONFIG_FILES = [c["file"] for c in SPEC["configs"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell", CELLS)
def test_result_line(root, cell):
    out = run(root, cell)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert "breakdown" not in out
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    names = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                     "end_to_end")}
    # the CPU reports no device memory, so peak_hbm_gb is left out here
    assert set(out["metrics"]) == names - {"peak_hbm_gb"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    dev = out["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert {"kind", "memory_peak_bytes"} <= set(dev)
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(out)


def test_every_spec_entry_has_its_files():
    spec = harness.load_spec(ROOT)
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["limits"]["scaled_rms_error"] > 0
    for w in spec["workloads"]:
        harness.load_cell(ROOT, w["name"])
    for m in spec["per_layer"]:
        assert hasattr(harness.load_reader(ROOT, m["name"]), "read")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")


def test_new_cell_is_files_only(tmp_path):
    """A configuration, a traffic mix and a per-layer metric that exist
    only as new files are found by name and run."""
    root = small_root(tmp_path)
    spec = harness.load_spec(root)
    cfg = json.loads((ROOT / spec["configs"][0]["file"]).read_text())
    cfg.update(name="dummy-cfg")
    cfg["deployment"].update(bodies=1500, distribution={
        "kind": "ellipsoid_surface", "axes": [2.0, 1.0, 0.5]})
    (root / "bench/configs/dummy-cfg.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/dummy-mix.json").write_text(json.dumps(
        {"why": "test", "update": "charges", "check_targets": 50}))
    (root / "bench/metrics/dummy_calls.py").write_text(
        "def prepare(session):\n    return session.geometry.n\n\n"
        "def read(run):\n    return run.calls + 0 * run.prepared"
        "['dummy_calls']\n")
    spec["configs"].append({"name": "dummy-cfg", "source": "test",
                            "file": "bench/configs/dummy-cfg.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy-cfg.dummy-mix",
                              "config": "dummy-cfg", "traffic": "dummy-mix",
                              "chips": 1, "why": "test"})
    spec["per_layer"] = [{"name": "dummy_calls", "unit": "count",
                          "better": "lower", "source": "program_counter",
                          "layer": "test", "moves": "call_ms"}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    _, cell, config, mix = harness.load_cell(root, "dummy-cfg.dummy-mix")
    assert config["deployment"]["bodies"] == 1500
    assert mix["update"] == "charges"
    out = run(root, "dummy-cfg.dummy-mix")
    assert out["correct"] is True and len(out["checks"]) == 1
    mod = harness.load_reader(root, "dummy_calls")
    r = harness.Run(cell=cell, config=config, mix=mix, evaluate_s=[1, 2],
                    prepared={"dummy_calls": 7})
    assert mod.read(r) == 2


@pytest.mark.parametrize("cfg", CONFIG_FILES)
def test_traffic_is_made_from_the_seed(cfg):
    """The configuration fixes the positions; the seed draws the charges
    and the checked targets, the same for the same seed."""
    deploy = json.loads((ROOT / cfg).read_text())
    deploy = dict(deploy["deployment"], bodies=3000)
    mix = json.loads((BENCH / "traffic" / "matvec.json").read_text())
    seed = 2 ** 31 + 12345            # the driver's seeds are that large
    x1, q1 = workload.bodies(deploy, seed)
    x2, q2 = workload.bodies(deploy, seed)
    x3, q3 = workload.bodies(deploy, seed + 1)
    assert np.array_equal(x1, x2) and np.array_equal(q1, q2)
    assert np.array_equal(x1, x3) and not np.array_equal(q1, q3)
    x4, _ = workload.bodies(dict(deploy, geometry_seed=7), seed)
    assert not np.array_equal(x1, x4)
    t1 = workload.Traffic(mix, deploy, seed, x1)
    t2 = workload.Traffic(mix, deploy, seed, x2)
    t3 = workload.Traffic(mix, deploy, seed + 1, x3)
    assert np.array_equal(t1.targets, t2.targets)
    assert not np.array_equal(t1.targets, t3.targets)
    assert np.array_equal(t1.charges(3), t2.charges(3))
    assert not np.array_equal(t1.charges(3), t1.charges(4))
    assert not np.array_equal(t1.charges(3), t3.charges(3))
    assert len(np.unique(t1.targets)) == len(t1.targets)


def test_reference_matches_brute_force():
    g = np.random.default_rng(3)
    x = g.normal(size=(300, 3))
    Q = g.uniform(-1, 1, (300, 2))
    tg = np.array([0, 17, 299])
    want = np.array([[sum(Q[j, c] / np.linalg.norm(x[i] - x[j])
                          for j in range(300) if j != i) for c in range(2)]
                     for i in tg])
    got = reference.potential(x, Q, tg, threads=2)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(reference.potential(x, Q[:, 1], tg),
                               want[:, 1], rtol=1e-12)
    scale = np.array([[np.sqrt(sum(Q[j, c] ** 2
                                   / np.sum((x[i] - x[j]) ** 2)
                                   for j in range(300) if j != i))
                       for c in range(2)] for i in tg])
    phi, s = reference.sums(x, Q, tg, threads=2)
    np.testing.assert_allclose(phi, want, rtol=1e-12)
    np.testing.assert_allclose(s, scale, rtol=1e-12)
    assert reference.rel_l2(want[:, 0], want[:, 0]) == 0.0
    assert reference.scaled_rms(want[:, 0], want[:, 0], scale[:, 0]) == 0.0
    e = (0.01 * want[:, 0]) / scale[:, 0]
    assert np.isclose(reference.scaled_rms(want[:, 0] * 1.01, want[:, 0],
                                           scale[:, 0]),
                      np.sqrt(np.mean(e * e)))
    # a potential 10% off fails the sphere's limit
    limit = json.loads((BENCH / "configs/sphere-1m.json").read_text())[
        "limits"]["scaled_rms_error"]
    assert reference.scaled_rms(want[:, 0] * 1.1, want[:, 0],
                                scale[:, 0]) > limit


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_tpu():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert "correct" not in r.stdout


def test_program_missing_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        harness._import_program(tmp_path)


def test_useful_pairs_count():
    """One partition whose one leaf holds every body: every pair is near
    field, so the useful pairs are N^2."""
    from repro.core.api import FMMSession, PartitionSpec
    x, q = workload.bodies({"bodies": 300, "distribution": {
        "kind": "ellipsoid_surface", "axes": [1, 1, 1]}, "geometry_seed": 1,
        "charges": {
        "law": "uniform", "low": -1, "high": 1}, "precision": "float32"}, 1)
    sess = FMMSession.from_points(x, q, PartitionSpec(nparts=1, ncrit=512),
                                  resilience=False)
    reader = harness.load_reader(ROOT, "p2p_gpairs_s")
    assert reader.prepare(sess) == 300 * 300
    sess = FMMSession.from_points(x, q, PartitionSpec(nparts=4, ncrit=16),
                                  resilience=False)
    assert 0 < reader.prepare(sess) < 300 * 300


def test_error_readers(root):
    """The traced run's accuracy readings are the largest call errors: the
    scaled RMS error that the check compared, and the relative L2 error."""
    scaled = harness.load_reader(ROOT, "scaled_rms_error")
    rel = harness.load_reader(ROOT, "rel_l2_error")
    _, cell, config, mix = harness.load_cell(root, CELLS[0])
    r = harness.Run(cell=cell, config=config, mix=mix,
                    errors={1: 3e-4, 2: 5e-4}, rel_l2={1: 2e-4, 2: 1e-4})
    assert scaled.read(r) == 5e-4 and rel.read(r) == 2e-4
    empty = harness.Run(cell=cell, config=config, mix=mix)
    assert scaled.read(empty) is None and rel.read(empty) is None


def test_priming_marker(tmp_path, monkeypatch):
    """A checkout is primed for a cell once its marker names the compile
    cache in use; a run given the marker reports it apart."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cell = CELLS[0]
    assert not harness.is_primed(cell, tmp_path)
    mark = harness.primed_marker(cell, tmp_path)
    mark.parent.mkdir(parents=True)
    mark.write_text(json.dumps({"compile_cache": str(tmp_path / "jax"),
                                "setup_s": 300.0}))
    assert harness.is_primed(cell, tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "other"))
    assert not harness.is_primed(cell, tmp_path)
    root = small_root(tmp_path / "checkout")
    out = run(root, cell, cold={"setup_s": 300.0})
    assert out["cold_setup"] == {"setup_s": 300.0}
    assert list(out)[-1] == "checks"

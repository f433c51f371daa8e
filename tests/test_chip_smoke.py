"""`chip_smoke.py` on the CPU: the one-chip phases at a small size with the
TPU knobs forced (engine, fused launch, Pallas kernels, streaming P2P) and
the kernels in interpret mode, so the smoke's control flow and the
tile-aligned stream tables are exercised without a chip; and the script
itself refusing to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TPU_KNOBS = dict(engine=True, fused=True, use_kernels=True, p2p_stream=True)


def test_one_chip_phases_interpret():
    lines = []
    out = chip_smoke.run_one_chip(
        3000, n_ref=300, session_kw=TPU_KNOBS, expect_interpret=True,
        log=lambda k, v: lines.append((k, v)))
    keys = [k for k, _ in lines]
    for key in ("N", "plan_s", "compile_s (cold evaluate, set-up)",
                "stream (block_t, n_buffers)", "warm_evaluate_ms",
                "exe_cache", "rel_l2_error", "step_ms",
                "rel_l2_error_after_step", "peak_bytes_in_use"):
        assert key in keys
    assert out["err"] <= chip_smoke.TOL
    assert out["err_after_step"] <= chip_smoke.TOL
    assert out["stream_params"][0] % 128 == 0


def test_one_chip_checks_interpret_mode():
    """The smoke must refuse a run whose kernels are not in the expected
    Pallas mode: on the CPU they interpret, so expecting compiled kernels
    fails before any phase."""
    with pytest.raises(chip_smoke.SmokeError, match="interpret"):
        chip_smoke.run_one_chip(300, n_ref=10, session_kw=TPU_KNOBS,
                                expect_interpret=False, log=lambda *a: None)


def test_script_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for var in ("REPRO_RESILIENCE", "REPRO_FAULTS"):
        env.pop(var, None)
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=str(ROOT))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_script_refuses_resilience_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_RESILIENCE="1")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=str(ROOT))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


_FOUR = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, %(root)r)
import jax
import chip_smoke
out = chip_smoke.run_four_chips(
    1500, n_ref=200, devices=jax.devices(),
    session_kw=dict(engine=True, fused=False, use_kernels=False),
    log=lambda *a: None)
assert set(out) == set(chip_smoke.PROTOCOLS), out
print("FOUR_OK")
"""


def test_four_chip_phase_virtual_devices():
    """The `--chips 4` phase on four virtual CPU devices: every protocol
    dispatches through ShardedEngine, delivers exactly the inter-rank
    bytes and matches the one-chip potential."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", _FOUR % {"root": str(ROOT)}],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR_OK" in r.stdout

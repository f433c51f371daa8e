#!/usr/bin/env python3
"""Chip smoke: drive the served FMM path once on a TPU and check the answer.

    python chip_smoke.py              # one chip, 10^6 boundary bodies
    python chip_smoke.py --chips 4    # LET exchange over four chips

One chip: the paper's boundary workload (`sphere`, 10^6 bodies, seed 42,
charges uniform in [-1, 1) from seed 0), partitioned by ORB into 8 parts
(theta 0.5, ncrit 64, order p = 4), served by `FMMSession.from_points` with
every TPU default taken and resilience off.  Phases, in order: plan; cold
`evaluate()` (set-up, includes compilation); two warm `evaluate()`s; one
within-slack `step(x + delta)`, which must not rebuild; `evaluate()` after
the step.  Checks: the kernels run compiled (not interpreted), the fused
launch and the streaming P2P kernel are on and its tables were built, warm
evaluates compile nothing, and the potential on 2,000 sampled targets is
within 3e-3 relative L2 of the float64 direct sum, before and after the
step.

`--chips 4`: the same configuration over a 1-D mesh of four TPU devices
(two partitions per rank), evaluated through `FMMSession(mesh=...)` for
the bulk, grain and hsdx exchange protocols and compared with the one-chip
`DeviceEngine` potential on the same geometry and with the float64
reference on 500 sampled targets.  The three protocols share the sharded
compute programs; each compiles only its own exchange program.

Any failed check or exception exits nonzero.  The last line of standard
output is one JSON object naming the device; it is printed only when every
phase passed.  Without a TPU the script exits nonzero before any phase.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

CONFIG = dict(distribution="sphere", n=1_000_000, seed=42, charge_seed=0,
              nparts=8, method="orb", theta=0.5, ncrit=64, p=4)
N_REF = 2000             # sampled targets checked against the f64 direct sum
N_REF_FOUR = 500         # --chips 4: every body is also checked against the
                         # one-chip potential; fewer f64 targets keep the
                         # four-chip call short
REF_SEED = 7
REF_THREADS = 6          # threads for the reference sum
REF_BLOCK = 1 << 17      # sources per reference task: ~50 MB of f64
                         # temporaries per thread (the one-chip machine's
                         # TPU process leaves little of its 40 GiB free)
STEP_SEED = 11
TOL = 3e-3               # relative L2 bound (examples/quickstart.py)
STEP_FRACTION = 0.25     # step displacement as a fraction of the least slack
PROTOCOLS = ("bulk", "grain", "hsdx")


class SmokeError(AssertionError):
    """A check of the smoke failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def _log(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def _import_repro():
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("chip_smoke: the repro sources are not next to "
                         "this script (expected src/repro)")
    sys.path.insert(0, str(ROOT / "src"))


def make_problem(n: int):
    import numpy as np
    from repro.core.distributions import make_distribution
    x = make_distribution(CONFIG["distribution"], n, seed=CONFIG["seed"])
    q = np.random.default_rng(CONFIG["charge_seed"]).uniform(-1, 1, n)
    return x, q


def make_spec():
    from repro.core.api import PartitionSpec
    return PartitionSpec(nparts=CONFIG["nparts"], method=CONFIG["method"],
                         theta=CONFIG["theta"], ncrit=CONFIG["ncrit"],
                         p=CONFIG["p"])


def reference(x, q, idx):
    """Float64 direct-sum potential at the sampled targets `x[idx]`: eight
    targets per task, summed over blocks of `REF_BLOCK` sources, over a
    thread pool (NumPy releases the interpreter lock in the O(N) passes)."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np
    from repro.core.fmm import direct_potential
    xt = x[idx]
    blocks = [slice(b, b + REF_BLOCK) for b in range(0, len(x), REF_BLOCK)]

    def task(i):
        return sum(direct_potential(x[b], q[b], x_tgt=xt[i:i + 8], chunk=8)
                   for b in blocks)

    # the host may report far more CPUs than this process may use
    workers = min(REF_THREADS, len(os.sched_getaffinity(0)))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        out = list(ex.map(task, range(0, len(xt), 8)))
    return np.concatenate(out)


def rel_l2(phi, ref, idx) -> float:
    import numpy as np
    return float(np.linalg.norm(phi[idx] - ref) / np.linalg.norm(ref))


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def run_one_chip(n: int = CONFIG["n"], *, n_ref: int = N_REF,
                 session_kw: dict | None = None,
                 expect_interpret: bool = False, log=_log) -> dict:
    """The one-chip phases and checks; raises SmokeError on a failed check.

    `session_kw` overrides `FMMSession` knobs (none on the chip: every
    default is the TPU's); `expect_interpret` is the Pallas mode the run
    must find (False on the chip)."""
    import jax
    import numpy as np
    from repro.core.api import FMMSession
    from repro.kernels import ops as kops

    check(kops.INTERPRET is expect_interpret,
          f"Pallas interpret mode is {kops.INTERPRET}, expected "
          f"{expect_interpret}")
    x, q = make_problem(n)
    idx = np.sort(np.random.default_rng(REF_SEED).choice(
        n, size=min(n_ref, n), replace=False))
    log("N", n)
    ref0 = reference(x, q, idx)

    t0 = time.perf_counter()
    sess = FMMSession.from_points(x, q, make_spec(), resilience=False,
                                  **(session_kw or {}))
    log("plan_s", time.perf_counter() - t0)
    log("host_rss", _host_rss())
    check(not sess.resilience.enabled, "resilience is on")
    check(sess.mesh is None and sess.engine_enabled,
          "the session does not dispatch through the DeviceEngine")
    eng = sess.engine
    check(eng.use_kernels, "engine.use_kernels is off")
    check(eng.fused, "the fused launch is off")
    check(eng.p2p_stream, "streaming P2P is off")
    check(eng.interpret is None or bool(eng.interpret) is expect_interpret,
          f"engine.interpret={eng.interpret}")

    phi, dt = _timed(sess.evaluate)
    log("compile_s (cold evaluate, set-up)", dt)
    log("host_rss", _host_rss())
    check(eng._stream is not None and eng.p2p_stream,
          "the stream tables were not built (gathered fallback taken)")
    bt, nb = eng._stream_params
    log("stream (block_t, n_buffers)", (bt, nb))
    log("stream tiles (live, padded)", (eng._stream["n_live_tiles"],
                                        eng._stream["n_tiles"]))
    entry, _ = eng._entries[("evaluate", bool(jax.config.jax_enable_x64))]
    check(entry.key[-1] == "stream",
          f"the fused evaluate runs the {entry.key[-1]!r} near field")
    cache0 = dict(sess.exe_cache_stats)
    check(np.all(np.isfinite(phi)), "non-finite potential")

    warm = []
    for _ in range(2):
        phi, dt = _timed(sess.evaluate)
        warm.append(dt * 1e3)
    log("warm_evaluate_ms", warm)
    cache1 = dict(sess.exe_cache_stats)
    log("exe_cache", cache1)
    check(cache1["misses"] == cache0["misses"],
          f"warm evaluates compiled {cache1['misses'] - cache0['misses']} "
          "executables")
    err0 = rel_l2(phi, ref0, idx)
    log("rel_l2_error", err0)
    check(err0 <= TOL, f"relative L2 error {err0} > {TOL}")

    slack = float(np.min(sess.geometry.slack))
    check(slack > 0, f"no MAC slack to step within ({slack})")
    step_dir = np.random.default_rng(STEP_SEED).normal(size=x.shape)
    step_dir /= np.linalg.norm(step_dir, axis=1, keepdims=True)
    x2 = x + STEP_FRACTION * slack * step_dir
    t0 = time.perf_counter()
    rep = sess.step(x2)
    log("step_ms", (time.perf_counter() - t0) * 1e3)
    log("step (rebuilt, refreshed)", (rep.rebuilt, rep.refreshed))
    check(rep.rebuilt == (), f"within-slack step rebuilt {rep.rebuilt}")
    check(len(rep.refreshed) > 0, "the step refreshed no partition")
    cache_step = dict(sess.exe_cache_stats)

    phi2, dt = _timed(sess.evaluate)
    log("evaluate_after_step_ms", dt * 1e3)
    check(sess.engine is eng and eng._stream is not None,
          "the step replaced the engine or its stream tables")
    err1 = rel_l2(phi2, reference(x2, q, idx), idx)
    log("rel_l2_error_after_step", err1)
    check(err1 <= TOL, f"relative L2 error after step {err1} > {TOL}")
    cache2 = dict(sess.exe_cache_stats)
    check(cache2["misses"] == cache_step["misses"],
          "the evaluate after the step compiled a new executable")
    log("peak_bytes_in_use", _peak_bytes(jax.devices()[0]))
    log("host_rss", _host_rss())
    return dict(err=err0, err_after_step=err1, warm_ms=warm,
                stream_params=(bt, nb), exe_cache=cache2)


def _host_rss() -> str:
    """This process's resident and peak resident host memory (Linux)."""
    try:
        with open("/proc/self/status") as f:
            kv = dict(line.split(":", 1) for line in f if ":" in line)
    except OSError:
        return "not reported"
    return (f"{kv.get('VmRSS', '?').strip()} "
            f"(peak {kv.get('VmHWM', '?').strip()})")


def _check_placement(dist, mesh_devices) -> None:
    """Every array the sharded engine feeds its programs is split over all
    the mesh's devices: none sits whole on one device."""
    import jax
    want = set(mesh_devices)
    for a in jax.tree.leaves((dist._x_pad, dist._q_pad, dist._part_tabs,
                              dist._rank_tabs)):
        if a.sharding.device_set != want:
            raise SmokeError(f"array {a.shape} {a.dtype} is placed on "
                             f"{sorted(d.id for d in a.sharding.device_set)}"
                             ", not split over the mesh")


def run_four_chips(n: int = CONFIG["n"], *, n_ref: int = N_REF,
                   devices=None, session_kw: dict | None = None,
                   log=_log) -> dict:
    """The four-chip phase: every exchange protocol through
    `FMMSession(mesh=...)`, compared with the one-chip engine
    (`session_kw` overrides its knobs; none on the chip)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.api import FMMSession, plan_geometry

    devs = list(jax.devices() if devices is None else devices)[:4]
    check(len(devs) == 4, f"{len(devs)} devices visible, 4 needed")
    mesh = Mesh(np.array(devs), ("ranks",))
    x, q = make_problem(n)
    idx = np.sort(np.random.default_rng(REF_SEED).choice(
        n, size=min(n_ref, n), replace=False))
    log("N", n)
    ref = reference(x, q, idx)
    t0 = time.perf_counter()
    geo = plan_geometry(x, q, make_spec())
    log("plan_s", time.perf_counter() - t0)
    log("host_rss", _host_rss())

    single = FMMSession(geo, resilience=False, **(session_kw or {}))
    phi1, dt = _timed(single.evaluate)
    log("one_chip_evaluate_s (cold)", dt)
    err1 = rel_l2(phi1, ref, idx)
    log("one_chip_rel_l2_error", err1)
    check(err1 <= TOL, f"one-chip relative L2 error {err1} > {TOL}")
    del single
    gc.collect()

    ppr = CONFIG["nparts"] // len(devs)
    rank = np.arange(CONFIG["nparts"]) // ppr
    agg = np.zeros((len(devs), len(devs)), np.int64)
    np.add.at(agg, (rank[:, None], rank[None, :]), geo.bytes_matrix)
    np.fill_diagonal(agg, 0)
    out = {}
    for proto in PROTOCOLS:
        sess = FMMSession(geo, mesh=mesh, dist_protocol=proto,
                          resilience=False)
        phi, dt = _timed(sess.evaluate)
        _check_placement(sess.dist, devs)
        st = sess.exchange_stats
        check(st["enabled"], f"{proto}: not dispatched through ShardedEngine")
        delivered = sess.dist.program(proto).delivered_bytes
        check(np.array_equal(delivered, agg),
              f"{proto}: delivered {delivered.tolist()} != inter-rank "
              f"bytes {agg.tolist()}")
        diff = float(np.max(np.abs(phi - phi1)) / np.max(np.abs(phi1)))
        err = rel_l2(phi, ref, idx)
        log(f"{proto}", dict(evaluate_s=dt, rounds=st["n_rounds"],
                             delivered_bytes=st["delivered_bytes"],
                             max_rel_diff_vs_one_chip=diff,
                             rel_l2_error=err))
        check(diff <= TOL, f"{proto}: differs from the one-chip potential "
                           f"by {diff}")
        check(err <= TOL, f"{proto}: relative L2 error {err} > {TOL}")
        out[proto] = dict(diff=diff, err=err)
        del sess
        gc.collect()
    log("peak_bytes_in_use", [_peak_bytes(d) for d in devs])
    log("host_rss", _host_rss())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    for var in ("REPRO_RESILIENCE", "REPRO_FAULTS"):
        if os.environ.get(var):
            print(f"chip_smoke: {var} is set; the smoke runs the plain path "
                  "only", file=sys.stderr)
            return 2
    _import_repro()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import configure_compile_cache
    _log("compile_cache", configure_compile_cache(ROOT))
    _log("device", f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    if args.chips == 4:
        tpus = [d for d in devs if d.platform == "tpu"]
        check(len(tpus) >= 4, f"{len(tpus)} TPU devices visible, 4 needed")
        run_four_chips(n_ref=N_REF_FOUR, devices=tpus[:4])
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Regression gate: a fresh `FMMSession.report()` vs pinned invariants.

    PYTHONPATH=src python -m repro.analysis.check_counters --out obs-artifacts

Builds a toy fused session plus a 4-virtual-device mesh session with
tracing enabled and checks the load-bearing counters the repo's guarantees
rest on (ISSUE 8 regression gate):

  1. warm fused evaluate is EXACTLY one entry-computation launch
     (`hlo_walk.count_entry_launches` over the compiled HLO);
  2. a second same-shape-class geometry triggers ZERO new XLA compilations
     (the executable-cache contract);
  3. the STREAMING near-field fused path (ISSUE 9) keeps both contracts:
     one entry launch with the kernel variant recorded in the executable
     key, and zero recompiles on a second same-shape-class geometry;
  4. every dist protocol's exchange program delivers exactly the
     rank-aggregated off-diagonal `GeometryPlan.bytes_matrix`;
  5. each protocol's `model_drift` (measured / LogGP exchange time) is
     finite and positive — the probe itself works;
  6. resilience invariants (ISSUE 10): resilience armed with no faults
     keeps the warm fused one-launch contract and a False `degraded` flag,
     and after a chaos drive every injected fault is either a counted
     fallback or a typed `ResilienceError` (the accounting identity).

Exits nonzero on any violation, printing each check; writes the full
`report()` JSON and a `jax.profiler` trace of the whole run (`profile/`:
the `.xplane.pb`, and a Perfetto `perfetto_trace.json.gz` in which the
`repro.*` spans sit beside the device ops) as artifacts under `--out` so a
CI failure ships the evidence.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main() -> int:
    # virtual devices must be configured before jax initializes a backend
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="directory for the report JSON and the profiler "
                         "trace")
    ap.add_argument("--n", type=int, default=800)
    ap.add_argument("--nparts", type=int, default=8)
    args = ap.parse_args()

    import numpy as np
    import jax

    from repro import obs
    obs.configure(enabled=True)
    if args.out:
        jax.profiler.start_trace(os.path.join(args.out, "profile"),
                                 create_perfetto_trace=True)

    from repro.analysis.hlo_walk import count_entry_launches
    from repro.core.api import FMMSession, PartitionSpec, plan_geometry
    from repro.core.engine.exe_cache import ExecutableCache

    failures: list[str] = []

    def check(ok: bool, label: str) -> None:
        print(f"{'ok  ' if ok else 'FAIL'} {label}")
        if not ok:
            failures.append(label)

    rng = np.random.default_rng(11)
    x = rng.normal(size=(args.n, 3))
    q = rng.uniform(-1, 1, args.n)
    spec = PartitionSpec(nparts=args.nparts, method="orb", ncrit=64)

    # --- fused single-device invariants (private cache: isolated counters) -
    cache = ExecutableCache()
    sess = FMMSession(plan_geometry(x, q, spec), engine=True, fused=True,
                      use_kernels=False, exe_cache=cache)
    sess.evaluate()                       # cold: compile + launch
    sess.evaluate()                       # warm: must be 1 entry launch
    eng = sess.engine
    (entry, _tabs) = eng._entries[("evaluate",
                                   bool(jax.config.jax_enable_x64))]
    check(count_entry_launches(entry.hlo_text) == 1,
          "warm fused evaluate compiles to exactly 1 entry computation")

    misses0 = cache.misses
    sess2 = FMMSession(plan_geometry(x.copy(), q.copy(), spec), engine=True,
                       fused=True, use_kernels=False, exe_cache=cache)
    sess2.evaluate()
    check(cache.misses == misses0,
          "second same-shape-class geometry -> 0 new XLA compilations "
          f"(misses {misses0} -> {cache.misses})")

    # --- streaming near-field invariants (ISSUE 9 gate) --------------------
    scache = ExecutableCache()
    s1 = FMMSession(plan_geometry(x, q, spec), engine=True, fused=True,
                    use_kernels=False, p2p_stream=True, exe_cache=scache)
    s1.evaluate()
    s1.evaluate()
    (sentry, _stabs) = s1.engine._entries[("evaluate",
                                           bool(jax.config.jax_enable_x64))]
    check(count_entry_launches(sentry.hlo_text) == 1,
          "warm fused STREAMING evaluate compiles to exactly 1 entry "
          "computation")
    check(sentry.key[-1] == "stream",
          "streaming executable key records the kernel variant "
          f"(key[-1]={sentry.key[-1]!r})")
    smisses0 = scache.misses
    s2 = FMMSession(plan_geometry(x.copy(), q.copy(), spec), engine=True,
                    fused=True, use_kernels=False, p2p_stream=True,
                    exe_cache=scache)
    s2.evaluate()
    check(scache.misses == smisses0,
          "second same-shape-class geometry on the STREAMING path -> 0 new "
          f"XLA compilations (misses {smisses0} -> {scache.misses})")

    # --- mesh-backed exchange invariants -----------------------------------
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4])
    if len(devs) < 4:
        print(f"note: only {len(devs)} device(s) visible; set XLA_FLAGS="
              "--xla_force_host_platform_device_count=4 before jax init")
    mesh = Mesh(devs, ("rk",))
    msess = FMMSession(plan_geometry(x, q, spec), mesh=mesh,
                       dist_protocol="bulk")
    rep = msess.report(measure_exchange=True, reps=2)

    geo = msess.geometry
    lay = msess.dist.layout
    expect = int(lay.rank_bytes.sum())      # zero diagonal by construction
    for name, st in rep["exchange"]["protocols"].items():
        check(st["delivered_bytes"] == expect,
              f"{name}: delivered_bytes {st['delivered_bytes']} == "
              f"rank off-diagonal bytes matrix {expect}")
        drift = st["model_drift"]
        check(np.isfinite(drift) and drift > 0,
              f"{name}: model_drift finite and positive ({drift:.3g})")
    inter = int(sum(geo.bytes_matrix[i, j]
                    for i in range(len(lay.part_rank))
                    for j in range(len(lay.part_rank))
                    if lay.part_rank[i] != lay.part_rank[j]))
    check(inter == expect,
          "rank_bytes aggregates GeometryPlan.bytes_matrix's inter-rank "
          f"entries exactly ({inter} == {expect})")

    # --- resilience invariants (ISSUE 10 gate) -----------------------------
    import warnings

    from repro.resilience import fallback as res_fb
    from repro.resilience import faults as res_faults
    from repro.resilience import ResilienceError, inject_faults

    res_faults.reset_stats()
    res_fb.reset_ledger()

    # 1. resilience armed with NO faults must not perturb the serving path:
    #    warm fused evaluate stays exactly one entry computation
    rcache = ExecutableCache()
    rsess = FMMSession(plan_geometry(x, q, spec), engine=True, fused=True,
                       use_kernels=False, exe_cache=rcache, resilience=True)
    rsess.evaluate()
    rsess.evaluate()
    (rentry, _rt) = rsess.engine._entries[("evaluate",
                                           bool(jax.config.jax_enable_x64))]
    check(count_entry_launches(rentry.hlo_text) == 1,
          "warm fused evaluate with resilience ENABLED (no faults) still "
          "compiles to exactly 1 entry computation")
    check(not rsess.resilience.degraded,
          "resilience enabled + no faults -> degraded flag stays False")
    check(res_faults.fired_total() == 0,
          "no armed plan -> zero faults fired")

    # 2. chaos accounting identity: drive a fallback AND a typed error, then
    #    every fired fault must be a counted fallback or a typed error
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        c1 = FMMSession(plan_geometry(x, q, spec), engine=True, fused=True,
                        use_kernels=False, exe_cache=ExecutableCache(),
                        resilience=True)
        with inject_faults("fused.launch"):
            c1.evaluate()
        check(c1.resilience.degraded
              and c1.resilience.fallbacks[0]["site"] == "fused.launch",
              "injected fused.launch RESOURCE_EXHAUSTED -> one counted "
              "ladder fallback")
        c2 = FMMSession(plan_geometry(x, q, spec), engine=False,
                        resilience=True)
        got_typed = False
        try:
            with inject_faults({"memo.upload": {"count": None}}):
                c2.evaluate()
        except ResilienceError as exc:
            got_typed = exc.site == "memo.upload"
        check(got_typed,
              "ladder exhaustion surfaces a typed ResilienceError naming "
              "the site")
    fired = res_faults.fired_total()
    absorbed = res_fb.fallback_total() + res_fb.typed_error_total()
    check(fired > 0 and fired == absorbed,
          f"chaos accounting: injected faults ({fired}) == counted "
          f"fallbacks + typed errors ({absorbed})")

    # --- artifacts ---------------------------------------------------------
    if args.out:
        jax.profiler.stop_trace()
        rep_path = os.path.join(args.out, "session_report.json")
        with open(rep_path, "w") as fh:
            json.dump(rep, fh, indent=1, sort_keys=True, default=str)
        print(f"wrote {rep_path} and the profiler trace under "
              f"{os.path.join(args.out, 'profile')}")

    if failures:
        print(f"\n{len(failures)} invariant violation(s)")
        return 1
    print("\nall counter invariants hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())

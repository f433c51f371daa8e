"""Compare the paper's four LET-exchange protocols on one problem.

    PYTHONPATH=src python examples/fmm_protocols.py

One `FMMSession` plans the geometry once (partitioning, local trees, batched
LET extraction, receiver traversals) and `sweep()` answers every protocol
from that single `GeometryPlan` — the potential is evaluated once and shared;
only the cheap communication schedules differ.  Prints the Table-2/Fig-7
style accounting: stages, messages, wire bytes, relay factor and LogGP model
time per protocol, for a boundary (sphere) distribution under hybrid-ORB
partitioning.

Running multi-device on CPU
---------------------------
The modeled schedules above also execute as *real* collective programs
(`repro.core.dist`) when the session gets a mesh.  No accelerator is
needed: JAX splits the host CPU into virtual devices.  Either export the
flag before python starts::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/fmm_protocols.py

or call `repro.launch.mesh.host_device_mesh(4)` BEFORE the first jax
computation (it sets the same flag, and raises a clear RuntimeError if the
backend already initialized with fewer devices).  Then::

    from repro.launch.mesh import host_device_mesh
    mesh = host_device_mesh(4)
    sess = FMMSession.from_points(x, q, PartitionSpec(nparts=8),
                                  mesh=mesh, dist_protocol="hsdx")
    phi = sess.evaluate()        # LET exchange runs over real wires
    print(sess.exchange_stats)   # measured moved/delivered bytes, rounds,
                                 # LogGP prediction for the same schedule

`dist_protocol` is one of "bulk" (one padded all_to_all), "grain"
(granularity-tuned ppermute rounds) or "hsdx" (hierarchical relay); all
three deliver bitwise-identical potentials to the single-device engine.
`main()` below runs the sweep when multiple devices are visible.

The streaming near-field knob
-----------------------------
`FMMSession(..., p2p_stream=True)` evaluates the leaf-leaf direct sum
through the unified stream table (`kernels/p2p_stream.py`): every P2P
width class concatenates into one tile grid whose source/target slabs are
gathered *inside* the kernel via double-buffered VMEM DMA, instead of one
XLA gather + launch per bucket.  The default (`p2p_stream=None`) turns it
on exactly when the backend is a TPU; on CPU the same table runs as one
XLA slab program when forced on (`use_kernels=False`), and geometries
whose bucket rows are not contiguous runs fall back to the gathered path
automatically.  See the "Streaming vs gathered P2P" paragraphs in
`core/plan.py` and ROADMAP.md for the selection and VMEM budget math.

The session flight recorder
---------------------------
Every tier is instrumented through `repro.obs`; turn it on before the
work you want recorded and read the result with one call::

    from repro import obs
    obs.configure(enabled=True)      # or REPRO_TRACE=1 in the environment
    sess = FMMSession.from_points(x, q, PartitionSpec(nparts=8), mesh=mesh)
    sess.evaluate()
    rep = sess.report()              # one structured dict
    rep["timings"]                   #   wall time per span (plan.*,
                                     #   engine.*, dist.evaluate, ...)
    rep["exchange"]["protocols"]     #   per-protocol measured exchange time
                                     #   vs LogGP -> "model_drift" (1.0 =
                                     #   the model still predicts the wire)
    rep["launches"]                  #   entry-computation counts per fused
                                     #   executable (warm evaluate == 1)
    rep["metrics"]["counters"]       #   memo/cache/upload/autotune counts

To see where the milliseconds went on a timeline, take a profiler trace
and load it in Perfetto::

    import jax
    with jax.profiler.trace("/tmp/fmm-profile", create_perfetto_trace=True):
        sess.evaluate()

then open https://ui.perfetto.dev and drop the `perfetto_trace.json.gz`
written under `/tmp/fmm-profile/plugins/profile/<run>/` onto it: every
span appears on the host thread as `repro.<name>` (`repro.plan.trees`,
`repro.engine.upload`, ...), and the device ops of the fused launch carry
their phase (`fmm.upward`, `fmm.m2l`, `fmm.p2p`, ...) in their `op_name`
metadata; `sess.device_phases()` maps each op to its phase.  Spans never
wait for the device, so the recorder keeps the fused path's async
dispatch.  `main()` below prints a per-protocol drift line when tracing is
on.
"""
import numpy as np

from repro.core.api import FMMSession, PartitionSpec
from repro.core.distributions import make_distribution
from repro.core.protocols import PROTOCOLS


def main():
    n, nparts = 4000, 8
    x = make_distribution("sphere", n, seed=1)
    q = np.ones(n) / n
    sess = FMMSession.from_points(x, q, PartitionSpec(nparts=nparts,
                                                      method="orb"))
    sweep = sess.sweep()
    print(f"{'protocol':<12}{'stages':>7}{'msgs':>7}{'wire MB':>9}"
          f"{'relay':>7}{'LogGP ms':>10}")
    for name in PROTOCOLS:
        res = sweep[name]
        st = res.schedule_stats
        print(f"{name:<12}{res.n_stages:>7}{st['n_msgs']:>7}"
              f"{st['wire_bytes']/1e6:>9.2f}{st['relay_factor']:>7.2f}"
              f"{res.loggp_time*1e3:>10.3f}")
    # every protocol delivered a schedule over the same LET volume, and the
    # shared potential matches the O(N^2) direct oracle
    from repro.core.fmm import direct_potential
    phi = sweep[PROTOCOLS[0]].phi
    ref = direct_potential(x, q)
    err = np.linalg.norm(phi - ref) / np.linalg.norm(ref)
    assert err < 3e-3, err
    print("all protocols served from one GeometryPlan "
          f"({sess.memo.misses} device uploads; rel L2 vs direct {err:.2e})")

    # --- real wires: with >1 visible device the exchange actually runs ----
    import jax
    ndev = jax.local_device_count()
    if ndev >= 2 and nparts % ndev == 0:
        from repro.launch.mesh import host_device_mesh
        mesh = host_device_mesh(ndev)
        for proto_name in ("bulk", "grain", "hsdx"):
            dsess = FMMSession(sess.geometry, mesh=mesh,
                               dist_protocol=proto_name)
            dphi = dsess.evaluate()
            st = dsess.exchange_stats
            ok = np.allclose(dphi, phi, rtol=1e-6, atol=2e-5)
            print(f"dist {proto_name:<6} D={ndev} rounds={st['n_rounds']:>2}"
                  f" moved={st['moved_bytes']/1e6:.3f}MB"
                  f" delivered={st['delivered_bytes']/1e6:.3f}MB"
                  f" parity={ok}")
        # flight recorder: measured exchange vs the LogGP model, one call
        from repro import obs
        if obs.enabled():
            dsess = FMMSession(sess.geometry, mesh=mesh)
            rep = dsess.report()       # measures exchanges when tracing is on
            for proto_name, st in rep["exchange"]["protocols"].items():
                print(f"drift {proto_name:<6}"
                      f" measured={st['measured_s']*1e3:.3f}ms"
                      f" loggp={st['loggp_s']*1e3:.3f}ms"
                      f" model_drift={st['model_drift']:.2f}")
        else:
            print("(REPRO_TRACE=1 adds measured-vs-LogGP model_drift via "
                  "session.report())")
    else:
        print(f"({ndev} visible device(s); export XLA_FLAGS="
              f"--xla_force_host_platform_device_count=4 before python to "
              f"run the LET exchange over real wires)")

    # --- resilience: inject a fault, watch the ladder absorb it -----------
    # A resilient session walks the degradation ladder (dist -> streaming
    # -> gathered -> xla_slab -> per_phase -> host f64 reference) instead
    # of raising: here the fused megakernel launch is killed with a
    # simulated RESOURCE_EXHAUSTED (the OOM an oversubscribed accelerator
    # raises), the session drops one rung, recomputes, and reports the
    # downgrade.  `REPRO_FAULTS="fused.launch:1"` arms the same plan from
    # the environment; `REPRO_RESILIENCE=1` flips the default on.
    import warnings
    from repro.resilience import inject_faults
    rsess = FMMSession(sess.geometry, engine=True, fused=True,
                       use_kernels=False, p2p_stream=False, resilience=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with inject_faults("fused.launch"):
            rphi = rsess.evaluate()
    blk = rsess.report()["resilience"]
    fb = blk["fallbacks"][0]
    assert np.allclose(rphi, phi, rtol=1e-6, atol=2e-5)
    print(f"chaos: killed {fb['site']} -> degraded {fb['from']!r} to "
          f"{fb['to']!r}, phi parity kept "
          f"(degraded={blk['degraded']}, rung={blk['rung']})")


if __name__ == "__main__":
    main()

"""Fig 8 (exchange): measured multi-device LET exchange vs LogGP prediction.

The three `repro.core.dist` collective programs — bulk `all_to_all`,
granularity-tuned `ppermute` rounds, and the HSDX relay — run over the real
devices in this process when an accelerator backend is present, and on the
CPU over virtual host devices in a subprocess (so this process keeps a
single device).  They are timed warm against `predicted_time`'s LogGP cost of the *same*
`protocols.Schedule` the program executes.  derived = measured vs modeled
ms, rounds, and moved/delivered wire bytes per protocol.

Results also land in benchmarks/BENCH_exchange.json (schema repro-bench-v1).

Toy-size smoke (CI):
  FIG8X_N=800 FIG8X_PARTS=8 FIG8X_REPS=5 python benchmarks/fig8_exchange.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

def exchange_rows(n: int, nparts: int, mesh, reps: int) -> list:
    """Time every dist protocol's exchange-only program over `mesh` (warm)
    against its LogGP prediction; one dict per protocol."""
    import time

    import numpy as np

    from repro.core.api import PartitionSpec, plan_geometry
    from repro.core.dist import DIST_PROTOCOLS, ShardedEngine

    rng = np.random.default_rng(5)
    x = rng.uniform(0, 1, (n, 3))
    x[:, 0] *= 4.0                       # stretched slab: HSDX must relay
    q = rng.uniform(-1, 1, n)
    geo = plan_geometry(x, q, PartitionSpec(nparts=nparts, method="morton",
                                            ncrit=64))
    eng = ShardedEngine(geo, mesh)
    rows = []
    for p in DIST_PROTOCOLS:
        fn = eng.exchange_fn(p)
        fn().block_until_ready()         # compile + first launch
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        st = eng.exchange_stats(p)
        rows.append(dict(protocol=p, measured_s=dt,
                         loggp_s=st["loggp_time"],
                         n_rounds=st["n_rounds"],
                         moved_bytes=st["moved_bytes"],
                         delivered_bytes=st["delivered_bytes"],
                         padded_wire_bytes=st["padded_wire_bytes"]))
    return rows


# CPU only: a child with virtual host devices, so this process keeps one
_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%(devices)d")
    import json
    from benchmarks.fig8_exchange import exchange_rows
    from repro.launch.mesh import host_device_mesh
    print(json.dumps(exchange_rows(%(n)d, %(nparts)d,
                                   host_device_mesh(%(devices)d),
                                   %(reps)d)))
""").strip()


def _run_child(n: int, nparts: int, devices: int, reps: int) -> list:
    script = _CHILD % dict(n=n, nparts=nparts, devices=devices, reps=reps)
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    paths = [os.path.join(here, "..", "src"), os.path.join(here, "..")]
    env["PYTHONPATH"] = os.pathsep.join(paths + [env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"fig8_exchange subprocess failed:\n"
                           f"{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(n: int | None = None, nparts: int | None = None,
        devices: int | None = None, reps: int | None = None):
    n = n or int(os.environ.get("FIG8X_N", 4000))
    nparts = nparts or int(os.environ.get("FIG8X_PARTS", 8))
    devices = devices or int(os.environ.get("FIG8X_DEVICES", 4))
    reps = reps or int(os.environ.get("FIG8X_REPS", 20))
    import jax
    if jax.default_backend() != "cpu":
        # real devices: run in this process, which already holds them — a
        # child could not open a chip its parent holds
        import numpy as np
        from jax.sharding import Mesh
        devs = jax.devices()[:devices]
        devices = len(devs)
        results = exchange_rows(n, nparts, Mesh(np.array(devs), ("ranks",)),
                                reps)
    else:
        results = _run_child(n, nparts, devices, reps)
    rows = []
    for r in results:
        derived = (f"loggp={r['loggp_s']*1e3:.3f}ms;"
                   f"rounds={r['n_rounds']};"
                   f"moved={r['moved_bytes']}B;"
                   f"delivered={r['delivered_bytes']}B;"
                   f"padded_wire={r['padded_wire_bytes']}B")
        rows.append((f"fig8_exchange_{r['protocol']}_D{devices}",
                     r["measured_s"] * 1e6, derived))
    from benchmarks.host_side import write_bench_json
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_exchange.json")
    write_bench_json(rows, path, meta=dict(n=n, nparts=nparts,
                                           devices=devices, reps=reps))
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}", flush=True)

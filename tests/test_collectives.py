"""Device-level collective patterns, run on 8 virtual host devices in a
subprocess (so the main test process keeps a single device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.core import collectives as C

    from repro.launch.mesh import make_mesh_compat as make_mesh

    mesh = make_mesh((8,), ("proc",))
    ok = {}

    x = jnp.arange(8 * 4 * 3, dtype=jnp.float32).reshape(8 * 4, 3)

    # ring all-gather == replicating the full array everywhere
    f = shard_map(lambda s: C.ring_all_gather(s, "proc"), mesh=mesh,
                  in_specs=P("proc"), out_specs=P("proc"))
    got = f(x)  # each shard returns the full (32,3); stacked -> (256, 3)
    ok["ring_all_gather"] = bool(np.allclose(np.asarray(got).reshape(8, 32, 3),
                                             np.broadcast_to(np.asarray(x), (8, 32, 3))))

    # ring reduce-scatter == psum then slice
    f = shard_map(lambda s: C.ring_reduce_scatter(s, "proc"), mesh=mesh,
                  in_specs=P(None), out_specs=P("proc"))
    got = np.asarray(f(x))
    ok["ring_reduce_scatter"] = bool(np.allclose(got, 8 * np.asarray(x)))

    # hierarchical all-reduce over a (pod=2, data=4) mesh == flat psum
    mesh2 = make_mesh((2, 4), ("pod", "data"))
    y = jnp.arange(8 * 5, dtype=jnp.float32).reshape(8, 5)
    f = shard_map(lambda s: C.hierarchical_all_reduce(s, "data", "pod"),
                  mesh=mesh2, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")))
    got = np.asarray(f(y))
    want = np.stack([np.asarray(y).sum(0)] * 8)
    ok["hierarchical_all_reduce"] = bool(np.allclose(got, want))

    # two-stage a2a == flat a2a over the combined axis
    z = jnp.arange(8 * 8 * 2, dtype=jnp.float32).reshape(8, 8, 2)
    f2 = shard_map(lambda s: C.two_stage_all_to_all(s[0], "data", "pod")[None],
                   mesh=mesh2, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")))
    flat = shard_map(lambda s: jax.lax.all_to_all(s[0], ("pod", "data"), 0, 0)[None],
                     mesh=mesh2, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")))
    ok["two_stage_a2a"] = bool(np.allclose(np.asarray(f2(z)), np.asarray(flat(z))))

    # overlapped all-gather matmul == plain (all_gather @ w)
    w = jnp.arange(3 * 7, dtype=jnp.float32).reshape(3, 7) / 10
    f = shard_map(lambda s: C.all_gather_matmul_overlapped(s, w, "proc"),
                  mesh=mesh, in_specs=P("proc"), out_specs=P("proc"))
    got = np.asarray(f(x)).reshape(8, 32, 7)
    want = np.asarray(x) @ np.asarray(w)
    ok["ag_matmul_overlap"] = bool(np.allclose(got, np.broadcast_to(want, (8, 32, 7)), atol=1e-4))

    # neighbor exchange: shift-by-1 ring
    f = shard_map(lambda s: C.neighbor_exchange(s, "proc", 1), mesh=mesh,
                  in_specs=P("proc"), out_specs=P("proc"))
    got = np.asarray(f(jnp.arange(8.0)[:, None])).ravel()
    ok["neighbor_exchange"] = bool(np.allclose(got, np.roll(np.arange(8.0), 1)))

    # hsdx grid exchange on a 2x2x2 grid: one stage delivers all 7 neighbors
    f = shard_map(lambda s: C.hsdx_grid_exchange(s[0], "proc", (2, 2, 2), stages=1)[None],
                  mesh=mesh, in_specs=P("proc"), out_specs=P("proc"))
    got = np.asarray(f(jnp.eye(8)[:, None, :]))      # payload = one-hot rank id
    # every rank must have received every other rank's payload in stage 0
    seen = got.reshape(8, 26, 8).argmax(-1)          # (8, 26) source ranks seen
    ok["hsdx_grid"] = all(set(seen[r]) >= (set(range(8)) - {r}) for r in range(8))

    print(json.dumps(ok))
""").strip()


@pytest.fixture(scope="module")
def collective_results():
    import json as _json
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", "import json\n" + _SCRIPT],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return _json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", [
    "ring_all_gather", "ring_reduce_scatter", "hierarchical_all_reduce",
    "two_stage_a2a", "ag_matmul_overlap", "neighbor_exchange", "hsdx_grid",
])
def test_collective(collective_results, name):
    assert collective_results[name], f"{name} failed on 8-device mesh"

"""Pallas-kernel parity microbench: wall time of the interpret-mode kernel
vs the jnp oracle on CPU (TPU timings require hardware; interpret mode
validates numerics + BlockSpec indexing).  derived = max |err| vs oracle.

Also sweeps the engine execution tier: per-width-class bucketed P2P (the
engine's Pallas route vs the jnp reference route, reporting per-bucket
speedup — >1x only on real device backends; interpret mode runs the kernel
as traced Python), full engine-vs-reference geometry evaluation, and the
ISSUE 9 streaming-vs-gathered near-field comparison (unified stream-table
slab program + in-kernel-gather Pallas kernel vs the per-bucket gathered
route, with scatter-accumulated max_err between the paths).
Environment knobs: ENGINE_BENCH_N (bodies, default 1500), ENGINE_BENCH_PARTS
(default 4).  As a script: ``python benchmarks/kernel_bench.py
[--stream-only] [--json=PATH|--no-json]`` — rows land in
benchmarks/BENCH_kernels.json with the common provenance header."""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def run():
    rows = []
    rng = np.random.default_rng(0)
    # p2p
    q = jnp.asarray(rng.uniform(-1, 1, (4, 128)), jnp.float32)
    xs = jnp.asarray(rng.uniform(-1, 1, (4, 128, 3)), jnp.float32)
    xt = jnp.asarray(rng.uniform(-1, 1, (4, 128, 3)), jnp.float32)
    us = _time(ops.p2p_blocked, q, xs, xt)
    err = float(jnp.max(jnp.abs(ops.p2p_blocked(q, xs, xt) - ref.p2p_ref(q, xs, xt))))
    rows.append(("kernel_p2p_4x128", us, f"max_err={err:.2e}"))
    # flash attention
    qa = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    ka = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    va = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    us = _time(lambda a, b, c: ops.flash_attention(a, b, c), qa, ka, va)
    err = float(jnp.max(jnp.abs(ops.flash_attention(qa, ka, va)
                                - ref.attention_ref(qa, ka, va))))
    rows.append(("kernel_flash_attn_gqa", us, f"max_err={err:.2e}"))
    # rwkv
    r = jnp.asarray(rng.normal(size=(2, 128, 64)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 64)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 64)) * 0.5, jnp.float32)
    w = jnp.asarray(rng.uniform(0.9, 0.999, (2, 128, 64)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(2, 64)) * 0.1, jnp.float32)
    s0 = jnp.zeros((2, 64, 64), jnp.float32)
    us = _time(lambda *a: ops.rwkv6_wkv(*a)[0], r, k, v, w, u, s0)
    y1, _ = ops.rwkv6_wkv(r, k, v, w, u, s0)
    y2, _ = ref.wkv_ref(r, k, v, w, u, s0)
    rows.append(("kernel_rwkv6_wkv", us, f"max_err={float(jnp.max(jnp.abs(y1-y2))):.2e}"))
    rows.extend(_bucketed_p2p_rows(rng))
    rows.extend(_stream_rows())
    rows.extend(_engine_rows())
    return rows


def _stream_rows():
    """Streaming vs gathered near field on one geometry — the ISSUE 9
    before/after.  Three routes over the SAME leaf-pair work: (a) the
    gathered per-width-class bucket path (one XLA gather + one launch per
    width class), (b) the unified stream table as one XLA slab program
    (`p2p_stream_gathered`, the use_kernels=False streaming route), (c) the
    streaming Pallas kernel with in-kernel slab DMA (interpret-mode
    emulation on CPU — the honest slower row; the kernel wins only on real
    device backends).  max_err compares the scatter-accumulated per-body
    sums, the quantity the engine actually consumes."""
    from repro.core.api import PartitionSpec, plan_geometry
    from repro.core.distributions import make_distribution
    from repro.core.engine import DeviceEngine, build_p2p_stream_tables
    from repro.core.engine.p2p import (p2p_bucket_vals, p2p_stream_gathered,
                                       stream_payload)
    from repro.core.engine.schedules import stream_out_index
    from repro.kernels.p2p_stream import p2p_stream
    n = int(os.environ.get("ENGINE_BENCH_N", "1500"))
    nparts = int(os.environ.get("ENGINE_BENCH_PARTS", "4"))
    x = make_distribution("sphere", n, seed=9)      # boundary distribution
    q = np.random.default_rng(10).uniform(-1, 1, n)
    geo = plan_geometry(x, q, PartitionSpec(nparts=nparts, ncrit=48))
    eng = DeviceEngine(geo, use_kernels=False, fused=False, p2p_stream=False)
    buckets = eng.tables.p2p_buckets
    stream = build_p2p_stream_tables(buckets, 128)
    if stream is None:
        return [(f"p2p_stream_vs_gathered_n{n}", 0.0,
                 "geometry cannot stream (non-contiguous rows)")]
    x_dev = jnp.asarray(eng._x_pad)
    q_dev = jnp.asarray(eng._q_pad)
    payload = stream_payload(x_dev, q_dev, stream["pad"])
    meta = jnp.asarray(stream["meta"])
    bt, smax = stream["block_t"], stream["smax"]

    def gathered():
        return [p2p_bucket_vals(x_dev, q_dev, b, use_kernels=False,
                                to_host=False) for b in buckets]

    xla_stream = jax.jit(lambda m, p: p2p_stream_gathered(
        m, p, block_t=bt, smax=smax))
    us_g = _time(lambda: gathered()[-1])
    us_x = _time(lambda: xla_stream(meta, payload))
    us_k = _time(lambda: p2p_stream(meta, payload, block_t=bt, smax=smax,
                                    n_buffers=2, interpret=ops.INTERPRET))

    # scatter-accumulate both paths to per-body sums for an honest max_err
    flat = payload.shape[1]
    phi_g = np.zeros(flat)
    for b, vals in zip(buckets, gathered()):
        v = np.asarray(vals)
        live = np.asarray(b["mask"]) != 0.0
        for r in np.nonzero(live)[0]:
            sel = b["t_valid"][r]
            np.add.at(phi_g, b["t_idx"][r][sel], v[r][sel])
    phi_s = np.zeros(flat)
    sv = np.asarray(p2p_stream_gathered(meta, payload, block_t=bt, smax=smax))
    out_idx, ok = stream_out_index(stream["meta"], stream["tgt_range"], bt)
    np.add.at(phi_s, out_idx[ok], sv[ok])
    err = float(np.max(np.abs(phi_g - phi_s)))

    kernel_mode = "interpret" if ops.INTERPRET else "compiled"
    return [
        (f"p2p_gathered_buckets_n{n}_p{nparts}", us_g,
         f"width_classes={len(buckets)}"),
        (f"p2p_stream_xla_n{n}_p{nparts}", us_x,
         f"tiles={stream['n_live_tiles']}/{stream['n_tiles']} "
         f"speedup_vs_gathered={us_g / max(us_x, 1e-9):.2f}x "
         f"max_err={err:.2e}"),
        (f"p2p_stream_kernel_{kernel_mode}_n{n}_p{nparts}", us_k,
         f"n_buffers=2 speedup_vs_gathered={us_g / max(us_k, 1e-9):.2f}x"),
    ]


def _bucketed_p2p_rows(rng):
    """Engine P2P bucket shapes: Pallas (autotuned block) vs jnp reference,
    per source-width class — the per-bucket speedup the engine dispatch
    trades on (expect < 1x under CPU interpret mode)."""
    from repro.core.fmm import _p2p_vals
    rows = []
    for P, S, T in ((16, 8, 64), (8, 64, 64), (4, 256, 64)):
        q = jnp.asarray(rng.uniform(-1, 1, (P, S)), jnp.float32)
        xs = jnp.asarray(rng.uniform(-1, 1, (P, S, 3)), jnp.float32)
        xt = jnp.asarray(rng.uniform(-1, 1, (P, T, 3)), jnp.float32)
        mask = jnp.ones((P,), jnp.float32)
        us_pl = _time(lambda a, b, c: ops.p2p_auto(a, b, c), q, xs, xt)
        us_ref = _time(lambda a, b, c: _p2p_vals(c, b, a, mask), q, xs, xt)
        err = float(jnp.max(jnp.abs(ops.p2p_auto(q, xs, xt)
                                    - _p2p_vals(xt, xs, q, mask))))
        rows.append((f"p2p_bucket_S{S}_pairs{P}", us_pl,
                     f"jnp_us={us_ref:.1f} speedup={us_ref / us_pl:.2f}x "
                     f"max_err={err:.2e}"))
    return rows


def _engine_rows():
    """Full engine-vs-reference sweep on one geometry (jnp engine path on
    CPU; the Pallas route needs hardware to win)."""
    from repro.core.api import DeviceMemo, PartitionSpec, execute_geometry, \
        plan_geometry
    from repro.core.distributions import make_distribution
    from repro.core.engine import DeviceEngine
    n = int(os.environ.get("ENGINE_BENCH_N", "1500"))
    nparts = int(os.environ.get("ENGINE_BENCH_PARTS", "4"))
    x = make_distribution("sphere", n, seed=5)
    q = np.random.default_rng(6).uniform(-1, 1, n)
    geo = plan_geometry(x, q, PartitionSpec(nparts=nparts, ncrit=48))
    memo = DeviceMemo()
    eng = DeviceEngine(geo, use_kernels=False)
    phi_ref = execute_geometry(geo, asarray=memo)    # warm both paths
    phi_eng = eng.evaluate()
    t0 = time.time()
    reps = 3
    for _ in range(reps):
        execute_geometry(geo, asarray=memo)
    us_ref = (time.time() - t0) / reps * 1e6
    t0 = time.time()
    for _ in range(reps):
        eng.evaluate()
    us_eng = (time.time() - t0) / reps * 1e6
    err = float(np.max(np.abs(phi_ref - phi_eng)))
    return [(f"engine_vs_reference_n{n}_p{nparts}", us_eng,
             f"ref_us={us_ref:.1f} speedup={us_ref / us_eng:.2f}x "
             f"max_err={err:.2e}")]


if __name__ == "__main__":
    try:
        from benchmarks.host_side import write_bench_json
    except ImportError:            # run as `python benchmarks/kernel_bench.py`
        from host_side import write_bench_json
    stream_only = False
    json_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_kernels.json")
    for a in sys.argv[1:]:
        if a == "--stream-only":   # CI interpret smoke: just the ISSUE 9
            stream_only = True     # streaming-vs-gathered comparison
        elif a.startswith("--json="):
            json_path = a.split("=", 1)[1]
        elif a == "--no-json":
            json_path = None
    out = _stream_rows() if stream_only else run()
    for name, us, derived in out:
        print(f"{name},{us:.1f},{derived}")
    if json_path:
        where = write_bench_json(out, json_path,
                                 meta={"module": "kernel_bench",
                                       "stream_only": stream_only})
        print(f"# wrote {where}", file=sys.stderr)

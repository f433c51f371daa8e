"""Bucketed P2P execution: every width class in one launch, Pallas-backed.

`plan.build_p2p_blocks` buckets leaf pairs by power-of-two source width per
(target, source) tree pair; `schedules.build_engine_tables` merges those
blocks ACROSS all (receiver, sender) pairs of the geometry, so one geometry
yields a handful of width classes — each executed as a single batched launch
over global body ids instead of one launch per tree pair per width.

Kernel dispatch: with `use_kernels=True` each bucket routes through the
Pallas kernel (`repro.kernels.ops.p2p_auto`) with a per-(S, n_pairs)
autotuned target block size; otherwise the jnp reference path
(`fmm._p2p_vals`) runs — the CPU/interpret fallback the engine defaults to
off-device.

Streaming alternative (`p2p_stream_vals`): ALL width classes as one grid of
target tiles over the unified stream table
(`schedules.build_p2p_stream_tables`), gathering source/target slabs inside
the kernel (`repro.kernels.p2p_stream`) instead of materializing per-bucket
gathered operands in HBM.  `use_kernels=False` runs the same slab math as an
XLA gather program (`p2p_stream_gathered`) — the CPU-fast reference the
interpret-smoke CI gate exercises.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fmm import _p2p_vals, device_hook
from repro.resilience import faults as _faults

__all__ = ["p2p_bucket_vals", "p2p_stream_vals", "p2p_stream_gathered",
           "p2p_rows_vals", "stream_payload", "P2P_ROW_CHUNK"]

# gathered pair rows per step of `p2p_rows_vals`: the jnp direct sum holds a
# (rows, wt, ws) distance tensor, which a deployment's million rows could
# not hold at once
P2P_ROW_CHUNK = 1 << 12


@jax.jit
def _gather_bucket(x, q, t_idx, s_idx, s_valid):
    """Global-id gathers for one bucket: x (P,N,3), q (P,N) payload."""
    x_flat = x.reshape(-1, 3)
    q_flat = q.reshape(-1)
    xt = x_flat[t_idx]                            # (B, wt, 3)
    xs = x_flat[s_idx]                            # (B, ws, 3)
    qs = jnp.where(s_valid, q_flat[s_idx], 0.0)   # (B, ws)
    return xt, xs, qs


def p2p_rows_vals(x_tgt, x_src, q_src, t_idx, s_idx, s_valid, mask):
    """jnp direct sum over gathered pair rows -> (B, wt) masked values:
    targets `x_tgt[t_idx]`, sources `x_src[s_idx]` with charges `q_src`
    (zeroed where `s_valid` is False).  Runs `P2P_ROW_CHUNK` rows at a
    time; tables of at most one chunk take the one-shot path.  Traceable."""
    chunk = P2P_ROW_CHUNK
    def rows(t, s, sv, m):
        qs = jnp.where(sv, q_src[s], 0.0)
        return _p2p_vals(x_tgt[t], x_src[s], qs, m)

    n = t_idx.shape[0]
    if n <= chunk:
        return rows(t_idx, s_idx, s_valid, mask)
    pad = (-n) % chunk
    k = (n + pad) // chunk

    def split(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((k, chunk) + a.shape[1:])

    out = jax.lax.map(lambda a: rows(*a),
                      (split(t_idx), split(s_idx), split(s_valid),
                       split(mask)))
    return out.reshape(k * chunk, -1)[:n]


def p2p_bucket_vals(x, q, bucket, use_kernels: bool = False,
                    interpret: bool | None = None, asarray=None,
                    to_host: bool = True):
    """Evaluate one width-class bucket -> (B, wt) f32 masked values.

    `to_host=True` (default) returns a NumPy array for the host f64
    accumulation; `to_host=False` keeps the values device-resident for the
    engine's x64 on-device accumulation (no round-trip)."""
    aa = device_hook(asarray)
    xt, xs, qs = _gather_bucket(x, q, aa(bucket["t_idx"]), aa(bucket["s_idx"]),
                                aa(bucket["s_valid"]))
    if use_kernels:
        _faults.fire("kernels.p2p.launch")
        from repro.kernels.ops import p2p_auto
        vals = p2p_auto(qs, xs, xt, interpret=interpret) \
            * aa(bucket["mask"])[:, None]
    else:
        vals = _p2p_vals(xt, xs, qs, aa(bucket["mask"]))
    return np.asarray(vals) if to_host else vals


def stream_payload(x, q, pad: int):
    """Flatten the (P, Nmax, ...) payload into the streaming kernel's
    structure-of-arrays slab source: (4, F) f32 rows [x; y; z; q] with at
    least `pad` zero lanes past the last body and F rounded up to the
    128-lane HBM tile.  Traceable — the fused program builds it in-trace
    from the donated payload (one transpose pass instead of one gather per
    bucket)."""
    from repro.kernels.p2p_stream import LANE
    x_flat = x.reshape(-1, 3).astype(jnp.float32)
    q_flat = q.reshape(-1).astype(jnp.float32)
    soa = jnp.concatenate([x_flat.T, q_flat[None, :]], axis=0)
    n = soa.shape[1]
    return jnp.pad(soa, ((0, 0), (0, pad + (-(n + pad)) % LANE)))


def p2p_stream_gathered(meta, payload, *, block_t: int, smax: int):
    """XLA reference for the streaming kernel: gather the SAME (4, ws) /
    (4, block_t) slabs the kernel DMAs, run the SAME tile expression
    (`stream_tile_phi`).  This is the `use_kernels=False` streaming path —
    on CPU it beats interpret-mode kernel emulation by orders of magnitude
    while keeping the unified one-program-all-width-classes structure."""
    from repro.kernels.p2p_stream import src_width, stream_tile_phi
    meta = meta.reshape(-1, 4)
    lane_s = jnp.arange(src_width(smax))
    lane_t = jnp.arange(block_t)
    src = payload[:, meta[:, 0:1] + lane_s[None, :]]    # (4, Ti, ws)
    tgt = payload[:, meta[:, 3:4] + lane_t[None, :]]    # (4, Ti, block_t)
    phi = jax.vmap(stream_tile_phi, in_axes=(1, 1, 0, 0))(
        src, tgt, meta[:, 1], meta[:, 2])
    return jnp.where((meta[:, 2] > meta[:, 1])[:, None], phi, 0.0)


def p2p_stream_vals(x, q, stream: dict, *, use_kernels: bool,
                    interpret: bool | None = None, asarray=None,
                    n_buffers: int = 2):
    """Evaluate the unified stream table -> (Ti, block_t) f32 device values
    (which lanes are real targets is `schedules.stream_out_index`; the
    others hold finite values the caller's accumulation must drop)."""
    aa = device_hook(asarray)
    payload = stream_payload(x, q, stream["pad"])
    meta = aa(stream["meta"])
    if use_kernels:
        _faults.fire("kernels.p2p.launch")
        from repro.kernels import ops as kops
        from repro.kernels.p2p_stream import p2p_stream
        interp = kops.INTERPRET if interpret is None else bool(interpret)
        return p2p_stream(meta, payload, block_t=stream["block_t"],
                          smax=stream["smax"], n_buffers=n_buffers,
                          interpret=interp)
    return p2p_stream_gathered(meta, payload, block_t=stream["block_t"],
                               smax=stream["smax"])

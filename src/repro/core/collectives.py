"""Paper-derived communication patterns as JAX collectives.

These are the device-level expressions of the paper's three ideas, shared by
the FMM executor and the LM framework:

  granularity (§4.1)  -> ring collectives chunked inside `lax.scan`, so each
                         ppermute chunk overlaps with the consumer compute
                         (the TPU analogue of subtree-grained LET messages);
  HSDX relay  (§4.2)  -> hierarchical collectives: intra-pod stage first,
                         then a small inter-pod stage over the `pod` axis
                         (relaying through "neighbor" groups);
  pairwise    (§4.3)  -> ring/butterfly ppermute schedules that keep every
                         transfer on direct ICI links.

All functions below are written for use inside `shard_map` (they take axis
names), except the `*_sharded` wrappers used with jit+GSPMD.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "ring_all_gather", "ring_reduce_scatter", "hierarchical_all_reduce",
    "two_stage_all_to_all", "all_gather_matmul_overlapped",
    "neighbor_exchange", "hsdx_grid_exchange",
]


def _axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


def _axis_size(axis_name):
    return jax.lax.axis_size(axis_name)


def _pvary(x, axis_name):
    """Mark a freshly-created array as varying over the manual axis (JAX's
    VMA check requires scan carries to match the body output's vma set)."""
    try:
        return jax.lax.pvary(x, (axis_name,))
    except Exception:
        return x


def ring_all_gather(x, axis_name: str, *, reverse: bool = False):
    """All-gather via N-1 neighbor ppermutes (contention-free ring; §4.3).

    x: (d, ...) local shard -> (N*d, ...) in rank order.  Expressed as a scan
    so XLA can overlap each hop with the consumer's compute when fused.
    """
    n = _axis_size(axis_name)
    if n == 1:
        return x
    perm = [((i + 1) % n, i) for i in range(n)] if not reverse else \
           [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        buf = jax.lax.ppermute(carry, axis_name, perm)
        return buf, buf

    _, hops = jax.lax.scan(step, x, None, length=n - 1)       # (n-1, d, ...)
    me = _axis_index(axis_name)
    chunks = jnp.concatenate([x[None], hops], axis=0)          # (n, d, ...)
    # chunk t came from rank (me + t) mod n (for the chosen ring direction)
    src = (me + jnp.arange(n)) % n if not reverse else (me - jnp.arange(n)) % n
    order = jnp.argsort(src)
    chunks = jnp.take(chunks, order, axis=0)
    return jnp.reshape(chunks, (n * x.shape[0],) + x.shape[1:])


def ring_reduce_scatter(x, axis_name: str):
    """Reduce-scatter via N-1 neighbor ppermutes. x: (N*d, ...) -> (d, ...)."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    d = x.shape[0] // n
    me = _axis_index(axis_name)
    parts = jnp.reshape(x, (n, d) + x.shape[1:])
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        # at step t, rank r holds the partial sum for chunk (r - t - 1) mod n;
        # add the local contribution for that chunk and pass it on
        idx = (me - t - 1) % n
        acc = carry + jnp.take(parts, idx, axis=0)
        acc = jax.lax.ppermute(acc, axis_name, perm)
        return acc, None

    init = _pvary(jnp.zeros((d,) + x.shape[1:], x.dtype), axis_name)
    acc, _ = jax.lax.scan(step, init, jnp.arange(n - 1))
    return acc + jnp.take(parts, me, axis=0)


def hierarchical_all_reduce(x, inner_axis: str, outer_axis: str | None):
    """HSDX-shaped all-reduce: reduce-scatter on the dense intra-pod axis,
    tiny all-reduce across pods, all-gather back intra-pod.  Wire bytes on
    the scarce inter-pod links drop by a factor of |inner_axis|."""
    if outer_axis is None:
        return jax.lax.psum(x, inner_axis)
    flat = jnp.reshape(x, (-1,))
    n = _axis_size(inner_axis)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    part = jax.lax.psum_scatter(jnp.reshape(flat, (n, -1)), inner_axis,
                                scatter_dimension=0, tiled=False)
    part = jax.lax.psum(part, outer_axis)
    full = jax.lax.all_gather(part, inner_axis, axis=0, tiled=False)
    flat = jnp.reshape(full, (-1,))
    if pad:
        flat = flat[:-pad]
    return jnp.reshape(flat, x.shape)


def two_stage_all_to_all(x, inner_axis: str, outer_axis: str,
                         split_axis: int = 0, concat_axis: int = 0):
    """Hierarchical all-to-all (the HSDX relay applied to MoE dispatch):
    stage 1 exchanges within the pod, stage 2 across pods — every transfer
    stays on direct links; the flat a2a across both axes is the baseline.

    x leading dim must equal n_inner * n_outer (destination-major order:
    index = outer * n_inner + inner).
    """
    n_in = _axis_size(inner_axis)
    n_out = _axis_size(outer_axis)
    lead = x.shape[split_axis]
    assert lead % (n_in * n_out) == 0, (lead, n_in, n_out)
    # reshape leading dim -> (n_out, n_in, rest)
    shape = x.shape
    x = jnp.moveaxis(x, split_axis, 0)
    x = jnp.reshape(x, (n_out, n_in) + x.shape[1:])
    # stage 1: intra-pod exchange of the inner index
    x = jax.lax.all_to_all(x, inner_axis, split_axis=1, concat_axis=1)
    # stage 2: inter-pod exchange of the outer index
    x = jax.lax.all_to_all(x, outer_axis, split_axis=0, concat_axis=0)
    x = jnp.reshape(x, (n_out * n_in,) + x.shape[2:])
    x = jnp.moveaxis(x, 0, split_axis) if split_axis != 0 else x
    if concat_axis != split_axis:
        x = jnp.moveaxis(x, split_axis, concat_axis)
    return x


def all_gather_matmul_overlapped(x, w, axis_name: str):
    """y = all_gather(x, axis) @ w, decomposed into ring hops so chunk t's
    matmul overlaps hop t+1's ppermute (granularity knob at its optimum
    instead of the bulk-synchronous extreme).

    x: (m, k) local shard of the gathered dim; w: (k, n) replicated (or
    column-sharded outside).  Returns (N*m, n) rows in rank order.
    """
    n_dev = _axis_size(axis_name)
    me = _axis_index(axis_name)
    perm = [((i + 1) % n_dev, i) for i in range(n_dev)]
    m = x.shape[0]
    out = _pvary(jnp.zeros((n_dev * m, w.shape[1]), dtype=jnp.result_type(x, w)),
                 axis_name)

    def step(carry, t):
        buf, out = carry
        nxt = jax.lax.ppermute(buf, axis_name, perm)     # prefetch next chunk
        y = buf @ w                                       # overlap: compute current
        src = (me + t) % n_dev
        out = jax.lax.dynamic_update_slice(out, y, (src * m, 0))
        return (nxt, out), None

    (buf, out), _ = jax.lax.scan(step, (x, out), jnp.arange(n_dev - 1))
    y = buf @ w
    src = (me + n_dev - 1) % n_dev
    out = jax.lax.dynamic_update_slice(out, y, (src * m, 0))
    return out


def neighbor_exchange(x, axis_name: str, shift: int = 1):
    """One HSDX hop: send to the +shift ring neighbor (direct link only)."""
    n = _axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def hsdx_grid_exchange(buf, axis_name: str, grid_shape, stages: int = 1):
    """HSDX on a process grid laid out along a flat axis: at each stage every
    rank exchanges with its 3^D-1 grid neighbors (Algorithm 1's per-level
    Neighbor_alltoallv), implemented as one ppermute per neighbor offset
    (each offset is a full permutation -> contention-free).

    buf: (slots, ...) where slots >= number of neighbor offsets; slot k
    accumulates what arrived from offset k.  Returns (stages, n_offsets, ...)
    received payloads.
    """
    import numpy as np
    gx, gy, gz = grid_shape
    n = gx * gy * gz
    coords = np.array([(i // (gy * gz), (i // gz) % gy, i % gz) for i in range(n)])
    offsets = [(dx, dy, dz) for dx in (-1, 0, 1) for dy in (-1, 0, 1)
               for dz in (-1, 0, 1) if (dx, dy, dz) != (0, 0, 0)]
    recv_stages = []
    x = buf
    for _ in range(stages):
        recvs = []
        for (dx, dy, dz) in offsets:
            tgt = coords + np.array([dx, dy, dz])
            tgt = tgt % np.array(grid_shape)                 # torus wrap (ICI)
            tgt_flat = tgt[:, 0] * gy * gz + tgt[:, 1] * gz + tgt[:, 2]
            perm = [(i, int(tgt_flat[i])) for i in range(n)]
            recvs.append(jax.lax.ppermute(x, axis_name, perm))
        stage_recv = jnp.stack(recvs, axis=0)                # (26, ...)
        x = jnp.mean(stage_recv, axis=0)                     # relay aggregate
        recv_stages.append(stage_recv)
    return jnp.stack(recv_stages, axis=0)

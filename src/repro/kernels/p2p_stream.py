"""Streaming P2P megakernel: in-kernel gather + double-buffered VMEM DMA.

The gathered path (`kernels.p2p` + `engine.p2p._gather_bucket`) makes XLA
materialize every width-class bucket's `(pairs, S, 3)`/`(pairs, S)` operands
in HBM before each `pallas_call` — one full HBM round-trip per bucket per
evaluate.  This kernel removes the round-trip: it takes the flat device
payload and a tile table (`schedules.build_p2p_stream_tables`) and does the
gather *inside* the kernel as slab DMAs into VMEM scratch,
pipelined so tile i+1's slabs stream in while tile i computes — the on-chip
analogue of the paper's overlap-communication-with-computation argument, at
DMA granularity instead of network granularity.

Layout contract (shared with `engine.p2p.stream_payload`):

  payload  (4, F) f32 — structure-of-arrays [x; y; z; q] over the flat body
           axis `F = n_parts * n_bodies_max + pad`, F a multiple of 128.  The
           body axis is the lane axis, which HBM tiles by 128: a DMA may only
           start at a multiple of 128 and move whole multiples of 128 lanes.
  meta     (4 * Ti,) int32 — per tile [src_base, src_lo, src_hi, tgt_base],
           flattened tile-major.  `src_base` and `tgt_base` are 128-aligned
           flat body ids where the source slab (`src_width(smax)` lanes)
           and the target slab (`block_t` lanes) start; the tile's sources
           are slab lanes `[src_lo, src_hi)`, every other lane is
           neutralized by masking q to 0 (coordinates may be garbage: 1/r
           of a garbage distance times q == 0 contributes exactly +0.0).
           Target lane j is flat body `tgt_base + j`; which lanes are real
           targets is the stream table's `tgt_range`
           (`schedules.stream_out_index`).  Tiles with src_hi <= src_lo are
           dead padding: no DMA, no compute, zero output.

Pipelining: `n_buffers` VMEM slots (2 = classic double buffering) rotate
over the tiles; tile i waits on slot i % NB and starts the slabs for tile
i + NB - 1.  One grid step runs `TILE_GROUP` tiles, so each output block is
a whole (8, block_t) tile.  The tile body itself is `kernels.p2p._tile_phi`
— the same expression the gathered kernel runs — so on identical slab
values the two paths are bitwise-equal (pinned in tests/test_p2p_stream.py).

The tile table stays in HBM and is copied into SMEM (1 MiB) one chunk of
`STREAM_CHUNK` tiles at a time, at the chunk's first grid step; the slab
pipeline drains and warms up again at each chunk boundary.

Interpret mode runs the same program through the Pallas emulator (DMAs
become copies), which is what CI pins on CPU; `best_stream_params` picks
`(block_t, n_buffers)` per stream shape class on real backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.p2p import _tile_phi

__all__ = ["p2p_stream", "stream_tile_phi", "src_width", "LANE",
           "STREAM_CHUNK"]

LANE = 128                  # HBM lane tiling of the payload's body axis
STREAM_CHUNK = 16384        # tiles per launch: 256 KiB of flat SMEM table
TILE_GROUP = 8              # tiles per grid step: one (8, 128) output tile


def src_width(smax: int) -> int:
    """Source slab lanes: the 128-multiple that holds `smax` sources starting
    at any in-slab offset in [0, 128)."""
    return -(-(smax + LANE - 1) // LANE) * LANE


def stream_tile_phi(src_slab, tgt_slab, s_lo, s_hi):
    """One streaming tile on (4, src_width) / (4, block_t) payload slabs:
    mask charges outside lanes [s_lo, s_hi), then the shared `_tile_phi`
    body.  Factored so the XLA reference path (`engine.p2p`) and tests run
    the exact expression the kernel runs."""
    ws = src_slab.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ws), 1)[0]
    q = jnp.where((lane >= s_lo) & (lane < s_hi), src_slab[3], 0.0)
    return _tile_phi(q, src_slab[:3], tgt_slab[:3])


def _stream_kernel(meta_hbm, pay_ref, out_ref, meta_ref, meta_sem, src_buf,
                   tgt_buf, src_sem, tgt_sem, *, block_t, ws, n_buffers,
                   chunk, n_chunks):
    # one grid step = TILE_GROUP consecutive tiles, so the output block is
    # a whole (8, block_t) sublane tile; the DMA pipeline runs across the
    # tiles of one chunk
    g = pl.program_id(0)
    groups = chunk // TILE_GROUP
    local = g % groups                           # group index in the chunk

    def m(step, k):
        return meta_ref[4 * step + k]

    def live(step):
        return m(step, 2) > m(step, 1)

    def slabs(step, slot):
        return (
            pltpu.make_async_copy(
                pay_ref.at[:, pl.ds(pl.multiple_of(m(step, 0), LANE), ws)],
                src_buf.at[slot], src_sem.at[slot]),
            pltpu.make_async_copy(
                pay_ref.at[:, pl.ds(pl.multiple_of(m(step, 3), LANE),
                                    block_t)],
                tgt_buf.at[slot], tgt_sem.at[slot]))

    def start(step, slot):
        # dead padding tiles are pruned: no DMA issued, and the matching
        # wait below is skipped under the same predicate
        @pl.when(live(step))
        def _():
            for cp in slabs(step, slot):
                cp.start()

    @pl.when(local == 0)
    def _():                 # new chunk: fetch its table, warm the pipeline
        src = (meta_hbm if n_chunks == 1 else meta_hbm.at[
            pl.ds(pl.multiple_of((g // groups) * (4 * chunk), 4 * chunk),
                  4 * chunk)])
        cp = pltpu.make_async_copy(src, meta_ref, meta_sem)
        cp.start()
        cp.wait()
        for j in range(min(n_buffers - 1, chunk)):
            start(j, j)

    nb = jnp.int32(n_buffers)                    # dtype-pinned (x64-safe)
    for k in range(TILE_GROUP):
        i = jnp.int32(TILE_GROUP) * local + k    # tile index in the chunk
        nxt = i + n_buffers - 1

        @pl.when(nxt < chunk)
        def _():                                 # keep the pipeline full
            start(nxt, jax.lax.rem(nxt, nb))

        slot = jax.lax.rem(i, nb)

        @pl.when(live(i))
        def _():
            for cp in slabs(i, slot):
                cp.wait()
            phi = stream_tile_phi(src_buf[slot], tgt_buf[slot],
                                  m(i, 1), m(i, 2))
            out_ref[k:k + 1, :] = phi[None, :]

        @pl.when(jnp.logical_not(live(i)))
        def _():
            out_ref[k:k + 1, :] = jnp.zeros((1, block_t), out_ref.dtype)


def p2p_stream(meta, payload, *, block_t: int, smax: int,
               n_buffers: int = 2, interpret: bool = True):
    """meta (4 * Ti,) int32, payload (4, F) f32 -> phi (Ti, block_t) f32.

    `meta` is the (Ti, 4) tile table flattened tile-major: a 2-D int32
    array with 4 columns would pad to 128 lanes in HBM (32x), and the
    kernel reads it flat anyway.

    Every slab `[base, base + width)` the table names must lie inside the
    payload (`build_p2p_stream_tables`'s `pad`).  Lanes that are not real
    targets return finite values and are dropped at accumulation
    (`schedules.stream_out_index`)."""
    return _p2p_stream(meta, payload, block_t=block_t, smax=smax,
                       n_buffers=n_buffers, interpret=interpret,
                       chunk=STREAM_CHUNK)


@functools.partial(jax.jit, static_argnames=("block_t", "smax", "n_buffers",
                                             "interpret", "chunk"))
def _p2p_stream(meta, payload, *, block_t, smax, n_buffers, interpret, chunk):
    if block_t % LANE != 0:
        raise ValueError(f"block_t must be a multiple of {LANE}, "
                         f"got {block_t}")
    if payload.shape[1] % LANE != 0:
        raise ValueError(f"payload width {payload.shape[1]} is not a "
                         f"multiple of {LANE}")
    if chunk % TILE_GROUP != 0:
        raise ValueError(f"chunk must be a multiple of {TILE_GROUP}, "
                         f"got {chunk}")
    n_tiles = meta.shape[0] // 4
    c = min(chunk, -(-n_tiles // TILE_GROUP) * TILE_GROUP)
    n_chunks = -(-n_tiles // c)
    flat = meta.astype(jnp.int32)
    if n_chunks * c != n_tiles:          # dead padding tiles: all zero
        flat = jnp.pad(flat, (0, 4 * (n_chunks * c - n_tiles)))
    ws = src_width(smax)
    out = pl.pallas_call(
        functools.partial(_stream_kernel, block_t=block_t, ws=ws,
                          n_buffers=n_buffers, chunk=c, n_chunks=n_chunks),
        grid=(n_chunks * c // TILE_GROUP,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((TILE_GROUP, block_t), lambda g: (g, 0)),
        scratch_shapes=[
            pltpu.SMEM((4 * c,), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.VMEM((n_buffers, 4, ws), jnp.float32),
            pltpu.VMEM((n_buffers, 4, block_t), jnp.float32),
            pltpu.SemaphoreType.DMA((n_buffers,)),
            pltpu.SemaphoreType.DMA((n_buffers,)),
        ],
        out_shape=jax.ShapeDtypeStruct((n_chunks * c, block_t),
                                       payload.dtype),
        interpret=interpret,
    )(flat, payload)
    return out[:n_tiles]

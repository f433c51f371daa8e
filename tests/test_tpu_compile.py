"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at the shapes `chip_smoke.py` drives on the chip.

Interpret mode (every other test) cannot see what Mosaic refuses: block
shapes whose last two dims are not (8, 128)-aligned, DMA slices that are
not tile-aligned, scratch that overflows SMEM or VMEM.  These compiles
can, and need no chip.

The shapes are those the host planner gives the smoke's configuration
(sphere, N = 10^6, seed 42, 8 ORB parts, theta 0.5, ncrit 64, p = 4):
four P2P width classes, a stream table of 1,478,057 live tiles at
block_t = 128 and 1,266,392 at 256 and 512 (source width 64), 8 x 131,072
payload bodies, and 16,384 padded cells per tree, whose first frontier
capacity is `traversal_caps(16384)[0]`.  The M2L loop compiles at the
benchmark's sphere-1m table: 8,388,608 pair rows (2^23) of which
4,219,692 are live, over 8 x 16,384 cells of p = 4 expansions.

The topology is described inside a module fixture: only one process at a
time may load the TPU compiler library, so nothing here touches it while
the module is imported.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.engine.traversal import traversal_caps
from repro.core.plan import bucket_size
from repro.kernels.mac import mac_margins
from repro.kernels.p2p import (BLOCK_CANDIDATES, STREAM_BUFFER_CANDIDATES,
                               p2p_pallas)
from repro.kernels.p2p_stream import p2p_stream, src_width

# (pairs, source width S, target width T) of each smoke width class
SMOKE_BUCKETS = ((262144, 8, 64), (524288, 16, 64), (1048576, 32, 64),
                 (262144, 64, 64))
SMOKE_SMAX = 64
SMOKE_LIVE_TILES = {128: 1478057, 256: 1266392, 512: 1266392}
SMOKE_FLAT_BODIES = 8 * 131072
SMOKE_PAD_CELLS = 16384
M2L_TABLE_ROWS, M2L_LIVE_ROWS = 1 << 23, 4219692


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_t", BLOCK_CANDIDATES)
@pytest.mark.parametrize("pairs,S,T", SMOKE_BUCKETS)
def test_p2p_pallas_compiles(one_chip, pairs, S, T, block_t):
    fn = jax.jit(lambda q, xs, xt: p2p_pallas(q, xs, xt, interpret=False,
                                              block_t=block_t))
    _assert_kernel(fn.lower(_sds(one_chip, (pairs, S)),
                            _sds(one_chip, (pairs, S, 3)),
                            _sds(one_chip, (pairs, T, 3))).compile())


@pytest.mark.parametrize("n_buffers", STREAM_BUFFER_CANDIDATES)
@pytest.mark.parametrize("block_t", BLOCK_CANDIDATES)
def test_p2p_stream_compiles(one_chip, block_t, n_buffers):
    """Every (block_t, n_buffers) the on-device autotune sweep may time."""
    n_tiles = bucket_size(SMOKE_LIVE_TILES[block_t])
    pad = max(src_width(SMOKE_SMAX), block_t)
    width = SMOKE_FLAT_BODIES + pad
    width += (-width) % 128
    fn = jax.jit(lambda m, p: p2p_stream(m, p, block_t=block_t,
                                         smax=SMOKE_SMAX,
                                         n_buffers=n_buffers,
                                         interpret=False))
    _assert_kernel(fn.lower(_sds(one_chip, (4 * n_tiles,), jnp.int32),
                            _sds(one_chip, (4, width))).compile())


@pytest.mark.parametrize("K", (4096, traversal_caps(SMOKE_PAD_CELLS)[0]))
def test_mac_margins_compiles(one_chip, K):
    fn = jax.jit(lambda ca, ra, cb, rb: mac_margins(ca, ra, cb, rb, 0.5,
                                                    interpret=False))
    _assert_kernel(fn.lower(_sds(one_chip, (K, 3)), _sds(one_chip, (K,)),
                            _sds(one_chip, (K, 3)),
                            _sds(one_chip, (K,))).compile())


def test_stream_kernel_keeps_its_name_and_phase(one_chip):
    """The trace's readers find the stream kernel by its op name
    `_p2p_stream` and its phase by the fused program's `fmm.p2p` scope."""
    from repro.analysis.hlo_walk import op_phases, top_level_ops
    n_tiles = bucket_size(SMOKE_LIVE_TILES[128])
    width = SMOKE_FLAT_BODIES + 128

    def fn(m, p):
        with jax.named_scope("fmm.p2p"):
            return p2p_stream(m, p, block_t=128, smax=SMOKE_SMAX,
                              interpret=False)

    text = jax.jit(fn).lower(_sds(one_chip, (4 * n_tiles,), jnp.int32),
                             _sds(one_chip, (4, width))).compile().as_text()
    kernels = [op for op, ph in op_phases(text).items()
               if op.startswith("_p2p_stream") and ph == "fmm.p2p"]
    assert len(kernels) == 1 and kernels[0] in top_level_ops(text)


def test_m2l_live_chunk_loop_compiles(one_chip):
    """The M2L loop whose trip count is the traced live row count lowers
    for the chip, and needs no more temporaries than the whole-table
    loop."""
    from repro.core.engine.m2l import M2L_CHUNK, m2l_accumulate, m2l_rows
    from repro.core.multipole import get_operators
    ops = get_operators(4)
    cells, n = 8 * SMOKE_PAD_CELLS, M2L_TABLE_ROWS
    args = (_sds(one_chip, (cells, ops.nk)), _sds(one_chip, (n,), jnp.int32),
            _sds(one_chip, (n,), jnp.int32), _sds(one_chip, (n, 3)),
            _sds(one_chip, (n,)))

    def whole(M, src, tgt, d, mask):
        return m2l_accumulate(ops, M, jnp.zeros_like(M), src, tgt, d, mask)

    def live(M, src, tgt, d, mask, n_live):
        return m2l_accumulate(ops, M, jnp.zeros_like(M), src, tgt, d, mask,
                              n_live)

    full = jax.jit(whole).lower(*args).compile()
    part = jax.jit(live).lower(*args, _sds(one_chip, (), jnp.int32)) \
        .compile()
    assert part.memory_analysis().temp_size_in_bytes \
        <= full.memory_analysis().temp_size_in_bytes
    # the loop runs 65 of the table's 128 chunks
    assert m2l_rows(M2L_LIVE_ROWS, n) == 65 * M2L_CHUNK == n // 128 * 65

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
capacity is `traversal_caps(16384)[0]`.

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

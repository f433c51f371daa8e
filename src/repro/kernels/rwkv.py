"""Pallas TPU kernel: RWKV6 (Finch) WKV recurrence, chunkwise.

Per head, with state S in R^{Dk x Dv}:

    y_t = sum_i r_t[i] * (S_{t-1}[i, :] + u[i] * k_t[i] * v_t)
    S_t = diag(w_t) S_{t-1} + k_t (x) v_t          (w_t = data-dependent decay)

The GPU implementations keep S in shared memory per block; the TPU analogue
keeps S resident in VMEM for an entire chunk while the per-token loop runs on
the VPU (outer products Dk x Dv), so HBM traffic is one read of (r,k,v,w) and
one write of y per chunk — the recurrence never round-trips the state.
The sequence dimension is chunked by the ops.py wrapper (lax.scan over
pallas_call), giving O(S) work with O(chunk) VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, s1_ref):
    # blocks: r/k/v/w (1, C, D); u (1, D); s0 (1, Dk, Dv)
    C, D = r_ref.shape[1], r_ref.shape[2]
    u = u_ref[0]                                   # (Dk,)
    state0 = s0_ref[0].astype(jnp.float32)         # (Dk, Dv)

    def step(t, state):
        ix = (0, pl.ds(t, 1), slice(None))
        r = r_ref[ix][0].astype(jnp.float32)
        k = k_ref[ix][0].astype(jnp.float32)
        v = v_ref[ix][0].astype(jnp.float32)
        w = w_ref[ix][0].astype(jnp.float32)
        kv = k[:, None] * v[None, :]               # (Dk, Dv) outer product
        y = jnp.sum(r[:, None] * (state + u[:, None] * kv), axis=0)  # (Dv,)
        y_ref[ix] = y[None, :].astype(y_ref.dtype)
        return w[:, None] * state + kv

    state = jax.lax.fori_loop(0, C, step, state0)
    s1_ref[0] = state.astype(s1_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_chunk(r, k, v, w, u, state, *, interpret: bool = True):
    """One chunk. r/k/v/w: (BH, C, D); u: (BH, D); state: (BH, Dk, Dv).
    Returns (y (BH, C, Dv), new_state)."""
    BH, C, D = r.shape
    Dv = v.shape[-1]
    y, s1 = pl.pallas_call(
        _wkv_kernel,
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, C, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C, Dv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, D), lambda b: (b, 0)),
            pl.BlockSpec((1, D, Dv), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, Dv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, D, Dv), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, C, Dv), r.dtype),
            jax.ShapeDtypeStruct((BH, D, Dv), state.dtype),
        ],
        interpret=interpret,
    )(r, k, v, w, u, state)
    return y, s1

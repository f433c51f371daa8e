"""Pallas TPU kernel: blocked causal flash attention (GQA + sliding window).

Online-softmax attention tiled for VMEM: the query block (BQ=128 rows) stays
resident while key/value blocks (BK=128) stream through; running max/sum
rescale the accumulator so nothing spills to HBM.  MXU-aligned contractions
(BQ x D) @ (D x BK) and (BQ x BK) @ (BK x D) with D a multiple of 128
recommended.  GQA is expressed in the BlockSpec index maps (query head h
reads kv head h // group) — no KV replication in HBM.

Sliding-window attention (gemma3 local layers, hymba) masks columns older
than `window` — the kernel grid prunes fully-masked KV blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128
BK = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window, seq_len):
    # q (1, 1, BQ, D); k/v (1, 1, S, D); o (1, 1, BQ, D)
    qb = pl.program_id(2)
    q = q_ref[0, 0] * scale                       # (BQ, D)
    S = k_ref.shape[2]
    D = q.shape[-1]
    q_pos = qb * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)

    def body(i, carry):
        acc, m_i, l_i = carry
        k = k_ref[0, 0, pl.ds(i * BK, BK), :]                          # (BK, D)
        v = v_ref[0, 0, pl.ds(i * BK, BK), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)        # (BQ, BK)
        k_pos = i * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
        mask = k_pos < seq_len                                          # pad mask
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))                    # (BQ,)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    n_kv = S // BK
    if causal:
        # only blocks at or before the query block contribute
        n_kv = jnp.minimum(n_kv, qb + 1) if isinstance(qb, jax.Array) else min(n_kv, qb + 1)
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (qb * BQ - window) // BK) if isinstance(qb, jax.Array) else max(0, (qb * BQ - window) // BK)
    acc = jnp.zeros((BQ, q.shape[-1]), jnp.float32)
    m_i = jnp.full((BQ,), NEG_INF, jnp.float32)
    l_i = jnp.zeros((BQ,), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(lo, n_kv, body, (acc, m_i, l_i))
    o_ref[0, 0] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    interpret: bool = True):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D); H % Hkv == 0. -> (B, H, S, D)."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    scale = 1.0 / (D ** 0.5)
    pad_s = (-S) % max(BQ, BK)
    if pad_s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    Sp = S + pad_s

    kern = functools.partial(_attn_kernel, scale=scale, causal=causal,
                             window=window, seq_len=S)
    out = pl.pallas_call(
        kern,
        grid=(B, H, Sp // BQ),
        in_specs=[
            pl.BlockSpec((1, 1, BQ, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sp, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, Sp, D), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BQ, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S, :]

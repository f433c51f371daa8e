"""Batched far field: one segment-summed M2L over every (receiver, sender)
pair, then a vmapped downward sweep and leaf evaluation.

The reference path scatters one M2L launch per interaction plan (local plus
one per remote block, per receiver) and walks each receiver's L2L levels in
its own Python loop.  The engine flattens all of it:

  - M2L: every plan's valid pair rows are concatenated — receiver-major,
    local block first then senders ascending, matching the reference
    accumulation order — with *global* cell ids (`p * n_cells_max + c`), and
    applied as `ops.m2l_v` + segment-sum scatters into the flat local
    array, `M2L_CHUNK` pairs at a time over the chunks that hold the
    table's `n_live` live rows (`m2l_accumulate`).  Grafted-LET
    sources were translated to sender-global ids at table-build time, so
    remote M2L reads the sender's device multipoles directly: no LET
    payload ever crosses the host boundary.
  - Downward/L2P: top-aligned stacked level tables, one vmapped L2L scatter
    per level slot, then one vmapped leaf evaluation producing the padded
    value tables the host accumulates in float64.
  - M2P fallback rows (truncated remote cells vs large local leaves) batch
    the same way against the flat multipole array.

Values return as padded f32 tables; the final float64 accumulation happens
once on the host (matching the reference executors' precision exactly).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["far_tail_kernel", "m2p_vals_kernel", "m2l_accumulate",
           "m2l_rows", "M2L_CHUNK"]

# M2L pairs per step of the accumulation loop.  One pair's operator needs
# every derivative of 1/r up to order 2(p-1) plus an (nk, nk) translation
# matrix; materialized for a deployment's millions of pairs at once that is
# tens of GB, so the pairs run in fixed-size chunks.
M2L_CHUNK = 1 << 16


def m2l_rows(n_live, n: int):
    """Pair rows `m2l_accumulate` runs for an `n`-row table whose first
    `n_live` rows are live: `n` in one shot, else the live rows rounded up
    to whole chunks (the rows after them are masked padding, never run).
    `n_live` may be a traced scalar; the loop and the `m2l.pairs_padded`
    counter share this rule."""
    return n if n <= M2L_CHUNK else n_live + (-n_live) % M2L_CHUNK


def m2l_accumulate(ops, M_src, L_flat, src, tgt, d, mask, n_live=None):
    """L_flat[tgt] += mask * M2L(M_src[src], d) over every pair row, in
    chunks of `M2L_CHUNK` rows so the per-pair operator temporaries stay
    bounded.  Tables of at most one chunk take the one-shot path.

    `n_live` (a traced 0-d int, so every live count of one table shape
    shares one program) says that the rows from it on are masked padding:
    the loop then runs only the chunks that hold live rows.  Without it
    the loop runs the whole table."""
    chunk = M2L_CHUNK
    n = src.shape[0]
    if n <= chunk:
        contrib = ops.m2l_v(M_src[src], d) * mask[:, None]
        return L_flat.at[tgt].add(contrib)
    pad = m2l_rows(n, n) - n
    if pad:                  # masked rows; d = e_x keeps the operator finite
        src = jnp.pad(src, (0, pad))
        tgt = jnp.pad(tgt, (0, pad))
        mask = jnp.pad(mask, (0, pad))
        d = jnp.concatenate(
            [d, jnp.broadcast_to(jnp.asarray([1.0, 0.0, 0.0], d.dtype),
                                 (pad, 3))], axis=0)

    live = n if n_live is None else n_live

    # the bound is computed in the condition: computed before the loop, it
    # cost the v5e program 32 KiB more temporaries
    def more(carry):
        return carry[0] * chunk < m2l_rows(live, n)

    def body(carry):
        i, L = carry

        def sl(a):
            return jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk)
        contrib = ops.m2l_v(M_src[sl(src)], sl(d)) * sl(mask)[:, None]
        return i + 1, L.at[sl(tgt)].add(contrib)

    return jax.lax.while_loop(more, body, (jnp.int32(0), L_flat))[1]


@partial(jax.jit, static_argnums=(0,))
def far_tail_kernel(ops, M, x, m2l, down_ids, down_parents, down_mask,
                    down_d, leaves, leaf_mask, leaf_centers, leaf_idx):
    """M (P,C,nk), x (P,N,3) + tables -> padded L2P values (P, Bl, W).
    The M2L loop and the downward sweep are the device phases `fmm.m2l`
    and `fmm.downward` (`jax.named_scope`)."""
    P, C, nk = M.shape
    with jax.named_scope("fmm.m2l"):
        M_flat = M.reshape(P * C, nk)
        L_flat = jnp.zeros_like(M_flat)
        if m2l["src"].shape[0]:
            L_flat = m2l_accumulate(ops, M_flat, L_flat, m2l["src"],
                                    m2l["tgt"], m2l["d"], m2l["mask"],
                                    m2l["n_live"])
    with jax.named_scope("fmm.downward"):
        L = L_flat.reshape(P, C, nk)

        def l2l_one(Lp, ids, parents, mask, d):
            contrib = ops.l2l_v(Lp[parents], d) * mask[:, None]
            return Lp.at[ids].add(contrib)

        for lvl in range(down_ids.shape[1]):     # slot 0 = level 1 (top)
            L = jax.vmap(l2l_one)(L, down_ids[:, lvl], down_parents[:, lvl],
                                  down_mask[:, lvl], down_d[:, lvl])

        def l2p_one(Lp, xp, lf, lm, lc, li):
            return ops.l2p_v(Lp[lf], xp[li], lc) * lm[:, None]

        return jax.vmap(l2p_one)(L, x, leaves, leaf_mask, leaf_centers,
                                 leaf_idx)


@partial(jax.jit, static_argnums=(0,))
def m2p_vals_kernel(ops, M, x, b, centers, mask, t_idx):
    """Batched M2P fallback values (B, wt) against flat global multipoles."""
    P, C, nk = M.shape
    M_flat = M.reshape(P * C, nk)
    x_flat = x.reshape(-1, 3)
    return ops.m2p_v(M_flat[b], x_flat[t_idx], centers) * mask[:, None]

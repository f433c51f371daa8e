"""Fused FMM megakernel: the whole evaluation (and the whole step
revalidation) as ONE donated XLA entry computation.

The per-phase engine (`DeviceEngine._phase_values` / `evaluate_device`)
already batches each FMM phase across partitions, but a warm `evaluate()`
still dispatches one jitted call per phase — upward, far-field tail, one per
P2P width bucket, M2P — plus the final accumulation scatter.  At
small-to-medium N the launch overhead of that handful of dispatches dwarfs
the FLOP time, exactly the per-message-overhead regime the paper's §4 bulk
exchange collapses.  This module collapses the launches the same way: the
builders below close over the *static* structure (expansion order, bucket
count, padded dims, kernel dispatch flags) and call the existing phase
kernels — `batched_upward_kernel`, `far_tail_kernel`, `m2p_vals_kernel`,
the bucketed P2P — inside one trace, so nested jits inline and the whole
pipeline compiles to a single entry computation with trace-identical
numerics to the per-phase path (which stays the pinned comparison).

Donation vs `DeviceMemo` residency
----------------------------------
The fused program takes two argument classes with opposite lifetimes:

  - **frozen index tables** — memoized device views served by the engine's
    `DeviceMemo`, shared with the per-phase path and alive for the
    geometry's lifetime.  These are NEVER donated: a donated buffer is
    deleted after the call, and the memo would go on serving a dead view.
  - **payload / accumulator buffers** — the `(P, Nmax, 3)`/`(P, Nmax)`
    coordinate/charge envelopes (and the step's `new_x` upload).  These are
    ALWAYS donated (`donate_argnums`), so XLA reuses their storage for the
    outputs in place of allocating fresh buffers every timestep.  Payload
    arrays are threaded through to outputs, which XLA turns into
    input-output aliasing; the engine rebinds its handles from the outputs
    after every call.  Donated uploads are explicit copies (`jnp.array`) —
    on CPU a zero-copy `asarray` view would let XLA scribble over caller
    memory — and `DeviceEngine._donatable` raises `TypeError` if a
    memo-resident view is ever offered for donation.

Accumulation dtype: with x64 enabled the potential accumulates on device in
float64 (bit-for-bit the `evaluate_device` contract); without x64 the fused
program can only accumulate in f32 — slightly looser than the per-phase
path's *host* f64 accumulation, documented and tested at a looser
tolerance.  Tight-tolerance equivalence tests therefore run under x64.

Executable identity: `executable_key` folds `schedules.shape_class_digest`
(dtype/shape of every table as uploaded — x64 canonicalization included)
with the scalar statics; `exe_cache.ExecutableCache` memoizes the
`jax.jit(...).lower(...).compile()` product per key, so a new geometry of
an already-seen shape class pays zero XLA time.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.engine.m2l import far_tail_kernel, m2p_vals_kernel
from repro.core.engine.schedules import stream_out_index
from repro.core.engine.traversal import _drift_changed_kernel, _restack_kernel
from repro.core.engine.upward import batched_upward_kernel
from repro.core.fmm import _p2p_vals

__all__ = ["flatten_eval_tables", "flatten_step_tables", "bucket_block_ts",
           "build_fused_evaluate", "build_fused_step", "executable_key",
           "theta_bucket"]

_UP_KEYS = ("leaves", "leaf_mask", "leaf_centers", "leaf_idx", "leaf_valid",
            "up_ids", "up_parents", "up_mask", "up_d",
            "down_ids", "down_parents", "down_mask", "down_d")


# ------------------------------------------------------------- table views --
def flatten_eval_tables(tables, stream: dict | None = None) -> dict:
    """Flat {name: host array} of every frozen table the fused evaluate
    reads — one pytree argument, memoized per-leaf by the engine's memo.
    Keys are stable across builds so the pytree structure (and therefore the
    compiled executable) depends only on the shape class.

    With `stream` (a `schedules.build_p2p_stream_tables` dict) the per-bucket
    gather tables are replaced by the unified stream tables — the fused
    program never touches the bucket indices on that path."""
    flat = {k: tables.up.tables[k] for k in _UP_KEYS}
    for k, v in tables.m2l.items():
        flat[f"m2l_{k}"] = v
    for k, v in tables.m2p.items():
        flat[f"m2p_{k}"] = v
    if stream is not None:
        flat["p2ps_meta"] = stream["meta"]
        flat["p2ps_tgt_range"] = stream["tgt_range"]
    else:
        for i, b in enumerate(tables.p2p_buckets):
            for k, v in b.items():
                flat[f"p2p{i}_{k}"] = v
    flat["l2p_t_idx"] = tables.l2p_t_idx
    flat["orig_idx"] = tables.orig_idx
    flat["flat_idx"] = tables.flat_idx
    return flat


def flatten_step_tables(tables, x_ref_pad) -> dict:
    """Flat frozen tables for the fused step revalidation: the orig->flat
    restack gathers plus the stacked slack reference."""
    return {"orig_idx": tables.orig_idx, "flat_idx": tables.flat_idx,
            "x_ref_pad": x_ref_pad}


def bucket_block_ts(tables, *, use_kernels: bool, interpret: bool | None):
    """Per-bucket Pallas target block sizes, resolved on the host at build
    time: `best_block_t` times candidates on a real backend, which cannot
    happen inside a trace, so the fused program bakes the choice in as a
    static (and the executable key carries it)."""
    if not use_kernels:
        return (None,) * len(tables.p2p_buckets)
    from repro.kernels import ops as kops
    from repro.kernels.p2p import best_block_t
    interp = kops.INTERPRET if interpret is None else bool(interpret)
    out = []
    for b in tables.p2p_buckets:
        n_pairs, ws = b["s_idx"].shape
        out.append(best_block_t(ws, n_pairs, b["t_idx"].shape[1],
                                interpret=interp))
    return tuple(out)


# ----------------------------------------------------------------- builders --
def build_fused_evaluate(ops, tables, *, use_kernels: bool,
                         interpret: bool | None, block_ts, acc_dtype,
                         stream: dict | None = None, n_buffers: int = 2):
    """Close over the static structure and return the fused evaluate
    `fused(x_pad, q_pad, tab) -> (phi, M, x_pad, q_pad)` — jit it with
    `donate_argnums=(0, 1)`.  `tab` is `flatten_eval_tables` uploaded; the
    donated payload pair is threaded to the outputs for aliasing, and the
    device multipoles `M` come back so the engine can serve `upward()`
    without a second launch.

    With `stream` the near field runs as ONE streaming grid over the unified
    tile table (kernels.p2p_stream with `use_kernels`, the XLA slab-gather
    program without) instead of one gather + `pallas_call` per width-class
    bucket — the donated payload is transposed once into the (4, F) slab
    source in-trace and no per-bucket gathered operands ever hit HBM."""
    from repro import obs
    if obs.enabled():
        obs.event("engine.fused_build",
                  {"kind": "evaluate", "n": tables.n,
                   "n_parts": tables.n_parts,
                   "n_buckets": len(tables.p2p_buckets),
                   "use_kernels": bool(use_kernels),
                   "p2p_impl": "stream" if stream is not None else "gathered"})
    P, Cmax = tables.n_parts, tables.n_cells_max
    Nmax, n = tables.n_bodies_max, tables.n
    n_buckets = len(tables.p2p_buckets)
    has_m2p = tables.m2p["b"].shape[0] > 0
    if use_kernels:
        from repro.kernels import ops as kops
        from repro.kernels.p2p import p2p_pallas
        interp = kops.INTERPRET if interpret is None else bool(interpret)

    def fused(x_pad, q_pad, tab):
        M = batched_upward_kernel(
            ops, x_pad, q_pad, tab["leaves"], tab["leaf_mask"],
            tab["leaf_centers"], tab["leaf_idx"], tab["leaf_valid"],
            tab["up_ids"], tab["up_parents"], tab["up_mask"], tab["up_d"],
            n_cells=Cmax)
        m2l = {k: tab[f"m2l_{k}"] for k in ("src", "tgt", "mask", "d")}
        l2p_vals = far_tail_kernel(
            ops, M, x_pad, m2l, tab["down_ids"], tab["down_parents"],
            tab["down_mask"], tab["down_d"], tab["leaves"], tab["leaf_mask"],
            tab["leaf_centers"], tab["leaf_idx"])

        phi_flat = jnp.zeros(P * Nmax, acc_dtype)

        def add(pf, idx, valid, vals):
            contrib = jnp.where(valid.ravel(),
                                vals.astype(acc_dtype).ravel(),
                                jnp.zeros((), acc_dtype))
            return pf.at[idx.ravel()].add(contrib)

        phi_flat = add(phi_flat, tab["l2p_t_idx"], tab["leaf_valid"],
                       l2p_vals)

        if stream is not None:
            from repro.core.engine.p2p import (p2p_stream_gathered,
                                               stream_payload)
            payload = stream_payload(x_pad, q_pad, stream["pad"])
            if use_kernels:
                from repro.kernels.p2p_stream import p2p_stream
                vals = p2p_stream(tab["p2ps_meta"], payload,
                                  block_t=stream["block_t"],
                                  smax=stream["smax"], n_buffers=n_buffers,
                                  interpret=interp)
            else:
                vals = p2p_stream_gathered(tab["p2ps_meta"], payload,
                                           block_t=stream["block_t"],
                                           smax=stream["smax"])
            out_idx, out_valid = stream_out_index(
                tab["p2ps_meta"], tab["p2ps_tgt_range"], stream["block_t"],
                xp=jnp)
            phi_flat = add(phi_flat, out_idx, out_valid, vals)
        else:
            x_flat = x_pad.reshape(-1, 3)
            q_flat = q_pad.reshape(-1)
            for i in range(n_buckets):
                t_idx, s_idx = tab[f"p2p{i}_t_idx"], tab[f"p2p{i}_s_idx"]
                xt, xs = x_flat[t_idx], x_flat[s_idx]
                qs = jnp.where(tab[f"p2p{i}_s_valid"], q_flat[s_idx], 0.0)
                if use_kernels:
                    vals = p2p_pallas(qs, xs, xt, interpret=interp,
                                      block_t=block_ts[i]) \
                        * tab[f"p2p{i}_mask"][:, None]
                else:
                    vals = _p2p_vals(xt, xs, qs, tab[f"p2p{i}_mask"])
                phi_flat = add(phi_flat, t_idx, tab[f"p2p{i}_t_valid"], vals)

        if has_m2p:
            vals = m2p_vals_kernel(ops, M, x_pad, tab["m2p_b"],
                                   tab["m2p_centers"], tab["m2p_mask"],
                                   tab["m2p_t_idx"])
            phi_flat = add(phi_flat, tab["m2p_t_idx"], tab["m2p_t_valid"],
                           vals)

        phi = (jnp.zeros(n, acc_dtype)
               .at[tab["orig_idx"]].set(phi_flat[tab["flat_idx"]]))
        return phi, M, x_pad, q_pad

    return fused


def build_fused_step(tables):
    """Fused within-slack step revalidation
    `fused(new_x, x_pad, tab) -> (drift, changed, x_new, x_pad)` — jit with
    `donate_argnums=(1,)`.  One launch restacks the uploaded `new_x` into
    the payload envelope and reduces every partition's drift/changed flags;
    `x_new` is the staged next payload and the previous `x_pad` is threaded
    back out so the engine keeps a live handle (donated -> aliased).
    `new_x` is NOT donated: it has no same-shape output to alias onto."""
    from repro import obs
    if obs.enabled():
        obs.event("engine.fused_build",
                  {"kind": "step", "n": tables.n,
                   "n_parts": tables.n_parts})
    P, Nmax = tables.n_parts, tables.n_bodies_max

    def fused(new_x, x_pad, tab):
        x_new = _restack_kernel(new_x, tab["orig_idx"], tab["flat_idx"],
                                shape=(P, Nmax))
        drift, changed = _drift_changed_kernel(x_new, tab["x_ref_pad"], x_pad)
        return drift, changed, x_new, x_pad

    return fused


# --------------------------------------------------------------- cache key --
def theta_bucket(theta: float) -> int:
    """MAC parameter bucketed to 1/16ths: theta only shapes the tables (the
    program text is theta-independent), but keying on the bucket keeps one
    executable per serving configuration — and gives the cache tests a
    dial that misses without touching the geometry."""
    return int(round(float(theta) * 16.0))


def executable_key(kind: str, digest: str, *, n: int, n_parts: int, p: int,
                   theta: float, x64: bool, backend: str, use_kernels: bool,
                   interpret, block_ts=(), p2p_impl: str = "gathered") -> tuple:
    """Shape-class key for one fused executable: everything that can change
    the compiled program (digest = per-table dtypes/shapes as uploaded,
    padded dims, statics) plus the conservative serving knobs.  `p2p_impl`
    names the near-field kernel variant ("gathered" per-bucket launches vs
    the unified "stream" grid); on the stream path `block_ts` carries the
    stream statics `(smax, block_t, n_buffers)` instead of per-bucket
    blocks — either way the tuple is part of the program text."""
    return (kind, digest, int(n), int(n_parts), int(p), theta_bucket(theta),
            bool(x64), str(backend), bool(use_kernels),
            None if interpret is None else bool(interpret), tuple(block_ts),
            str(p2p_impl))

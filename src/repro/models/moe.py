"""Expert-parallel MoE with explicit all-to-all dispatch.

This is where the paper's HSDX idea lands in the LM framework: expert
dispatch is a sparse data exchange.  Experts are sharded over the `model`
axis; tokens are routed with top-k gating and fixed per-group capacity
(ORB-style balance: capacity is the histogram-splitter analogue), then
exchanged with `lax.all_to_all` inside a shard_map manual over
(data, model[, pod]).  With `hierarchical=True` and a pod axis, the a2a runs
in two stages (intra-pod, inter-pod) via core.collectives.two_stage_all_to_all
— the HSDX relay — keeping every transfer on direct links.

A collective-free dense path (`_moe_dense`) with identical math serves single-
device smoke tests and as the oracle for the shard_map path.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.collectives import two_stage_all_to_all
from repro.models.params import ParamDef


def moe_defs(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamDef((d, e), (None, None), dtype="float32"),
        "w_gate": ParamDef((e, d, f), ("model", "data", None)),
        "w_up": ParamDef((e, d, f), ("model", "data", None)),
        "w_down": ParamDef((e, f, d), ("model", None, "data")),
    }


def _route(x2d, router_w, n_experts, top_k, capacity):
    """Common routing math.  x2d: (T, D) -> dispatch metadata."""
    logits = (x2d.astype(jnp.float32) @ router_w)               # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)         # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    # position of each (token, k) slot within its expert's capacity
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # (T,k,E)
    flat = onehot.reshape(-1, n_experts)                        # (T*k, E)
    pos = jnp.cumsum(flat, axis=0) - flat                       # pos before me
    pos = (pos * flat).sum(-1).reshape(-1, top_k)               # (T, k)
    keep = pos < capacity
    # aux losses: load-balance (switch) + router z-loss
    frac = flat.reshape(-1, top_k, n_experts).sum(1).mean(0)    # tokens/expert
    imp = probs.mean(0)
    aux = n_experts * jnp.sum(frac * imp) + 1e-3 * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gate_vals, expert_idx, pos, keep, aux


def _dispatch(x2d, expert_idx, pos, keep, n_experts, capacity):
    """Scatter tokens into the (E, C, D) send buffer."""
    T, D = x2d.shape
    k = expert_idx.shape[1]
    slot = (expert_idx * capacity + pos).reshape(-1)            # (T*k,)
    slot = jnp.where(keep.reshape(-1), slot, n_experts * capacity)  # dropped
    buf = jnp.zeros((n_experts * capacity + 1, D), x2d.dtype)
    buf = buf.at[slot].add(jnp.repeat(x2d, k, axis=0))
    return buf[:-1].reshape(n_experts, capacity, D)


def _combine(y_buf, gate_vals, expert_idx, pos, keep):
    """Gather expert outputs back to tokens, weighted by gates."""
    E, C, D = y_buf.shape
    T, k = expert_idx.shape
    slot = (expert_idx * C + pos).reshape(-1)
    rows = y_buf.reshape(E * C, D)[jnp.where(keep.reshape(-1), slot, 0)]
    rows = rows * (keep.reshape(-1, 1) * gate_vals.reshape(-1, 1)).astype(rows.dtype)
    return rows.reshape(T, k, D).sum(axis=1)


def _expert_ffn(xb, w_gate, w_up, w_down):
    """xb: (E_loc, C', D); weights (E_loc, D, F)/(E_loc, F, D)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xb, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _capacity(tokens: int, cfg) -> int:
    c = math.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8


def _moe_dense(x, p, cfg):
    """Single-shard reference (also the smoke-test path)."""
    B, S, D = x.shape
    x2d = x.reshape(-1, D)
    C = _capacity(x2d.shape[0], cfg)
    gate, eidx, pos, keep, aux = _route(x2d, p["router"], cfg.n_experts,
                                        cfg.top_k, C)
    buf = _dispatch(x2d, eidx, pos, keep, cfg.n_experts, C)
    y_buf = _expert_ffn(buf, p["w_gate"], p["w_up"], p["w_down"])
    y = _combine(y_buf, gate, eidx, pos, keep)
    return y.reshape(B, S, D), aux


def moe_ffn(x, p, cfg, par):
    """x: (B, S, D) -> (y, aux_loss)."""
    if par.mesh is None or par.model_axis is None or par.tp_size() == 1:
        return _moe_dense(x, p, cfg)
    return _moe_shard_map(x, p, cfg, par)


def _moe_shard_map(x, p, cfg, par):
    mesh = par.mesh
    n_model = mesh.shape[par.model_axis]
    assert cfg.n_experts % n_model == 0, (cfg.n_experts, n_model)
    dp = par.data_axes
    model = par.model_axis
    manual = set(dp) | {model}

    def body(xl, router_w, w_gate, w_up, w_down):
        # xl: (B_loc, S, D) local tokens — REPLICATED over the model axis;
        # experts local on axis 0
        B_loc, S, D = xl.shape
        x2d = xl.reshape(-1, D)
        T_full = x2d.shape[0]
        # §Perf hillclimb: without sequence sharding every model shard routes
        # the SAME tokens, so dispatch compute and a2a bytes are replicated
        # n_model times.  Slicing tokens over the model axis first removes
        # the redundancy (Megatron-style sequence parallelism applied to MoE).
        seq_shard = par.moe_seq_shard and T_full % n_model == 0
        if seq_shard:
            me = jax.lax.axis_index(model)
            Tl = T_full // n_model
            x2d = jax.lax.dynamic_slice_in_dim(x2d, me * Tl, Tl, axis=0)
        C = _capacity(x2d.shape[0], cfg)
        gate, eidx, pos, keep, aux = _route(x2d, router_w, cfg.n_experts,
                                            cfg.top_k, C)
        buf = _dispatch(x2d, eidx, pos, keep, cfg.n_experts, C)   # (E, C, D)
        # FSDP gather of expert weights over the data axes (ZeRO-3)
        for ax in dp:
            w_gate = jax.lax.all_gather(w_gate, ax, axis=1, tiled=True)
            w_up = jax.lax.all_gather(w_up, ax, axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, ax, axis=2, tiled=True)
        if par.hierarchical and par.pod_axis and par.pod_axis in dp:
            # HSDX two-stage dispatch is available when EP spans pods; with
            # EP inside one pod, token exchange stays on intra-pod links and
            # only weight-FSDP gathers cross pods (already hierarchical).
            pass
        # a2a with split==concat axis (clean transpose rule); destination-
        # major reshape keeps expert rows contiguous per rank
        E_loc = cfg.n_experts // n_model
        buf4 = buf.reshape(n_model, E_loc * C, D)
        recv = jax.lax.all_to_all(buf4, model, split_axis=0, concat_axis=0)
        recv = recv.reshape(n_model, E_loc, C, D).transpose(1, 0, 2, 3) \
                   .reshape(E_loc, n_model * C, D)
        y = _expert_ffn(recv, w_gate, w_up, w_down)               # (E_loc, nC, D)
        y4 = y.reshape(E_loc, n_model, C, D).transpose(1, 0, 2, 3) \
              .reshape(n_model, E_loc * C, D)
        back = jax.lax.all_to_all(y4, model, split_axis=0, concat_axis=0)
        back = back.reshape(cfg.n_experts, C, D)
        out = _combine(back, gate, eidx, pos, keep)
        if seq_shard:
            # reconstruct the full token set (transpose: reduce-scatter)
            out = jax.lax.all_gather(out, model, axis=0, tiled=True)
            aux = jax.lax.pmean(aux, model)
        # aux identical across model (replicated routing); average over data
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(B_loc, S, D), aux

    # expert weights enter UN-gathered on their FSDP (data) dim — the body
    # all-gathers them manually (ZeRO-3); specs must match the true layout
    fsdp = dp if dp else None
    in_specs = (P(dp, None, None), P(None, None),
                P(model, fsdp, None), P(model, fsdp, None),
                P(model, None, fsdp))
    out_specs = (P(dp, None, None), P())
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, axis_names=manual,
                       check_vma=False)
    y, aux = fn(x, p["router"].astype(jnp.float32), p["w_gate"], p["w_up"],
                p["w_down"])
    return y, aux

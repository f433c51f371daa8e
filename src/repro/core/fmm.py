"""Single-partition FMM evaluator: host-built plans + JAX arithmetic.

The numeric passes (P2M, M2M, M2L, L2L, L2P, P2P, M2P) run as *jitted,
bucketed* vmaps over the padded index tables of an `FMMPlan`
(repro.core.plan): all list lengths and gather widths are padded to
power-of-two buckets so the JIT cache is shared across trees, partitions and
LET pairs (tree shapes vary; the compiled kernels must not).

Plan construction (traversal, padding, bucketing — pure NumPy geometry) lives
in plan.py; this module only *executes* plans: `execute_fmm_plan` does zero
list construction and zero padding work, so a plan built once can be
evaluated many times (time-stepping, protocol sweeps) at kernel cost only.

Kernel dispatch: `use_kernels=True` routes the P2P hot spot through the
Pallas kernels (repro.kernels); the jnp path is the CPU reference.  The
batched multi-tree execution tier lives in repro.core.engine — these
executors are the per-tree reference it is pinned against.  The legacy
`use_pallas=` flag is a deprecated alias for `use_kernels` (warns once per
call site name, then honors the request).
"""
from __future__ import annotations

import warnings
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.multipole import MultipoleOperators, get_operators
from repro.core.plan import (FMMPlan, InteractionPlan, TreeSchedules,
                             build_fmm_plan, build_interaction_plan,
                             build_tree_schedules)
from repro.core.tree import Tree, build_tree

__all__ = ["fmm_potential", "evaluate", "execute_fmm_plan", "direct_potential",
           "upward_pass", "downward_pass", "m2l_pass", "m2l_apply", "p2p_pass",
           "p2p_apply", "m2p_pass", "m2p_apply", "l2p_pass", "device_hook"]

_USE_PALLAS_WARNED: set = set()


def _resolve_kernels(use_kernels, use_pallas, where: str) -> bool:
    """Deprecated-flag shim: `use_pallas=` warns once per call site, then is
    honored as `use_kernels` (repo convention: warn-once DeprecationWarning,
    byte-identical behavior)."""
    if use_pallas is None:
        return bool(use_kernels)
    if where not in _USE_PALLAS_WARNED:
        _USE_PALLAS_WARNED.add(where)
        warnings.warn(
            f"{where}(use_pallas=...) is deprecated; use use_kernels=... or "
            "the engine dispatch flag (repro.core.engine.DeviceEngine / "
            "api.FMMSession(engine=...))",
            DeprecationWarning, stacklevel=3)
    return bool(use_pallas)


def device_hook(asarray):
    """Normalize an `asarray=` executor hook (api.DeviceMemo or compatible).

    Contract: the hook must return a *device* array (`jax.Array`) — returning
    a NumPy view would silently re-upload on every kernel call, defeating the
    memoization the hook exists for, so it raises instead.

    Donation-vs-residency contract (the fused tier's mirror image): views
    served by the hook are memoized and shared across callers, so they must
    NEVER be donated to a launch — XLA deletes donated buffers after the
    call, and the memo would keep serving the dead view.  Only per-call
    payload buffers (fresh `jnp.array` copies, or previous donated-launch
    outputs) may be donated; `engine.DeviceEngine._donatable` raises
    `TypeError` on a memo-resident view (`DeviceMemo.is_resident`), exactly
    as this wrapper raises on a host-returning hook."""
    if asarray is None:
        return jnp.asarray

    def checked(arr, dtype=None):
        out = asarray(arr, dtype) if dtype is not None else asarray(arr)
        if not isinstance(out, jax.Array):
            raise TypeError(
                "asarray hook must return a device array (jax.Array), got "
                f"{type(out).__name__}: a NumPy-returning hook would silently "
                "re-upload every table on every call (see api.DeviceMemo)")
        return out

    return checked


def direct_potential(x, q, x_tgt=None, chunk: int = 2048) -> np.ndarray:
    """O(N^2) float64 oracle (self-interaction excluded)."""
    x = np.asarray(x, dtype=np.float64)
    q = np.asarray(q, dtype=np.float64)
    xt = x if x_tgt is None else np.asarray(x_tgt, dtype=np.float64)
    cols = [np.ascontiguousarray(x[:, k]) for k in range(3)]
    out = np.zeros(len(xt))
    for s in range(0, len(xt), chunk):
        t = xt[s:s + chunk]
        r2 = (t[:, 0:1] - cols[0]) ** 2       # same order as summing d**2
        r2 += (t[:, 1:2] - cols[1]) ** 2      # over the last axis, without
        r2 += (t[:, 2:3] - cols[2]) ** 2      # a (chunk, N, 3) temporary
        inv = np.where(r2 > 0, 1.0 / np.sqrt(np.maximum(r2, 1e-300)), 0.0)
        out[s:s + chunk] = inv @ q
    return out


# ----------------------------------------------------- jitted kernels ------
@partial(jax.jit, static_argnums=(0,), static_argnames=("n_cells",))
def _p2m_scatter(ops, q, x, centers, leaf_ids, mask, n_cells):
    M_leaf = ops.p2m_v(q, x, centers) * mask[:, None]
    return jnp.zeros((n_cells, ops.nk), jnp.float32).at[leaf_ids].add(M_leaf)


@partial(jax.jit, static_argnums=(0,))
def _m2m_scatter(ops, M, M_child, d, parents, mask):
    contrib = ops.m2m_v(M_child, d) * mask[:, None]
    return M.at[parents].add(contrib)


@partial(jax.jit, static_argnums=(0,), static_argnames=("n_cells",))
def _m2l_scatter(ops, M_src, d, a, mask, n_cells):
    contrib = ops.m2l_v(M_src, d) * mask[:, None]
    return jnp.zeros((n_cells, ops.nk), M_src.dtype).at[a].add(contrib)


@partial(jax.jit, static_argnums=(0,))
def _l2l_scatter(ops, L, L_parent, d, ids, mask):
    contrib = ops.l2l_v(L_parent, d) * mask[:, None]
    return L.at[ids].add(contrib)


@partial(jax.jit, static_argnums=(0,))
def _l2p_vals(ops, L_leaf, y, centers, mask):
    return ops.l2p_v(L_leaf, y, centers) * mask[:, None]


@partial(jax.jit, static_argnums=(0,))
def _m2p_vals(ops, M, y, centers, mask):
    return ops.m2p_v(M, y, centers) * mask[:, None]


@jax.jit
def _p2p_vals(xt, xs, qs, mask):
    d = xt[:, :, None, :] - xs[:, None, :, :]
    r2 = (d * d).sum(-1)
    inv = jnp.where(r2 > 0, jax.lax.rsqrt(jnp.maximum(r2, 1e-30)), 0.0)
    return jnp.einsum("pts,ps->pt", inv, qs) * mask[:, None]


# ------------------------------------------------------------- passes ------
# Every executor takes an optional `asarray` hook (default `jnp.asarray`): a
# session can pass a memoizing uploader (api.DeviceMemo) so the frozen NumPy
# index tables are transferred to the device exactly once, keeping plan.py
# NumPy-only while repeated execution stays kernels-only.  The hook MUST
# return device arrays — `device_hook` enforces the contract.
def upward_pass(tree: Tree, ops: MultipoleOperators,
                sched: TreeSchedules | None = None, asarray=None) -> jnp.ndarray:
    """P2M at leaves, then M2M level-by-level (deepest first). -> (C, nk)."""
    if sched is None:
        sched = build_tree_schedules(tree)
    aa = device_hook(asarray)
    x = aa(tree.x, jnp.float32)
    q = aa(tree.q, jnp.float32)
    xi = x[aa(sched.leaf_idx)]
    qi = jnp.where(aa(sched.leaf_valid), q[aa(sched.leaf_idx)], 0.0)
    M = _p2m_scatter(ops, qi, xi, aa(sched.leaf_centers),
                     aa(sched.leaves), aa(sched.leaf_mask),
                     n_cells=sched.n_cells)
    for ls in reversed(sched.levels):
        M = _m2m_scatter(ops, M, M[aa(ls.ids)], aa(ls.d),
                         aa(ls.parents), aa(ls.mask))
    return M


def downward_pass(tree: Tree, ops, L,
                  sched: TreeSchedules | None = None, asarray=None) -> jnp.ndarray:
    if sched is None:
        sched = build_tree_schedules(tree)
    aa = device_hook(asarray)
    for ls in sched.levels:
        L = _l2l_scatter(ops, L, L[aa(ls.parents)], aa(ls.d),
                         aa(ls.ids), aa(ls.mask))
    return L


def l2p_pass(tree: Tree, ops, L, sched: TreeSchedules | None = None,
             asarray=None) -> np.ndarray:
    if sched is None:
        sched = build_tree_schedules(tree)
    aa = device_hook(asarray)
    y = aa(tree.x, jnp.float32)[aa(sched.leaf_idx)]
    vals = _l2p_vals(ops, L[aa(sched.leaves)], y,
                     aa(sched.leaf_centers), aa(sched.leaf_mask))
    phi = np.zeros(len(tree.x))
    np.add.at(phi, sched.leaf_idx.ravel(),
              np.where(sched.leaf_valid.ravel(),
                       np.asarray(vals, np.float64).ravel(), 0.0))
    return phi


def m2l_apply(ops, M, plan: InteractionPlan, asarray=None) -> jnp.ndarray:
    """Execute the plan's padded M2L list against multipoles M."""
    aa = device_hook(asarray)
    M = aa(M, jnp.float32)
    if plan.n_m2l == 0:
        return jnp.zeros((plan.n_tgt_cells, ops.nk), jnp.float32)
    return _m2l_scatter(ops, M[aa(plan.m2l_b)], aa(plan.m2l_d),
                        aa(plan.m2l_a), aa(plan.m2l_mask),
                        n_cells=plan.n_tgt_cells)


def m2l_pass(ops, M, tgt_tree, src_tree, pairs) -> jnp.ndarray:
    plan = build_interaction_subset(tgt_tree, src_tree, m2l_pairs=pairs)
    return m2l_apply(ops, M, plan)


def build_interaction_subset(tgt_tree, src_tree, m2l_pairs=None,
                             p2p_pairs=None, m2p_pairs=None) -> InteractionPlan:
    """Plan just the supplied pair lists (compat shim for the pair-based API)."""
    empty = np.zeros((0, 2), dtype=np.int64)
    return build_interaction_plan(
        tgt_tree, src_tree,
        m2l_pairs=(empty if m2l_pairs is None else m2l_pairs),
        p2p_pairs=(empty if p2p_pairs is None else p2p_pairs),
        m2p_pairs=m2p_pairs)


def p2p_apply(tgt_tree, src_tree, plan: InteractionPlan,
              use_kernels: bool = False, asarray=None,
              use_pallas: bool | None = None) -> np.ndarray:
    """Execute the plan's bucketed P2P blocks.  Each block's source width is
    sized to its own leaves, so a grafted LET's one big boundary leaf no
    longer inflates every pair's padding."""
    use_kernels = _resolve_kernels(use_kernels, use_pallas, "p2p_apply")
    phi = np.zeros(plan.n_tgt_bodies)
    if plan.n_p2p == 0:
        return phi
    aa = device_hook(asarray)
    xt_all = aa(tgt_tree.x, jnp.float32)
    xs_all = aa(src_tree.x, jnp.float32)
    qs_all = aa(src_tree.q, jnp.float32)
    for blk in plan.p2p_blocks:
        xt = xt_all[aa(blk.t_idx)]
        xs = xs_all[aa(blk.s_idx)]
        qs = jnp.where(aa(blk.s_valid), qs_all[aa(blk.s_idx)], 0.0)
        if use_kernels:
            from repro.kernels.ops import p2p_auto
            vals = np.asarray(p2p_auto(qs, xs, xt)) * blk.mask[:, None]
        else:
            vals = np.asarray(_p2p_vals(xt, xs, qs, aa(blk.mask)))
        np.add.at(phi, blk.t_idx.ravel(),
                  np.where(blk.t_valid.ravel(),
                           vals.astype(np.float64).ravel(), 0.0))
    return phi


def p2p_pass(tgt_tree: Tree, src_tree, pairs, use_kernels: bool = False,
             use_pallas: bool | None = None) -> np.ndarray:
    use_kernels = _resolve_kernels(use_kernels, use_pallas, "p2p_pass")
    plan = build_interaction_subset(tgt_tree, src_tree, p2p_pairs=pairs)
    return p2p_apply(tgt_tree, src_tree, plan, use_kernels=use_kernels)


def m2p_apply(tgt_tree, src_M, plan: InteractionPlan, p: int = 4,
              asarray=None) -> np.ndarray:
    """Execute the plan's padded M2P fallback list (truncated remote cells
    that fail the MAC against a large local leaf)."""
    ops = get_operators(p)
    phi = np.zeros(plan.n_tgt_bodies)
    if plan.n_m2p == 0:
        return phi
    aa = device_hook(asarray)
    y = aa(tgt_tree.x, jnp.float32)[aa(plan.m2p_t_idx)]
    M = aa(src_M, jnp.float32)[aa(plan.m2p_b)]
    vals = np.asarray(_m2p_vals(ops, M, y, aa(plan.m2p_centers),
                                aa(plan.m2p_mask)))
    np.add.at(phi, plan.m2p_t_idx.ravel(),
              np.where(plan.m2p_t_valid.ravel(),
                       vals.astype(np.float64).ravel(), 0.0))
    return phi


def m2p_pass(tgt_tree: Tree, src_M, src_centers, pairs, p: int = 4) -> np.ndarray:
    if len(pairs) == 0:
        return np.zeros(len(tgt_tree.x))
    src = SimpleNamespace(center=src_centers)   # the planner only needs centers
    plan = build_interaction_subset(tgt_tree, src, m2p_pairs=pairs)
    return m2p_apply(tgt_tree, src_M, plan, p=p)


# ------------------------------------------------------- plan execution ----
def execute_fmm_plan(plan: FMMPlan, use_kernels: bool = False,
                     M=None, asarray=None,
                     use_pallas: bool | None = None) -> np.ndarray:
    """Evaluate a prebuilt FMMPlan: kernels + gathers only, no host-side list
    construction or padding.  `M` overrides the source multipoles (grafted
    LETs ship theirs; locally they are rebuilt from the plan's schedules).
    `asarray` optionally memoizes host->device uploads (api.DeviceMemo)."""
    use_kernels = _resolve_kernels(use_kernels, use_pallas, "execute_fmm_plan")
    ops = get_operators(plan.p)
    inter = plan.interactions
    if M is None:
        if plan.src_sched is not None:
            M = upward_pass(plan.src_tree, ops, sched=plan.src_sched,
                            asarray=asarray)
        else:
            M = plan.src_tree.M           # grafted LET: shipped multipoles
    L = m2l_apply(ops, M, inter, asarray=asarray)
    L = downward_pass(plan.tgt_tree, ops, L, sched=plan.tgt_sched,
                      asarray=asarray)
    phi = l2p_pass(plan.tgt_tree, ops, L, sched=plan.tgt_sched, asarray=asarray)
    phi += p2p_apply(plan.tgt_tree, plan.src_tree, inter,
                     use_kernels=use_kernels, asarray=asarray)
    if inter.n_m2p:
        phi += m2p_apply(plan.tgt_tree, M, inter, p=plan.p, asarray=asarray)
    return phi


def evaluate(tgt_tree: Tree, src_tree: Tree, theta: float = 0.5, p: int = 4,
             m2l_pairs=None, p2p_pairs=None, use_kernels: bool = False,
             plan: FMMPlan | None = None,
             use_pallas: bool | None = None) -> np.ndarray:
    """Potential at tgt_tree bodies (sorted order) due to src_tree bodies.
    Pass a prebuilt `plan` (see plan.build_fmm_plan) to skip all host-side
    geometry work."""
    use_kernels = _resolve_kernels(use_kernels, use_pallas, "evaluate")
    if plan is None:
        plan = build_fmm_plan(tgt_tree, src_tree, theta=theta, p=p,
                              m2l_pairs=m2l_pairs, p2p_pairs=p2p_pairs)
    return execute_fmm_plan(plan, use_kernels=use_kernels)


def fmm_potential(x, q, theta: float = 0.5, ncrit: int = 64, p: int = 4,
                  use_kernels: bool = False,
                  use_pallas: bool | None = None) -> np.ndarray:
    """FMM potential in the *original* body order."""
    use_kernels = _resolve_kernels(use_kernels, use_pallas, "fmm_potential")
    tree = build_tree(x, q, ncrit=ncrit)
    phi_sorted = evaluate(tree, tree, theta=theta, p=p,
                          use_kernels=use_kernels)
    out = np.empty_like(phi_sorted)
    out[tree.perm] = phi_sorted
    return out

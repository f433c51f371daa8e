"""Interaction plans: the plan/execute split for the FMM host pipeline.

Architecture: plan -> schedule -> dist exchange -> engine -> executable cache
-----------------------------------------------------------------------------
Every FMM evaluation decomposes into two very different kinds of work —
**plan construction** (this module: dual-tree traversal, pair-list padding
and bucketing, leaf body-gather index tables, per-level upward/downward
schedules) and **plan execution** (JAX kernels gathering through the
precomputed index tables with no list construction and no padding work).
Execution comes in two tiers: the per-tree *reference* executors
(`fmm.execute_fmm_plan` and the `*_pass` functions, one launch per tree per
pass) and the *batched device engine* (repro.core.engine), which stacks
every partition's frozen tables into `(n_parts, ...)` envelopes and runs
each phase for the whole geometry in a single launch — one vmapped
multi-tree upward pass, one segment-summed M2L over all (receiver, sender)
pairs, and Pallas-bucketed P2P with autotuned block sizes.

Since the device-resident traversal tier (engine/traversal.py), plan
*construction* itself is backend-split: `traversal_backend="device"` runs
the dual-traversal frontier loop as one `jax.lax.while_loop` program with a
Pallas MAC kernel scoring whole frontiers per launch — emitting the exact
pair lists (same order, same sets) the NumPy reference produces, plus the
minimum accepted-M2L margin the MAC-slack revalidation consumes.  The host
loop in core/traversal.py survives as the f64 *reference*: it is the CPU
default, the precision anchor the f32 device decisions are golden-tested
against, and the fallback wherever no accelerator exists.  Padding,
bucketing and gather-table construction stay NumPy here either way.

The distributed pipeline composes those tiers (repro.core.api), one per
independent axis of the paper plus the hardware floor:

  1. `plan_geometry(x, q, PartitionSpec) -> GeometryPlan` — partitioning,
     completely local trees, batched sender-side LET extraction and every
     receiver's frozen `InteractionPlan`s, built ONCE with no protocol
     argument.  This is the expensive geometry work — traversal on the
     accelerator when one is present — and exactly the "communication
     metadata" Kailasa et al. precompute before any evaluation.
  2. `schedule_comm(geometry, protocol, ...) -> CommSchedule` — a cheap pure
     function over the frozen bytes matrix and Lemma-1 adjacency boxes
     (protocols.py), so sweeping all four exchange protocols reuses one
     `GeometryPlan` with zero re-extraction.
  3. `repro.core.dist` — the exchange tier: when a `shard_map` mesh is
     present (`FMMSession(mesh=...)`; `launch.mesh.host_device_mesh(n)` for
     virtual CPU devices), the stacked envelopes shard over a 1-D rank axis
     and the CommSchedule's modeled transfers execute as real collective
     programs — bulk `all_to_all`, granularity-tuned `ppermute` rounds, or
     the HSDX relay tree — over a frozen wire layout whose span bytes equal
     the `GeometryPlan` bytes matrix exactly (layout.py / programs.py /
     engine.ShardedEngine).  Without a mesh this tier is skipped and the
     schedules remain LogGP-modeled only.
  4. `engine.DeviceEngine(geometry)` — the execution tier: payload-
     independent stacked index tables compiled once per geometry, LET
     indices translated to sender-global device ids (no LET payload ever
     materializes on the host).  Within-slack timesteps upload ONE new_x
     array, revalidate every partition's MAC slack in one batched drift
     launch, adopt the device-restacked payload, and recompute drifting
     multipoles on device.  With x64 enabled the f64 phi accumulation also
     stays on device and returns a single (N,) array; otherwise f64
     accumulation happens once on the host at the API boundary.
  5. `engine.fused` + `engine.exe_cache` — the serving tier: the per-phase
     launches collapse into ONE donated entry computation per warm
     `evaluate()` / within-slack `step()` (`fused=` flag), and the
     AOT-compiled executable (`jax.jit(...).lower(...).compile()`) is
     cached by *shape class* — padded table dims, device dtypes/x64,
     theta-bucket, backend, kernel statics (`schedules
     .shape_class_digest`) — so a new geometry of an already-seen shape
     class pays zero XLA compile time.  Donation-vs-residency contract:
     memoized `DeviceMemo` table views are never donated (a donated buffer
     is deleted, poisoning the memo); per-call payload buffers are always
     donated and threaded through to outputs for input-output aliasing.
  6. `FMMSession` — orchestration: memoized device views, protocol sweeps
     from a single evaluation, `.step(new_x)` MAC-slack revalidation that
     rebuilds only invalidated partitions, engine/reference dispatch
     (`engine=` flag, default on when a device backend is present), the
     fused/per-phase knob (`fused=`, `exe_cache_stats`), and multi-device
     dispatch (`mesh=`, `dist_protocol=`, `exchange_stats`).

Threaded through all six tiers — not a tier of its own — is the
observability layer (`repro.obs`): nested wall-time spans around plan
construction, the step's host stages, engine uploads and exchanges, each
also a `jax.profiler` annotation so a profile shows it on the device ops'
clock; named device phases inside the fused launch (`jax.named_scope`,
read back by `FMMSession.device_phases()`); a process-wide metrics
registry absorbing the scattered counters (memo hits, cache misses,
autotune decisions, host-to-device bytes, live vs padded M2L pairs and
stream tiles); and mesh-session probes comparing measured exchange time
against the LogGP prediction (`model_drift`).  Disabled — the default — it
costs one global load per call site; `FMMSession.report()` and a
`jax.profiler` trace are the read side.

Also cross-cutting is the resilience tier (`repro.resilience`): named fault
seams threaded through the stack (`faults.fire(site)` — autotune cache I/O,
XLA compilation, stream-table build, Pallas launches, memo uploads, exchange
-program builds, fused launches) and a degradation ladder the session walks
when a rung fails (`fallback.LADDER`): dist exchange -> streaming Pallas ->
gathered Pallas -> XLA slab -> per-phase engine -> host f64 reference.
Transient failures retry with deterministic backoff; every downgrade is
ledgered (`resilience.fallback` counters, warn-once, the `degraded` flag in
`report()["resilience"]`); ladder exhaustion raises a typed
`ResilienceError` naming the failing site.  Like obs, disabled costs one
global load + None test per seam (`REPRO_FAULTS=` / `REPRO_RESILIENCE=` /
`FMMSession(resilience=...)` are the switches).

Streaming vs gathered P2P.  The engine evaluates the near field one of two
ways.  The *gathered* path (`engine/p2p.p2p_bucket_vals`) materializes each
width-class bucket's `(pairs, S, 3)`/`(pairs, S)` operands via XLA gathers
before its launch — robust to any index pattern, but one HBM round-trip per
bucket.  The *streaming* path (`engine/schedules.build_p2p_stream_tables` +
`kernels/p2p_stream`) concatenates ALL width classes into one unified tile
table `[src_base, src_lo, src_hi, tgt_base]` (slab starts 128-aligned, as
HBM DMAs require) and runs one grid that gathers source/target slabs
inside the kernel as double-buffered VMEM DMAs.
It is only legal because this module's gather tables make every bucket
row's flat ids a contiguous run (`padded_body_gather` emits
`body_start + arange`, and the engine's LET translation preserves per-leaf
runs); `build_p2p_stream_tables` verifies that invariant at build time and
returns None on violation, falling back to gathered buckets — correctness
never depends on the fast path.  Selection: `FMMSession(p2p_stream=...)`,
default on iff the backend is TPU (`engine.default_p2p_stream`); with
`use_kernels=False` the same unified table runs as one XLA slab program
(`p2p_stream_gathered`), the CPU/CI route.  VMEM budget: scratch is
`n_buffers * 4 * (src_width(smax) + block_t)` f32s (SoA [x;y;z;q] source +
target slabs); on real backends the `(block_t, n_buffers)` autotune
(`kernels.p2p.best_stream_params`) times every candidate, and
`tests/test_tpu_compile.py` compiles each one for a v5e.

A plan is built once and executed many times — time-stepped N-body where
geometry changes slowly, or protocol sweeps over the same partitioning —
which is what makes the host side disappear from the hot path.  All plan
dataclasses are frozen: a plan is immutable geometry metadata.  Device
residency of the frozen tables is the session/engine concern
(api.DeviceMemo threads through the executors' `asarray` hook, and the
engine's stacked tables ride the same memo).

Key structures:

  - `InteractionPlan` — padded M2L pair arrays (with precomputed f32
    displacement vectors), P2P pair *blocks bucketed by source-leaf width*
    (one huge boundary leaf in a grafted LET no longer forces every pair to
    pad to the global maximum — the O(pairs × max_leaf²) blowup the seed's
    single-width padding had), and padded M2P fallback pairs.
  - `TreeSchedules` — padded leaf gathers plus per-level (ids, parents,
    displacement) arrays shared by the upward and downward passes.
  - `FMMPlan` — one (target tree, source tree) evaluation: interactions +
    both trees' schedules.

All pad widths and bucket sizes are powers of two so the jitted kernels hit
the JIT cache across trees, partitions and LET pairs.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.traversal import dual_traversal
from repro.core.tree import bucket_size

__all__ = [
    "P2PBlock", "InteractionPlan", "LevelSchedule", "TreeSchedules", "FMMPlan",
    "bucket_size", "pad_pairs", "pad_ids", "padded_body_gather",
    "build_p2p_blocks", "build_interaction_plan", "build_tree_schedules",
    "build_fmm_plan",
]

_EMPTY_PAIRS = np.zeros((0, 2), dtype=np.int64)


# ------------------------------------------------------- padding helpers ---
# bucket_size lives in tree.py (one power-of-two rule for plan padding and
# device cell tables alike) and is re-exported here for its historic callers.
def pad_pairs(pairs: np.ndarray):
    """Pad a (n, 2) pair list to a power-of-2 bucket.  Padding replicates the
    first pair: indices stay valid (root cells can be huge) and M2L
    displacements stay nonzero; the mask zeroes the values."""
    n = len(pairs)
    m = bucket_size(max(n, 1))
    out = np.tile(pairs[0], (m, 1)).astype(np.int64) if n else np.zeros((m, 2), np.int64)
    out[:n] = pairs
    mask = np.zeros(m, dtype=np.float32)
    mask[:n] = 1.0
    return out, mask


def pad_ids(ids: np.ndarray, pad_value: int | None = None):
    n = len(ids)
    m = bucket_size(max(n, 1))
    fill = (ids[0] if (pad_value is None and n) else (pad_value or 0))
    out = np.full(m, fill, dtype=np.int64)
    out[:n] = ids
    mask = np.zeros(m, dtype=np.float32)
    mask[:n] = 1.0
    return out, mask


def padded_body_gather(tree, cells: np.ndarray, width: int):
    """(len(cells), width) body gather table: clipped-safe indices + validity
    mask, built with one broadcast (no per-cell loop)."""
    nb = np.asarray(tree.n_body)[cells]
    if width < 1 or int(nb.max(initial=0)) > width:
        # never truncate silently (matches Tree.padded_leaf_bodies)
        raise ValueError("padded_body_gather: cell population exceeds gather width")
    col = np.arange(width, dtype=np.int64)
    idx = np.asarray(tree.body_start)[cells][:, None] + col[None, :]
    valid = col[None, :] < nb[:, None]
    return np.where(valid, idx, 0), valid


# ------------------------------------------------------------ dataclasses --
@dataclass(frozen=True)
class P2PBlock:
    """One bucket of P2P leaf pairs whose source leaves share a padded width."""
    n: int                   # valid pairs
    mask: np.ndarray         # (B,) float32
    t_idx: np.ndarray        # (B, wt) clipped-safe target body gather
    t_valid: np.ndarray      # (B, wt) bool
    s_idx: np.ndarray        # (B, ws) clipped-safe source body gather
    s_valid: np.ndarray      # (B, ws) bool

    @property
    def shape(self):
        return (len(self.mask), self.t_idx.shape[1], self.s_idx.shape[1])


@dataclass(frozen=True)
class InteractionPlan:
    """Padded, bucketed interaction lists for one (target, source) tree pair."""
    n_tgt_cells: int
    n_tgt_bodies: int
    # M2L: padded pair arrays + precomputed displacement vectors
    n_m2l: int
    m2l_a: np.ndarray        # (B,) padded target cell ids
    m2l_b: np.ndarray        # (B,) padded source cell ids
    m2l_mask: np.ndarray     # (B,) float32
    m2l_d: np.ndarray        # (B, 3) float32  tgt_center - src_center
    # P2P: blocks bucketed by source-leaf width
    n_p2p: int
    p2p_blocks: tuple
    # M2P fallback (truncated LET cells vs large local leaves)
    n_m2p: int
    m2p_b: np.ndarray        # (B,) padded source cell ids
    m2p_mask: np.ndarray     # (B,) float32
    m2p_centers: np.ndarray  # (B, 3) float32 source centers
    m2p_t_idx: np.ndarray    # (B, wt)
    m2p_t_valid: np.ndarray  # (B, wt) bool


@dataclass(frozen=True)
class LevelSchedule:
    """One tree level's padded (ids, parents, displacement) arrays — used by
    M2M (child -> parent) and L2L (parent -> child) alike."""
    ids: np.ndarray          # (B,) padded cell ids
    parents: np.ndarray      # (B,)
    mask: np.ndarray         # (B,) float32
    d: np.ndarray            # (B, 3) float32  center[ids] - center[parents]


@dataclass(frozen=True)
class TreeSchedules:
    """Charge-independent schedules for one tree's vertical passes."""
    n_cells: int
    leaves: np.ndarray       # (B,) padded leaf ids
    leaf_mask: np.ndarray    # (B,) float32
    leaf_centers: np.ndarray # (B, 3) float32
    leaf_idx: np.ndarray     # (B, w) clipped-safe body gather
    leaf_valid: np.ndarray   # (B, w) bool
    levels: tuple            # LevelSchedule per level 1..max (top-down order)


@dataclass(frozen=True)
class FMMPlan:
    """Everything needed to evaluate src -> tgt repeatedly with zero host-side
    list construction: build once with `build_fmm_plan`, execute many times
    with `fmm.execute_fmm_plan`."""
    tgt_tree: object
    src_tree: object
    theta: float
    p: int
    interactions: InteractionPlan
    tgt_sched: TreeSchedules
    src_sched: object        # TreeSchedules, or None for grafted LETs
                             # (their multipoles arrive precomputed)


# --------------------------------------------------------------- builders --
def build_p2p_blocks(tgt_tree, src_tree, pairs: np.ndarray,
                     tgt_width: int | None = None) -> tuple:
    """Bucket P2P pairs by power-of-two source-leaf width.

    This replaces the seed's single global source width
    (`src_tree.ncrit == n_body.max()` for grafted LETs), which padded every
    pair to the largest boundary leaf.  Pairs whose source leaves hold 5 and
    500 bodies now land in separate (8-wide and 512-wide) blocks."""
    if len(pairs) == 0:
        return ()
    wt = tgt_width or bucket_size(max(int(tgt_tree.ncrit), 1), lo=8)
    src_nb = np.asarray(src_tree.n_body)[pairs[:, 1]]
    widths = np.maximum(8, 2 ** np.ceil(np.log2(np.maximum(src_nb, 1))).astype(np.int64))
    blocks = []
    for w in np.unique(widths):
        sub = pairs[widths == w]
        padded, mask = pad_pairs(sub)
        t_idx, t_valid = padded_body_gather(tgt_tree, padded[:, 0], wt)
        s_idx, s_valid = padded_body_gather(src_tree, padded[:, 1], int(w))
        blocks.append(P2PBlock(n=len(sub), mask=mask, t_idx=t_idx,
                               t_valid=t_valid, s_idx=s_idx, s_valid=s_valid))
    return tuple(blocks)


def build_interaction_plan(tgt_tree, src_tree, theta: float = 0.5,
                           with_m2p: bool = False,
                           m2l_pairs=None, p2p_pairs=None,
                           m2p_pairs=None,
                           traversal_backend: str | None = None) -> InteractionPlan:
    """Traverse (unless pair lists are supplied) and freeze the padded /
    bucketed interaction lists for one (target, source) tree pair.

    `traversal_backend` selects where the dual traversal runs: "host" (the
    NumPy frontier reference, the default on CPU), "device" (the
    `jax.lax.while_loop` + Pallas MAC program of repro.core.engine.traversal,
    the default on accelerator backends), or None/"auto"."""
    if m2l_pairs is None or p2p_pairs is None:
        from repro.core.engine.traversal import resolve_traversal_backend
        if resolve_traversal_backend(traversal_backend) == "device":
            from repro.core.engine.traversal import device_dual_traversal
            m2l_pairs, p2p_pairs, m2p_d, _ = device_dual_traversal(
                tgt_tree, src_tree, theta, with_m2p=with_m2p)
            if with_m2p:
                m2p_pairs = m2p_d
        elif with_m2p:
            m2l_pairs, p2p_pairs, m2p_pairs = dual_traversal(
                tgt_tree, src_tree, theta, with_m2p=True)
        else:
            m2l_pairs, p2p_pairs = dual_traversal(tgt_tree, src_tree, theta)
    m2l_pairs = np.asarray(m2l_pairs, dtype=np.int64).reshape(-1, 2)
    p2p_pairs = np.asarray(p2p_pairs, dtype=np.int64).reshape(-1, 2)
    m2p_pairs = (np.asarray(m2p_pairs, dtype=np.int64).reshape(-1, 2)
                 if m2p_pairs is not None else _EMPTY_PAIRS)

    wt = bucket_size(max(int(tgt_tree.ncrit), 1), lo=8)

    m2l_p, m2l_mask = pad_pairs(m2l_pairs)
    m2l_d = (np.asarray(tgt_tree.center)[m2l_p[:, 0]]
             - np.asarray(src_tree.center)[m2l_p[:, 1]]).astype(np.float32)

    p2p_blocks = build_p2p_blocks(tgt_tree, src_tree, p2p_pairs, tgt_width=wt)

    if len(m2p_pairs):
        m2p_p, m2p_mask = pad_pairs(m2p_pairs)
        m2p_t_idx, m2p_t_valid = padded_body_gather(tgt_tree, m2p_p[:, 0], wt)
        m2p_centers = np.asarray(src_tree.center)[m2p_p[:, 1]].astype(np.float32)
    else:
        m2p_p = np.zeros((0, 2), dtype=np.int64)
        m2p_mask = np.zeros(0, dtype=np.float32)
        m2p_t_idx = np.zeros((0, wt), dtype=np.int64)
        m2p_t_valid = np.zeros((0, wt), dtype=bool)
        m2p_centers = np.zeros((0, 3), dtype=np.float32)

    return InteractionPlan(
        n_tgt_cells=int(tgt_tree.n_cells),
        n_tgt_bodies=len(tgt_tree.x),
        n_m2l=len(m2l_pairs), m2l_a=m2l_p[:, 0], m2l_b=m2l_p[:, 1],
        m2l_mask=m2l_mask, m2l_d=m2l_d,
        n_p2p=len(p2p_pairs), p2p_blocks=p2p_blocks,
        n_m2p=len(m2p_pairs), m2p_b=m2p_p[:, 1], m2p_mask=m2p_mask,
        m2p_centers=m2p_centers, m2p_t_idx=m2p_t_idx, m2p_t_valid=m2p_t_valid,
    )


def build_tree_schedules(tree) -> TreeSchedules:
    """Freeze the leaf gathers and per-level M2M/L2L index arrays of a tree."""
    leaves, leaf_mask = pad_ids(tree.leaves)
    w = bucket_size(max(int(tree.ncrit), 1), lo=8)
    leaf_idx, leaf_valid = padded_body_gather(tree, leaves, w)
    leaf_centers = np.asarray(tree.center)[leaves].astype(np.float32)
    levels = []
    for lvl in range(1, int(tree.level.max()) + 1):
        ids = np.nonzero(tree.level == lvl)[0]
        if len(ids) == 0:
            continue
        ids_p, mask = pad_ids(ids)
        parents = np.asarray(tree.parent)[ids_p]
        d = (np.asarray(tree.center)[ids_p]
             - np.asarray(tree.center)[parents]).astype(np.float32)
        levels.append(LevelSchedule(ids=ids_p, parents=parents, mask=mask, d=d))
    return TreeSchedules(
        n_cells=int(tree.n_cells), leaves=leaves, leaf_mask=leaf_mask,
        leaf_centers=leaf_centers, leaf_idx=leaf_idx, leaf_valid=leaf_valid,
        levels=tuple(levels),
    )


def build_fmm_plan(tgt_tree, src_tree, theta: float = 0.5, p: int = 4,
                   with_m2p: bool = False,
                   m2l_pairs=None, p2p_pairs=None, m2p_pairs=None,
                   traversal_backend: str | None = None) -> FMMPlan:
    """Build the full plan for evaluating src_tree -> tgt_tree."""
    interactions = build_interaction_plan(
        tgt_tree, src_tree, theta=theta, with_m2p=with_m2p,
        m2l_pairs=m2l_pairs, p2p_pairs=p2p_pairs, m2p_pairs=m2p_pairs,
        traversal_backend=traversal_backend)
    tgt_sched = build_tree_schedules(tgt_tree)
    if src_tree is tgt_tree:
        src_sched = tgt_sched
    elif hasattr(src_tree, "level"):
        src_sched = build_tree_schedules(src_tree)
    else:                    # grafted LET: multipoles are shipped, not built
        src_sched = None
    return FMMPlan(tgt_tree=tgt_tree, src_tree=src_tree, theta=theta, p=p,
                   interactions=interactions, tgt_sched=tgt_sched,
                   src_sched=src_sched)

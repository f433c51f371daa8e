"""Layered distributed-FMM API: GeometryPlan -> CommSchedule -> FMMSession.

The paper's contributions are independent axes — partitioning (§3),
communication granularity (§4.1) and exchange protocol (§4.2–4.3) — and this
facade keeps them composable instead of entangled:

  1. `plan_geometry(x, q, PartitionSpec) -> GeometryPlan` — ALL host-side
     geometry, built once with no protocol argument: partitioning, completely
     local trees, batched sender-side LET extraction (`extract_lets` runs
     exactly once per sender for all P-1 remote boxes), per-receiver frozen
     interaction plans against every grafted subtree, and the (P, P) bytes
     matrix.
  2. `schedule_comm(geometry, protocol, ...) -> CommSchedule` — a cheap pure
     function over the frozen bytes matrix and Lemma-1 adjacency boxes.
     Sweeping all four protocols reuses one `GeometryPlan` with zero
     re-partitioning, re-treeing or re-extraction.
  3. `FMMSession` — holds a `GeometryPlan` plus memoized device-resident
     views of its frozen NumPy index tables (`DeviceMemo`: every table is
     uploaded exactly once, so executions after the first perform zero
     host->device transfers of plan tables).  `.potentials(protocol=...)`
     evaluates once per geometry version, `.sweep()` serves all protocols
     from that one evaluation, and `.step(new_x)` revalidates the cached
     plan through MAC slack margins and rebuilds only invalidated
     partitions (time-stepped N-body with slowly drifting geometry).
     Evaluation dispatches to the batched device engine
     (repro.core.engine.DeviceEngine — one launch per FMM phase for the
     whole geometry, Pallas-bucketed P2P, multipoles device-resident across
     steps) when `engine=True` (default on device backends), else to the
     per-partition reference executor `execute_geometry`.

MAC slack revalidation (`FMMSession.step`)
------------------------------------------
Every structural decision in a plan is a strict inequality with a margin:
M2L pairs were accepted with  R_A + R_B < theta * d  (margin
m = theta*d - R_A - R_B > 0) and LET truncations with  2R < theta * dist
(margin theta*dist - 2R).  If every body of a partition moves by at most
delta, tight-cell centers shift and radii grow by at most sqrt(3) * delta,
so a sufficient condition for every accepted decision of a pair (i, j) to
remain valid is  delta_i + delta_j <= m / (sqrt(3) * (1 + theta)).  The
per-partition slack budget is therefore

    slack_j = min(margins touching j) / (2 * sqrt(3) * (1 + theta))

(the factor 2 splits the pair budget).  A partition whose drift since the
plan's reference positions stays within its slack keeps its tree topology,
interaction lists and LET structure; only the numeric payload (coordinates,
charges, multipoles) is rebound — expansion centers deliberately stay at
their build-time positions, which keeps P2M/M2L/L2P mutually consistent
while the slack bounds the extra truncation error.  A partition that
exceeds its slack is rebuilt, together with every LET and receiver plan
that touches it; untouched partitions are reused as-is.

The legacy entry points `run_distributed_fmm` / `build_distributed_plan`
(repro.core.distributed_fmm) are deprecated shims over these layers, pinned
byte-identical by golden tests.
"""
from __future__ import annotations

import math
import os
import weakref
from dataclasses import dataclass, field, replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import protocols as proto
from repro.resilience import fallback as _rfb
from repro.resilience import faults as _rfaults
from repro.core.fmm import (_resolve_kernels, downward_pass, l2p_pass,
                            m2l_apply, m2p_apply, p2p_apply, upward_pass)
from repro.core.hsdx import adjacency_from_boxes, graph_diameter
from repro.core.let import LETData, extract_lets, graft, refresh_let
from repro.core.multipole import get_operators
from repro.core.partition.hot import hot_partition
from repro.core.partition.orb import orb_partition
from repro.core.plan import (InteractionPlan, TreeSchedules,
                             build_interaction_plan, build_tree_schedules)
from repro.core.tree import build_tree

__all__ = ["PartitionSpec", "GeometryPlan", "CommSchedule", "SessionResult",
           "StepReport", "RemoteBlock", "ReceiverPlan", "DeviceMemo",
           "plan_geometry", "schedule_comm", "execute_geometry", "FMMSession",
           "sync_host_multipoles", "DEFAULT_SFC_BOX_INFLATION"]

# default eps-inflation of SFC partitions' tight boxes when deriving the
# adjacency graph (fraction of the global span); ORB regions share split
# planes exactly and need no inflation
DEFAULT_SFC_BOX_INFLATION = 0.03

_EMPTY_LO, _EMPTY_HI = np.inf, -np.inf      # empty-partition box sentinel


# ------------------------------------------------------------------ specs --
@dataclass(frozen=True)
class PartitionSpec:
    """Protocol-independent geometry parameters: everything `plan_geometry`
    needs, and nothing `schedule_comm` cares about.

    `traversal_backend`: where dual traversal + MAC margin scoring run —
    "host" (NumPy frontier reference), "device" (lax.while_loop + Pallas MAC
    kernel, repro.core.engine.traversal), or None/"auto" (device whenever an
    accelerator backend is present, host on CPU)."""
    nparts: int = 8
    method: str = "orb"          # "orb" | "hilbert" | "morton"
    theta: float = 0.5
    ncrit: int = 64
    p: int = 4
    sfc_box_inflation: float = DEFAULT_SFC_BOX_INFLATION
    traversal_backend: str | None = None


@dataclass
class RemoteBlock:
    """One sender's grafted LET at one receiver: the frozen interaction plan
    plus the minimum M2L MAC margin (absolute units) for slack revalidation."""
    sender: int
    graft: object                # let._GraftedTree view over lets[(sender, j)]
    inter: InteractionPlan
    margin: float


@dataclass
class ReceiverPlan:
    """One partition's frozen receiver-side geometry."""
    tree: object
    sched: TreeSchedules
    local: InteractionPlan       # own tree vs own tree
    local_margin: float
    remote: list                 # [RemoteBlock], ascending sender id


@dataclass
class GeometryPlan:
    """Layer 1: every protocol-independent artifact, built once per geometry.

    Frozen in spirit — nothing mutates a GeometryPlan in place;
    `FMMSession.step` derives a successor that shares all untouched
    components and bumps `version`."""
    spec: PartitionSpec
    n: int
    x0: np.ndarray               # (N, 3) current positions, original order
    q0: np.ndarray               # (N,)   current charges
    x_ref: np.ndarray            # (N, 3) positions each partition's structure
                                 #        was built from (slack reference)
    part: np.ndarray
    owners: list                 # per-partition original body indices
    boxes: np.ndarray            # (P, 2, 3) tight boxes (empty => sentinel)
    adj_boxes: np.ndarray        # (P, 2, 3) Lemma-1 adjacency boxes
    trees: list                  # Tree per partition (None if empty)
    scheds: list                 # TreeSchedules per partition (None if empty)
    Ms: list                     # per-partition multipoles, NumPy (None if empty)
    lets: dict                   # (i, j) -> LETData
    receivers: list              # ReceiverPlan per partition (None if empty)
    bytes_matrix: np.ndarray     # (P, P) LET bytes i -> j
    adjacency_degree: float
    diameter: int
    slack: np.ndarray            # (P,) per-partition MAC drift budget
    partition_stats: dict = field(default_factory=dict)
    version: int = 0
    # Partitions whose *host-side* numeric mirrors (Ms, LET payloads, grafted
    # views) are deferred: an engine-backed session recomputes multipoles on
    # device during within-slack steps, so the NumPy mirrors are only filled
    # when the reference path actually needs them (sync_host_multipoles).
    # Structure, margins, slack and the bytes matrix are never stale.
    Ms_stale: tuple = ()

    @property
    def nparts(self) -> int:
        return self.spec.nparts

    @property
    def theta(self) -> float:
        return self.spec.theta

    @property
    def p(self) -> int:
        return self.spec.p


@dataclass(frozen=True)
class CommSchedule:
    """Layer 2: one protocol's schedule over a frozen GeometryPlan."""
    protocol: str
    schedule: proto.Schedule
    stats: dict
    loggp_time: float
    grain_bytes: int | None

    @property
    def n_stages(self) -> int:
        return self.schedule.n_stages


@dataclass(frozen=True)
class SessionResult:
    """One protocol's end-to-end answer: the (shared) potential plus this
    protocol's communication accounting."""
    phi: np.ndarray
    protocol: str
    comm: CommSchedule
    bytes_matrix: np.ndarray
    partition_stats: dict
    adjacency_degree: float
    diameter: int

    @property
    def schedule_stats(self) -> dict:
        return self.comm.stats

    @property
    def loggp_time(self) -> float:
        return self.comm.loggp_time

    @property
    def n_stages(self) -> int:
        return self.comm.n_stages


@dataclass(frozen=True)
class StepReport:
    """What `FMMSession.step` did: which partitions kept their cached
    structure, which were numerically refreshed, which were rebuilt."""
    cache_hit: bool              # True iff nothing changed at all
    rebuilt: tuple               # partitions whose drift exceeded their slack
    refreshed: tuple             # structure kept; payload rebound
    shift: tuple                 # per-partition max drift vs x_ref
    slack: tuple                 # per-partition budget the shift was tested against
    version: int                 # geometry version after the step


# ------------------------------------------------------------ device memo --
class DeviceMemo:
    """Memoized host->device uploads keyed by (array identity, dtype).

    Drop-in for `jnp.asarray` in the fmm executors: the first execution
    uploads each frozen plan table once; later executions reuse the cached
    device view (zero transfers).  Entries are anchored by a *weak*
    reference to the host array: while the array lives, `id()` stays unique
    and the view is served from cache; when a `step` replaces it (new
    positions, multipoles, LET payloads) and the old geometry is dropped,
    the entry self-evicts — long-running sessions do not accumulate stale
    host or device buffers.

    Hook contract (`asarray=` in the executors and the engine)
    ----------------------------------------------------------
    Any replacement hook MUST return a **device array** (`jax.Array`) for
    every call — `hook(arr)` and `hook(arr, dtype)`.  Returning a NumPy
    array (or any host view) would type-check downstream, but every jitted
    kernel call would silently re-upload it, defeating the memoization the
    hook exists for and turning the "zero transfers after warmup" guarantee
    into a per-call transfer.  The executors therefore wrap every hook in
    `fmm.device_hook`, which raises `TypeError` on a non-`jax.Array` return
    instead of degrading silently.  `misses` counts actual uploads and
    `hits` counts served cache views, so `misses` is the session's
    host->device transfer meter (tests pin it).

    NOTE: the executors call `jnp.asarray if asarray is None else asarray`
    — never `asarray or jnp.asarray`, which would silently drop an *empty*
    memo because `__len__` makes a fresh memo falsy."""

    def __init__(self):
        self._views: dict = {}
        self.hits = 0
        self.misses = 0

    def __call__(self, arr, dtype=None):
        if isinstance(arr, jax.Array):      # already device-resident
            return arr if dtype is None else jnp.asarray(arr, dtype)
        key = (id(arr), None if dtype is None else np.dtype(dtype).name)
        hit = self._views.get(key)
        if hit is not None:
            self.hits += 1
            obs.counter_add("memo.hits")
            return hit[1]
        _rfaults.fire("memo.upload")
        self.misses += 1
        obs.counter_add("memo.misses")
        if obs.enabled():
            a = np.asarray(arr)
            obs.event("memo.upload", {"nbytes": int(a.nbytes),
                                      "shape": list(a.shape),
                                      "dtype": str(a.dtype if dtype is None
                                                   else np.dtype(dtype))})
        # jnp.array (copy), not jnp.asarray: the CPU backend can alias the
        # host buffer on dtype-preserving uploads, which would keep replaced
        # arrays alive through the cached device view and defeat eviction
        dev = jnp.array(arr, dtype=dtype)
        obs.counter_add("engine.h2d_bytes", dev.nbytes)
        try:
            anchor = weakref.ref(arr, lambda _, k=key: self._views.pop(k, None))
        except TypeError:                   # non-weakrefable input: pin it
            anchor = arr
        self._views[key] = (anchor, dev)
        return dev

    def is_resident(self, arr) -> bool:
        """True iff `arr` IS one of the memoized device views (identity, not
        equality).  The fused engine's donation guard: memo-resident views
        must never be donated to a launch — donation deletes the buffer and
        the memo would keep serving the dead view (see
        `engine.DeviceEngine._donatable` / `fmm.device_hook`)."""
        return any(view is arr for _, view in self._views.values())

    def __len__(self) -> int:
        return len(self._views)


# --------------------------------------------------------------- layer 1 ---
def _validate_geometry_inputs(x, q, spec: PartitionSpec) -> None:
    """Reject degenerate inputs at the API boundary with the offending
    argument NAMED, instead of failing deep inside partitioning (a zero-size
    reduction) or silently producing garbage (NaN coordinates survive the
    morton cast with only a RuntimeWarning).

    Deliberately NOT rejected: n < nparts.  Partitions holding no points are
    a supported configuration — they carry the empty-box sentinel
    (lo=+inf, hi=-inf) and are skipped by adjacency/LET extraction — and the
    paper's boundary distributions depend on that path (tests pin it)."""
    if x.ndim != 2 or x.shape[1] != 3:
        raise ValueError(f"x: expected positions of shape (n, 3), got "
                         f"{x.shape}")
    if len(x) == 0:
        raise ValueError("x: at least one body is required (got 0); empty "
                         "PARTITIONS are fine, an empty problem is not")
    if q.shape != (len(x),):
        raise ValueError(f"q: expected charges of shape ({len(x)},) to "
                         f"match x, got {q.shape}")
    if not np.isfinite(x).all():
        raise ValueError("x: positions contain non-finite values "
                         "(NaN or +-inf)")
    if not np.isfinite(q).all():
        raise ValueError("q: charges contain non-finite values (NaN or "
                         "+-inf)")
    if not spec.theta > 0.0:
        raise ValueError(f"theta: MAC opening angle must be > 0, got "
                         f"{spec.theta}")
    if spec.nparts < 1:
        raise ValueError(f"nparts: need at least one partition, got "
                         f"{spec.nparts}")


def _partition(x, nparts, method,
               sfc_box_inflation: float = DEFAULT_SFC_BOX_INFLATION):
    """Returns (part, tight_boxes, adjacency_boxes).  ORB regions share split
    planes exactly; SFC partitions fall back to eps-inflated tight boxes.
    Partitions holding no points carry the empty-box sentinel (lo=+inf,
    hi=-inf), which survives inflation and is skipped by Lemma-1 adjacency
    and LET extraction."""
    if method == "orb":
        part, tight, regions = orb_partition(x, nparts, regions=True)
        return part, tight, regions
    if method in ("hilbert", "morton"):
        part, _ = hot_partition(x, nparts, curve=method)
        boxes = np.empty((nparts, 2, 3))
        boxes[:, 0], boxes[:, 1] = _EMPTY_LO, _EMPTY_HI
        for p in range(nparts):
            pts = x[part == p]
            if len(pts):
                boxes[p, 0], boxes[p, 1] = pts.min(axis=0), pts.max(axis=0)
        span = (x.max(axis=0) - x.min(axis=0)).max()
        infl = boxes.copy()
        infl[:, 0] -= sfc_box_inflation * span
        infl[:, 1] += sfc_box_inflation * span
        return part, boxes, infl
    raise ValueError(method)


def _m2l_margin(inter: InteractionPlan, tgt, src, theta: float) -> float:
    """Min over the plan's valid M2L pairs of theta*d - (R_a + R_b) — the
    absolute distance the MAC has to spare before any accepted pair flips."""
    if inter.n_m2l == 0:
        return float("inf")
    a = inter.m2l_a[:inter.n_m2l]
    b = inter.m2l_b[:inter.n_m2l]
    d = np.linalg.norm(np.asarray(tgt.center)[a] - np.asarray(src.center)[b],
                       axis=1)
    return float(np.min(theta * d
                        - (np.asarray(tgt.radius)[a] + np.asarray(src.radius)[b])))


def _slack_budget(nparts: int, theta: float, receivers: list,
                  lets: dict) -> np.ndarray:
    """Per-partition drift budget from the minimum MAC / truncation margin of
    every plan and LET the partition participates in (module docstring)."""
    margin = np.full(nparts, np.inf)
    for j, r in enumerate(receivers):
        if r is None:
            continue
        margin[j] = min(margin[j], r.local_margin)
        for rb in r.remote:
            margin[rb.sender] = min(margin[rb.sender], rb.margin)
            margin[j] = min(margin[j], rb.margin)
    for (i, j), let in lets.items():
        margin[i] = min(margin[i], let.trunc_margin)
        margin[j] = min(margin[j], let.trunc_margin)
    return np.maximum(margin, 0.0) / (2.0 * math.sqrt(3.0) * (1.0 + theta))


def _geometry_pad_cells(trees) -> int | None:
    """One padded-cell envelope for every traversal of a geometry, so all
    (receiver, sender) pairs share a single traced device program (grafted
    LETs never exceed their sender's cell count)."""
    live = [t.n_cells for t in trees if t is not None]
    if not live:
        return None
    from repro.core.plan import bucket_size
    return bucket_size(max(live))


def _plan_pair(tgt, src, theta: float, with_m2p: bool, backend: str,
               pad_cells: int | None = None):
    """Traverse one (target, source) pair on the chosen backend and freeze
    its interaction plan; returns (inter, min accepted M2L margin).  The
    device path consumes the traversal's own margin output — no host NumPy
    margin recompute (`_m2l_margin` stays the host-path scorer)."""
    if backend == "device":
        from repro.core.engine.traversal import device_dual_traversal
        m2l, p2p, m2p, margin = device_dual_traversal(
            tgt, src, theta, with_m2p=True, pad_cells=pad_cells)
        assert with_m2p or len(m2p) == 0, \
            "truncated source cells require with_m2p=True"
        inter = build_interaction_plan(
            tgt, src, theta, with_m2p=with_m2p, m2l_pairs=m2l, p2p_pairs=p2p,
            m2p_pairs=(m2p if with_m2p else None))
        return inter, float(margin)
    inter = build_interaction_plan(tgt, src, theta, with_m2p=with_m2p)
    return inter, _m2l_margin(inter, tgt, src, theta)


def _remote_block(i: int, let: LETData, tree, theta: float,
                  backend: str = "host",
                  pad_cells: int | None = None) -> RemoteBlock:
    g = graft(let)
    inter, margin = _plan_pair(tree, g, theta, True, backend, pad_cells)
    return RemoteBlock(sender=i, graft=g, inter=inter, margin=margin)


def _rebind_remote(rb: RemoteBlock, let: LETData) -> RemoteBlock:
    """Rebind a drifted sender's refreshed LET payload onto the cached
    interaction plan: new graft view, same inter/margin (structure and MAC
    margins are drift-invariant within slack)."""
    return RemoteBlock(sender=rb.sender, graft=graft(let), inter=rb.inter,
                       margin=rb.margin)


def _batched_multipoles(ops, trees, scheds) -> list:
    """Every partition's multipoles from ONE batched upward launch (the
    engine's `batched_upward`, bitwise-equal per partition to
    `fmm.upward_pass`).  Per-tree passes would compile a program per tree
    and level shape, which dominates planning on an accelerator."""
    if all(t is None for t in trees):
        return [None] * len(trees)
    from repro.core.engine.schedules import build_batched_upward, stack_bodies
    from repro.core.engine.upward import batched_upward
    up = build_batched_upward(trees, scheds)
    M = np.asarray(batched_upward(ops, *stack_bodies(trees, up.n_bodies_max),
                                  up))
    return [None if s is None else M[p, :s.n_cells].copy()
            for p, s in enumerate(scheds)]


def plan_geometry(x, q, spec: PartitionSpec | None = None,
                  **overrides) -> GeometryPlan:
    """Layer 1: partition, build local trees, extract every LET (one batched
    `extract_lets` call per sender), traverse every receiver pair — with no
    protocol argument.  Keyword overrides patch the spec:
    `plan_geometry(x, q, nparts=16, method="hilbert")`."""
    spec = dc_replace(spec or PartitionSpec(), **overrides)
    from repro.core.engine.traversal import resolve_traversal_backend
    backend = resolve_traversal_backend(spec.traversal_backend)
    x = np.asarray(x, dtype=np.float64)
    q = np.asarray(q, dtype=np.float64)
    _validate_geometry_inputs(x, q, spec)
    n = len(x)
    P = spec.nparts
    with obs.span("plan.geometry") as sp_plan:
        with obs.span("plan.partition"):
            part, boxes, adj_boxes = _partition(
                x, P, spec.method, sfc_box_inflation=spec.sfc_box_inflation)
        ops = get_operators(spec.p)

        # --- completely local trees (local bounding box, tight cells; §3) --
        with obs.span("plan.trees"):
            owners, trees, scheds = [], [], []
            for pid in range(P):
                idx = np.nonzero(part == pid)[0]
                owners.append(idx)
                if len(idx) == 0:
                    trees.append(None)
                    scheds.append(None)
                    continue
                t = build_tree(x[idx], q[idx], ncrit=spec.ncrit)
                trees.append(t)
                scheds.append(build_tree_schedules(t))
            Ms = _batched_multipoles(ops, trees, scheds)

        # --- sender-initiated LET extraction: all remote boxes per sender in
        #     one batched frontier pass; empty partitions neither send nor
        #     receive -----------------------------------------------------
        with obs.span("plan.lets"):
            lets: dict[tuple[int, int], LETData] = {}
            B = np.zeros((P, P), dtype=np.int64)
            for i in range(P):
                if trees[i] is None:
                    continue
                others = np.array([j for j in range(P)
                                   if j != i and trees[j] is not None],
                                  dtype=np.int64)
                if len(others) == 0:
                    continue
                for j, let in zip(others, extract_lets(trees[i], Ms[i],
                                                       boxes[others, 0],
                                                       boxes[others, 1],
                                                       spec.theta)):
                    lets[(i, int(j))] = let
                    B[i, j] = let.nbytes

        # --- receiver side: graft + traverse ONCE into frozen plans --------
        with obs.span("plan.receivers"):
            pad_cells = _geometry_pad_cells(trees)
            receivers: list = []
            for j in range(P):
                if trees[j] is None:
                    receivers.append(None)
                    continue
                t = trees[j]
                local, local_margin = _plan_pair(t, t, spec.theta, False,
                                                 backend, pad_cells)
                remote = [_remote_block(i, lets[(i, j)], t, spec.theta,
                                        backend, pad_cells)
                          for i in range(P) if (i, j) in lets]
                receivers.append(ReceiverPlan(
                    tree=t, sched=scheds[j], local=local,
                    local_margin=local_margin, remote=remote))

        adj = adjacency_from_boxes(adj_boxes)
        deg = float(np.max([len(a) for a in adj]))
        obs.counter_add("plan.builds")
        if obs.enabled():
            sp_plan.set({"n": int(n), "nparts": int(P),
                         "method": spec.method, "backend": backend,
                         "let_bytes": int(B.sum())})
        return GeometryPlan(
            spec=spec, n=n, x0=x.copy(), q0=q.copy(), x_ref=x.copy(),
            part=part, owners=owners, boxes=boxes, adj_boxes=adj_boxes,
            trees=trees, scheds=scheds, Ms=Ms, lets=lets,
            receivers=receivers, bytes_matrix=B,
            adjacency_degree=deg, diameter=graph_diameter(adj),
            slack=_slack_budget(P, spec.theta, receivers, lets),
            partition_stats=dict(nparts=P, method=spec.method),
        )


# --------------------------------------------------------------- layer 2 ---
def schedule_comm(geometry, protocol: str = "hsdx",
                  prm: proto.LogGPParams | None = None,
                  grain_bytes: int | None = None,
                  check_delivery: bool = True) -> CommSchedule:
    """Layer 2: a pure function over the geometry's frozen bytes matrix and
    adjacency boxes — no partitioning, trees, traversal or LET work, so a
    protocol sweep costs four cheap schedule constructions, not four
    geometry builds."""
    B = geometry.bytes_matrix
    sched = proto.make_schedule(protocol, B, boxes=geometry.adj_boxes)
    if check_delivery:
        delivered = proto.simulate_delivery(sched)
        expect = {(i, j): int(B[i, j]) for i in range(len(B))
                  for j in range(len(B)) if i != j and B[i, j] > 0}
        if delivered != expect:
            raise RuntimeError(f"{protocol} failed to deliver the LET")
    return CommSchedule(
        protocol=protocol, schedule=sched, stats=proto.schedule_stats(sched),
        loggp_time=proto.loggp_time(sched, prm=prm, grain_bytes=grain_bytes),
        grain_bytes=grain_bytes)


# --------------------------------------------------------------- executor --
def sync_host_multipoles(geo) -> None:
    """Fill the deferred host-side numeric mirrors of `geo.Ms_stale`
    partitions: recompute their NumPy multipoles about the build-time
    expansion centers, rebind every LET payload they send, and re-graft the
    receiver views over the refreshed LETs.  In place — this is a cache
    fill (the values are exactly what an eager step would have produced),
    not a semantic mutation; no-op when nothing is stale."""
    stale = set(getattr(geo, "Ms_stale", ()))
    if not stale:
        return
    ops = get_operators(geo.spec.p)
    for j in sorted(stale):
        geo.Ms[j] = np.asarray(upward_pass(geo.trees[j], ops,
                                           sched=geo.scheds[j]))
    for (i, j), let in list(geo.lets.items()):
        if i in stale:
            geo.lets[(i, j)] = refresh_let(let, geo.trees[i], geo.Ms[i])
    for j, r in enumerate(geo.receivers):
        if r is None:
            continue
        if j not in stale and not any(rb.sender in stale for rb in r.remote):
            continue
        remote = [_rebind_remote(rb, geo.lets[(rb.sender, j)])
                  if rb.sender in stale else rb
                  for rb in r.remote]
        # rebind the receiver's own (payload-rebound) tree too: the deferred
        # step skipped the ReceiverPlan rebuild, so r.tree still references
        # pre-step coordinates
        geo.receivers[j] = ReceiverPlan(tree=geo.trees[j], sched=r.sched,
                                        local=r.local,
                                        local_margin=r.local_margin,
                                        remote=remote)
    geo.Ms_stale = ()


def execute_geometry(geo, use_kernels: bool = False, asarray=None,
                     use_pallas: bool | None = None) -> np.ndarray:
    """Reference executor — kernels + gathers only, one partition at a time:
    no traversal, no list building, no padding.  Works on any plan-shaped
    object (GeometryPlan or the legacy DistributedPlan).  With
    `asarray=DeviceMemo(...)`, every frozen index table is uploaded to the
    device at most once across calls.  The batched replacement lives in
    repro.core.engine (this path is what its golden tests pin against)."""
    use_kernels = _resolve_kernels(use_kernels, use_pallas, "execute_geometry")
    sync_host_multipoles(geo)
    ops = get_operators(geo.p)
    phi = np.zeros(geo.n)
    for j in range(geo.nparts):
        r = geo.receivers[j]
        if r is None:
            continue
        t = r.tree
        L = m2l_apply(ops, geo.Ms[j], r.local, asarray=asarray)
        phi_local = p2p_apply(t, t, r.local, use_kernels=use_kernels,
                              asarray=asarray)
        for rb in r.remote:
            if rb.inter.n_m2l:
                L = L + m2l_apply(ops, rb.graft.M, rb.inter, asarray=asarray)
            if rb.inter.n_p2p:
                phi_local += p2p_apply(t, rb.graft, rb.inter,
                                       use_kernels=use_kernels, asarray=asarray)
            if rb.inter.n_m2p:
                phi_local += m2p_apply(t, rb.graft.M, rb.inter, p=geo.p,
                                       asarray=asarray)
        L = downward_pass(t, ops, L, sched=r.sched, asarray=asarray)
        phi_local += l2p_pass(t, ops, L, sched=r.sched, asarray=asarray)
        phi[geo.owners[j][t.perm]] = phi_local
    return phi


# --------------------------------------------------------------- layer 3 ---
class FMMSession:
    """Layer 3: one geometry, all protocols, many timesteps.

    Holds a `GeometryPlan` plus a `DeviceMemo` of its frozen index tables:
    the first evaluation uploads each table once; every later evaluation is
    kernels-only with zero host->device plan transfers.  `potentials` caches
    the (protocol-independent) potential per geometry version, so
    `.sweep()` answers all four protocols from a single execution.

    Engine dispatch: with `engine=True` (the default whenever a device
    backend is present) evaluation runs through `repro.core.engine`'s
    `DeviceEngine` — one batched multi-tree upward launch, a segment-summed
    M2L over all (receiver, sender) pairs, and Pallas-bucketed P2P — and
    within-slack `.step()`s skip the per-partition host multipole refresh
    entirely: the engine restacks one (x, q) payload pair and recomputes
    every drifting partition's multipoles on device (the host-side NumPy
    mirrors are refilled lazily by `sync_host_multipoles` only if the
    reference path asks for them).  `engine=False` forces the per-partition
    reference executor (`execute_geometry`)."""

    def __init__(self, geometry: GeometryPlan, engine: bool | None = None,
                 use_kernels: bool | None = None,
                 use_pallas: bool | None = None,
                 fused: bool | None = None, exe_cache=None,
                 mesh=None, dist_protocol: str = "bulk",
                 dist_grain_bytes: int | None = None,
                 p2p_stream: bool | None = None,
                 resilience: bool | None = None,
                 health_checks: bool | None = None):
        from repro.core.engine import (default_engine_enabled,
                                       default_use_kernels)
        if use_pallas is not None:      # deprecated alias, warn-once + honor
            if use_kernels is not None:
                raise ValueError(
                    "pass use_kernels only; use_pallas is its deprecated "
                    "alias and conflicts when both are given")
            use_kernels = _resolve_kernels(False, use_pallas, "FMMSession")
        if not (hasattr(geometry, "receivers")
                and hasattr(geometry, "bytes_matrix")):
            raise ValueError(
                f"geometry: expected a GeometryPlan (plan_geometry(...) "
                f"output or plan-shaped object), got {type(geometry).__name__}")
        self._geo = geometry
        self.engine_enabled = (default_engine_enabled() if engine is None
                               else bool(engine))
        self.use_kernels = (default_use_kernels() if use_kernels is None
                            else bool(use_kernels))
        self.fused = fused               # None -> default_fused_enabled()
        self.p2p_stream = p2p_stream     # None -> default_p2p_stream()
        self.exe_cache = exe_cache       # None -> process-wide GLOBAL_CACHE
        self.mesh = mesh                 # 1-D mesh -> dist exchange dispatch
        if dist_protocol not in ("bulk", "grain", "hsdx"):
            raise ValueError(f"unknown dist_protocol {dist_protocol!r}; "
                             "expected 'bulk', 'grain' or 'hsdx'")
        self.dist_protocol = dist_protocol
        self.dist_grain_bytes = dist_grain_bytes
        self.resilience = _rfb.ResilienceState(
            enabled=(_rfb.default_resilience_enabled() if resilience is None
                     else bool(resilience)),
            health_checks=bool(health_checks) if health_checks is not None
            else False)
        self._engine = None
        self._dist = None
        self._memo = DeviceMemo()
        self._comm_cache: dict = {}
        self._phi: np.ndarray | None = None
        self._phi_version = -1
        self._exchange_verified: set = set()

    @classmethod
    def from_points(cls, x, q, spec: PartitionSpec | None = None,
                    engine: bool | None = None,
                    use_kernels: bool | None = None,
                    use_pallas: bool | None = None,
                    fused: bool | None = None, exe_cache=None,
                    mesh=None, dist_protocol: str = "bulk",
                    dist_grain_bytes: int | None = None,
                    p2p_stream: bool | None = None,
                    resilience: bool | None = None,
                    health_checks: bool | None = None,
                    **overrides) -> "FMMSession":
        return cls(plan_geometry(x, q, spec, **overrides), engine=engine,
                   use_kernels=use_kernels, use_pallas=use_pallas,
                   fused=fused, exe_cache=exe_cache, mesh=mesh,
                   dist_protocol=dist_protocol,
                   dist_grain_bytes=dist_grain_bytes,
                   p2p_stream=p2p_stream, resilience=resilience,
                   health_checks=health_checks)

    @property
    def geometry(self) -> GeometryPlan:
        return self._geo

    @property
    def memo(self) -> DeviceMemo:
        return self._memo

    @property
    def engine(self):
        """The session's `DeviceEngine`, building it on first access (engine
        dispatch enabled) or returning None (reference dispatch)."""
        if not self.engine_enabled:
            return None
        if self._engine is None or self._engine.geo is not self._geo:
            from repro.core.engine import DeviceEngine
            # share the session memo: sess.memo.misses stays THE transfer
            # meter whichever dispatch path runs
            self._engine = DeviceEngine(self._geo,
                                        use_kernels=self.use_kernels,
                                        asarray=self._memo,
                                        fused=self.fused,
                                        exe_cache=self.exe_cache,
                                        p2p_stream=self.p2p_stream)
        return self._engine

    @property
    def dist(self):
        """The session's `ShardedEngine` (mesh dispatch), built on first
        access; None without a mesh.  Rebuilt automatically after a step
        that rebuilds any partition (structure changed)."""
        if self.mesh is None:
            return None
        if self._dist is None or self._dist.geo is not self._geo:
            from repro.core.dist import ShardedEngine
            self._dist = ShardedEngine(self._geo, self.mesh,
                                       grain_bytes=self.dist_grain_bytes)
        return self._dist

    @property
    def exchange_stats(self) -> dict:
        """Per-rank wire accounting of the session's dist protocol (measured
        moved/delivered bytes, rounds, padding) + its LogGP prediction."""
        if self.mesh is None:
            # Deprecation note: before PR 8 this raised RuntimeError on
            # mesh-less sessions while exe_cache_stats returned a dict; the
            # stats surface is now uniformly non-raising — a structured
            # disabled payload marks "no mesh" instead.
            return {"enabled": False, "protocol": self.dist_protocol,
                    "reason": "no mesh: pass FMMSession(mesh=...) for "
                              "multi-device exchange accounting",
                    "n_rounds": 0, "moved_bytes": 0, "delivered_bytes": 0,
                    "padded_wire_bytes": 0, "per_rank_sent": [],
                    "per_rank_recv": [], "grain_bytes": None,
                    "loggp_time": 0.0, "rank_bytes": []}
        st = dict(self.dist.exchange_stats(self.dist_protocol))
        st["enabled"] = True
        return st

    @property
    def exe_cache_stats(self) -> dict:
        """Hit/miss/eviction counters of the fused executable cache this
        session resolves against (the process-wide GLOBAL_CACHE unless a
        private `exe_cache=` was passed).  `misses` counts actual XLA
        compilations — a second same-shape-class geometry must not move it
        (the zero-recompile guarantee tests pin)."""
        from repro.core.engine import resolve_cache
        eng = self._engine
        cache = eng.exe_cache if eng is not None else resolve_cache(self.exe_cache)
        return cache.stats()

    def device_phases(self) -> dict:
        """{HLO instruction: phase} of the warm fused evaluate's top-level
        ops, the names a device trace's `XLA Ops` line carries (`fmm.upward`,
        `fmm.m2l`, ... — `engine.fused.build_fused_evaluate`); summing a
        trace's op times by this map gives each phase's device time, since
        a loop's op already covers its body's.  `{}` while no fused
        evaluate executable has been built."""
        eng = self._engine
        hit = (eng._entries.get(("evaluate", bool(jax.config.jax_enable_x64)))
               if eng is not None else None)
        if hit is None:
            return {}
        from repro.analysis.hlo_walk import op_phases, top_level_ops
        text = hit[0].hlo_text
        top = top_level_ops(text)
        return {op: ph for op, ph in op_phases(text).items() if op in top}

    def report(self, *, measure_exchange: bool | None = None,
               protocols=None, reps: int = 3) -> dict:
        """One structured flight-recorder dict for this session: per-span
        timings, metrics counters, memo/cache/launch accounting and — on
        mesh-backed sessions — per-protocol exchange stats with the
        `model_drift` ratio (measured wall time / LogGP-predicted time).

        `measure_exchange` controls whether exchanges are actually *run and
        timed* (defaults to tracing-enabled); when off, the exchange block
        carries the static byte/round accounting only.  Never raises on
        mesh-less or engine-less sessions — disabled sub-blocks are marked
        `{"enabled": False}` (same contract as `exchange_stats`)."""
        tracer = obs.get_tracer()
        rep: dict = {
            "obs": {"enabled": obs.enabled(),
                    "events": len(tracer.events) if tracer else 0,
                    "dropped": tracer.dropped if tracer else 0},
            "timings": tracer.summary() if tracer else {},
            "metrics": obs.metrics_snapshot(),
            "memo": {"hits": self._memo.hits, "misses": self._memo.misses,
                     "resident_views": len(self._memo._views)},
            "exe_cache": self.exe_cache_stats,
            "geometry": {"n": int(self._geo.n),
                         "nparts": int(self._geo.spec.nparts),
                         "version": int(self._geo.version),
                         "bytes_matrix_total":
                             int(self._geo.bytes_matrix.sum())},
            "resilience": self.resilience.snapshot(),
        }

        # Launch accounting: per compiled fused entry, observed call count
        # and the HLO-verified entry-computation count (the one-launch pin).
        eng = self._engine
        if eng is not None and getattr(eng, "_entries", None):
            from repro.analysis.hlo_walk import count_entry_launches
            launches: dict = {}
            for (kind, x64), (entry, _tabs) in eng._entries.items():
                launches[kind] = {
                    "calls": entry.calls,
                    "entry_computations":
                        count_entry_launches(entry.hlo_text),
                    "x64": bool(x64)}
            launches["fused_dispatches"] = len(eng.launch_log)
            rep["launches"] = launches
        else:
            rep["launches"] = {"enabled": False}

        # Exchange accounting (+ measured-vs-LogGP drift when measuring).
        if self.mesh is None:
            rep["exchange"] = {"enabled": False, "protocols": {}}
        else:
            do_measure = (obs.enabled() if measure_exchange is None
                          else bool(measure_exchange))
            names = tuple(protocols) if protocols else ("bulk", "grain",
                                                        "hsdx")
            per_proto = {}
            for name in names:
                if do_measure:
                    per_proto[name] = self.dist.measure_exchange(name,
                                                                 reps=reps)
                else:
                    per_proto[name] = self.dist.exchange_stats(name)
            rep["exchange"] = {"enabled": True,
                               "protocol": self.dist_protocol,
                               "measured": do_measure,
                               "protocols": per_proto}
        return rep

    # ------------------------------------------------------------- comm ---
    def comm(self, protocol: str = "hsdx", grain_bytes: int | None = None,
             prm: proto.LogGPParams | None = None,
             check_delivery: bool = True) -> CommSchedule:
        """Memoized `schedule_comm` (cache dropped when a step rebuilds any
        partition, i.e. whenever the bytes matrix can change)."""
        key = (protocol, grain_bytes, check_delivery)
        if prm is None and key in self._comm_cache:
            return self._comm_cache[key]
        cs = schedule_comm(self._geo, protocol, prm=prm,
                           grain_bytes=grain_bytes,
                           check_delivery=check_delivery)
        if prm is None:
            self._comm_cache[key] = cs
        return cs

    # ------------------------------------------------------- resilience ---
    def _current_rung(self) -> str:
        """Classify the session's knobs onto the degradation ladder
        (`fallback.LADDER`).  The mapping is the inverse of `_apply_rung`:
        applying a rung then classifying returns that same rung, which is
        what makes downgrades monotone."""
        if self.mesh is not None:
            return "dist"
        if not self.engine_enabled:
            return "reference"
        from repro.core.engine import (default_fused_enabled,
                                       default_p2p_stream)
        fused = (default_fused_enabled() if self.fused is None
                 else bool(self.fused))
        stream = (default_p2p_stream() if self.p2p_stream is None
                  else bool(self.p2p_stream))
        if self.use_kernels and stream:
            return "streaming"
        if self.use_kernels:
            return "gathered"
        if stream or fused:
            return "xla_slab"
        return "per_phase"

    def _apply_rung(self, rung: str) -> None:
        """Mutate the session knobs to the given ladder rung and drop the
        stale engine so the next evaluation rebuilds on the new path (the
        memo and executable cache are shared, so the rebuild reuses every
        uploaded table and compatible compiled entry)."""
        if rung == "streaming":
            self.use_kernels, self.p2p_stream = True, True
        elif rung == "gathered":
            self.use_kernels, self.p2p_stream = True, False
        elif rung == "xla_slab":
            self.use_kernels, self.p2p_stream = False, True
        elif rung == "per_phase":
            self.use_kernels, self.p2p_stream = False, False
            self.fused = False
        elif rung == "reference":
            self.engine_enabled = False
        else:                               # pragma: no cover - guarded
            raise ValueError(f"unknown ladder rung {rung!r}")
        self._engine = None

    def _downgrade(self, exc: BaseException) -> None:
        """Step one rung DOWN the ladder after `exc` killed the current one.
        Dist failures drop the mesh and re-enter at whatever single-device
        rung the knobs select; exhaustion below `reference` raises the
        terminal typed `ResilienceError` carrying the failing site."""
        frm = self._current_rung()
        site = getattr(exc, "site", frm)
        if frm == "dist":
            self.mesh = None
            self._dist = None
            to = self._current_rung()
        else:
            i = _rfb.LADDER.index(frm)
            if i + 1 >= len(_rfb.LADDER):
                raise _rfb.ResilienceError(
                    site, f"resilience ladder exhausted at {frm!r}: "
                          f"{exc}") from exc
            to = _rfb.LADDER[i + 1]
            self._apply_rung(to)
        self.resilience.note_fallback(site, frm, to, exc)

    def _phi_healthy(self, phi) -> bool:
        """Opt-in numerical sentinel: phi (and, engine dispatch, the cached
        device multipoles) must be finite.  A failure is treated like any
        rung failure — downgrade and recompute on the next rung."""
        st = self.resilience
        st.health["checks"] += 1
        ok = bool(np.isfinite(phi).all())
        if ok and self._engine is not None and self._engine._M is not None:
            ok = bool(np.isfinite(np.asarray(self._engine._M)).all())
        if not ok:
            st.health["failures"] += 1
            obs.counter_add("resilience.health_failures")
        return ok

    def _verify_exchange_once(self) -> None:
        """REPRO_VERIFY_EXCHANGE=1: checksum every delivered wire span
        against its sender-side payload, once per (protocol, geometry
        version).  Raises `ExchangeVerificationError` on mismatch — terminal
        without resilience, a dist->engine downgrade with it."""
        key = (self.dist_protocol, self._geo.version)
        if key in self._exchange_verified:
            return
        self.dist.verify_exchange(self.dist_protocol)
        self._exchange_verified.add(key)
        self.resilience.exchange_verified += 1

    def _dispatch_evaluate(self) -> tuple:
        """One evaluation attempt on the CURRENT rung -> (phi, dispatch)."""
        if self.mesh is not None:
            if os.environ.get("REPRO_VERIFY_EXCHANGE", "") in (
                    "1", "on", "yes", "true"):
                self._verify_exchange_once()
            return self.dist.evaluate(self.dist_protocol), "dist"
        if self.engine_enabled:
            return self.engine.evaluate(), "engine"
        return execute_geometry(self._geo, use_kernels=self.use_kernels,
                                asarray=self._memo), "reference"

    def _evaluate_resilient(self) -> tuple:
        """Walk the ladder until a rung produces a (healthy) potential.
        Transient failures retry in place with backoff; anything else costs
        one rung.  Terminates: every iteration either returns or strictly
        descends the finite ladder (`_downgrade` raises at the bottom)."""
        st = self.resilience
        while True:
            rung = self._current_rung()
            try:
                phi, dispatch = _rfb.call_with_retry(
                    self._dispatch_evaluate, site=rung,
                    policy=st.retry, state=st)
            except _rfb.ResilienceError:
                raise                       # already terminal + counted
            except Exception as exc:
                self._downgrade(exc)
                continue
            if st.health_checks and not self._phi_healthy(phi):
                exc = RuntimeError(
                    f"non-finite potential from rung {rung!r}")
                exc.site = "health.phi"
                self._downgrade(exc)
                continue
            st.rung = rung
            return phi, dispatch

    # ------------------------------------------------------------ kernels -
    def evaluate(self) -> np.ndarray:
        """Run the kernel pipeline now (ignoring the potential cache) against
        memoized device views; refreshes the cached potential.  Dispatches
        through the batched `DeviceEngine` when engine mode is on, else the
        per-partition reference executor.  With `resilience=True` a failing
        path degrades down `fallback.LADDER` instead of raising (see
        `_evaluate_resilient`).  The returned array is marked read-only: it
        is shared by every SessionResult of this geometry version, so
        in-place mutation would corrupt the cache — copy it to
        post-process."""
        with obs.span("session.evaluate") as sp:
            if self.resilience.enabled:
                phi, dispatch = self._evaluate_resilient()
            else:
                phi, dispatch = self._dispatch_evaluate()
            obs.counter_add("session.evaluations")
            if obs.enabled():
                sp.set({"dispatch": dispatch, "n": int(self._geo.n),
                        "version": int(self._geo.version)})
        phi.setflags(write=False)
        self._phi, self._phi_version = phi, self._geo.version
        return phi

    def potentials(self, protocol: str = "hsdx",
                   grain_bytes: int | None = None,
                   prm: proto.LogGPParams | None = None,
                   check_delivery: bool = True) -> SessionResult:
        """Potential (original body order) + this protocol's communication
        accounting.  The potential is protocol-independent and computed once
        per geometry version."""
        cs = self.comm(protocol, grain_bytes=grain_bytes, prm=prm,
                       check_delivery=check_delivery)
        if self._phi is None or self._phi_version != self._geo.version:
            self.evaluate()
        return SessionResult(
            phi=self._phi, protocol=protocol, comm=cs,
            bytes_matrix=self._geo.bytes_matrix,
            partition_stats=self._geo.partition_stats,
            adjacency_degree=self._geo.adjacency_degree,
            diameter=self._geo.diameter)

    def sweep(self, protocols=proto.PROTOCOLS,
              grain_bytes: int | None = None,
              prm: proto.LogGPParams | None = None,
              check_delivery: bool = True) -> dict:
        """All protocols from one GeometryPlan and one kernel execution."""
        return {name: self.potentials(name, grain_bytes=grain_bytes, prm=prm,
                                      check_delivery=check_delivery)
                for name in protocols}

    # ------------------------------------------------------------- step ---
    def step(self, new_x, new_q=None) -> StepReport:
        """Advance to new body positions/charges, reusing every cached
        structure the MAC slack margins still cover (module docstring).

        Unmoved bodies are a 100% cache hit: the geometry object, its
        version, the device memo and the cached potential are all untouched.
        Drift within a partition's slack rebinds that partition's numeric
        payload (positions, multipoles, shipped LET bodies) onto the cached
        index structure; drift beyond it rebuilds the partition and exactly
        the LETs / receiver plans that touch it."""
        with obs.span("session.step") as sp:
            report = self._step_impl(new_x, new_q)
            obs.counter_add("session.steps")
            if obs.enabled():
                sp.set({"cache_hit": report.cache_hit,
                        "rebuilt": len(report.rebuilt),
                        "refreshed": len(report.refreshed)})
        return report

    def _step_impl(self, new_x, new_q=None) -> StepReport:
        geo = self._geo
        spec = geo.spec
        P = spec.nparts
        with obs.span("step.check"):
            new_x = np.array(new_x, dtype=np.float64)
            if new_x.shape != (geo.n, 3):
                raise ValueError(f"step: expected positions {(geo.n, 3)}, "
                                 f"got {new_x.shape}")
            if not np.isfinite(new_x).all():
                raise ValueError("new_x: positions contain non-finite "
                                 "values (NaN/Inf); refusing to poison the "
                                 "cached geometry")
            q_unchanged = new_q is None
            new_q = (geo.q0 if new_q is None
                     else np.array(new_q, dtype=np.float64))
            if new_q.shape != (geo.n,):
                raise ValueError(f"step: expected charges {(geo.n,)}, "
                                 f"got {new_q.shape}")
            if not np.isfinite(new_q).all():
                raise ValueError("new_q: charges contain non-finite values "
                                 "(NaN/Inf)")
            q_unchanged = q_unchanged or np.array_equal(new_q, geo.q0)

        # Batched device revalidation: a warm engine scores every partition's
        # drift (and changed flag) in ONE launch from a single new_x upload —
        # the per-partition NumPy loop below is the host/reference path.  The
        # restacked device payload is reused as the next evaluation's payload.
        eng = (self._engine
               if self.engine_enabled and self._engine is not None
               and self._engine.geo is geo else None)
        use_dev = eng is not None and q_unchanged
        if use_dev:
            try:
                with obs.span("step.drift"):
                    delta, stale = eng.step_drift(new_x)
            except Exception as exc:
                if not self.resilience.enabled:
                    raise
                # device revalidation died: fall through to the host f64
                # loop below — same answers, one rung slower, session lives
                self.resilience.note_fallback(
                    getattr(exc, "site", "engine.step_drift"),
                    "device_revalidation", "host", exc)
                use_dev = False
            if use_dev and np.any(stale & (delta > geo.slack
                                           - eng.drift_guard)):
                # a rebuild is coming OR a drift sits within the f32 guard
                # band of its slack: recompute drifts exactly (f64) on the
                # host — rebuild decisions and the conservative LET
                # re-extraction boxes must not ride f32 rounding
                use_dev = False
            if use_dev and self.resilience.health_checks:
                # Sampled MAC-slack audit: recompute up to 4 partitions'
                # drifts exactly (host f64) and require the device scores
                # to agree within the f32 guard band — a silent drift
                # underestimate is the one failure mode that serves a stale
                # potential as "cache hit".
                aud = self.resilience.audits
                sampled = [j for j in range(P) if len(geo.owners[j])][:4]
                for j in sampled:
                    idx = geo.owners[j]
                    exact = math.sqrt(float(
                        ((new_x[idx] - geo.x_ref[idx]) ** 2)
                        .sum(axis=1).max()))
                    aud["checks"] += 1
                    if abs(exact - float(delta[j])) > eng.drift_guard:
                        aud["failures"] += 1
                        obs.counter_add("resilience.audit_failures")
                        use_dev = False
                        break
        if not use_dev:
            if eng is not None:
                eng.discard_pending()
            with obs.span("step.drift"):
                delta = np.zeros(P)              # drift vs structure ref.
                stale = np.zeros(P, dtype=bool)  # numeric payload stale
                for j in range(P):
                    idx = geo.owners[j]
                    if len(idx) == 0:
                        continue
                    delta[j] = math.sqrt(float(
                        ((new_x[idx] - geo.x_ref[idx]) ** 2)
                        .sum(axis=1).max()))
                    stale[j] = (
                        not np.array_equal(new_x[idx], geo.x0[idx])
                        or not np.array_equal(new_q[idx], geo.q0[idx]))

        rebuilt = tuple(int(j) for j in range(P)
                        if stale[j] and delta[j] > geo.slack[j])
        refreshed = tuple(int(j) for j in range(P)
                          if stale[j] and j not in rebuilt)
        report = StepReport(cache_hit=not (rebuilt or refreshed),
                            rebuilt=rebuilt, refreshed=refreshed,
                            shift=tuple(delta.tolist()),
                            slack=tuple(geo.slack.tolist()),
                            version=geo.version + bool(rebuilt or refreshed))
        if report.cache_hit:
            if eng is not None:
                eng.discard_pending()
            return report

        # Engine-backed sessions keep within-slack refreshes device-resident:
        # defer the per-partition host multipole/LET payload refresh (filled
        # lazily by sync_host_multipoles iff the reference path needs it) —
        # the engine recomputes every drifting partition's multipoles in one
        # batched launch from the restacked (x, q) payload.
        defer = self.engine_enabled and not rebuilt
        with obs.span("step.advance"):
            self._geo = self._advance(geo, new_x, new_q, delta,
                                      set(rebuilt), set(refreshed),
                                      defer_numeric=defer)
        self._phi = None
        if rebuilt:                         # bytes matrix / adjacency changed
            self._comm_cache.clear()
            self._engine = None             # structure changed: tables stale
            self._dist = None               # wire layout / spans changed too
        else:
            with obs.span("step.restack"):
                if self._engine is not None:
                    self._engine.refresh_payload(self._geo,
                                                 use_pending=use_dev)
                if self._dist is not None:
                    # dist recomputes multipoles AND LET wire payloads on
                    # device from the restacked (x, q) — no host LET
                    # refresh needed
                    self._dist.refresh_payload(self._geo)
        return report

    @staticmethod
    def _advance(geo: GeometryPlan, new_x, new_q, delta,
                 rebuilt: set, refreshed: set,
                 defer_numeric: bool = False) -> GeometryPlan:
        spec = geo.spec
        from repro.core.engine.traversal import resolve_traversal_backend
        backend = resolve_traversal_backend(spec.traversal_backend)
        P = spec.nparts
        ops = get_operators(spec.p)
        touched = rebuilt | refreshed
        if rebuilt:
            # LET re-extraction below reads refreshed senders' host
            # multipoles: fill any deferred mirrors first
            sync_host_multipoles(geo)
        trees, scheds, Ms = list(geo.trees), list(geo.scheds), list(geo.Ms)
        boxes, adj_boxes = geo.boxes.copy(), geo.adj_boxes.copy()
        lets, B = dict(geo.lets), geo.bytes_matrix.copy()
        x_ref = geo.x_ref.copy()

        # 1. rebuild invalidated partitions' local structure from scratch
        for j in rebuilt:
            idx = geo.owners[j]
            t = build_tree(new_x[idx], new_q[idx], ncrit=spec.ncrit)
            trees[j], scheds[j] = t, build_tree_schedules(t)
            Ms[j] = np.asarray(upward_pass(t, ops, sched=scheds[j]))
            boxes[j, 0] = new_x[idx].min(axis=0)
            boxes[j, 1] = new_x[idx].max(axis=0)
            # union-expand the adjacency box: Lemma-1 neighbor sets only grow,
            # so cached HSDX reachability stays conservative
            adj_boxes[j, 0] = np.minimum(adj_boxes[j, 0], boxes[j, 0])
            adj_boxes[j, 1] = np.maximum(adj_boxes[j, 1], boxes[j, 1])
            x_ref[idx] = new_x[idx]

        # 2. drift within slack: same structure, rebound coordinates/charges
        #    and recomputed multipoles about the build-time expansion centers
        #    (multipole recompute deferred to the device engine when it owns
        #    evaluation — sync_host_multipoles fills the NumPy mirror lazily)
        for j in refreshed:
            idx = geo.owners[j]
            t = trees[j]
            t = dc_replace(t, x=new_x[idx][t.perm], q=new_q[idx][t.perm])
            trees[j] = t
            if not defer_numeric:
                Ms[j] = np.asarray(upward_pass(t, ops, sched=scheds[j]))

        # 3. LETs: re-extract a pair iff either end was rebuilt; rebind the
        #    payload iff only the sender drifted within slack
        for i in range(P):
            if trees[i] is None:
                continue
            targets = [j for j in range(P) if j != i and trees[j] is not None
                       and (i in rebuilt or j in rebuilt)]
            if targets:
                tj = np.asarray(targets)
                lo, hi = boxes[tj, 0].copy(), boxes[tj, 1].copy()
                # a valid-but-drifted receiver can poke past its build-time
                # tight box by at most its drift — extract conservatively
                pad = np.array([delta[j] if j not in rebuilt else 0.0
                                for j in targets])
                lo -= pad[:, None]
                hi += pad[:, None]
                for j, let in zip(targets, extract_lets(trees[i], Ms[i],
                                                        lo, hi, spec.theta)):
                    lets[(i, j)] = let
                    B[i, j] = let.nbytes
            if i in refreshed and not defer_numeric:
                # rebuilt senders were re-extracted above
                for j in range(P):
                    if j != i and (i, j) in lets and j not in rebuilt:
                        lets[(i, j)] = refresh_let(lets[(i, j)], trees[i],
                                                   Ms[i])

        # 4. receiver plans: re-traverse a pair iff either end was rebuilt;
        #    re-graft (cheap view) iff its LET payload was rebound (deferred
        #    with the payload itself under engine dispatch)
        receivers = list(geo.receivers)
        pad_cells = _geometry_pad_cells(trees) if rebuilt else None
        for j in range(P) if not defer_numeric else ():
            if trees[j] is None:
                continue
            r = receivers[j]
            senders = [i for i in range(P) if (i, j) in lets]
            if j not in touched and not any(i in touched for i in senders):
                continue
            old = {rb.sender: rb for rb in r.remote}
            remote = []
            for i in senders:
                if i in rebuilt or j in rebuilt:
                    remote.append(_remote_block(i, lets[(i, j)], trees[j],
                                                spec.theta, backend,
                                                pad_cells))
                elif i in touched:
                    remote.append(_rebind_remote(old[i], lets[(i, j)]))
                else:
                    remote.append(old[i])
            if j in rebuilt:
                local, lm = _plan_pair(trees[j], trees[j], spec.theta, False,
                                       backend, pad_cells)
            else:
                local, lm = r.local, r.local_margin
            receivers[j] = ReceiverPlan(tree=trees[j], sched=scheds[j],
                                        local=local, local_margin=lm,
                                        remote=remote)

        if rebuilt:
            adj = adjacency_from_boxes(adj_boxes)
            deg = float(np.max([len(a) for a in adj]))
            diam = graph_diameter(adj)
            slack = _slack_budget(P, spec.theta, receivers, lets)
        else:
            deg, diam, slack = geo.adjacency_degree, geo.diameter, geo.slack

        # deferred-mirror bookkeeping: rebuilds synced everything up front;
        # otherwise carry prior stale partitions (minus any recomputed now)
        prior = set() if rebuilt else set(geo.Ms_stale)
        stale = tuple(sorted((prior | refreshed) if defer_numeric
                             else (prior - refreshed)))
        return GeometryPlan(
            spec=spec, n=geo.n, x0=new_x, q0=new_q, x_ref=x_ref,
            part=geo.part, owners=geo.owners, boxes=boxes,
            adj_boxes=adj_boxes, trees=trees, scheds=scheds, Ms=Ms, lets=lets,
            receivers=receivers, bytes_matrix=B, adjacency_degree=deg,
            diameter=diam, slack=slack,
            partition_stats=geo.partition_stats, version=geo.version + 1,
            Ms_stale=stale)

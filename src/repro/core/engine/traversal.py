"""Device-resident dual-tree traversal and step revalidation.

The host traversal (repro.core.traversal.dual_traversal) is already
frontier-vectorized, but every generation is a NumPy pass with a host
round-trip per level — the dominant `plan_geometry` cost ahead of the PR 3
device engine.  The frontier arrays are pure flat index math (Hu, Gumerov &
Duraiswami's observation that FMM data-structure construction is itself
data-parallel), so this module runs the whole loop as ONE device program:

  - state is a padded `(pair_frontier, count)` tuple driven by
    `jax.lax.while_loop` — no host round-trip between generations;
  - the MAC score `theta*d - (Ra+Rb)` for a whole frontier is one Pallas
    launch (repro.kernels.mac), jnp reference where Pallas would interpret;
  - accepted / leaf-leaf / truncated pairs append to padded output buffers
    via mask + exclusive-cumsum scatters (mode="drop" keeps shapes static);
  - child expansion replicates the host ordering exactly (target-split
    children first, then source-split), so the emitted pair lists are
    *byte-identical in order* to `dual_traversal` whenever the f32 MAC
    decisions agree with the f64 host decisions — which the golden tests pin
    on robust cases (see tests/test_traversal_device.py).

Capacities are static powers of two derived from the padded cell count; an
overflow flag triggers a doubled-capacity retry on the host (rare — the
heuristics overshoot).  All trees of one geometry share one padded cell
envelope, so every (receiver, sender) pair of a `plan_geometry` reuses a
single traced program.

The traversal also returns the minimum accepted-M2L margin — exactly the
slack quantity `api._m2l_margin` recomputes on the host — so a device-planned
geometry's MAC-slack budgets consume device margins directly.

Step revalidation (`partition_drift` / `restack_payload`): a within-slack
`FMMSession.step` needs per-partition `max |x_new - x_ref|` drift and a
changed-partition mask.  Instead of the per-partition NumPy loop, the engine
uploads `new_x` once, restacks it into the `(P, Nmax, 3)` payload envelope
through the frozen global-id gather tables ON DEVICE, and reduces drift for
all partitions in one batched launch — the restacked payload then *is* the
next evaluation's payload, so a within-slack step transfers exactly one
`(N, 3)` array host->device and `(P,)` scalars back.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tree import flat_cell_tables
from repro.kernels.mac import mac_margins, mac_margins_ref

__all__ = ["device_dual_traversal", "default_traversal_backend",
           "resolve_traversal_backend", "partition_drift", "restack_payload",
           "traversal_caps"]

_TABLE_KEYS = ("center", "radius", "child_start", "n_child", "is_leaf",
               "truncated")


def default_traversal_backend() -> str:
    """Mirror the engine dispatch default: frontier math on the accelerator
    wherever one is present; the NumPy reference stays the CPU default so CPU
    test runs pin it byte-identically."""
    return "host" if jax.default_backend() in ("cpu",) else "device"


def resolve_traversal_backend(backend: str | None) -> str:
    b = default_traversal_backend() if backend in (None, "auto") else backend
    if b not in ("host", "device"):
        raise ValueError(f"traversal_backend must be 'host', 'device' or "
                         f"'auto', got {backend!r}")
    return b


def default_use_mac_kernel() -> bool:
    from repro.kernels import ops
    return not ops.INTERPRET


# measured ratios vs cell count (sphere/plummer/cube, theta=0.5): frontier
# peaks at 26-212x cells, m2l totals 49-256x, p2p 10-128x, m2p tiny.  Start
# from the mid-range multipliers below and remember overflow-doubled caps per
# padded-cell class, so one geometry pays at most one wasted partial run.
_CAP_MULT = (32, 64, 32, 2)      # frontier, m2l, p2p, m2p
_CAPS_CACHE: dict[int, tuple] = {}


def traversal_caps(pad_cells: int) -> tuple:
    """(frontier, m2l, p2p, m2p) capacities — powers of two (multiples of the
    MAC kernel's 128-lane tile) shared by every pair of one geometry.  Serves
    the last overflow-doubled choice for this padded-cell class when one is
    cached."""
    hit = _CAPS_CACHE.get(int(pad_cells))
    if hit is not None:
        return hit
    def cap(k):
        return max(128, 1 << int(np.ceil(np.log2(max(k, 1)))))
    return tuple(cap(m * pad_cells) for m in _CAP_MULT)


# --------------------------------------------------------- traced program ---
@functools.partial(jax.jit,
                   static_argnames=("theta", "caps", "use_kernel", "interpret"))
def _traversal_loop(tt, ts, *, theta, caps, use_kernel, interpret):
    """One device program: the whole dual traversal of one (target, source)
    tree pair.  tt/ts: flat cell tables (tree.flat_cell_tables, uploaded).
    Returns padded output buffers + counts + min accepted margin + overflow.
    """
    Kcap, Mcap, Pcap, Qcap = caps
    i32 = jnp.int32

    def score(ca, ra, cb, rb):
        if use_kernel:
            return mac_margins(ca, ra, cb, rb, theta, interpret=interpret)
        return mac_margins_ref(ca, ra, cb, rb, theta)

    def append(mask, A, B, out_a, out_b, count, cap):
        m = mask.astype(i32)
        pos = count + jnp.cumsum(m) - m              # exclusive prefix
        idx = jnp.where(mask, pos, cap)              # cap => dropped
        return (out_a.at[idx].set(A, mode="drop"),
                out_b.at[idx].set(B, mode="drop"),
                count + m.sum())

    def body(st):
        A, B, n = st["A"], st["B"], st["n"]
        valid = jnp.arange(Kcap, dtype=i32) < n
        ca, ra = tt["center"][A], tt["radius"][A]
        cb, rb = ts["center"][B], ts["radius"][B]
        margin = score(ca, ra, cb, rb)
        far = valid & (margin > 0)
        min_margin = jnp.minimum(
            st["min_margin"], jnp.min(jnp.where(far, margin, jnp.inf)))
        leaf_t, leaf_s = tt["is_leaf"][A], ts["is_leaf"][B]
        both_leaf = valid & ~far & leaf_t & leaf_s
        trunc = both_leaf & ts["truncated"][B]
        near = both_leaf & ~trunc

        m2l_a, m2l_b, n_m2l = append(far, A, B, st["m2l_a"], st["m2l_b"],
                                     st["n_m2l"], Mcap)
        p2p_a, p2p_b, n_p2p = append(near, A, B, st["p2p_a"], st["p2p_b"],
                                     st["n_p2p"], Pcap)
        m2p_a, m2p_b, n_m2p = append(trunc, A, B, st["m2p_a"], st["m2p_b"],
                                     st["n_m2p"], Qcap)

        # split the larger cell (or the only splittable one) — host rule,
        # host ordering: target-split children first, then source-split
        rem = valid & ~far & ~both_leaf
        split_t = rem & ~leaf_t & (leaf_s | (ra >= rb))
        split_s = rem & ~split_t
        nt = jnp.where(split_t, tt["n_child"][A], 0).astype(i32)
        ns = jnp.where(split_s, ts["n_child"][B], 0).astype(i32)
        off_t = jnp.cumsum(nt) - nt
        total_t = nt.sum()
        off_s = total_t + jnp.cumsum(ns) - ns
        new_n = total_t + ns.sum()

        col = jnp.arange(8, dtype=i32)[None, :]      # octree: <= 8 children
        newA = jnp.zeros(Kcap, i32)
        newB = jnp.zeros(Kcap, i32)
        tpos = jnp.where(col < nt[:, None], off_t[:, None] + col, Kcap)
        newA = newA.at[tpos.ravel()].set(
            (tt["child_start"][A][:, None] + col).ravel(), mode="drop")
        newB = newB.at[tpos.ravel()].set(
            jnp.broadcast_to(B[:, None], (Kcap, 8)).ravel(), mode="drop")
        spos = jnp.where(col < ns[:, None], off_s[:, None] + col, Kcap)
        newA = newA.at[spos.ravel()].set(
            jnp.broadcast_to(A[:, None], (Kcap, 8)).ravel(), mode="drop")
        newB = newB.at[spos.ravel()].set(
            (ts["child_start"][B][:, None] + col).ravel(), mode="drop")

        overflow = (st["overflow"] | (n_m2l > Mcap) | (n_p2p > Pcap)
                    | (n_m2p > Qcap) | (new_n > Kcap))
        return {"A": newA, "B": newB, "n": new_n,
                "m2l_a": m2l_a, "m2l_b": m2l_b, "n_m2l": n_m2l,
                "p2p_a": p2p_a, "p2p_b": p2p_b, "n_p2p": n_p2p,
                "m2p_a": m2p_a, "m2p_b": m2p_b, "n_m2p": n_m2p,
                "min_margin": min_margin, "overflow": overflow}

    init = {"A": jnp.zeros(Kcap, i32), "B": jnp.zeros(Kcap, i32),
            "n": jnp.asarray(1, i32),
            "m2l_a": jnp.zeros(Mcap, i32), "m2l_b": jnp.zeros(Mcap, i32),
            "n_m2l": jnp.asarray(0, i32),
            "p2p_a": jnp.zeros(Pcap, i32), "p2p_b": jnp.zeros(Pcap, i32),
            "n_p2p": jnp.asarray(0, i32),
            "m2p_a": jnp.zeros(Qcap, i32), "m2p_b": jnp.zeros(Qcap, i32),
            "n_m2p": jnp.asarray(0, i32),
            "min_margin": jnp.asarray(jnp.inf, jnp.float32),
            "overflow": jnp.asarray(False)}
    return jax.lax.while_loop(
        lambda st: (st["n"] > 0) & ~st["overflow"], body, init)


# ----------------------------------------------------------- host wrapper ---
def _as_device_tables(tables: dict) -> dict:
    return {k: jnp.asarray(tables[k]) for k in _TABLE_KEYS}


# (id(tree), pad_cells) -> (weakref anchor, device tables).  plan_geometry
# traverses every receiver tree against P-1 senders plus itself; without this
# memo each pair would rebuild + re-upload the same flat tables.  Entries
# self-evict when the tree dies (same pattern as api.DeviceMemo).  Grafted
# LET views are deliberately NOT memoized: each graft is traversed exactly
# once but lives in its RemoteBlock for the geometry's lifetime, so caching
# would pin O(P^2 * pad_cells) device tables with zero reuse.
_TREE_TABLE_CACHE: dict = {}


def _device_tables_for(tree, pad_cells: int | None) -> dict:
    if getattr(tree, "truncated", None) is not None:    # grafted LET view
        return _as_device_tables(flat_cell_tables(tree, pad_cells=pad_cells))
    key = (id(tree), pad_cells)
    hit = _TREE_TABLE_CACHE.get(key)
    if hit is not None:
        return hit[1]
    dev = _as_device_tables(flat_cell_tables(tree, pad_cells=pad_cells))
    try:
        anchor = weakref.ref(tree,
                             lambda _, k=key: _TREE_TABLE_CACHE.pop(k, None))
    except TypeError:
        anchor = tree
    _TREE_TABLE_CACHE[key] = (anchor, dev)
    return dev


def device_dual_traversal(tgt_tree, src_tree, theta: float = 0.5,
                          with_m2p: bool = False, *, pad_cells: int | None = None,
                          use_kernel: bool | None = None,
                          interpret: bool | None = None,
                          max_retries: int = 8):
    """Device dual traversal of one (target, source) tree pair.

    Returns `(m2l, p2p, m2p, min_margin)`: `(*, 2)` int64 host pair arrays in
    the exact emission order of the host reference, plus the minimum accepted
    M2L margin `theta*d - (Ra+Rb)` (f32; +inf when no pair was accepted).
    With `with_m2p=False`, truncated source cells are a contract violation
    (same assert as the host path).  Overflowing a capacity retries with all
    capacities doubled (`max_retries` guards runaways).
    """
    if use_kernel is None:
        use_kernel = default_use_mac_kernel()
    if interpret is None:
        from repro.kernels import ops
        interpret = ops.INTERPRET
    tt = _device_tables_for(tgt_tree, pad_cells)
    ts = tt if src_tree is tgt_tree else _device_tables_for(src_tree,
                                                           pad_cells)
    pad_class = max(tt["radius"].shape[0], ts["radius"].shape[0])
    caps = traversal_caps(pad_class)
    grew = False
    for _ in range(max_retries + 1):
        out = _traversal_loop(tt, ts, theta=float(theta), caps=caps,
                              use_kernel=bool(use_kernel),
                              interpret=bool(interpret))
        if not bool(out["overflow"]):
            if grew:        # remember only capacities that actually worked
                _CAPS_CACHE[int(pad_class)] = caps
            break
        caps = tuple(2 * c for c in caps)
        grew = True
    else:
        raise RuntimeError(f"device traversal overflowed after "
                           f"{max_retries} capacity doublings")

    # one transfer, then slice on the host: a device slice per count would
    # compile a program for every distinct length
    out = jax.device_get({k: v for k, v in out.items()
                          if k not in ("A", "B")})

    def pairs(kind):
        n = int(out[f"n_{kind}"])
        return np.stack([out[f"{kind}_a"][:n], out[f"{kind}_b"][:n]],
                        axis=1).astype(np.int64)

    m2l, p2p, m2p = pairs("m2l"), pairs("p2p"), pairs("m2p")
    if not with_m2p and len(m2p):
        raise AssertionError("truncated source cells require with_m2p=True")
    return m2l, p2p, m2p, float(out["min_margin"])


# ------------------------------------------------------ step revalidation ---
@functools.partial(jax.jit, static_argnames=("shape",))
def _restack_kernel(new, orig_idx, flat_idx, *, shape):
    P, Nmax = shape
    tail = new.shape[1:]
    flat = jnp.zeros((P * Nmax,) + tail, jnp.float32)
    return flat.at[flat_idx].set(new[orig_idx]).reshape((P, Nmax) + tail)


def restack_payload(new, orig_idx, flat_idx, n_parts: int, n_bodies_max: int):
    """Scatter an original-order device array (N, ...) into the engine's
    stacked `(P, Nmax, ...)` payload envelope — the device-side equivalent of
    `schedules.stack_bodies`, consuming the uploaded `new_x` directly (no
    host restack, no per-partition transfers)."""
    return _restack_kernel(new, orig_idx, flat_idx,
                           shape=(int(n_parts), int(n_bodies_max)))


@jax.jit
def _drift_changed_kernel(x_pad, ref_pad, old_pad):
    drift = jnp.sqrt(((x_pad - ref_pad) ** 2).sum(-1).max(1))
    changed = jnp.abs(x_pad - old_pad).max(axis=(1, 2)) > 0
    return drift, changed


def partition_drift(x_pad, ref_pad, old_pad):
    """Batched MAC-slack revalidation inputs: per-partition drift
    `max_i |x_i - x_ref_i|` against the structure reference and a
    changed-since-last-payload mask — ONE launch for all partitions (the
    host path loops partitions in NumPy).  Padded rows are zero in all three
    arrays and contribute drift 0 / changed False."""
    return _drift_changed_kernel(x_pad, ref_pad, old_pad)

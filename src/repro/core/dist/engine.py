"""ShardedEngine: the batched FMM engine distributed over a shard_map mesh.

Partitions are grouped into contiguous blocks of `nparts / n_ranks` per mesh
rank; every stacked `(n_parts, ...)` envelope of the single-device engine is
sharded on its leading axis, so each rank runs the *same* phase kernels the
`DeviceEngine` runs — on its own partitions only — with one new step wedged
between the upward pass and the far field:

  1. upward (local)   : `engine.upward.batched_upward_kernel` on the rank's
                        (P_r, ...) slice — bitwise-identical per partition;
  2. pack + EXCHANGE  : gather the dynamic words (multipoles, bodies) of
                        every LET span this rank originates into the shared
                        pool (`dist.layout`), then run one protocol's
                        collective program (`dist.programs`) — bulk
                        all_to_all, grain-chunked ppermute rounds, or the
                        HSDX relay tree;
  3. far field + P2P  : M2L/M2P/P2P tables whose remote sources point into
                        the received *halo* rows (`M_src = [local | halo]`),
                        then the same downward sweep / leaf evaluation.

Steps 1 and 3 are each one jitted program shared by every protocol of a
geometry (`_pack`, `_far`); only the small exchange program of step 2 is
compiled per protocol, so a protocol sweep compiles the FMM phases once.

Each phase returns the engine's padded f32 value tables; the host f64
accumulation is identical to `DeviceEngine.evaluate`'s non-x64 path, which
is what pins phi parity (the acceptance tolerance) across all protocols.

The compute tables differ from `engine.schedules.build_engine_tables` only
in id spaces: targets are rank-local (`j_local * Cmax + c`), co-resident
senders stay direct reads, and off-rank senders index the halo block
appended after the rank's own cells/bodies.  Everything crossing the wire is
f32 words of the frozen LET format, so the bytes each collective carries are
exactly `GeometryPlan.bytes_matrix` aggregated to rank granularity —
asserted at program build time and again in tests.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.dist import programs as prog_mod
from repro.core.dist.layout import build_wire_layout, build_wire_tables
from repro.core.engine.m2l import m2l_accumulate
from repro.core.engine.p2p import p2p_rows_vals
from repro.core.multipole import get_operators

__all__ = ["ShardedEngine"]

# padded-row fills that keep every masked lane finite: a zero displacement /
# coincident target-center pair would send the kernel's 1/r derivatives to
# inf, and inf * 0-mask is NaN
_SAFE_D = np.array([1.0, 0.0, 0.0], np.float32)
_FAR_CENTER = np.array([1e6, 1e6, 1e6], np.float32)


def _pad_rank_rows(rows: dict, cap: int, fills: dict) -> dict:
    out = {}
    n = len(next(iter(rows.values()))) if rows else 0
    for k, a in rows.items():
        if n == cap:
            out[k] = a
            continue
        pad = np.broadcast_to(fills[k], (cap - n,) + a.shape[1:]).astype(
            a.dtype)
        out[k] = np.concatenate([a, pad], axis=0) if n else pad.copy()
    return out


def _rank_spec(mesh):
    from jax.sharding import PartitionSpec as PS
    return PS(mesh.axis_names[0])


# The two compute programs of a sharded evaluation take no protocol: every
# protocol of one geometry shares their compiled executables, and only the
# small exchange program between them differs (ShardedEngine._exchange).
@partial(jax.jit, static_argnames=("mesh", "ops", "n_cells", "ppr"))
def _pack(x, q, pt, rt, *, mesh, ops, n_cells, ppr):
    """Upward pass, then the dynamic words (multipoles, bodies) of every LET
    span a rank originates packed into its wire pool.
    -> (M_flat (D*ppr*n_cells, nk), pool (D, W))."""
    from repro.core.engine.upward import batched_upward_kernel

    def rank_pack(x, q, pt, rt):
        M = batched_upward_kernel(
            ops, x, q, pt["leaves"], pt["leaf_mask"], pt["leaf_centers"],
            pt["leaf_idx"], pt["leaf_valid"], pt["up_ids"], pt["up_parents"],
            pt["up_mask"], pt["up_d"], n_cells=n_cells)
        M_flat = M.reshape(ppr * n_cells, ops.nk)
        src_vec = jnp.concatenate([M_flat.reshape(-1), x.reshape(-1),
                                   q.reshape(-1)])
        pool = rt["pool_template"][0]
        pool = pool.at[rt["pack_dst"][0]].set(src_vec[rt["pack_src"][0]])
        return M_flat, pool[None]

    spec = _rank_spec(mesh)
    return jax.shard_map(rank_pack, mesh=mesh, in_specs=(spec,) * 4,
                         out_specs=(spec, spec), check_vma=False)(x, q, pt, rt)


@partial(jax.jit,
         static_argnames=("mesh", "ops", "n_cells", "ppr", "n_buckets"))
def _far(x, q, M_flat, pool, pt, rt, *, mesh, ops, n_cells, ppr, n_buckets):
    """Far field over [local | halo] sources, the downward sweep, L2P, P2P
    and M2P of every rank, reading the received halo rows out of the
    exchanged pool.  -> the padded f32 value tables (L2P, each P2P bucket,
    M2P when present)."""
    def rank_far(x, q, M_flat, pool, pt, rt):
        pool = pool[0]
        M_halo = pool[rt["halo_M_idx"][0]]
        x_halo = pool[rt["halo_x_idx"][0]]
        q_halo = pool[rt["halo_q_idx"][0]]
        M_src = jnp.concatenate([M_flat, M_halo], axis=0)
        L_flat = jnp.zeros((ppr * n_cells, ops.nk), jnp.float32)
        if "m2l_src" in rt:
            L_flat = m2l_accumulate(ops, M_src, L_flat, rt["m2l_src"][0],
                                    rt["m2l_tgt"][0], rt["m2l_d"][0],
                                    rt["m2l_mask"][0])
        L = L_flat.reshape(ppr, n_cells, ops.nk)

        def l2l_one(Lp, ids, parents, mask, d):
            return Lp.at[ids].add(ops.l2l_v(Lp[parents], d) * mask[:, None])

        for lvl in range(pt["down_ids"].shape[1]):
            L = jax.vmap(l2l_one)(L, pt["down_ids"][:, lvl],
                                  pt["down_parents"][:, lvl],
                                  pt["down_mask"][:, lvl],
                                  pt["down_d"][:, lvl])

        def l2p_one(Lp, xp, lf, lm, lc, li):
            return ops.l2p_v(Lp[lf], xp[li], lc) * lm[:, None]

        outs = [jax.vmap(l2p_one)(L, x, pt["leaves"], pt["leaf_mask"],
                                  pt["leaf_centers"], pt["leaf_idx"])]
        x_flat = x.reshape(-1, 3)
        x_src = jnp.concatenate([x_flat, x_halo], axis=0)
        q_src = jnp.concatenate([q.reshape(-1), q_halo], axis=0)
        for bi in range(n_buckets):
            outs.append(p2p_rows_vals(
                x_flat, x_src, q_src, rt[f"pb{bi}_t_idx"][0],
                rt[f"pb{bi}_s_idx"][0], rt[f"pb{bi}_s_valid"][0],
                rt[f"pb{bi}_mask"][0]))
        if "m2p_b" in rt:
            outs.append(ops.m2p_v(M_src[rt["m2p_b"][0]],
                                  x_flat[rt["m2p_t_idx"][0]],
                                  rt["m2p_centers"][0])
                        * rt["m2p_mask"][0][:, None])
        return tuple(outs)

    spec = _rank_spec(mesh)
    n_outs = 1 + n_buckets + (1 if "m2p_b" in rt else 0)
    return jax.shard_map(rank_far, mesh=mesh, in_specs=(spec,) * 6,
                         out_specs=(spec,) * n_outs, check_vma=False)(
        x, q, M_flat, pool, pt, rt)


class ShardedEngine:
    """Multi-device evaluation of one `GeometryPlan` over a 1-D mesh.

    Parameters
    ----------
    geometry : api.GeometryPlan (nparts must divide evenly over the mesh)
    mesh : a 1-D `jax.sharding.Mesh` (e.g. `launch.mesh.host_device_mesh`)
    grain_bytes : chunk size of the "grain" protocol's ppermute rounds;
        default the LogGP eager limit (the granularity the paper tunes
        around, Fig 6).
    """

    def __init__(self, geometry, mesh, *, grain_bytes: int | None = None):
        from repro.core.engine.schedules import (build_batched_upward,
                                                 stack_bodies)
        if len(mesh.axis_names) != 1:
            raise ValueError("ShardedEngine needs a 1-D mesh; got axes "
                             f"{mesh.axis_names}")
        self.geo = geometry
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_ranks = int(np.prod(mesh.devices.shape))
        self.grain_bytes = grain_bytes
        self._ops = get_operators(geometry.p)

        up = build_batched_upward(geometry.trees, geometry.scheds)
        self.up = up
        P, Cmax, Nmax = up.n_parts, up.n_cells_max, up.n_bodies_max
        self.layout = build_wire_layout(geometry, self.n_ranks)
        self.wire = build_wire_tables(geometry, self.layout,
                                      n_cells_max=Cmax, n_bodies_max=Nmax,
                                      nk=self._ops.nk)
        self._build_compute_tables()
        self._x_pad, self._q_pad = self._shard(stack_bodies(geometry.trees,
                                                            Nmax))
        self._programs: dict = {}
        self._ex_fns: dict = {}

    # ------------------------------------------------------------- tables --
    def _build_compute_tables(self) -> None:
        geo, up = self.geo, self.up
        lay, wire = self.layout, self.wire
        D, ppr = lay.n_ranks, lay.parts_per_rank
        P, Cmax, Nmax = up.n_parts, up.n_cells_max, up.n_bodies_max
        nk = self._ops.nk

        m2l_rk = [{"src": [], "tgt": [], "mask": [], "d": []}
                  for _ in range(D)]
        m2p_rk = [{"b": [], "mask": [], "centers": [], "t_idx": [],
                   "t_valid": []} for _ in range(D)]
        buckets_rk: list = [dict() for _ in range(D)]

        def add_m2l(r, inter, tgt_off, src_map):
            n = inter.n_m2l
            if n:
                m2l_rk[r]["tgt"].append(tgt_off + inter.m2l_a[:n])
                m2l_rk[r]["src"].append(src_map(inter.m2l_b[:n]))
                m2l_rk[r]["mask"].append(inter.m2l_mask[:n])
                m2l_rk[r]["d"].append(inter.m2l_d[:n])

        def add_m2p(r, inter, body_off, src_map):
            n = inter.n_m2p
            if n:
                m2p_rk[r]["b"].append(src_map(inter.m2p_b[:n]))
                m2p_rk[r]["mask"].append(inter.m2p_mask[:n])
                m2p_rk[r]["centers"].append(inter.m2p_centers[:n])
                m2p_rk[r]["t_idx"].append(body_off + inter.m2p_t_idx[:n])
                m2p_rk[r]["t_valid"].append(inter.m2p_t_valid[:n])

        def add_p2p(r, inter, tgt_off, s_map):
            for blk in inter.p2p_blocks:
                n = blk.n
                key = (blk.t_idx.shape[1], blk.s_idx.shape[1])
                rows = buckets_rk[r].setdefault(
                    key, {"t_idx": [], "t_valid": [], "s_idx": [],
                          "s_valid": [], "mask": []})
                rows["t_idx"].append(tgt_off + blk.t_idx[:n])
                rows["t_valid"].append(blk.t_valid[:n])
                rows["s_idx"].append(s_map(blk.s_idx[:n], blk.s_valid[:n]))
                rows["s_valid"].append(blk.s_valid[:n])
                rows["mask"].append(blk.mask[:n])

        for j, recv in enumerate(geo.receivers):
            if recv is None:
                continue
            r, jl = j // ppr, j % ppr
            coff, boff = jl * Cmax, jl * Nmax
            add_m2l(r, recv.local, coff, lambda b, o=coff: o + b)
            add_p2p(r, recv.local, boff, lambda s, v, o=boff: o + s)
            for rb in recv.remote:
                i = rb.sender
                let = geo.lets[(i, j)]
                if lay.part_rank[i] == r:
                    # co-resident sender: read its device cells/bodies
                    # directly, exactly like the single-device engine
                    cs, bs = let.cell_src, let.body_src
                    soff_c = (i % ppr) * Cmax
                    soff_b = (i % ppr) * Nmax
                    add_m2l(r, rb.inter, coff,
                            lambda b, cs=cs, o=soff_c: o + cs[b])
                    add_m2p(r, rb.inter, boff,
                            lambda b, cs=cs, o=soff_c: o + cs[b])
                    add_p2p(r, rb.inter, boff,
                            lambda s, v, bs=bs, o=soff_b:
                            np.where(v, o + bs[np.where(v, s, 0)], 0))
                else:
                    # off-rank sender: graft-local ids index the received
                    # halo rows appended after this rank's own block
                    hco = ppr * Cmax + wire.halo_cell_off[(i, j)]
                    hbo = ppr * Nmax + wire.halo_body_off[(i, j)]
                    add_m2l(r, rb.inter, coff, lambda b, o=hco: o + b)
                    add_m2p(r, rb.inter, boff, lambda b, o=hco: o + b)
                    add_p2p(r, rb.inter, boff,
                            lambda s, v, o=hbo: np.where(v, o + s, 0))

        def cat(rows):
            return {k: np.concatenate(v, axis=0) for k, v in rows.items()}

        # ---- m2l: (D, Bm) stacked, NaN-safe padded ------------------------
        m2l_cat = [cat(r) if r["src"] else None for r in m2l_rk]
        m2l_cap = max((len(r["src"]) for r in m2l_cat if r), default=0)
        m2l_fill = {"src": np.int64(0), "tgt": np.int64(0),
                    "mask": np.float32(0.0), "d": _SAFE_D}
        m2l_stk = {k: [] for k in m2l_fill}
        for r in range(D):
            rows = _pad_rank_rows(m2l_cat[r] or {
                "src": np.zeros(0, np.int64), "tgt": np.zeros(0, np.int64),
                "mask": np.zeros(0, np.float32),
                "d": np.zeros((0, 3), np.float32)}, m2l_cap, m2l_fill)
            for k in m2l_stk:
                m2l_stk[k].append(rows[k])
        self.m2l = {k: np.stack(v) for k, v in m2l_stk.items()} \
            if m2l_cap else None

        # ---- m2p: (D, Bf, ...) ------------------------------------------
        wt = up.tables["leaf_idx"].shape[2]
        m2p_cat = [cat(r) if r["b"] else None for r in m2p_rk]
        m2p_cap = max((len(r["b"]) for r in m2p_cat if r), default=0)
        m2p_fill = {"b": np.int64(0), "mask": np.float32(0.0),
                    "centers": _FAR_CENTER, "t_idx": np.int64(0),
                    "t_valid": np.False_}
        m2p_stk = {k: [] for k in m2p_fill}
        for r in range(D):
            rows = _pad_rank_rows(m2p_cat[r] or {
                "b": np.zeros(0, np.int64), "mask": np.zeros(0, np.float32),
                "centers": np.zeros((0, 3), np.float32),
                "t_idx": np.zeros((0, wt), np.int64),
                "t_valid": np.zeros((0, wt), bool)}, m2p_cap, m2p_fill)
            for k in m2p_stk:
                m2p_stk[k].append(rows[k])
        self.m2p = {k: np.stack(v) for k, v in m2p_stk.items()} \
            if m2p_cap else None

        # ---- p2p: globally sorted width classes, rows padded per rank ----
        keys = sorted({k for br in buckets_rk for k in br})
        self.p2p_buckets = []
        for key in keys:
            wt_b, ws_b = key
            fill = {"t_idx": np.int64(0), "t_valid": np.False_,
                    "s_idx": np.int64(0), "s_valid": np.False_,
                    "mask": np.float32(0.0)}
            empty = {"t_idx": np.zeros((0, wt_b), np.int64),
                     "t_valid": np.zeros((0, wt_b), bool),
                     "s_idx": np.zeros((0, ws_b), np.int64),
                     "s_valid": np.zeros((0, ws_b), bool),
                     "mask": np.zeros(0, np.float32)}
            per_rank = [cat(buckets_rk[r][key]) if key in buckets_rk[r]
                        else empty for r in range(D)]
            cap = max(len(p["mask"]) for p in per_rank)
            stk = {k: np.stack([_pad_rank_rows(p, cap, fill)[k]
                                for p in per_rank]) for k in fill}
            self.p2p_buckets.append(stk)

        # ---- host accumulation indices -----------------------------------
        self._l2p_idx = (up.tables["leaf_idx"]
                         + (np.arange(P, dtype=np.int64)
                            * Nmax)[:, None, None])
        self._l2p_valid = up.tables["leaf_valid"]
        rank_body_off = (np.arange(D, dtype=np.int64)
                         * ppr * Nmax)[:, None, None]
        self._bucket_gidx = [b["t_idx"] + rank_body_off
                             for b in self.p2p_buckets]
        self._m2p_gidx = (self.m2p["t_idx"] + rank_body_off
                          if self.m2p is not None else None)
        orig_chunks, flat_chunks = [], []
        for j, t in enumerate(geo.trees):
            if t is None:
                continue
            orig_chunks.append(geo.owners[j][t.perm])
            flat_chunks.append(j * Nmax + np.arange(len(t.x), dtype=np.int64))
        self._orig_idx = np.concatenate(orig_chunks)
        self._flat_idx = np.concatenate(flat_chunks)

        # ---- shard_map input pytrees (int32 on the wire side) ------------
        ut = up.tables
        self._part_tabs = {k: ut[k] for k in
                           ("leaves", "leaf_mask", "leaf_centers", "leaf_idx",
                            "leaf_valid", "up_ids", "up_parents", "up_mask",
                            "up_d", "down_ids", "down_parents", "down_mask",
                            "down_d")}
        rt = {"pool_template": wire.pool_template,
              "pack_src": wire.pack_src, "pack_dst": wire.pack_dst,
              "halo_M_idx": wire.halo_M_idx, "halo_x_idx": wire.halo_x_idx,
              "halo_q_idx": wire.halo_q_idx}
        if self.m2l is not None:
            for k, v in self.m2l.items():
                rt[f"m2l_{k}"] = v
        if self.m2p is not None:
            for k, v in self.m2p.items():
                rt[f"m2p_{k}"] = v
        for bi, b in enumerate(self.p2p_buckets):
            for k, v in b.items():
                rt[f"pb{bi}_{k}"] = v
        self._part_tabs, self._rank_tabs = self._shard((self._part_tabs, rt))

    def _shard(self, tree):
        """Upload host arrays once, split on their leading (partition or
        rank) axis over the mesh, so no evaluation re-uploads them."""
        from jax.sharding import NamedSharding
        return jax.device_put(tree, NamedSharding(self.mesh,
                                                  _rank_spec(self.mesh)))

    # ----------------------------------------------------------- programs --
    def program(self, protocol: str) -> prog_mod.ExchangeProgram:
        if protocol not in self._programs:
            with obs.span("dist.build_program"):
                self._programs[protocol] = prog_mod.build_exchange_program(
                    self.layout, protocol, grain_bytes=self.grain_bytes)
        return self._programs[protocol]

    def exchange_stats(self, protocol: str) -> dict:
        """Measured wire accounting of one protocol's program plus the LogGP
        prediction for the schedule it executes."""
        p = self.program(protocol)
        s = p.stats()
        s["loggp_time"] = prog_mod.predicted_time(p)
        s["rank_bytes"] = self.layout.rank_bytes.tolist()
        return s

    def _statics(self) -> dict:
        return dict(mesh=self.mesh, ops=self._ops,
                    n_cells=self.up.n_cells_max,
                    ppr=self.layout.parts_per_rank)

    def _exchange(self, protocol: str):
        """(jitted exchange-only program, its round tables) for one protocol,
        memoized; the compute programs are shared by every protocol."""
        if protocol not in self._ex_fns:
            program = self.program(protocol)
            self._ex_fns[protocol] = (self._build_exchange_fn(program),
                                      prog_mod.round_tables(program))
        return self._ex_fns[protocol]

    def evaluate(self, protocol: str = "bulk") -> np.ndarray:
        """Full potential in original body order (float64, host) — the
        rank-sharded phases run under `shard_map`, phi accumulates in host
        f64 exactly like `DeviceEngine.evaluate`'s non-x64 path."""
        with obs.span("dist.evaluate") as sp:
            statics = self._statics()
            x, q, pt, rt = self._x_pad, self._q_pad, self._part_tabs, \
                self._rank_tabs
            M_flat, pool = _pack(x, q, pt, rt, **statics)
            ex, rtabs = self._exchange(protocol)
            outs = _far(x, q, M_flat, ex(pool, rtabs), pt, rt,
                        n_buckets=len(self.p2p_buckets), **statics)
            obs.counter_add("dist.evaluations")
            if obs.enabled():
                sp.set({"protocol": protocol, "n_ranks": self.n_ranks})
        up = self.up
        P, Nmax = up.n_parts, up.n_bodies_max
        phi_flat = np.zeros(P * Nmax)
        np.add.at(phi_flat, self._l2p_idx.ravel(),
                  np.where(self._l2p_valid.ravel(),
                           np.asarray(outs[0], np.float64).ravel(), 0.0))
        for gidx, bucket, vals in zip(self._bucket_gidx, self.p2p_buckets,
                                      outs[1:1 + len(self.p2p_buckets)]):
            np.add.at(phi_flat, gidx.ravel(),
                      np.where(bucket["t_valid"].ravel(),
                               np.asarray(vals, np.float64).ravel(), 0.0))
        if self.m2p is not None:
            np.add.at(phi_flat, self._m2p_gidx.ravel(),
                      np.where(self.m2p["t_valid"].ravel(),
                               np.asarray(outs[-1], np.float64).ravel(),
                               0.0))
        phi = np.zeros(self.geo.n)
        phi[self._orig_idx] = phi_flat[self._flat_idx]
        return phi

    def refresh_payload(self, geometry) -> None:
        """Rebind to a same-structure geometry (within-slack step): restack
        the (x, q) payload only.  Multipoles and LET payloads are recomputed
        on device from this payload each evaluation, so — unlike the
        single-device engine — no host-side multipole/LET refresh is ever
        needed here."""
        from repro.core.engine.schedules import stack_bodies
        self.geo = geometry
        self._x_pad, self._q_pad = self._shard(
            stack_bodies(geometry.trees, self.up.n_bodies_max))

    # ------------------------------------------------------- verification --
    def verify_exchange(self, protocol: str = "bulk") -> int:
        """Audit one protocol's wire: run pack + exchange (real upward-pass
        payload, no FMM phases after) returning every rank's pool BOTH
        before and after the collective, then check word-exact on the host
        that each inter-rank span landed at its receiver unchanged —
        `packed[rank(i), off:off+w] == exchanged[rank(j), off:off+w]` for
        every layout pair (i, j).  Raises `ExchangeVerificationError` on the
        first corrupted span (resilient sessions treat that as a dist
        failure and fall back to the single-device engine); returns the
        number of verified spans.  Triggered by `REPRO_VERIFY_EXCHANGE=1`
        once per (protocol, geometry version) via the session."""
        from repro.resilience.fallback import ExchangeVerificationError

        with obs.span("dist.verify_exchange"):
            _, packed = _pack(self._x_pad, self._q_pad, self._part_tabs,
                              self._rank_tabs, **self._statics())
            ex, rtabs = self._exchange(protocol)
            exchanged = ex(packed, rtabs)
        packed = np.asarray(packed)
        exchanged = np.asarray(exchanged)
        lay = self.layout
        for (i, j) in lay.pairs:
            off, w = lay.span_off[(i, j)], lay.span_words[(i, j)]
            ri, rj = int(lay.part_rank[i]), int(lay.part_rank[j])
            sent = packed[ri, off:off + w]
            got = exchanged[rj, off:off + w]
            if not np.array_equal(sent, got):
                nbad = int((sent != got).sum())
                raise ExchangeVerificationError(
                    "dist.exchange.verify",
                    f"protocol {protocol!r}: span ({i}, {j}) "
                    f"[rank {ri} -> rank {rj}, {w} words @ {off}] arrived "
                    f"corrupted: {nbad} mismatched words")
        obs.counter_add("dist.exchange.verified")
        return len(lay.pairs)

    # ---------------------------------------------------------- benchmark --
    def _build_exchange_fn(self, program: prog_mod.ExchangeProgram):
        """Jitted shard_map program running ONLY one `ExchangeProgram` over
        the rank pools: `fn(pool (D, W), round_tables) -> pool`.  Evaluation
        runs it between the shared pack and far-field programs; the
        benchmarks time it alone, including single-round sub-programs
        (`measure_exchange(per_round=True)`)."""
        from jax.sharding import PartitionSpec as PS
        axis = self.axis

        def rank_ex(pool, rtabs):
            return prog_mod.apply_exchange(pool[0], program, rtabs,
                                           axis)[None]

        return jax.jit(jax.shard_map(
            rank_ex, mesh=self.mesh, in_specs=(PS(axis),) * 2,
            out_specs=PS(axis), check_vma=False))

    def exchange_fn(self, protocol: str):
        """`fn()` running one protocol's exchange program on the wire
        template alone (no FMM phases) — what `benchmarks/fig8_exchange.py`
        times against the LogGP prediction."""
        ex, rtabs = self._exchange(protocol)
        return self._template_runner(ex, rtabs)

    def _template_runner(self, ex, rtabs):
        pool = jnp.asarray(self.wire.pool_template)
        return lambda: ex(pool, rtabs)

    def measure_exchange(self, protocol: str, *, reps: int = 3,
                         per_round: bool = False) -> dict:
        """Run one protocol's exchange-only program and compare measured
        wall time against its LogGP prediction — the `model_drift` probe
        (ISSUE 8): drift = measured_s / loggp_s, so 1.0 means the analytic
        model still predicts the wire.

        Returns the program's static `stats()` plus measured_s / loggp_s /
        model_drift / reps and a per-round breakdown (kind + wire bytes,
        with measured_s per round when `per_round=True` — each round is
        compiled as its own single-round sub-program)."""
        import dataclasses as _dc
        import time as _time
        p = self.program(protocol)
        fn = self.exchange_fn(protocol)
        jax.block_until_ready(fn())          # warm: compile outside timing
        t0 = _time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        measured = (_time.perf_counter() - t0) / reps
        loggp = prog_mod.predicted_time(p)
        drift = measured / loggp if loggp > 0 else float("inf")
        rounds = [{"kind": r.kind, "wire_bytes": 4 * r.wire_words}
                  for r in p.rounds]
        if per_round:
            for rnd, rec in zip(p.rounds, rounds):
                sub = _dc.replace(p, rounds=(rnd,))
                sub_fn = self._template_runner(self._build_exchange_fn(sub),
                                               prog_mod.round_tables(sub))
                jax.block_until_ready(sub_fn())
                rt0 = _time.perf_counter()
                for _ in range(reps):
                    rout = sub_fn()
                jax.block_until_ready(rout)
                rec["measured_s"] = (_time.perf_counter() - rt0) / reps
        st = p.stats()
        st.update(measured_s=measured, loggp_s=loggp, model_drift=drift,
                  reps=reps, rounds=rounds,
                  rank_bytes=self.layout.rank_bytes.tolist())
        obs.observe(f"dist.model_drift.{protocol}", drift)
        if obs.enabled():
            obs.event("dist.exchange_probe",
                      {"protocol": protocol, "measured_s": measured,
                       "loggp_s": loggp, "model_drift": drift,
                       "moved_bytes": int(p.moved_bytes.sum()),
                       "n_rounds": p.n_rounds})
        return st

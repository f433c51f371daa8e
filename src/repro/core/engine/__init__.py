"""Device evaluation engine: batched multi-tree FMM execution.

Fourth tier of the pipeline — `plan_geometry` (host geometry) ->
`schedule_comm` (protocol schedules) -> **`DeviceEngine`** (batched device
execution) -> `FMMSession` (orchestration).  A `DeviceEngine` is compiled
once per `GeometryPlan`: `schedules.build_engine_tables` stacks every
partition's frozen per-tree tables into `(n_parts, ...)` envelopes, and
evaluation then runs

  1. one batched upward launch (`upward.batched_upward_kernel`) — P2M + M2M
     for ALL partitions, replacing the per-partition Python sweep;
  2. one far-field launch (`m2l.far_tail_kernel`) — a segment-summed M2L
     over every (receiver, sender) pair reading sender-global device
     multipoles (grafted LETs never materialize on the host), the stacked
     downward sweep, and the leaf evaluation;
  3. one launch per P2P width-class bucket (`p2p.p2p_bucket_vals`),
     Pallas-backed with per-(S, n_pairs) autotuned block sizes on device
     backends, jnp reference on CPU; plus one batched M2P fallback launch.

Float64 accumulation of the f32 value tables happens once on the host, at
the API boundary — identical precision to the reference executors, which is
what pins the engine allclose to `api.execute_geometry`.

Timesteps: index tables are payload-independent, so a within-slack
`FMMSession.step` calls `refresh_payload` — restack + upload ONE `(x, q)`
array pair, invalidate the cached multipoles — and the next evaluation
recomputes every drifting partition's multipoles on device in a single
launch: zero per-partition host->device multipole transfers (the
`DeviceMemo.misses` counter is the transfer meter tests pin).

Serving tier on top (`fused=True`, default on device backends): the phases
above fuse into ONE donated entry-computation launch per warm `evaluate()` /
within-slack `step_drift()` (engine.fused), AOT-compiled once per *shape
class* through `engine.exe_cache.ExecutableCache` — a second geometry with
the same padded dims/statics pays zero XLA compilations.  Payload buffers
are donated and threaded back out (XLA input-output aliasing); DeviceMemo
table views are never donated (see `fmm.device_hook`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.engine import fused as _fused_mod
from repro.core.engine.exe_cache import (ExecutableCache, GLOBAL_CACHE,
                                         resolve_cache)
from repro.core.engine.m2l import far_tail_kernel, m2p_vals_kernel
from repro.core.engine.p2p import p2p_bucket_vals, p2p_stream_vals
from repro.core.engine.schedules import (BatchedUpwardSchedule, EngineTables,
                                         build_batched_upward,
                                         build_engine_tables,
                                         build_p2p_stream_tables,
                                         shape_class_digest, stack_bodies,
                                         stack_reference_bodies,
                                         stream_out_index)
from repro.core.engine.traversal import (default_traversal_backend,
                                         device_dual_traversal,
                                         partition_drift,
                                         resolve_traversal_backend,
                                         restack_payload)
from repro.core.engine.upward import batched_upward, batched_upward_kernel
from repro.core.fmm import device_hook
from repro.core.multipole import get_operators
from repro.resilience import faults as _faults

__all__ = ["DeviceEngine", "EngineTables", "BatchedUpwardSchedule",
           "build_engine_tables", "build_batched_upward", "batched_upward",
           "batched_upward_kernel", "build_p2p_stream_tables", "stack_bodies",
           "default_engine_enabled", "default_use_kernels",
           "default_fused_enabled", "default_p2p_stream",
           "default_traversal_backend", "resolve_traversal_backend",
           "device_dual_traversal", "partition_drift", "restack_payload",
           "ExecutableCache", "GLOBAL_CACHE", "resolve_cache",
           "shape_class_digest"]


def default_engine_enabled() -> bool:
    """Engine dispatch default: batched execution wins on any real device
    backend (launch count dominates); the per-partition reference path stays
    the CPU default so CPU test runs pin it byte-identically."""
    return jax.default_backend() not in ("cpu",)


def default_use_kernels() -> bool:
    """Pallas kernel dispatch default: only where the kernels actually
    COMPILE — the same predicate repro.kernels.ops uses for interpret mode.
    On backends where Pallas would run interpreted (traced Python, orders of
    magnitude slower than the jnp path), the engine keeps the jnp route."""
    from repro.kernels import ops
    return not ops.INTERPRET


def default_fused_enabled() -> bool:
    """Fused-megakernel dispatch default: mirror `default_engine_enabled` —
    launch overhead only dominates where there is a launch (device
    backends); on CPU the per-phase engine stays the default so CPU test
    runs keep pinning its counters byte-identically.  Opt in anywhere with
    `fused=True`."""
    return jax.default_backend() not in ("cpu",)


def default_p2p_stream() -> bool:
    """Streaming-P2P dispatch default: only where the kernel's DMA pipeline
    is real — the TPU backend.  Elsewhere (CPU tests, GPU) the gathered
    buckets stay the default; opt in anywhere with `p2p_stream=True` (on CPU
    that routes through the XLA slab-gather program unless `use_kernels`
    forces interpret-mode kernel emulation)."""
    return jax.default_backend() == "tpu"


class DeviceEngine:
    """Batched device executor for one `GeometryPlan` (one tree *structure*;
    the numeric payload may rebind across timesteps via `refresh_payload`).

    Parameters
    ----------
    geometry : api.GeometryPlan
    use_kernels : route P2P buckets through the Pallas kernels; default
        `default_use_kernels()` (on iff a device backend is present).
    interpret : force Pallas interpret mode (CI smoke on CPU runners).
    asarray : device-upload hook (api.DeviceMemo or compatible); a fresh
        `DeviceMemo` is created when omitted.  `memo.misses` counts every
        host->device transfer the engine performs.
    fused : collapse each warm `evaluate()` / `step_drift()` into ONE
        donated entry-computation launch (engine.fused), AOT-compiled
        through the shape-class executable cache; default
        `default_fused_enabled()` (on iff a device backend is present).
        The per-phase path stays available on the same engine and is the
        pinned numeric comparison.
    exe_cache : `exe_cache.ExecutableCache` for fused executables; the
        process-wide `GLOBAL_CACHE` when omitted, so geometries of one
        shape class share one compilation across sessions.
    p2p_stream : run the P2P near field through the unified streaming
        kernel (`kernels.p2p_stream`: in-kernel slab gathers, double-
        buffered VMEM DMA, all width classes one grid) instead of one
        gathered launch per width-class bucket; default
        `default_p2p_stream()` (on iff TPU).  Falls back to the gathered
        buckets per geometry when the stream-table contiguity invariant
        does not hold (`p2p.stream.fallbacks` counter).
    """

    def __init__(self, geometry, *, use_kernels: bool | None = None,
                 interpret: bool | None = None, asarray=None,
                 fused: bool | None = None, exe_cache=None,
                 p2p_stream: bool | None = None):
        from repro.core.api import DeviceMemo
        self.geo = geometry
        self.use_kernels = (default_use_kernels() if use_kernels is None
                            else bool(use_kernels))
        self.interpret = interpret
        self.fused = default_fused_enabled() if fused is None else bool(fused)
        self.p2p_stream = (default_p2p_stream() if p2p_stream is None
                           else bool(p2p_stream))
        self._stream = None          # unified stream tables, built lazily
        self._stream_params = None   # autotuned (block_t, n_buffers)
        self._stream_out_tabs = None  # host per-lane stream output slots
        self._stream_candidates = {}  # block_t -> tables, autotune sweep
        self._stream_warm = set()     # (block_t, n_buffers) compiled+warmed
        self.exe_cache = resolve_cache(exe_cache)
        self._entries: dict = {}     # (kind, x64) -> (CompiledEntry, tabs)
        self.launch_log: list = []   # (kind, key) per fused dispatch
        self.memo = DeviceMemo() if asarray is None else asarray
        self._aa = device_hook(self.memo)
        self.tables: EngineTables = build_engine_tables(geometry)
        self._x_pad, self._q_pad = stack_bodies(geometry.trees,
                                                self.tables.n_bodies_max)
        self._ops = get_operators(geometry.p)
        self._M = None               # cached device multipoles (P, Cmax, nk)
        self._x_ref_pad = None       # stacked slack reference, built lazily
        self._pending_x_pad = None   # device payload staged by step_drift
        self.payload_refreshes = 0
        # f32 guard band for drift-vs-slack decisions: step_drift measures in
        # f32 (inputs rounded before subtraction), so its absolute error is
        # a few ulps of the coordinate scale.  Decisions within the band must
        # fall back to the exact f64 host revalidation (api.FMMSession.step).
        self.drift_guard = float(4 * np.finfo(np.float32).eps
                                 * max(np.abs(geometry.x_ref).max(), 1.0))

    # ----------------------------------------------------------- payload --
    def refresh_payload(self, geometry, *, use_pending: bool = False) -> None:
        """Rebind to a same-structure geometry (within-slack step): restack
        the (x, q) payload and invalidate cached device multipoles.  Index
        tables — and their memoized device views — are reused untouched.

        With `use_pending=True` the device payload staged by the last
        `step_drift` call becomes the new x payload directly — the host never
        restacks and the step's only host->device transfer was `new_x` (the
        session guarantees q is unchanged on this path)."""
        self.geo = geometry
        if use_pending and self._pending_x_pad is not None:
            self._x_pad = self._pending_x_pad
        else:
            self._x_pad, self._q_pad = stack_bodies(geometry.trees,
                                                    self.tables.n_bodies_max)
        self._pending_x_pad = None
        self._M = None
        self.payload_refreshes += 1

    def discard_pending(self) -> None:
        self._pending_x_pad = None

    # ------------------------------------------------------------- fused --
    def _donatable(self, arr, dtype=None):
        """Upload (explicit copy) or pass through an array that is safe to
        DONATE to a fused launch.  Memo-resident views are rejected: donation
        deletes the buffer after the call, and the `DeviceMemo` would keep
        serving the dead view to every other consumer (the per-phase path,
        sibling engines) — the residency/donation contract of engine.fused
        and `fmm.device_hook`."""
        if isinstance(arr, jax.Array):
            if self.memo.is_resident(arr):
                raise TypeError(
                    "refusing to donate a DeviceMemo-resident view: donated "
                    "buffers are deleted after the launch, which would "
                    "poison the memo (engine.fused donation contract); pass "
                    "a fresh upload or a previous fused output instead")
            obs.counter_add("engine.donate.reuse")
            return arr if dtype is None else jnp.asarray(arr, dtype)
        # jnp.array (copy), never asarray: CPU zero-copy uploads alias the
        # caller's host buffer, and XLA would scribble over it on donation
        obs.counter_add("engine.donate.upload")
        return jnp.array(np.asarray(arr), dtype=dtype)

    def _payload_device(self):
        """The (x_pad, q_pad) payload as donatable device buffers: fresh
        copies on first use / after a host `refresh_payload`, previous fused
        outputs (aliased storage) on warm calls."""
        return (self._donatable(self._x_pad, jnp.float32),
                self._donatable(self._q_pad, jnp.float32))

    # ---------------------------------------------------------- streaming --
    def _measure_stream(self, block_t: int, n_buffers: int) -> float:
        """Time one streaming launch at candidate (block_t, n_buffers) —
        the `best_stream_params` measure closure on real backends (the
        tiling depends on block_t, so tables are built once per block_t)."""
        import time
        stream = self._stream_candidates.get(block_t)
        if stream is None:
            stream = build_p2p_stream_tables(self.tables.p2p_buckets,
                                             block_t)
            if stream is None:
                return float("inf")
            self._stream_candidates[block_t] = stream
        aa = self._aa
        fn = lambda: p2p_stream_vals(
            aa(self._x_pad, jnp.float32), aa(self._q_pad, jnp.float32),
            stream, use_kernels=True, interpret=self.interpret,
            asarray=self.memo, n_buffers=n_buffers)
        if (block_t, n_buffers) not in self._stream_warm:
            jax.block_until_ready(fn())      # compile + warm, once
            self._stream_warm.add((block_t, n_buffers))
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    def _stream_tables(self):
        """Resolve the unified stream tables for this geometry (lazily, once):
        autotune (block_t, n_buffers) for the stream shape class, build the
        tile table, and VERIFY the contiguous-run invariant — returns None
        (and flips the engine back to gathered buckets, with a
        RuntimeWarning and the `p2p.stream.fallbacks` counter) when the
        invariant does not hold."""
        if not self.p2p_stream:
            return None
        if self._stream is not None:
            return self._stream
        t = self.tables
        if not t.p2p_buckets:
            self.p2p_stream = False
            return None
        from repro.kernels import ops as kops
        from repro.kernels.p2p import best_stream_params
        interp = (kops.INTERPRET if self.interpret is None
                  else bool(self.interpret))
        smax = max(b["s_idx"].shape[1] for b in t.p2p_buckets)
        wt_max = max(b["t_idx"].shape[1] for b in t.p2p_buckets)
        n_rows = sum(len(b["mask"]) for b in t.p2p_buckets)
        measure = (self._measure_stream
                   if self.use_kernels and not interp else None)
        bt, nb = best_stream_params(smax, n_rows, wt_max,
                                    interpret=interp, measure=measure)
        stream = self._stream_candidates.get(bt)
        self._stream_candidates = {}     # drop the sweep's other tilings
        self._stream_warm = set()
        if stream is None:
            stream = build_p2p_stream_tables(t.p2p_buckets, bt)
        if stream is None:
            obs.counter_add("p2p.stream.fallbacks")
            import warnings
            warnings.warn(
                "streaming P2P is off for this geometry: its near-field rows "
                "are not contiguous runs of bodies, so the gathered buckets "
                "run instead (counted at p2p.stream.fallbacks)",
                RuntimeWarning, stacklevel=3)
            self.p2p_stream = False
            return None
        self._stream = stream
        self._stream_params = (bt, nb)
        obs.counter_add("p2p.stream.builds")
        if obs.enabled():
            obs.event("p2p.stream.tables",
                      {"n_tiles": stream["n_tiles"],
                       "n_live_tiles": stream["n_live_tiles"],
                       "smax": stream["smax"], "block_t": bt,
                       "n_buffers": nb, "n_buckets": len(t.p2p_buckets)})
        return stream

    def _stream_out(self):
        """Host per-lane (out_idx, out_valid) of the stream tables, built
        once for the per-phase accumulation (the fused program derives them
        in-trace instead)."""
        if self._stream_out_tabs is None:
            s = self._stream
            self._stream_out_tabs = stream_out_index(
                s["meta"], s["tgt_range"], s["block_t"])
        return self._stream_out_tabs

    def _fused_entry(self, kind: str):
        """Resolve this engine's fused executable + uploaded tables for
        `kind` in ("evaluate", "step"), memoized per (kind, x64): the
        shape-class cache is consulted ONCE per engine lifetime, so its
        hit/miss counters meter per-geometry resolutions — a second
        same-shape-class geometry is exactly one `hits` increment and zero
        compilations."""
        x64 = bool(jax.config.jax_enable_x64)
        hit = self._entries.get((kind, x64))
        if hit is not None:
            return hit
        t = self.tables
        aa = self._aa
        if kind == "evaluate":
            donate = (0, 1)          # both payload halves alias outputs
            stream = self._stream_tables()
            flat = _fused_mod.flatten_eval_tables(t, stream=stream)
            if stream is not None:
                p2p_impl = "stream"
                nb = self._stream_params[1]
                block_ts = (stream["smax"], stream["block_t"], nb)
            else:
                p2p_impl = "gathered"
                nb = 2
                block_ts = _fused_mod.bucket_block_ts(
                    t, use_kernels=self.use_kernels, interpret=self.interpret)
            fn = _fused_mod.build_fused_evaluate(
                self._ops, t, use_kernels=self.use_kernels,
                interpret=self.interpret, block_ts=block_ts,
                acc_dtype=jnp.float64 if x64 else jnp.float32,
                stream=stream, n_buffers=nb)
            in_sds = (jax.ShapeDtypeStruct((t.n_parts, t.n_bodies_max, 3),
                                           jnp.float32),
                      jax.ShapeDtypeStruct((t.n_parts, t.n_bodies_max),
                                           jnp.float32))
        elif kind == "step":
            # donate x_pad only: new_x has no same-shape output to alias
            # onto, so donating it would just trigger XLA's unusable-buffer
            # warning without saving an allocation
            donate = (1,)
            if self._x_ref_pad is None:
                self._x_ref_pad = stack_reference_bodies(self.geo, t)
            flat = _fused_mod.flatten_step_tables(t, self._x_ref_pad)
            block_ts, p2p_impl = (), "gathered"   # step runs no P2P
            fn = _fused_mod.build_fused_step(t)
            in_sds = (jax.ShapeDtypeStruct((t.n, 3), jnp.float32),
                      jax.ShapeDtypeStruct((t.n_parts, t.n_bodies_max, 3),
                                           jnp.float32))
        else:
            raise ValueError(f"unknown fused entry kind {kind!r}")
        # memoized device views — the digest sees canonicalized dtypes
        tabs = {k: aa(v) for k, v in flat.items()}
        key = _fused_mod.executable_key(
            kind, shape_class_digest(tabs), n=t.n, n_parts=t.n_parts, p=t.p,
            theta=self.geo.theta, x64=x64, backend=jax.default_backend(),
            use_kernels=self.use_kernels, interpret=self.interpret,
            block_ts=block_ts, p2p_impl=p2p_impl)
        entry = self.exe_cache.get_or_compile(
            key, lambda: jax.jit(fn, donate_argnums=donate)
            .lower(*in_sds, tabs).compile())
        self._entries[(kind, x64)] = (entry, tabs)
        return entry, tabs

    def _evaluate_fused(self):
        """One donated launch: payload in, potential (and multipoles) out.
        The threaded-through payload outputs rebind the engine's handles —
        XLA aliases them onto the donated inputs' storage."""
        with obs.span("engine.fused_evaluate") as sp:
            # simulated-OOM seam: a RESOURCE_EXHAUSTED here is what an
            # oversubscribed accelerator raises on the entry launch, and
            # what the resilience ladder downgrades past
            _faults.fire("fused.launch")
            entry, tabs = self._fused_entry("evaluate")
            xd, qd = self._payload_device()
            phi, M, x_out, q_out = sp.fence(entry(xd, qd, tabs))
            obs.counter_add("engine.fused_launches")
            if self._stream is not None:
                obs.counter_add("p2p.stream.launches")
                obs.counter_add("p2p.stream.tiles",
                                self._stream["n_live_tiles"])
                obs.counter_add("p2p.stream.dma_tiles",
                                2 * self._stream["n_live_tiles"])
        self._x_pad, self._q_pad = x_out, q_out
        self._M = M
        self.launch_log.append(("evaluate", entry.key))
        return phi

    def step_drift(self, new_x) -> tuple:
        """Batched MAC-slack revalidation: upload `new_x` ONCE, restack it
        into the (P, Nmax, 3) payload envelope on device through the frozen
        global-id tables, and reduce every partition's drift (vs the slack
        reference `x_ref`) and changed flag (vs the current payload) in one
        launch — replacing the session's per-partition NumPy loop.  The
        restacked payload is staged for `refresh_payload(use_pending=True)`.

        Returns (drift (P,) float64, changed (P,) bool) host arrays.

        Fused mode runs the restack + both reductions as ONE donated entry
        computation (engine.fused.build_fused_step): `new_x` uploads as a
        donated copy, the current payload is donated and threaded back out
        (aliased), and the restacked envelope is staged as the pending
        payload without ever touching the host."""
        if self.fused:
            with obs.span("engine.step_drift"):
                entry, tabs = self._fused_entry("step")
                nd = self._donatable(new_x, jnp.float32)
                xd = self._donatable(self._x_pad, jnp.float32)
                drift, changed, x_new, x_out = entry(nd, xd, tabs)
                self._x_pad = x_out
                self._pending_x_pad = x_new
                self.launch_log.append(("step", entry.key))
                obs.counter_add("engine.fused_launches")
                return (np.asarray(drift, np.float64),
                        np.asarray(changed, bool))
        with obs.span("engine.step_drift"):
            t = self.tables
            aa = self._aa
            if self._x_ref_pad is None:
                self._x_ref_pad = stack_reference_bodies(self.geo, t)
            xd = aa(new_x, jnp.float32)
            x_pad = restack_payload(xd, aa(t.orig_idx), aa(t.flat_idx),
                                    t.n_parts, t.n_bodies_max)
            drift, changed = partition_drift(x_pad, aa(self._x_ref_pad),
                                             aa(self._x_pad, jnp.float32))
            self._pending_x_pad = x_pad
            return (np.asarray(drift, np.float64),
                    np.asarray(changed, bool))

    # ------------------------------------------------------------ passes --
    def upward(self):
        """Device multipoles (P, n_cells_max, nk); cached per payload."""
        if self._M is None:
            with obs.span("engine.upward") as sp:
                self._M = sp.fence(
                    batched_upward(self._ops, self._x_pad, self._q_pad,
                                   self.tables.up, asarray=self.memo))
        return self._M

    def _phase_values(self):
        """Run the three batched phases; yields (idx, valid, vals) value
        tables (device f32) for the final accumulation."""
        t = self.tables
        aa = self._aa
        M = self.upward()
        x = aa(self._x_pad, jnp.float32)
        q = aa(self._q_pad, jnp.float32)
        ut = t.up.tables

        with obs.span("engine.far_field") as sp:
            l2p_vals = sp.fence(far_tail_kernel(
                self._ops, M, x,
                {k: aa(v) for k, v in t.m2l.items()},
                aa(ut["down_ids"]), aa(ut["down_parents"]),
                aa(ut["down_mask"]), aa(ut["down_d"]), aa(ut["leaves"]),
                aa(ut["leaf_mask"]), aa(ut["leaf_centers"]),
                aa(ut["leaf_idx"])))
        yield t.l2p_t_idx, ut["leaf_valid"], l2p_vals

        stream = self._stream_tables()
        if stream is not None:
            with obs.span("engine.p2p_stream") as sp:
                vals = sp.fence(p2p_stream_vals(
                    x, q, stream, use_kernels=self.use_kernels,
                    interpret=self.interpret, asarray=self.memo,
                    n_buffers=self._stream_params[1]))
                obs.counter_add("p2p.stream.launches")
                obs.counter_add("p2p.stream.tiles",
                                stream["n_live_tiles"])
                # two slab DMAs (sources + targets) per live tile
                obs.counter_add("p2p.stream.dma_tiles",
                                2 * stream["n_live_tiles"])
            yield (*self._stream_out(), vals)
        else:
            for bucket in t.p2p_buckets:
                with obs.span("engine.p2p_bucket") as sp:
                    vals = sp.fence(p2p_bucket_vals(
                        x, q, bucket, use_kernels=self.use_kernels,
                        interpret=self.interpret, asarray=self.memo,
                        to_host=False))
                yield bucket["t_idx"], bucket["t_valid"], vals

        if t.m2p["b"].shape[0]:
            with obs.span("engine.m2p") as sp:
                vals = sp.fence(m2p_vals_kernel(
                    self._ops, M, x, aa(t.m2p["b"]), aa(t.m2p["centers"]),
                    aa(t.m2p["mask"]), aa(t.m2p["t_idx"])))
            yield t.m2p["t_idx"], t.m2p["t_valid"], vals

    def evaluate_device(self) -> jnp.ndarray:
        """Full potential in original body order as ONE device (N,) float64
        array — the whole pipeline from payload to potentials stays on the
        accelerator.  Requires x64 on the backend (jax_enable_x64): without
        it the f64 segment sums would silently truncate to f32, so this
        raises instead (the host accumulation path keeps f64 precision when
        x64 is off)."""
        if not jax.config.jax_enable_x64:
            raise RuntimeError(
                "evaluate_device requires jax_enable_x64 (device f64 "
                "accumulation); use evaluate() for host f64 accumulation")
        if self.fused:
            return self._evaluate_fused()
        t = self.tables
        aa = self._aa
        phi_flat = jnp.zeros(t.n_parts * t.n_bodies_max, jnp.float64)
        for idx, valid, vals in self._phase_values():
            contrib = jnp.where(aa(valid).ravel(),
                                vals.astype(jnp.float64).ravel(), 0.0)
            phi_flat = phi_flat.at[aa(idx).ravel()].add(contrib)
        return (jnp.zeros(t.n, jnp.float64)
                .at[aa(t.orig_idx)].set(phi_flat[aa(t.flat_idx)]))

    def evaluate(self) -> np.ndarray:
        """Full potential in original body order (float64, host).

        With x64 enabled on the backend, the f64 accumulation itself runs on
        device (`evaluate_device`) and the only host transfer is the final
        (N,) potential; otherwise each phase's padded f32 value tables are
        accumulated in host float64 (identical precision to the reference
        executors, which is what pins the engine against them).

        Fused mode is one donated launch either way; without x64 the fused
        program can only accumulate in device f32 — marginally looser than
        this host-f64 path (tight-tolerance equivalence holds under x64)."""
        if jax.config.jax_enable_x64:
            return np.asarray(self.evaluate_device())
        if self.fused:
            return np.asarray(self._evaluate_fused(), np.float64)
        t = self.tables
        phi_flat = np.zeros(t.n_parts * t.n_bodies_max)
        for idx, valid, vals in self._phase_values():
            np.add.at(phi_flat, np.asarray(idx).ravel(),
                      np.where(np.asarray(valid).ravel(),
                               np.asarray(vals, np.float64).ravel(), 0.0))
        phi = np.zeros(t.n)
        phi[t.orig_idx] = phi_flat[t.flat_idx]
        return phi

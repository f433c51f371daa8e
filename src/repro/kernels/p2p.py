"""Pallas TPU kernel: batched P2P Laplace direct sum.

The FMM's compute floor (paper §5: Laplace kernel, Cartesian, P=4) is the
leaf-leaf particle interaction.  For a batch of interaction pairs, each with
up to S sources and T targets:

    phi[p, t] = sum_s q[p, s] / |x_tgt[p, t] - x_src[p, s]|     (self term 0)

TPU adaptation (vs the paper's SIMD CPU loops): targets are tiled into
VMEM-resident blocks of `block_t` lanes (lane-aligned multiples of 128); the
full source block for the pair stays in VMEM across the target tile;
coordinates are laid out structure-of-arrays (3, S) so the subtraction
broadcasts on the VPU's 8x128 registers; the q-weighted reduction runs as an
(block_t, S) x (S,) contraction.  Arithmetic intensity ~ 6 flops / 4 bytes
per (t, s) pair at S=256.

The engine's P2P buckets (repro.core.engine.p2p) arrive with power-of-two
source widths S that vary per bucket; `best_block_t` picks the target block
size per (S, n_pairs) shape class and caches the choice — a one-entry
autotune per bucket shape, measured on real device backends and heuristic
under interpret mode (where wall time is meaningless).
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.resilience import faults as _faults
from repro.resilience.faults import InjectedFault as _InjectedFault

TB = 128                        # default target block (lane-aligned)
BLOCK_CANDIDATES = (128, 256, 512)
STREAM_BUFFER_CANDIDATES = (2, 3)   # double vs triple buffering (p2p_stream)

# (S, n_pairs, T) -> chosen target block size.  Keyed by the bucket's padded
# shape class, NOT by array identity: every execution of the same geometry
# (and every geometry sharing bucket shapes) reuses one autotune decision.
# T is part of the key — buckets sharing (S, n_pairs) but differing in
# target width need different tilings.
_BLOCK_CACHE: dict[tuple[int, int, int], int] = {}

# (smax, n_rows, wt_max) -> (block_t, n_buffers) for the streaming kernel
# (repro.kernels.p2p_stream): a 2-D autotune space — the VMEM target tile
# AND the DMA pipeline depth — keyed by the unified stream schedule's
# block_t-independent shape class.
_STREAM_CACHE: dict[tuple[int, int, int], tuple[int, int]] = {}

# --- on-disk persistence of MEASURED autotune choices ----------------------
# Measured sweeps (real device backends) are the expensive part of warmup;
# persisting them keyed by (backend, shape class) lets repeat runs — and
# serving fleets — skip the sweep entirely.  Interpret-mode heuristics are
# free to recompute and are never persisted, so CPU test runs touch no disk.
# Opt out with REPRO_P2P_CACHE=0; relocate with REPRO_P2P_CACHE_PATH.
#
# Schema (version 2): {"version": 2, "entries": {backend: {key: value}}}.
# Keys are "S,n,T" (gathered kernel, value = int block_t) or
# "stream:smax,rows,wt" (streaming kernel, value = [block_t, n_buffers]).
# The original unversioned format ({backend: {"S,n,T": int}}) is migrated
# silently on read and rewritten as version 2 on the next save; files with
# an UNKNOWN (future) version are ignored rather than misread as shape keys.
#
# Degradation contract: the disk cache is an optimization, NEVER a
# correctness or liveness dependency.  An unreadable/unwritable location
# (read-only container fs, $HOME on a squashed image, a path under a file)
# warns ONCE, flips the process to in-memory-only operation and never
# touches the disk again — a mid-benchmark run must not crash or spam.
_PERSIST_LOADED = False
_PERSIST_BROKEN = False
_QUARANTINED = False
_SCHEMA_VERSION = 2


def _cache_io_failed(action: str, exc: BaseException) -> None:
    """First disk failure: one RuntimeWarning, then in-memory-only mode."""
    global _PERSIST_BROKEN
    if _PERSIST_BROKEN:
        return
    _PERSIST_BROKEN = True
    from repro.resilience import fallback as _fb
    _fb.record_fallback(f"p2p.cache.{action}", "disk_cache", "in_memory",
                        warn=False)      # the warning below is the warn-once
    import warnings
    warnings.warn(
        f"p2p autotune cache disabled: could not {action} "
        f"{_persist_path()!r} ({exc!r}); continuing with the in-memory "
        f"cache only (set REPRO_P2P_CACHE_PATH to a writable location or "
        f"REPRO_P2P_CACHE=0 to silence)", RuntimeWarning, stacklevel=3)


def _quarantine_corrupt(exc: BaseException) -> None:
    """Corrupt/truncated cache JSON: move the file aside (quarantine) so the
    next save rebuilds a clean one, warn ONCE, and keep running — a damaged
    cache file must never take a session down (it is an optimization, not a
    correctness dependency).  Distinct from `_cache_io_failed`: the location
    is still usable, so persistence stays ON and rebuilds."""
    global _QUARANTINED
    path = _persist_path()
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        pass                             # racing process already moved it
    from repro import obs
    obs.counter_add("p2p.cache.quarantined")
    if _QUARANTINED:
        return
    _QUARANTINED = True
    import warnings
    warnings.warn(
        f"p2p autotune cache {path!r} is corrupt ({exc!r}); quarantined to "
        f"{path + '.corrupt'!r} and rebuilding from scratch (warns once)",
        RuntimeWarning, stacklevel=3)


def _persist_enabled() -> bool:
    return os.environ.get("REPRO_P2P_CACHE", "1").lower() not in (
        "0", "", "off", "no", "false")


def _persist_path() -> str:
    return os.environ.get("REPRO_P2P_CACHE_PATH") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro-fmm",
        "p2p_block_cache.json")


def _parse_entries(data) -> dict:
    """Normalize an on-disk payload to {backend: {key_str: value}}.

    Accepts the current versioned schema AND the original unversioned
    format (silent migration: version 1 was exactly the entries mapping).
    Anything else — including a FUTURE version this build does not
    understand — yields {} so stale processes never misread new keys."""
    if not isinstance(data, dict):
        return {}
    version = data.get("version")
    if version is None:                      # legacy v1: entries at top level
        return {k: v for k, v in data.items() if isinstance(v, dict)}
    if version == _SCHEMA_VERSION:
        entries = data.get("entries", {})
        return entries if isinstance(entries, dict) else {}
    return {}                                # unknown/future schema: ignore


def _load_persisted(backend: str) -> None:
    """Merge this backend's persisted choices into the in-process caches
    (once per process; in-process entries win)."""
    global _PERSIST_LOADED
    if _PERSIST_LOADED:
        return
    _PERSIST_LOADED = True
    try:
        _faults.fire("p2p.cache.read")
        with open(_persist_path()) as f:
            data = json.load(f)
    except FileNotFoundError:
        return                       # cold cache: normal, silent
    except ValueError as exc:        # corrupt/truncated JSON: quarantine it
        _quarantine_corrupt(exc)
        return
    except (OSError, _InjectedFault) as exc:
        # unreadable location (or injected read fault): warn once, degrade
        _cache_io_failed("read", exc)
        return
    for k, v in _parse_entries(data).get(backend, {}).items():
        try:
            if k.startswith("stream:"):
                sm, rows, wt = (int(t) for t in k[len("stream:"):].split(","))
                bt, nb = int(v[0]), int(v[1])
                if bt > 0 and bt % 128 == 0 and nb in STREAM_BUFFER_CANDIDATES:
                    _STREAM_CACHE.setdefault((sm, rows, wt), (bt, nb))
                continue
            S, n, T = (int(t) for t in k.split(","))
            choice = int(v)
        except (TypeError, ValueError, IndexError):
            continue
        # effective_block_t may clamp candidates to any lane-aligned width
        # (e.g. 384), so validate alignment, not membership in CANDIDATES
        if choice > 0 and choice % 128 == 0:
            _BLOCK_CACHE.setdefault((S, n, T), choice)


def _save_persisted(backend: str, key_str: str, value) -> None:
    """Read-merge-write (atomic rename) in the versioned schema — a legacy
    unversioned file is migrated wholesale on the first save.  An unwritable
    location warns once (`_cache_io_failed`) and flips to in-memory-only —
    the cache is an optimization, never a correctness dependency."""
    path = _persist_path()
    try:
        _faults.fire("p2p.cache.write")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        try:
            with open(path) as f:
                entries = _parse_entries(json.load(f))
        except OSError:
            entries = {}
        except ValueError as exc:    # corrupt on the read-merge: quarantine
            _quarantine_corrupt(exc)
            entries = {}
        entries.setdefault(backend, {})[key_str] = value
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump({"version": _SCHEMA_VERSION, "entries": entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except (OSError, _InjectedFault) as exc:
        _cache_io_failed("write", exc)


def _tile_phi(q, xs, xt):
    """One VMEM tile of the Laplace direct sum: q (S,) · xs (3, S) SoA ·
    xt (3, block_t) SoA -> phi (block_t,).  Shared verbatim by the gathered
    kernel below and the streaming kernel (repro.kernels.p2p_stream), which
    is what makes the two paths bitwise-comparable: identical expressions on
    identically shaped tiles."""
    dx = xt[0][:, None] - xs[0][None, :]       # (block_t, S)
    dy = xt[1][:, None] - xs[1][None, :]
    dz = xt[2][:, None] - xs[2][None, :]
    r2 = dx * dx + dy * dy + dz * dz
    inv_r = jnp.where(r2 > 0.0, jax.lax.rsqrt(jnp.maximum(r2, 1e-30)), 0.0)
    return jnp.sum(inv_r * q[None, :], axis=1)


def effective_block_t(T: int, block_t: int) -> int:
    """The target tile width actually worth launching: never wider than the
    128-lane-aligned cover of T.  An autotuned 512 block on a 64-target
    bucket would compute 448 garbage lanes per tile — clamping to the cover
    (128 here) stops paying for them without changing any valid lane."""
    return max(128, min(block_t, ((T + 127) // 128) * 128))


PAIR_GROUP = 8                  # pairs per grid step: one sublane tile


def _p2p_kernel(q_ref, xs_ref, xt_ref, out_ref, *, t_total, block_t):
    # blocks of PAIR_GROUP pairs: q (G, S); xs (G, 3, S); xt (G, 3, block_t);
    # out (G, block_t).  Mosaic requires the last two block dims to be
    # (8, 128)-divisible or whole, so pairs travel in sublane-sized groups
    # and each pair's tile is computed on its own.
    if t_total % block_t:
        # partial tail tile: zero the padded lanes (cheap VPU select) so
        # padded targets return 0 instead of garbage
        lane = (pl.program_id(1) * block_t
                + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1)[0])
    for k in range(PAIR_GROUP):
        phi = _tile_phi(q_ref[k], xs_ref[k], xt_ref[k])
        if t_total % block_t:
            phi = jnp.where(lane < t_total, phi, 0.0)
        out_ref[k:k + 1, :] = phi[None, :]


@functools.partial(jax.jit, static_argnames=("interpret", "block_t"))
def p2p_pallas(q, x_src, x_tgt, *, interpret: bool = True,
               block_t: int = TB):
    """q: (P, S); x_src: (P, S, 3); x_tgt: (P, T, 3) -> (P, T).

    Padding convention: padded sources carry q = 0; padded target lanes
    return exactly 0 (the tail tile masks them — the jnp reference's
    garbage rows were always discarded by callers, so only the zeros are
    observable).  `block_t` is the VMEM target tile (lane-aligned multiple
    of 128), clamped to the 128-aligned cover of T (`effective_block_t`)
    so narrow buckets never pay for lanes past their width; pick it with
    `best_block_t` for bucketed shapes.
    """
    if block_t % 128 != 0:
        raise ValueError(f"block_t must be a multiple of 128, got {block_t}")
    P, S, _ = x_src.shape
    T = x_tgt.shape[1]
    block_t = effective_block_t(T, block_t)
    pad_t = (-T) % block_t
    pad_p = (-P) % PAIR_GROUP            # padded pairs: q = 0, sliced off
    xt = jnp.pad(x_tgt, ((0, pad_p), (0, pad_t), (0, 0)))
    Tp, Pp = T + pad_t, P + pad_p
    # structure-of-arrays for lane-friendly broadcast
    xs_t = jnp.swapaxes(jnp.pad(x_src, ((0, pad_p), (0, 0), (0, 0))),
                        1, 2)            # (Pp, 3, S)
    xt_t = jnp.swapaxes(xt, 1, 2)        # (Pp, 3, Tp)
    G = PAIR_GROUP

    out = pl.pallas_call(
        functools.partial(_p2p_kernel, t_total=T, block_t=block_t),
        grid=(Pp // G, Tp // block_t),
        in_specs=[
            pl.BlockSpec((G, S), lambda p, t: (p, 0)),
            pl.BlockSpec((G, 3, S), lambda p, t: (p, 0, 0)),
            pl.BlockSpec((G, 3, block_t), lambda p, t: (p, 0, t)),
        ],
        out_specs=pl.BlockSpec((G, block_t), lambda p, t: (p, t)),
        out_shape=jax.ShapeDtypeStruct((Pp, Tp), q.dtype),
        interpret=interpret,
    )(jnp.pad(q, ((0, pad_p), (0, 0))), xs_t, xt_t)
    return out[:P, :T]


def _heuristic_block_t(S: int, T: int) -> int:
    """Interpret-mode / cold-cache choice: the smallest candidate covering T
    in one tile (fewer grid steps), never exceeding a ~1 MB (3, S)+(block, S)
    VMEM footprint per program (the last fitting candidate wins when all
    covering ones would overflow)."""
    choice = BLOCK_CANDIDATES[0]
    for c in BLOCK_CANDIDATES:
        if (c + 3) * S * 4 > 1 << 20:
            break
        choice = c
        if c >= T:
            break
    return choice


def best_block_t(S: int, n_pairs: int, T: int = TB, *,
                 interpret: bool = True,
                 sample=None) -> int:
    """Autotuned target block size for a P2P bucket shape, cached by
    (S, n_pairs, T).  On a real backend (`interpret=False`) the first call
    for a shape class times every candidate on `sample` (a (q, xs, xt)
    tuple) and keeps the argmin; under interpret mode timing is meaningless,
    so a VMEM heuristic is cached instead.  Measured choices persist to a
    small on-disk JSON keyed (backend, shape class) — see `_persist_path` /
    REPRO_P2P_CACHE — so repeat runs skip the warmup sweep."""
    key = (int(S), int(n_pairs), int(T))
    persist = not interpret and _persist_enabled() and not _PERSIST_BROKEN
    if persist:
        _load_persisted(jax.default_backend())
        persist = not _PERSIST_BROKEN    # load may have just broken it
    from repro import obs
    hit = _BLOCK_CACHE.get(key)
    if hit is not None:
        obs.counter_add("p2p.autotune.cache_hits")
        return hit
    if interpret or sample is None:
        mode = "heuristic"
        choice = _heuristic_block_t(S, T)
    else:
        mode = "measured"
        import statistics
        import time
        q, xs, xt = sample
        # candidates above the 128-aligned cover of T collapse to the same
        # effective tiling (effective_block_t) — time each tiling once
        cands = sorted({effective_block_t(T, c) for c in BLOCK_CANDIDATES})
        best, choice = float("inf"), cands[0]
        for cand in cands:
            fn = lambda: p2p_pallas(q, xs, xt, interpret=False, block_t=cand)
            jax.block_until_ready(fn())          # compile + warm
            reps = []
            for _ in range(3):                   # median rides out one hiccup
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                reps.append(time.perf_counter() - t0)
            dt = statistics.median(reps)
            if dt < best:
                best, choice = dt, cand
        if persist:
            _save_persisted(jax.default_backend(),
                            ",".join(map(str, key)), int(choice))
    _BLOCK_CACHE[key] = choice
    obs.counter_add("p2p.autotune.decisions")
    if obs.enabled():
        obs.event("p2p.autotune",
                  {"S": int(S), "n_pairs": int(n_pairs), "T": int(T),
                   "block_t": int(choice), "mode": mode})
    return choice


def stream_target_cover(wt_max: int) -> int:
    """Lanes a streaming tile must span to hold a whole target row: stream
    windows start at the 128-aligned flat id below the row's first target
    (repro.kernels.p2p_stream), so a row of `wt_max` targets may reach 127
    lanes further."""
    return wt_max + 127


def _heuristic_stream_params(smax: int, wt_max: int) -> tuple[int, int]:
    """Interpret-mode / cold-cache choice for the streaming kernel's 2-D
    space.  block_t: smallest candidate covering the widest target row in
    one window (fewer tiles), shrunk until NB=2 buffers of (source slab +
    target slab) plus the (block_t, source slab) tile temporaries fit a
    ~4 MB VMEM budget.  n_buffers: 2 — triple buffering only pays when DMA
    latency exceeds one tile's compute, which the measured sweep (real
    backends) detects and heuristics can't."""
    from repro.kernels.p2p_stream import src_width
    nb = 2
    ws = src_width(smax)
    cover = stream_target_cover(wt_max)
    choice = BLOCK_CANDIDATES[0]
    for c in BLOCK_CANDIDATES:
        if (nb * 4 * (ws + c) + 6 * c * ws) * 4 > 4 << 20:
            break
        choice = c
        if c >= cover:
            break
    return choice, nb


def best_stream_params(smax: int, n_rows: int, wt_max: int, *,
                       interpret: bool = True,
                       measure=None) -> tuple[int, int]:
    """Autotuned (block_t, n_buffers) for the streaming P2P kernel
    (repro.kernels.p2p_stream), cached by the stream schedule's
    block_t-independent shape class (smax, n_rows, wt_max).

    On a real backend the first call sweeps the 2-D candidate grid through
    `measure(block_t, n_buffers) -> seconds` (a caller-supplied closure that
    rebuilds the stream tables for that block and times the kernel) and
    keeps the argmin; under interpret mode a VMEM-budget heuristic is cached
    instead.  Measured choices persist alongside the gathered-kernel entries
    ("stream:" key prefix, versioned schema)."""
    key = (int(smax), int(n_rows), int(wt_max))
    persist = not interpret and _persist_enabled() and not _PERSIST_BROKEN
    if persist:
        _load_persisted(jax.default_backend())
        persist = not _PERSIST_BROKEN
    from repro import obs
    hit = _STREAM_CACHE.get(key)
    if hit is not None:
        obs.counter_add("p2p.autotune.cache_hits")
        return hit
    if interpret or measure is None:
        mode = "heuristic"
        choice = _heuristic_stream_params(smax, wt_max)
    else:
        mode = "measured"
        import statistics
        bt_cands = sorted({effective_block_t(stream_target_cover(wt_max), c)
                           for c in BLOCK_CANDIDATES})
        best = float("inf")
        choice = (bt_cands[0], STREAM_BUFFER_CANDIDATES[0])
        for bt in bt_cands:
            for nb in STREAM_BUFFER_CANDIDATES:
                reps = [measure(bt, nb) for _ in range(3)]
                dt = statistics.median(reps)
                if dt < best:
                    best, choice = dt, (bt, nb)
        if persist:
            _save_persisted(jax.default_backend(),
                            "stream:" + ",".join(map(str, key)),
                            [int(choice[0]), int(choice[1])])
    _STREAM_CACHE[key] = choice
    obs.counter_add("p2p.autotune.decisions")
    if obs.enabled():
        obs.event("p2p.autotune.stream",
                  {"smax": int(smax), "n_rows": int(n_rows),
                   "wt_max": int(wt_max), "block_t": int(choice[0]),
                   "n_buffers": int(choice[1]), "mode": mode})
    return choice

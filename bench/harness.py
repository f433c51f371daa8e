"""One run of one benchmark cell, driven by data.

Everything a cell is made of is found by name under `bench/`:
`BENCHMARK.json` names the cell's configuration (its `file`) and traffic
mix (`traffic/<mix>.json`), and each per-layer metric is read by
`metrics/<metric>.py`, a module with `read(run) -> float | None` and,
where it needs the session, `prepare(session)`.  So a new configuration,
mix or metric is a new file.

The phases of `run_cell`, in order:

1. set-up: the device check, the caches, the bodies (the configuration's
   positions, charges from `--seed`), the plan (`FMMSession.from_points`,
   resilience off, every default of the platform taken), a cold
   `evaluate()` and one warm-up call of the mix;
2. the window: calls of the mix, closed loop, from one caller, until
   `seconds` have passed; with `trace` the profiler records exactly the
   window, and JAX's compile events inside it are counted;
3. the peak of device memory, then the program's state is freed;
4. the check: every call's potential on targets drawn from the seed
   against the float64 reference (`reference.sums`), by the scaled RMS
   error (`reference.scaled_rms`), each against the configuration's
   limit.

A checkout's first run is primed: `run.py` runs the set-up alone in a
child process (`prime`) before it touches the chip, so the run itself
finds every program and autotune pick in the caches.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"                 # fixed, inside the checkout
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

import reference  # noqa: E402  (bench/ is on sys.path: run.py, tests)
import trace_reduce  # noqa: E402
import workload  # noqa: E402


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- the data --
def load_spec(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str) -> tuple[dict, dict, dict, dict]:
    """(spec, cell, configuration, traffic mix) of workload `name`."""
    spec = load_spec(root)
    cell = _by_name(spec["workloads"], name, "workload")
    cfg = _by_name(spec["configs"], cell["config"], "config")
    config = _json(root / cfg["file"])
    mix = _json(root / BENCH.relative_to(ROOT) / "traffic"
                / f"{cell['traffic']}.json")
    return spec, cell, config, mix


def load_reader(root: Path, name: str):
    """The per-layer metric's reader module, `metrics/<name>.py`."""
    path = root / BENCH.relative_to(ROOT) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics reported in `cell`."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_peaks(kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ------------------------------------------------------------- the run ---
@dataclass
class Run:
    """What a run measured: the readers' input."""
    cell: dict
    config: dict
    mix: dict
    plan_s: float = 0.0
    warmup_s: float = 0.0
    update_s: list = field(default_factory=list)    # per window call
    evaluate_s: list = field(default_factory=list)  # per window call
    window_s: float = 0.0
    window_compiles: int = 0
    errors: dict = field(default_factory=dict)      # call -> scaled RMS
    rel_l2: dict = field(default_factory=dict)      # call -> rel. L2 error
    trace: dict | None = None        # trace_reduce.reduce(...) or None
    prepared: dict = field(default_factory=dict)    # metric -> prepare()

    @property
    def calls(self) -> int:
        return len(self.evaluate_s)


class CompileCounter:
    """Counts JAX backend compiles (persistent-cache loads included) while
    active."""

    def __init__(self):
        self.count = 0
        self.active = False

    def __call__(self, event, *args, **kwargs):
        if self.active and event == BACKEND_COMPILE:
            self.count += 1


def configure_caches(cache: Path) -> None:
    """Fixed cache paths inside the checkout: JAX's compilation cache
    (unless `JAX_COMPILATION_CACHE_DIR` names one) and the P2P autotune
    cache, so only a checkout's first run compiles and sweeps."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(cache / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    os.environ["REPRO_P2P_CACHE_PATH"] = str(cache / "p2p_autotune.json")


def check_device(chips: int):
    """The devices, or NoDevice where there is no TPU or too few chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"{len(devs)} TPU chips, the cell asks for {chips}")
    load_peaks(devs[0].device_kind)
    return devs


def _import_program(root: Path):
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout "
                                f"({src / 'repro'} is missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro import obs
    from repro.core.api import FMMSession, PartitionSpec
    obs.configure(enabled=False)
    return FMMSession, PartitionSpec


def _peak_bytes(devs) -> int | None:
    """Peak device memory of the fullest chip: the allocator's peak of
    buffers in use plus its peak reserved for executables' temporaries,
    which `peak_bytes_in_use` leaves out (on a TPU v5e the fused
    evaluate's temporaries are most of the footprint)."""
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(st["peak_bytes_in_use"]
                         + st.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def _host_rss() -> str:
    try:
        with open("/proc/self/status") as f:
            kv = dict(line.split(":", 1) for line in f if ":" in line)
    except OSError:
        return "not reported"
    return f"{kv.get('VmRSS', '?').strip()} (peak {kv.get('VmHWM', '?').strip()})"




def program_session(root: Path, session_kw: dict | None = None):
    """The system under test as the window drives it: a factory of
    `FMMSession`s, one plan of the deployment's partition per call,
    resilience off and every other default of the platform taken."""
    FMMSession, PartitionSpec = _import_program(Path(root))

    def make(x, q, deploy, traffic):
        return FMMSession.from_points(
            x, q, PartitionSpec(**deploy["partition"]), resilience=False,
            **(session_kw or {}))
    return make


# -------------------------------------------------- a checkout's first run --
def compile_cache_dir(cache: Path) -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(cache / "jax")


def primed_marker(name: str, cache: Path = CACHE) -> Path:
    return cache / "primed" / f"{name}.json"


def is_primed(name: str, cache: Path = CACHE) -> bool:
    """Whether this checkout's caches already hold cell `name`'s programs
    and autotune picks (a `prime` run wrote its marker for the compile
    cache in use)."""
    try:
        mark = _json(primed_marker(name, cache))
    except (OSError, ValueError):
        return False
    return mark.get("compile_cache") == compile_cache_dir(cache)


def prime(name: str, seed: int, *, t0: float | None = None,
          root: Path = ROOT, cache: Path = CACHE, log=None) -> dict:
    """The set-up of cell `name` alone, in a process of its own: it fills
    the compile and autotune caches (the compile, the P2P sweep) and writes
    the marker that `is_primed` reads, with its own times.  A checkout's
    first run calls it in a child before touching the chip, so that the
    run itself loads every program from the caches, as every later run
    does, and its device memory holds nothing of the sweep."""
    t0 = time.perf_counter() if t0 is None else t0
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    _, cell, config, mix = load_cell(Path(root), name)
    check_device(cell["chips"])
    configure_caches(cache)
    make = program_session(Path(root))
    setup = _set_up(make, config["deployment"], mix, seed, log)
    mark = {"compile_cache": compile_cache_dir(cache), "seed": seed,
            "setup_s": time.perf_counter() - t0, "plan_s": setup.plan_s,
            "warmup_s": setup.warmup_s}
    primed_marker(name, cache).parent.mkdir(parents=True, exist_ok=True)
    primed_marker(name, cache).write_text(json.dumps(mark))
    log(f"primed {name}: {mark}")
    return mark


# ------------------------------------------------------------- the run ---
@dataclass
class SetUp:
    x: np.ndarray
    q: np.ndarray
    traffic: workload.Traffic
    session: object
    plan_s: float
    warmup_s: float


def _set_up(make, deploy: dict, mix: dict, seed: int, log) -> SetUp:
    """Bodies from the seed, the plan, a cold `evaluate()` and one warm-up
    call of the mix (call 0)."""
    x, q = workload.bodies(deploy, seed)
    traffic = workload.Traffic(mix, deploy, seed, x)
    ta = time.perf_counter()
    sess = make(x, q, deploy, traffic)
    plan_s = time.perf_counter() - ta
    ta = time.perf_counter()
    sess.evaluate()
    cold_s = time.perf_counter() - ta
    traffic.apply(sess, 0)
    sess.evaluate()
    warmup_s = time.perf_counter() - ta
    log(f"plan_s: {plan_s}  cold_evaluate_s: {cold_s}  warmup_s: {warmup_s}")
    return SetUp(x, q, traffic, sess, plan_s, warmup_s)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t0: float | None = None, root: Path = ROOT,
             require_tpu: bool = True, session_kw: dict | None = None,
             program=None, trace_dir: str | None = None,
             cache: Path | None = CACHE, cold: dict | None = None,
             log=None) -> dict:
    """One run of cell `name`; returns the result line's object.

    `program(x, q, deploy, traffic)` makes the session the window drives
    (`program_session` by default; `bench/control.py` puts the control in
    its place).  `cold` is the marker of a `prime` this run started; it is
    reported apart under `cold_setup`.  `require_tpu=False`, `session_kw`
    and `cache=None` are for tests on the CPU: they skip the device check,
    set session knobs that the TPU takes by default, and leave the
    process's caches as they are."""
    t0 = time.perf_counter() if t0 is None else t0
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec, cell, config, mix = load_cell(Path(root), name)
    for var in ("REPRO_RESILIENCE", "REPRO_FAULTS"):
        if os.environ.get(var):
            raise RuntimeError(f"{var} is set; the benchmark runs the plain "
                               "path only")
    import jax
    devs = (check_device(cell["chips"]) if require_tpu
            else jax.devices()[:cell["chips"]])
    if cache is not None:
        configure_caches(cache)
    make = program or program_session(Path(root), session_kw)

    setup = _set_up(make, config["deployment"], mix, seed, log)
    traffic, sess = setup.traffic, setup.session
    run = Run(cell=cell, config=config, mix=mix, plan_s=setup.plan_s,
              warmup_s=setup.warmup_s)

    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    log_dir = (trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
               if trace else None)
    note = (jax.profiler.TraceAnnotation if trace
            else lambda _: contextlib.nullcontext())
    samples = {}
    if trace:
        jax.profiler.start_trace(log_dir)
    setup_s = time.perf_counter() - t0
    compiles.active = True
    tw = time.perf_counter()
    with note(trace_reduce.WINDOW):
        k = 0
        while True:
            k += 1
            ta = time.perf_counter()
            with note("bench.update"):
                traffic.apply(sess, k)
            tb = time.perf_counter()
            with note("bench.evaluate"):
                phi = sess.evaluate()
            tc = time.perf_counter()
            run.update_s.append(tb - ta)
            run.evaluate_s.append(tc - tb)
            samples[k] = np.array(phi[traffic.targets])
            if tc - tw >= seconds:
                break
    run.window_s = time.perf_counter() - tw
    compiles.active = False
    if trace:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(compiles)
    run.window_compiles = compiles.count
    peak = _peak_bytes(devs)

    per_layer = cell_metrics(spec, name, "per_layer") if trace else []
    readers = {m["name"]: load_reader(Path(root), m["name"])
               for m in per_layer}
    for m, mod in readers.items():
        if hasattr(mod, "prepare"):
            run.prepared[m] = mod.prepare(sess)
    del sess, setup, phi
    gc.collect()

    if trace:
        try:
            run.trace = trace_reduce.reduce(trace_reduce.load_events(
                trace_reduce.find_xplane(log_dir)))
        finally:
            if not trace_dir:
                shutil.rmtree(log_dir, ignore_errors=True)

    # the check of every call, after the window, with the program's state
    # freed
    calls = list(samples)
    Q = np.stack([traffic.charges(k) for k in calls], axis=1)
    ref, scale = reference.sums(traffic.x, Q, traffic.targets)
    limit = float(config["limits"]["scaled_rms_error"])
    run.errors = {k: reference.scaled_rms(samples[k], ref[:, i],
                                          scale[:, i])
                  for i, k in enumerate(calls)}
    run.rel_l2 = {k: reference.rel_l2(samples[k], ref[:, i])
                  for i, k in enumerate(calls)}
    failed = sum(1 for e in run.errors.values() if not e <= limit)

    metrics = {}
    if trace:
        for m in per_layer:
            v = readers[m["name"]].read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"call_ms": run.window_s / run.calls * 1e3,
               "peak_hbm_gb": None if peak is None else peak / 1e9,
               "setup_s": setup_s}
        for m in cell_metrics(spec, name, "end_to_end"):
            v = e2e[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": failed == 0, "attempted": run.calls,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = trace_reduce.breakdown(run.trace)
    if cold is not None:
        result["cold_setup"] = cold
    result["checks"] = {f"scaled_rms_error.call{k}": {"value": e,
                                                       "limit": limit}
                        for k, e in run.errors.items()}
    log(f"setup_s: {setup_s}  calls: {run.calls}  window_s: "
        f"{run.window_s}  update_s: {run.update_s}  evaluate_s: "
        f"{run.evaluate_s}  window_compiles: {run.window_compiles}  "
        f"peak_bytes: {peak}  host_rss: {_host_rss()}  memory_stats: "
        f"{devs[0].memory_stats()}")
    return result

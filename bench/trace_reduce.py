"""Reduce a profiler trace of one benchmark window to device numbers.

    python bench/trace_reduce.py TRACE.xplane.pb                  # summary
    python bench/trace_reduce.py TRACE.xplane.pb --trim OUT.json  # fixture

`load_events` keeps what the reduction reads from an `.xplane.pb`: every
event of the `XLA Ops` line of each TPU device plane, named by its HLO
instruction (`_p2p_stream.1` for `%_p2p_stream.1 = f32[...] custom-call(...)`),
and the benchmark's own host annotations (`bench.*`,
`jax.profiler.TraceAnnotation`), on the profiler's one clock.  `reduce` clips the device ops to the `bench.window`
annotation and returns, in seconds:

- `window_s`: the window's length;
- `busy_s`: the union of the device's op intervals, averaged over devices;
- `ops`: each op name's summed duration, averaged over devices;
- `gaps`: every idle stretch of the first device inside the window, named
  by the innermost host annotation running at its midpoint (what the host
  was doing while the device waited), longest first.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_events(path: str) -> list[dict]:
    """Device op events and `bench.*` host annotations of one trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:TPU:")
        host = plane.name == "/host:CPU"
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if host and not ev.name.startswith("bench."):
                    continue
                out.append({"plane": plane.name,
                            "name": op_name(ev.name) if device else ev.name,
                            "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce(events: list[dict]) -> dict:
    host = [e for e in events if e["plane"] == "/host:CPU"]
    windows = [e for e in host if e["name"] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, "
                         f"found {len(windows)}")
    w0 = windows[0]["start_ns"]
    w1 = w0 + windows[0]["dur_ns"]
    per_dev: dict[str, list[tuple[float, float, str]]] = {}
    for e in events:
        if e["plane"].startswith("/device:"):
            a = max(e["start_ns"], w0)
            b = min(e["start_ns"] + e["dur_ns"], w1)
            if b > a:
                per_dev.setdefault(e["plane"], []).append((a, b, e["name"]))
    if not per_dev:
        raise ValueError("no device op ran inside the window")
    n_dev = len(per_dev)
    busy = 0.0
    ops: dict[str, float] = {}
    for evs in per_dev.values():
        busy += sum(b - a for a, b in _union([(a, b) for a, b, _ in evs]))
        for a, b, name in evs:
            ops[name] = ops.get(name, 0.0) + (b - a)
    first = _union([(a, b) for a, b, _ in per_dev[min(per_dev)]])
    edges = [w0] + [t for iv in first for t in iv] + [w1]
    notes = [e for e in host if e["name"] != WINDOW]
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        over = [e for e in notes
                if e["start_ns"] <= mid <= e["start_ns"] + e["dur_ns"]]
        name = (min(over, key=lambda e: e["dur_ns"])["name"] if over
                else WINDOW)
        gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": busy / n_dev * 1e-9,
            "n_devices": n_dev,
            "ops": {k: v / n_dev * 1e-9 for k, v in ops.items()},
            "gaps": gaps}


def op_seconds(summary: dict, match: str) -> float:
    """Summed device seconds of the ops whose name contains `match`."""
    return sum(s for name, s in summary["ops"].items() if match in name)


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary["gaps"][:top]]}


def trim(events: list[dict], max_ops: int, keep: str = "") -> list[dict]:
    """A small fixture: the first `max_ops` device ops of the window and
    its first op whose name contains `keep`, the host annotations that
    overlap them, and the window cut to end with the last kept op."""
    win = next(e for e in events if e["name"] == WINDOW
               and e["plane"] == "/host:CPU")
    w0 = win["start_ns"]
    dev = sorted((e for e in events if e["plane"].startswith("/device:")
                  and e["start_ns"] >= w0), key=lambda e: e["start_ns"])
    kept = [e for e in dev if keep and keep in e["name"]][:1]
    dev = dev[:max_ops] + [e for e in kept if e not in dev[:max_ops]]
    w1 = max(e["start_ns"] + e["dur_ns"] for e in dev)
    host = [dict(e) for e in events if e["plane"] == "/host:CPU"
            and e["name"] != WINDOW and e["start_ns"] < w1
            and e["start_ns"] + e["dur_ns"] > w0]
    cut = dict(win, dur_ns=w1 - w0)
    return [cut] + host + dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="an .xplane.pb file or a profiler log dir")
    ap.add_argument("--trim", help="write a fixture of the window's first "
                                   "ops to this JSON file")
    ap.add_argument("--max-ops", type=int, default=300)
    ap.add_argument("--keep", default="", help="also keep the first op "
                                               "whose name contains this")
    args = ap.parse_args(argv)
    path = (find_xplane(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    events = load_events(path)
    if args.trim:
        with open(args.trim, "w") as f:
            json.dump(trim(events, args.max_ops, args.keep), f, indent=0)
    s = reduce(events)
    print(json.dumps({"window_s": s["window_s"], "busy_s": s["busy_s"],
                      "n_devices": s["n_devices"], **breakdown(s, 25)},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

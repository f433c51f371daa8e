#!/usr/bin/env python3
"""The readings that a cell's limit of `correct` is set from, on the chip
at the cell's own size:

- the lower: the program's scaled RMS errors over many seeds, read in one
  process (the configuration's positions are the same for every seed, so
  one plan and its compiled programs serve them all; each seed's calls go
  through the window's own `step` and `evaluate`);
- the upper: the control, the float64 reference computed one precision
  lower (`reference.control_potential`: bfloat16 for the program's
  float32), put in the program's place in a run of the cell
  (`harness.run_cell`), which has to come out not correct.

    python3 bench/control.py --workload sphere-1m.matvec \
        --seeds 11 12 13 --control-seeds 21 22 23

Prints one line per reading and, last, a JSON object of them all.  The
benchmark's own runs never run this; bench/tests/test_bench_control.py
keeps it as a test at a small size.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import reference  # noqa: E402
import workload  # noqa: E402


class ControlSession:
    """The control in the program's place: `step` and `evaluate` as
    `FMMSession` has them, the potential computed by
    `reference.control_potential` at the checked targets (the only bodies
    a run compares; the others read NaN)."""

    def __init__(self, x, q, targets):
        self.x, self.q, self.targets = x, q, targets

    def step(self, new_x, new_q=None):
        self.x = new_x
        if new_q is not None:
            self.q = new_q

    def evaluate(self) -> np.ndarray:
        phi = np.full(len(self.x), np.nan)
        phi[self.targets] = reference.control_potential(self.x, self.q,
                                                        self.targets)
        return phi


def control_program(x, q, deploy, traffic):
    return ControlSession(x, q, traffic.targets)


def control_run(cell: str, seed: int, seconds: float, **kw) -> dict:
    """A run of `cell` with the control in the program's place: its result
    line (`correct` has to be false)."""
    return harness.run_cell(cell, seed, seconds, False,
                            program=control_program, **kw)


def program_readings(cell: str, seeds: list[int], calls: int = 2, *,
                     root: Path = harness.ROOT, cache=harness.CACHE,
                     session_kw: dict | None = None,
                     require_tpu: bool = True, log=print) -> dict:
    """{seed: [(scaled RMS error, relative L2 error) of each call]}: the
    program's answers for `calls` calls of each seed's traffic, driven as
    the window drives them through one session, against the float64
    reference."""
    _, spec_cell, config, mix = harness.load_cell(Path(root), cell)
    if require_tpu:
        harness.check_device(spec_cell["chips"])
    if cache is not None:
        harness.configure_caches(cache)
    deploy = config["deployment"]
    make = harness.program_session(Path(root), session_kw)
    x, q = workload.bodies(deploy, seeds[0])
    traffic = workload.Traffic(mix, deploy, seeds[0], x)
    sess = make(x, q, deploy, traffic)
    samples = {}
    for s in seeds:
        traffic = workload.Traffic(mix, deploy, s, x)
        for k in range(1, calls + 1):
            traffic.apply(sess, k)
            samples[s, k] = np.array(sess.evaluate()[traffic.targets])
    del sess
    gc.collect()
    out = {}
    for s in seeds:
        traffic = workload.Traffic(mix, deploy, s, x)
        ks = range(1, calls + 1)
        Q = np.stack([traffic.charges(k) for k in ks], axis=1)
        ref, scale = reference.sums(x, Q, traffic.targets)
        out[s] = [(reference.scaled_rms(samples[s, k], ref[:, i],
                                        scale[:, i]),
                   reference.rel_l2(samples[s, k], ref[:, i]))
                  for i, k in enumerate(ks)]
        log(f"program {cell} seed {s}: (scaled_rms_error, rel_l2_error) "
            f"{out[s]!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="the control's window")
    args = ap.parse_args(argv)
    try:
        devs = harness.check_device(1)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    out = {"workload": args.workload, "device": devs[0].device_kind}
    if args.seeds:
        out["program"] = program_readings(args.workload, args.seeds,
                                          args.calls)
    out["control"] = {}
    for s in args.control_seeds:
        r = control_run(args.workload, s, args.seconds)
        errs = [c["value"] for c in r["checks"].values()]
        out["control"][s] = {"correct": r["correct"], "calls": r["attempted"],
                             "scaled_rms_error": errs}
        print(f"control {args.workload} seed {s}: correct {r['correct']} "
              f"scaled_rms_error {errs!r}", flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

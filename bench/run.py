#!/usr/bin/env python3
"""Run one cell of the benchmark (`BENCHMARK.json`) on the chips of this
machine and print its result.

    python3 bench/run.py --workload sphere-1m.matvec --seed 7 --seconds 20 \
        --trace 0

`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics, read from a profiler trace of the window.  The last
line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` (and with `--trace 1` `breakdown`), then
`checks`, each compared number beside its limit; the same checks are the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits with code 3.

The first run of a cell in a checkout first runs the cell's set-up in a
child process (`--prime`), which fills the compile and autotune caches;
its times are reported apart, under `cold_setup`, and count in this run's
`setup_s`.
"""
import time

T0 = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", help="keep the window's profiler trace "
                                        "in this directory")
    ap.add_argument("--prime", action="store_true",
                    help="run the cell's set-up only, to fill the caches")
    args = ap.parse_args(argv)
    try:
        if args.prime:
            harness.prime(args.workload, args.seed, t0=T0)
            return 0
        cold = None
        if not harness.is_primed(args.workload):
            cold = _prime_in_child(args)
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t0=T0,
                                  trace_dir=args.trace_dir, cold=cold)
    except harness.NoDevice as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _prime_in_child(args) -> dict:
    """`--prime` in a child process, before this one touches the chip
    (one process holds a chip at a time); its marker's times."""
    rc = subprocess.run(
        [sys.executable, __file__, "--workload", args.workload, "--seed",
         str(args.seed), "--seconds", "0", "--prime"],
        stdout=sys.stderr).returncode
    if rc == 3:
        raise harness.NoDevice("the priming run found no TPU")
    if rc != 0:
        raise RuntimeError(f"the priming run exited with code {rc}")
    return json.loads(harness.primed_marker(args.workload).read_text())


if __name__ == "__main__":
    sys.exit(main())

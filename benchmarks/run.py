# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# Usage:  python benchmarks/run.py [filter ...] [--json=PATH] [--no-json]
# With no arguments every module runs; otherwise only modules whose label
# contains one of the (case-insensitive) filter substrings run — e.g.
# ``python benchmarks/run.py kernel`` runs just the kernel/engine sweep.
#
# Every run also persists the collected rows as machine-readable
# benchmarks/BENCH_run.json (git-ignored; see host_side.write_bench_json),
# so the perf trajectory — cold-compile, warm-evaluate, warm-step,
# fused-vs-per-phase — is tracked across PRs instead of scrolling away.
import os
import sys
import traceback

from benchmarks import (fig6_granularity, fig7_protocols, fig8_exchange,
                        fig8_weak, host_side, kernel_bench,
                        partition_quality, roofline_table, table3_hsdx)

MODULES = [
    ("host_side (plan vs loop geometry)", host_side),
    ("partition_quality (Fig 3 / §2.2)", partition_quality),
    ("fig6_granularity (Fig 6)", fig6_granularity),
    ("table3_hsdx (Table 3)", table3_hsdx),
    ("fig7_protocols (Fig 7)", fig7_protocols),
    ("fig8_weak (Fig 8)", fig8_weak),
    ("fig8_exchange (dist LET exchange, measured vs LogGP)", fig8_exchange),
    ("kernel_bench (bucketed P2P/attn/WKV + engine sweep)", kernel_bench),
    ("roofline_table (§Roofline)", roofline_table),
]


def main(argv=None) -> None:
    args = sys.argv[1:] if argv is None else argv
    json_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_run.json")
    filters = []
    for a in args:
        if a.startswith("--json="):
            json_path = a.split("=", 1)[1]
        elif a == "--no-json":
            json_path = None
        else:
            filters.append(a.lower())
    selected = [(label, mod) for label, mod in MODULES
                if not filters or any(f in label.lower() for f in filters)]
    if not selected:
        print(f"no benchmark matches {filters}; "
              f"labels: {[l for l, _ in MODULES]}", file=sys.stderr)
        sys.exit(2)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    print("name,us_per_call,derived")
    failures = 0
    collected = []
    for label, mod in selected:
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
                collected.append((name, us, derived))
        except Exception:
            failures += 1
            print(f"{label},-1,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if json_path:
        where = host_side.write_bench_json(
            collected, json_path,
            meta={"modules": [label for label, _ in selected],
                  "failures": failures})
        print(f"# wrote {where}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()

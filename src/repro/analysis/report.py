"""Generate EXPERIMENTS.md sections (§Dry-run, §Roofline) from artifacts.

    PYTHONPATH=src python -m repro.analysis.report --out EXPERIMENTS.md
"""
from __future__ import annotations

import argparse
import json
import os

from repro.analysis.roofline import (PEAK_FLOPS, roofline_from_artifact)


def load(art_dir):
    recs = []
    for f in sorted(os.listdir(art_dir)):
        if f.endswith(".json"):
            with open(os.path.join(art_dir, f)) as fh:
                d = json.load(fh)
            d["_file"] = f
            recs.append(d)
    return recs


def fmt_bytes(b):
    return f"{b/1e9:.2f}"


def dryrun_table(recs, pod):
    lines = [
        "| arch | shape | status | compile s | args GB/dev | temp GB/dev | "
        "coll GB/dev | n_micro |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if f"__{pod}.json" != r["_file"].split("__", 2)[-1][len(r['shape']) + 2:] \
                and not r["_file"].endswith(f"__{pod}.json"):
            continue
        if "skipped" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | SKIP ({r['skipped'][:40]}…) "
                         "| – | – | – | – | – |")
            continue
        if "error" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | ERROR | – | – | – | – | – |")
            continue
        m = r["memory"]
        w = r.get("walked", {})
        coll = w.get("total_collective_bytes", r["collectives"]["total_bytes"])
        lines.append(
            f"| {r['arch']} | {r['shape']} | ok | {r['compile_s']} | "
            f"{fmt_bytes(m.get('argument_size_in_bytes', 0))} | "
            f"{fmt_bytes(m.get('temp_size_in_bytes', 0))} | "
            f"{fmt_bytes(coll)} | {r.get('n_micro', '–')} |")
    return "\n".join(lines)


def roofline_table(recs):
    lines = [
        "| arch | shape | compute ms | memory ms | collective ms | dominant | "
        "roofline frac | useful ratio | what would move the dominant term |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    advice = {
        ("moe", "collective"): "hierarchical/two-stage a2a; larger grain",
        ("moe", "memory"): "sequence-parallel activations; lower capacity factor",
        ("moe", "compute"): "kernel fusion (Pallas attention) on device",
        ("dense", "memory"): "fused attention kernel keeps tiles in VMEM; "
                             "sequence-parallel residuals",
        ("dense", "collective"): "chunked ring all-gather overlapped with matmul",
        ("dense", "compute"): "already compute-bound — tune MXU tiling",
    }
    rows = []
    for r in recs:
        if not r["_file"].endswith("__1pod.json"):
            continue
        if "skipped" in r or "error" in r:
            continue
        w = r.get("walked", {})
        rr = roofline_from_artifact(r, w if "dot_flops" in w else None)
        rows.append((r, rr))
    rows.sort(key=lambda t: (t[0]["arch"], t[0]["shape"]))
    from repro.configs import get_config
    for r, rr in rows:
        fam = get_config(r["arch"]).family
        fam_key = "moe" if fam == "moe" else "dense"
        hint = advice.get((fam_key, rr["dominant"]), "overlap/shard the dominant mover")
        lines.append(
            f"| {rr['arch']} | {rr['shape']} | {rr['compute_s']*1e3:.2f} | "
            f"{rr['memory_s']*1e3:.2f} | {rr['collective_s']*1e3:.2f} | "
            f"{rr['dominant']} | {rr['roofline_fraction']:.3f} | "
            f"{min(rr['useful_ratio'], 99.0):.2f} | {hint} |")
    return "\n".join(lines)


def observability_section(rep: dict) -> str:
    """§Observability markdown from a `FMMSession.report()` dict (or a JSON
    file of one, e.g. the artifact `analysis/check_counters.py` writes)."""
    lines = ["## §Observability — session flight recorder\n"]
    o = rep.get("obs", {})
    lines.append(f"tracing: {'on' if o.get('enabled') else 'off'}"
                 f" · events: {o.get('events', 0)}"
                 f" · dropped: {o.get('dropped', 0)}\n")
    timings = rep.get("timings", {})
    if timings:
        lines.append("| span | count | total ms | mean ms | max ms |")
        lines.append("|---|---|---|---|---|")
        for name in sorted(timings, key=lambda k: -timings[k]["total_s"]):
            t = timings[name]
            lines.append(f"| {name} | {t['count']} | {t['total_s']*1e3:.3f} "
                         f"| {t['mean_s']*1e3:.3f} | {t['max_s']*1e3:.3f} |")
        lines.append("")
    ex = rep.get("exchange", {})
    if ex.get("enabled") and ex.get("protocols"):
        lines.append("| protocol | rounds | moved bytes | loggp ms "
                     "| measured ms | model drift |")
        lines.append("|---|---|---|---|---|---|")
        for name, st in ex["protocols"].items():
            meas = st.get("measured_s")
            drift = st.get("model_drift")
            loggp = st.get("loggp_s", st.get("loggp_time", 0.0))
            meas_c = f"{meas*1e3:.3f}" if meas is not None else "–"
            drift_c = f"{drift:.2f}" if drift is not None else "–"
            lines.append(f"| {name} | {st.get('n_rounds', '–')} "
                         f"| {st.get('moved_bytes', '–')} | {loggp*1e3:.3f} "
                         f"| {meas_c} | {drift_c} |")
        lines.append("")
    counters = rep.get("metrics", {}).get("counters", {})
    if counters:
        lines.append("counters: "
                     + " · ".join(f"{k}={int(v)}"
                                  for k, v in sorted(counters.items())))
        lines.append("")
    ec = rep.get("exe_cache", {})
    if ec:
        lines.append(f"exe_cache: hits={ec.get('hits')} "
                     f"misses={ec.get('misses')} "
                     f"evictions={ec.get('evictions')} "
                     f"size={ec.get('size')}")
    la = rep.get("launches", {})
    if la and la.get("enabled", True):
        for kind, d in la.items():
            if not isinstance(d, dict):
                continue
            lines.append(f"launches[{kind}]: calls={d['calls']} "
                         f"entry_computations={d['entry_computations']}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default="artifacts")
    ap.add_argument("--section", default="all")
    ap.add_argument("--obs", default=None,
                    help="path to a FMMSession.report() JSON; renders the "
                         "§Observability section from it")
    args = ap.parse_args()
    if args.obs:
        with open(args.obs) as fh:
            print(observability_section(json.load(fh)))
        if args.section == "obs":
            return
    recs = load(args.artifacts)
    print("## §Dry-run — single pod (16x16 = 256 chips)\n")
    print(dryrun_table(recs, "1pod"))
    print("\n## §Dry-run — multi-pod (2x16x16 = 512 chips)\n")
    print(dryrun_table(recs, "2pod"))
    print("\n## §Roofline — single pod, per (arch x shape)\n")
    print(roofline_table(recs))


if __name__ == "__main__":
    main()

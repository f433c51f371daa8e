"""Trip-count-aware HLO walker.

XLA's cost_analysis counts every computation ONCE — a scanned 32-layer stack
reports 1/32 of the real FLOPs, and FSDP all-gathers inside the loop body are
similarly undercounted.  This walker parses the post-partitioning HLO text,
recovers while-loop trip counts from their condition computations, propagates
multipliers down the call graph (while bodies, fusions, calls), and sums

  - collective result bytes  (all-gather/all-reduce/reduce-scatter/
    all-to-all/collective-permute), and
  - dot FLOPs  (2 * prod(result_dims) * contracted_size),

each weighted by how many times its computation actually executes.
Shapes in the partitioned module are per-device, so totals are per-device.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_list(s: str):
    out = []
    for m in _SHAPE_RE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d)
        out.append((dt, shape))
    return out


def _nbytes(shapes):
    tot = 0
    for dt, shape in shapes:
        n = 1
        for d in shape:
            n *= d
        tot += n * _DTYPE_BYTES[dt]
    return tot


def parse_computations(txt: str) -> dict:
    """name -> list of instruction lines."""
    comps = {}
    cur = None
    for line in txt.splitlines():
        # headers may contain nested parens (tuple-typed params) — match
        # greedily on the one-line "name (args) -> result {" form
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{", line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None and "=" in line:
            comps[cur].append(line.strip())
    return comps


def _entry_name(txt: str) -> str | None:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", txt, re.M)
    return m.group(1) if m else None


def count_entry_launches(txt: str) -> int:
    """Number of ENTRY computations in (possibly concatenated) compiled HLO
    text — the dispatch count a warm caller pays: every compiled executable
    has exactly one ENTRY, so a pipeline's launch count is the ENTRY count
    over its executables' HLO.  Counts only ENTRY headers that parse as real
    computations (`parse_computations`), so stray 'ENTRY' tokens in operand
    metadata never inflate the result.  The fused-engine tests pin warm
    evaluate()/step() at exactly 1.

    NOTE: feed `compiled.as_text()` (post-compilation HLO).  `lowered
    .as_text()` is StableHLO, which has no ENTRY headers and counts as 0."""
    comps = parse_computations(txt)
    entries = re.findall(r"^ENTRY\s+%?([\w.\-]+)", txt, re.M)
    return sum(1 for e in entries if e in comps)


_INSTR_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def op_phases(txt: str, prefix: str = "fmm.") -> dict:
    """{instruction name: phase} from the `op_name` metadata of compiled
    HLO text (`Compiled.as_text()`): the phase is the innermost component
    of the op_name path that starts with `prefix`, so nested
    `jax.named_scope`s resolve to the deepest one.  Instructions without
    such a component are left out.

    These are the names a device trace's `XLA Ops` line carries: a fusion
    takes its root's op_name (ops of two phases fused into one count for
    the root's phase), a `while` its loop's, and the ops of a loop body
    appear too, nested inside their loop's op (`top_level_ops` tells them
    apart)."""
    out = {}
    for line in txt.splitlines():
        m = _INSTR_RE.match(line.strip())
        if not m:
            continue
        meta = _OP_NAME_RE.search(line)
        if not meta:
            continue
        hits = [c for c in meta.group(1).split("/") if c.startswith(prefix)]
        if hits:
            out[m.group(1)] = hits[-1]
    return out


def top_level_ops(txt: str) -> set:
    """Instruction names of the ENTRY computation: the ops a device trace
    times one after another.  Loop bodies, branches, calls, fusion
    internals and reducers are left out, since their time lies inside an
    op of this set."""
    lines = parse_computations(txt).get(_entry_name(txt), [])
    return {m.group(1) for m in map(_INSTR_RE.match, lines) if m}


def _trip_count(cond_lines) -> int:
    """Largest integer constant in the while condition ~= trip bound."""
    best = 1
    for ln in cond_lines:
        for c in re.findall(r"constant\((\d+)\)", ln):
            best = max(best, int(c))
    return best


def _called(line: str):
    """Computations invoked by this instruction: (name, multiplier_kind)."""
    out = []
    m = re.search(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)", line)
    if m:
        out.append((m.group(2), "while_body"))
        out.append((m.group(1), "while_cond"))
        return out
    is_fusion = " fusion(" in line
    for key in ("calls=", "to_apply=", "true_computation=", "false_computation=",
                "branch_computations={"):
        idx = line.find(key)
        if idx >= 0:
            seg = line[idx + len(key):]
            names = re.findall(r"%?([\w.\-]+)", seg.split("}")[0] if "{" in key
                               else seg.split(",")[0].split(")")[0])
            out.extend((n, "fusion" if is_fusion else "call") for n in names[:4] if n)
    return out


def compute_multipliers(txt: str):
    """Returns (multiplier map, fusion-internal set)."""
    comps = parse_computations(txt)
    entry = _entry_name(txt)
    mult = defaultdict(float)
    fusion_internal = set()
    if entry is None:
        return {name: 1.0 for name in comps}, fusion_internal
    stack = [(entry, 1.0, False)]
    seen_pairs = set()
    while stack:
        name, m, in_fusion = stack.pop()
        if name not in comps:
            continue
        mult[name] += m
        if in_fusion:
            fusion_internal.add(name)
        for ln in comps[name]:
            for callee, kind in _called(ln):
                if callee not in comps:
                    continue
                if kind == "while_body":
                    cond = re.search(r"condition=%?([\w.\-]+)", ln).group(1)
                    trips = _trip_count(comps.get(cond, []))
                    child_m = m * trips
                else:
                    child_m = m
                key = (name, callee, kind, id(ln))
                if key in seen_pairs:
                    continue
                seen_pairs.add(key)
                stack.append((callee, child_m,
                              in_fusion or kind == "fusion"))
    return dict(mult), fusion_internal


def _crosses_pod(line: str, pod_size: int) -> bool:
    """Does this collective's replica_groups span pod boundaries?"""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                  line)
    if m:
        g, k = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        n = 1
        for d in dims:
            n *= d
        try:
            import numpy as _np
            ids = _np.arange(n).reshape(dims)
            if m.group(4):
                perm = [int(p) for p in m.group(4).split(",")]
                ids = ids.transpose(perm)
            ids = ids.reshape(g, k)
            return bool((ids // pod_size != ids[:, :1] // pod_size).any())
        except Exception:
            return True
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        ids = [int(v) for v in m.group(1).split(",")]
        return len({i // pod_size for i in ids}) > 1
    return False


def weighted_analysis(txt: str, pod_size: int = 256) -> dict:
    """Per-device collective bytes, dot FLOPs and result bytes (HBM-write
    proxy), all trip-count weighted.  Collective bytes are also split into
    intra-pod vs inter-pod (replica groups crossing `pod_size` boundaries)."""
    comps = parse_computations(txt)
    mult, fusion_internal = compute_multipliers(txt)
    coll_bytes = defaultdict(float)
    coll_counts = defaultdict(float)
    inter_pod_bytes = 0.0
    intra_pod_bytes = 0.0
    dot_flops = 0.0
    result_bytes = 0.0

    for name, lines in comps.items():
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        count_bytes = name not in fusion_internal
        # map of instruction name -> result shapes (for dot operand lookup)
        shapes = {}
        for ln in lines:
            mm = re.match(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", ln)
            if not mm:
                continue
            iname, rhs = mm.group(1), mm.group(2)
            op_end = rhs.find("(")
            header = rhs[:op_end] if op_end > 0 else rhs
            shapes[iname] = _shape_list(header)
        for ln in lines:
            mm = re.match(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", ln)
            if not mm:
                continue
            rhs = mm.group(2)
            if "-done(" in rhs:
                continue
            if count_bytes and " parameter(" not in rhs:
                op_end = rhs.find("(")
                header = rhs[:op_end] if op_end > 0 else rhs
                op = header.split()[-1] if op_end > 0 else ""
                # only ops that genuinely write HBM on TPU: tuple plumbing
                # (get-tuple-element etc.) is free, fusions/dots are not
                if op in ("fusion", "dot", "copy", "convert", "reduce",
                          "scatter", "gather", "dynamic-slice",
                          "dynamic-update-slice", "concatenate", "transpose",
                          "convolution", "reduce-window", "iota", "reverse",
                          "pad", "slice"):
                    result_bytes += _nbytes(_shape_list(header)) * m
            for cname in _COLLECTIVES:
                if re.search(rf"\b{cname}(-start)?\(", rhs):
                    header = rhs.split(cname)[0]
                    b = _nbytes(_shape_list(header))
                    coll_bytes[cname] += b * m
                    coll_counts[cname] += m
                    if _crosses_pod(rhs, pod_size):
                        inter_pod_bytes += b * m
                    else:
                        intra_pod_bytes += b * m
                    break
            dm = re.search(r"\bdot\(([^)]*)\)", rhs)
            if dm:
                header = rhs.split(" dot(")[0]
                res_shapes = _shape_list(header)
                if not res_shapes:
                    continue
                res_elems = 1
                for d in res_shapes[0][1]:
                    res_elems *= d
                # contracted size from lhs operand shape + contracting dims.
                # Some XLA versions print typed operands inline
                # (dot(f32[128,512] %a, ...)); others print bare names — try
                # the inline shapes first, then the name -> shape map.
                cdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rhs)
                arg_shapes = _shape_list(dm.group(1))
                lshape = arg_shapes[0][1] if arg_shapes else None
                if lshape is None:
                    ops = [o.strip().lstrip("%")
                           for o in dm.group(1).split(",")[:2]]
                    if ops and ops[0] in shapes and shapes[ops[0]]:
                        lshape = shapes[ops[0]][0][1]
                csize = 1
                if cdims and lshape is not None:
                    for d in cdims.group(1).split(","):
                        if d and int(d) < len(lshape):
                            csize *= lshape[int(d)]
                dot_flops += 2.0 * res_elems * csize * m
    return {
        "collective_bytes": dict(coll_bytes),
        "collective_counts": dict(coll_counts),
        "total_collective_bytes": sum(coll_bytes.values()),
        "inter_pod_bytes": inter_pod_bytes,
        "intra_pod_bytes": intra_pod_bytes,
        "dot_flops": dot_flops,
        "result_bytes": result_bytes,
    }

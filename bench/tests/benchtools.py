"""Helpers for the benchmark's CPU tests: a scratch checkout whose cells
are the real ones cut to a few thousand bodies, run on the CPU's default
(reference) path with the device check skipped."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SMALL = 2000
# the chip's session path (engine, fused launch, stream tables) with XLA in
# place of the Pallas kernels, which the CPU could only interpret slowly
CPU_KNOBS = dict(engine=True, fused=True, use_kernels=False, p2p_stream=True)


def small_root(tmp: Path, n: int = SMALL) -> Path:
    """A copy of BENCHMARK.json and the data it names, every
    configuration cut to `n` bodies; `src` links to the program."""
    (tmp / "bench").mkdir(parents=True)
    spec = harness.load_spec(ROOT)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    for sub in ("traffic", "metrics"):
        shutil.copytree(BENCH / sub, tmp / "bench" / sub)
    (tmp / "bench" / "configs").mkdir()
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["deployment"]["bodies"] = n
        (tmp / c["file"]).write_text(json.dumps(cfg))
    (tmp / "src").symlink_to(ROOT / "src")
    return tmp


def cells() -> list[str]:
    """The cells of BENCHMARK.json."""
    return [w["name"] for w in harness.load_spec(ROOT)["workloads"]]


def run(root: Path, cell: str, seed: int = 5, **kw) -> dict:
    """One run of `cell` on the CPU with a window of one call."""
    kw.setdefault("session_kw", CPU_KNOBS)
    return harness.run_cell(cell, seed, 0.0, False, root=root,
                            require_tpu=False, cache=None,
                            log=lambda msg: None, **kw)

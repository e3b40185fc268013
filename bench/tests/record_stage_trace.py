#!/usr/bin/env python3
"""Record the tiny flat cell's trace and stage map for `test_tick_stages.py`.

    python bench/tests/record_stage_trace.py [--out bench/tests/data]

Run it on one TPU chip from the root of a checkout. It lays out the tiny
cells of `tiny.py` in a temporary directory, runs a few chunks of
`flat-tiny-k4` (K = 4, d = 4096, the fused `commit_batch` kernel) through the
benchmark's own window with the profiler on, and writes the trace as
`tiny_flat_k4_stages.xplane.pb` and the compiled chunk's stage map as
`tiny_flat_k4_stages.json` under --out.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS.parent))
sys.path.insert(0, str(TESTS))

import run  # noqa: E402
import tick_stages  # noqa: E402
import tiny  # noqa: E402

CELL = "flat-tiny-k4"
SEED = 2147483659
#: a window of a few chunks keeps the trace small
SECONDS = 0.01


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(TESTS / "data"))
    args = ap.parse_args(argv)
    out = Path(args.out)
    work = Path(tempfile.mkdtemp(prefix="stage_trace_"))
    root = tiny.make_root(work / "root")
    cell = run.Cell(CELL, root)
    chunk, carry, _ = cell.first_steps(SEED)
    trace_dir = str(work / "trace")
    rec = run.window(cell, chunk, carry, SECONDS, trace_dir)
    del carry, chunk, rec["carry"]
    trace_mod = run.load_module(TESTS.parent / "trace.py", "record_trace")
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_mod.find_xplane(trace_dir), out / "tiny_flat_k4_stages.xplane.pb")
    stage_map = tick_stages.program_stage_map(dict(
        config=cell.config, family=cell.family, traffic=cell.traffic,
        chips=cell.chips))
    (out / "tiny_flat_k4_stages.json").write_text(
        json.dumps(stage_map, indent=0, sort_keys=True) + "\n")
    shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ticks": rec["ticks"], "window_s": rec["window_s"],
                      "ops": len(stage_map)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

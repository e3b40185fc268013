#!/usr/bin/env python3
"""Read what the limits of a cell are set from, in one process.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds a,b,c] [--faults half_batch,altered]

For each seed: the program's first chunks through the timed path, then the
plain reference over the same chunks, and the readings between them (the
lower readings of the limits). For each control seed also the control — the
reference computed in bfloat16 in the program's place — and each planted
fault, read against the float32 reference (the upper readings). One JSON line
per reading; the last line sums them up: the largest program reading and the
smallest control and fault readings of each number.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as bench_run  # noqa: E402

FAULTS = ("half_batch", "altered")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    cell = bench_run.Cell(args.workload)
    import afl_reference
    import jax.numpy as jnp
    faults = [f for f in args.faults.split(",") if f]
    if any(f not in FAULTS for f in faults):
        raise SystemExit(f"faults are {FAULTS}")
    summary = {"program": {}, "control": {}, **{f: {} for f in faults}}

    def emit(kind, seed, readings, secs):
        print(json.dumps({"kind": kind, "seed": seed, "seconds": secs, **readings}),
              flush=True)
        agg = summary[kind]
        for k, v in readings.items():
            agg[k] = max(agg.get(k, v), v) if kind == "program" else min(agg.get(k, v), v)

    for seed in ints(args.seeds):
        t0 = time.perf_counter()
        chunk, carry, prog = cell.first_steps(seed)
        del chunk, carry
        gc.collect()
        t1 = time.perf_counter()
        ref, w0 = cell.reference(seed)
        emit("program", seed, afl_reference.readings(prog, ref, w0),
             [t1 - t0, time.perf_counter() - t1])
        if seed in ints(args.control_seeds):
            for kind, kw in [("control", {"dtype": jnp.bfloat16})] + [
                    (f, {"fault": f}) for f in faults]:
                t0 = time.perf_counter()
                other, _ = cell.reference(seed, **kw)
                emit(kind, seed, afl_reference.readings(other, ref, w0),
                     time.perf_counter() - t0)
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark orchestrator — one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus writes the raw JSON to
results/bench.jsonl). Suites:
  fig2      heterogeneity x delay grid (quadratic amplification + vision)
  fig3      ACED dropout robustness + tau_algo ablation
  table_a1  comms per server iteration + App. E equal-comms accuracy
  table_a2  text-classification (20NG stand-in) under label shift
  table_a3  server-state memory accounting
  figa3     8-bit cache quantization
  kernels   server-aggregation kernel microbenchmarks
  scan      device-resident scan engine vs host event loop (sweep scaling)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _derived(row):
    for k in ("amplification", "acc", "floor", "comms_per_update",
              "bytes_per_param", "derived"):
        if k in row and row[k] is not None:
            v = row[k]
            return f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
    return ""


def _name(row):
    parts = [row.get("bench", ""), row.get("algo", row.get("name", ""))]
    for k in ("alpha", "beta", "zeta", "dropout", "tau_algo"):
        if k in row:
            parts.append(f"{k}{row[k]}")
    return "/".join(str(p) for p in parts if p != "")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suites", default="table_a3,kernels,scan,table_a1,figa3,"
                                        "figa1,fig3,table_a2,fig2")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero if any suite raises (CI smoke mode)")
    ap.add_argument("--out", default="results/bench.jsonl")
    args = ap.parse_args()
    fast = not args.full
    failed = []

    from benchmarks import (fig2_heterogeneity, fig3_dropout, figa1_stability,
                            figa3_quant, kernels_bench, scan_bench,
                            table_a1_comms, table_a2_bert, table_a3_memory)
    from benchmarks.common import clear_runner_cache
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    suites = {
        "scan": scan_bench.main,
        "fig2": fig2_heterogeneity.main,
        "fig3": fig3_dropout.main,
        "table_a1": table_a1_comms.main,
        "table_a2": table_a2_bert.main,
        "table_a3": table_a3_memory.main,
        "figa3": figa3_quant.main,
        "figa1": figa1_stability.main,
        "kernels": kernels_bench.main,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    print("name,us_per_call,derived")
    with open(args.out, "a") as f:
        for s in args.suites.split(","):
            s = s.strip()
            t0 = time.time()
            try:
                rows = suites[s](fast=fast)
            except Exception as e:
                print(f"{s},0,ERROR:{type(e).__name__}:{e}", flush=True)
                failed.append(s)
                continue
            finally:
                # drop compiled runners (and the tasks they pin) per suite
                clear_runner_cache()
            for row in rows:
                row["suite"] = s
                f.write(json.dumps(row) + "\n")
                us = row.get("us_per_iter", row.get("us_per_call", 0.0))
                print(f"{_name(row)},{us:.1f},{_derived(row)}", flush=True)
            print(f"# suite {s} done in {time.time()-t0:.1f}s",
                  file=sys.stderr, flush=True)
    if args.strict and failed:
        sys.exit(f"benchmark suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()

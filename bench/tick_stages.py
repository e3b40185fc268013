"""Device time of the scan tick by stage, for the `tick_*_ms` readers.

The program wraps each stage of its scan tick in a named scope
(`repro.core.scan_staleness.STAGES`), and `ChunkedStalenessRunner.op_stages`
maps every instruction of the compiled chunk to the outermost stage in its
metadata. This module rebuilds the cell's program as the run built it,
takes that map for the window's chunk shapes (a compile of the chunk, once
per code version: `op_stages` keys the compilation cache with the program's
metadata) and sums the trace's device-0 self time per op
(`Reduced.op_time_s`, over the traced window) by stage. An op with no
stage, or one missing from the map, counts under "". A fusion counts under
the stage of its root.

Where the program names no stages (it has no `op_stages`), every reading is
None and the metrics are left out of the result line.
"""
from __future__ import annotations

import sys
import time
from typing import Dict, Optional, Tuple

#: the stages with a metric of their own, in the tick's code order;
#: "afl.guards" and "afl.resync" (built by no cell) count as unscoped
NAMED = ("afl.sample", "afl.stale_read", "afl.client", "afl.commit",
         "afl.select", "afl.update", "afl.ring")

#: where a run's record keeps its stage times once the first reader has
#: computed them
RECORD_KEY = "tick_stage_times"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def program_stage_map(record) -> Optional[Dict[str, str]]:
    """The stage map of the chunk the cell ran: the cell's program built
    again from its configuration and traffic (weights from a fixed key: the
    compiled chunk does not depend on their values) and compiled for the
    window's chunk shapes. None when the program has no stage map."""
    from repro.core.scan_staleness import ChunkedStalenessRunner
    if not hasattr(ChunkedStalenessRunner, "op_stages"):
        return None
    import jax
    import jax.numpy as jnp
    import numpy as np
    config, traffic, family = record["config"], record["traffic"], record["family"]
    mesh = None
    if record["chips"] > 1:
        from repro.core.scan_sharded import staleness_mesh
        mesh = staleness_mesh()
    weights = family.init_params(jax.random.PRNGKey(0), config["model"])
    runner = family.build_program(config, traffic, weights, mesh)
    del weights
    n, C, K = config["clients"]["n_clients"], traffic.chunk_events, traffic.k_batch
    lr0 = np.float32(0.0)
    carry = jax.eval_shape(runner.init, jax.random.PRNGKey(0), lr0)
    never = jax.ShapeDtypeStruct((n,), jnp.int32)
    return runner.op_stages(
        carry, jax.ShapeDtypeStruct((C, n), jnp.float32),
        jax.ShapeDtypeStruct((C,) if K == 1 else (C, K), jnp.float32),
        never, never, lr0)


def group(op_time_s: Dict[str, float], stage_map: Dict[str, str]
          ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """(seconds by stage, seconds of the ops missing from the map) from the
    trace's self time per op, keyed `<instruction> <shape>`."""
    by_stage: Dict[str, float] = {}
    missing: Dict[str, float] = {}
    for op, secs in op_time_s.items():
        instr = op.split(" ", 1)[0]
        if instr not in stage_map:
            missing[op] = secs
        stage = stage_map.get(instr, "")
        by_stage[stage] = by_stage.get(stage, 0.0) + secs
    return by_stage, missing


def stage_times(record) -> Optional[Dict[str, float]]:
    """Device-0 self seconds by stage over the traced window ("" for the
    ops of no stage), or None where the program names no stages. Computed
    once per record, which keeps them for the other readers; the ops
    missing from the map go to standard error."""
    if RECORD_KEY in record:
        return record[RECORD_KEY]
    trace = record["trace"]
    t0 = time.perf_counter()
    stage_map = program_stage_map(record)
    times = None
    if stage_map is not None:
        times, missing = group(trace.op_time_s, stage_map)
        busy = sum(trace.op_time_s.values())
        lost = sum(missing.values())
        log(f"stages: map of {len(stage_map)} instructions in "
            f"{time.perf_counter() - t0:.1f} s; {len(missing)} op(s) missing "
            f"from it, {lost:.6g} s of {busy:.6g} s busy "
            f"({100 * lost / max(busy, 1e-30):.3g} %)")
        for op, secs in sorted(missing.items(), key=lambda kv: -kv[1]):
            log(f"stages: missing {op} {secs:.6g} s")
        log("stages: " + ", ".join(f"{k or 'unscoped'} {v:.6g} s"
                                   for k, v in sorted(times.items())))
    record[RECORD_KEY] = times
    return times


def tick_ms(record, stage: str) -> Optional[float]:
    """`stage`'s device time per tick in ms, over the traced window."""
    times = stage_times(record)
    if times is None:
        return None
    return 1e3 * times.get(stage, 0.0) / record["ticks"]


def unscoped_tick_ms(record) -> Optional[float]:
    """Device time per tick in ms of the ops in none of the `NAMED` stages:
    XLA's own copies, the loop's control, the outputs, and the guards and
    resync stages where built."""
    times = stage_times(record)
    if times is None:
        return None
    rest = sum(v for k, v in times.items() if k not in NAMED)
    return 1e3 * rest / record["ticks"]

#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of `workloads` in BENCHMARK.json) is one configuration
(`bench/configs/<config>.json`, whose `family` names the module beside it that
builds the program, its plain reference and its operation counts) under one
traffic mix (`bench/traffic/<traffic>.json`), held to the limits in
`bench/limits/<cell>.json`. A per-layer metric is the reader
`bench/metrics/<metric>.py`. Everything is found by name; nothing here names a
cell.

One run:
  1. turns on JAX's persistent compile cache in the checkout, and fails with
     no result unless JAX's first device is a TPU and there are as many chips
     as the cell asks for;
  2. makes the weights and the arrival stream on the device from --seed,
     builds the chunked AFL server exactly as the trainer builds it, and
     runs the first `check_chunks` chunks through the same `chunk` call the
     window uses (this compiles; it is set-up), keeping the running mean after
     the first and the model after the last;
  3. measures whole chunks for --seconds: each chunk is dispatched and its
     `emit`, `loss` and `t` read back on the host, as the trainer's loop does
     (with --trace 1 a window of at most TRACE_SECONDS is profiled instead);
  4. reads the peak device memory, frees the program's state, follows the
     same first chunks with the plain reference and compares: `correct` is
     whether every compared number is within its limit.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, breakdown (traced runs) and checks (every compared
number beside its limit). The last lines of standard error repeat the checks.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: longest window a traced run profiles; traces are large and slow to read
TRACE_SECONDS = 5.0
#: environment switches that take a path of the program out of the run
PROGRAM_SWITCHES = ("REPRO_NO_PALLAS", "REPRO_NO_FUSED_COMMIT", "REPRO_CHECKIFY")


class NoChip(RuntimeError):
    pass


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell `name` with its configuration, traffic, limits and readers,
    all found by name under `root`."""
    sys.path.insert(0, str(root / "bench"))
    import traffic as traffic_mod
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    family = load_module(root / "bench" / "configs" / f"{config['family']}.py",
                         f"bench_family_{config['family']}")
    traffic = traffic_mod.load(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    limits = json.loads((root / "bench" / "limits" / f"{name}.json").read_text())
    readers = {}
    for m in spec["per_layer"]:
        if name in m.get("workloads", cells):
            readers[m["name"]] = load_module(
                root / "bench" / "metrics" / f"{m['name']}.py",
                f"bench_metric_{m['name'].replace('.', '_')}")
    return dict(cell=cell, config=config, family=family, traffic=traffic,
                limits=limits, readers=readers, spec=spec)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Cell:
    """One cell, loaded and ready to run: its files, the program's modules and
    JAX, on a device this cell may use."""

    def __init__(self, name: str, root: Path = ROOT, require_tpu: bool = True):
        if not (root / "src" / "repro").is_dir():
            raise SystemExit(f"no program under {root / 'src'}: run from a checkout")
        sys.path.insert(0, str(root / "src"))
        for var in PROGRAM_SWITCHES:
            if os.environ.get(var):
                raise SystemExit(f"{var} is set; it changes the program under test")
        self.root = root
        self.__dict__.update(load_cell(name, root))
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        import jax
        # every program goes to the persistent cache, however quick its compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.devices = jax.devices()
        self.chips = self.cell["chips"]
        if require_tpu and self.devices[0].platform != "tpu":
            raise NoChip(f"JAX's first device is {self.devices[0].platform!r}, not a TPU")
        if require_tpu and len(self.devices) < self.chips:
            raise NoChip(f"the cell needs {self.chips} chips, JAX sees {len(self.devices)}")
        self.n = self.config["clients"]["n_clients"]

    def keys(self, seed: int):
        """(weights, stream, program init) keys of a seed."""
        import jax
        import traffic as traffic_mod
        root = traffic_mod.root_key(seed)
        return tuple(jax.random.fold_in(root, i) for i in (1, 2, 3))

    def first_steps(self, seed: int):
        """Set-up: weights and stream from the seed, the program as the
        trainer builds it, and its first `check_chunks` chunks through the
        window's own `chunk` call. Returns (chunk, carry, trace of the first
        chunks); `chunk(carry, i)` runs the i-th chunk of the stream."""
        import jax
        import numpy as np
        import traffic as traffic_mod
        from afl_reference import Trace
        k_weights, k_stream, k_init = self.keys(seed)
        mesh = None
        if self.chips > 1:
            from repro.core.scan_sharded import staleness_mesh
            mesh = staleness_mesh()
        weights = self.family.init_params(k_weights, self.config["model"])
        stream = traffic_mod.make_stream(k_stream, self.traffic, self.n)
        runner = self.family.build_program(self.config, self.traffic, weights, mesh)
        del weights
        lr0 = np.float32(0.0)   # the schedule is baked in; the runtime lr is unused
        carry = runner.init(k_init, lr0)

        def chunk(carry, i):
            g, tau = stream.chunk(i)
            return runner.chunk(carry, g, tau, stream.leave_at, stream.rejoin_at, lr0)

        losses = []
        for i in range(self.traffic.check_chunks):
            carry, outs = chunk(carry, i)
            losses.append(np.asarray(outs["loss"]))
            if i == 0:
                u_step1 = [np.asarray(x, np.float32)
                           for x in jax.tree.leaves(carry["state"]["u"])]
        w_last = [np.asarray(x, np.float32) for x in jax.tree.leaves(carry["w"])]
        return chunk, carry, Trace(np.concatenate(losses), u_step1, w_last,
                                   int(carry["t"]))

    def reference(self, seed: int, dtype=None, fault=None):
        """The plain reference over the same first chunks of the same seed:
        (trace, w0 leaves). Run it once the program's state is freed."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        import afl_reference
        import traffic as traffic_mod
        dtype = dtype or jnp.float32
        k_weights, k_stream, k_init = self.keys(seed)
        tr = self.traffic
        stream = traffic_mod.make_stream(k_stream, tr, self.n)
        gumbels = np.concatenate([np.asarray(stream.chunk(j)[0])
                                  for j in range(tr.check_chunks)])
        tau_raw = np.concatenate([np.asarray(stream.chunk(j)[1])
                                  for j in range(tr.check_chunks)])
        del stream
        w0 = self.family.init_params(k_weights, self.config["model"])
        w0_leaves = [np.asarray(x, np.float32) for x in jax.tree.leaves(w0)]
        clients = self.config["clients"]
        trace = afl_reference.run(
            w0=w0, payload=self.family.reference_payload(
                self.config, dtype, fault if tr.k_batch == 1 else None),
            n_clients=self.n, traffic=tr, cache_dtype=clients["cache_dtype"],
            history_dtype=clients["history_dtype"], key=k_init, gumbels=gumbels,
            tau_raw=tau_raw, n_chunks=tr.check_chunks, dtype=dtype, fault=fault)
        return trace, w0_leaves


def window(cell: Cell, chunk, carry, seconds: float, trace_dir=None) -> dict:
    """Measure whole chunks for `seconds`: dispatch a chunk, read its emit,
    loss and t back on the host, as the trainer's loop does. Returns the
    window's record; profiles it into `trace_dir` when given."""
    import jax
    import numpy as np
    tr = cell.traffic
    compiles = [0]

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    i, ticks, committed = tr.check_chunks, 0, 0
    host_s = []     # per chunk: from the last outputs on the host to the next dispatch's return
    t_start = time.perf_counter()
    last_out = None
    while True:
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            carry, outs = chunk(carry, i)
        t_disp = time.perf_counter()
        if last_out is not None:
            host_s.append(t_disp - last_out)
        with jax.profiler.TraceAnnotation("bench.readback"):
            em = np.asarray(outs["emit"])
            np.asarray(outs["loss"])[em]
            t_now = int(carry["t"])
        last_out = time.perf_counter()
        i += 1
        ticks += tr.chunk_events
        committed += int(em.sum()) * tr.k_batch
        if t_now >= tr.T:
            raise RuntimeError(f"the server reached T={tr.T} inside the window")
        if last_out - t_start >= seconds:
            break
    if trace_dir:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(on_compile)
    if compiles[0]:
        raise RuntimeError(f"{compiles[0]} compilation(s) inside the measured window")
    return dict(t_start=t_start, window_s=last_out - t_start, ticks=ticks,
                arrivals=ticks * tr.k_batch, committed=committed, host_s=host_s,
                carry=carry)


def run(args, *, require_tpu: bool = True, root: Path = ROOT) -> dict:
    """One run of one cell; returns the result object."""
    import afl_reference
    cell = Cell(args.workload, root, require_tpu)
    trace_mod = load_module(root / "bench" / "trace.py", "bench_trace")
    chunk, carry, prog = cell.first_steps(args.seed)
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    rec = window(cell, chunk, carry, seconds, trace_dir)
    setup_s = rec["t_start"] - _T0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in cell.devices[:cell.chips])
    log(f"window {rec['window_s']:.3f}s arrivals {rec['arrivals']} committed "
        f"{rec['committed']} setup {setup_s:.3f}s peak {peak}")

    # the program's state goes before the reference comes
    del carry, chunk, rec["carry"]
    gc.collect()
    t_ref = time.perf_counter()
    ref, w0_leaves = cell.reference(args.seed)
    readings = afl_reference.readings(prog, ref, w0_leaves)
    log(f"reference {time.perf_counter() - t_ref:.1f}s; readings {readings}")
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in cell.limits["limits"].items()}

    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": int(peak)}
    result = {"correct": all(v["value"] <= v["limit"] for v in checks.values()),
              "attempted": rec["arrivals"],
              "failed": rec["arrivals"] - rec["committed"]}
    if args.trace:
        reduced = trace_mod.reduce_file(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        record = dict(rec, config=cell.config, family=cell.family,
                      traffic=cell.traffic, chips=cell.chips, trace=reduced,
                      peaks=peak_table(dev.device_kind, root))
        units = {m["name"]: m["unit"] for m in cell.spec["per_layer"]}
        metrics = {}
        for name, reader in cell.readers.items():
            value = reader.read(record)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result.update(metrics=metrics, device=device, breakdown=reduced.breakdown())
    else:
        result.update(metrics={
            "events_per_s": {"value": rec["committed"] / rec["window_s"],
                             "unit": "events/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}, device=device)
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} = {v['value']!r} limit {v['limit']!r} "
            f"{'ok' if v['value'] <= v['limit'] else 'FAILED'}")
    return result


def peak_table(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

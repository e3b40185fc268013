"""End-to-end AFL training driver — the scanned real-model path.

Runs the paper's sampled-staleness protocol (Fig. 2) on a REAL transformer
from repro.models: client gradients are the model's own pjit grads, the
O(d) incremental server rules (ACE/ACED/CA2FL/…) run inside `jax.lax.scan`
on the tree-cache layout, and the (tau_max+1, ·) model-history ring carries
the stale reads (opt-in int8 via --history-dtype). Execution is chunked
(`make_chunked_staleness_runner`): every chunk boundary is a checkpoint/
resume point carrying the FULL protocol state — model, aggregator cache +
running sums + owner-ring, history ring, PRNG key — so --ckpt-dir resumes
exactly where it stopped, server rule included.

``--driver host`` runs the pinned host-loop replay reference
(`StalenessSimulator` consuming the same precomputed randomness): given the
same seed/config its trajectory matches the scanned path to ≤1e-5
(tests/test_train_scan.py pins all five algorithms on the reduced yi
config). On >1 visible devices the scan shards over a (data, model) mesh
(``--mesh auto``; repro/core/scan_sharded.py layout contract).

Example (CPU, ~0.8M-param yi-family model, 200 server iterations):
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 200 --batch 8 --seq 256 --algo ace
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint import restore_train_checkpoint, save_train_checkpoint
from repro.configs.registry import afl_config, get_config
from repro.core.aggregators import make_aggregator
from repro.core.distributed import afl_state_bytes, history_ring_bytes
from repro.core.fl_tasks import make_lm_task
from repro.core.scan_engine import default_n_events
from repro.core.scan_staleness import (build_fault_schedule,
                                       build_staleness_randomness,
                                       make_chunked_staleness_runner)
from repro.core.scan_sharded import staleness_mesh
from repro.core.staleness_sim import StalenessSimulator, default_tau_max
from repro.launch.compile_cache import use_compile_cache
from repro.optim import sqrt_nt_schedule

#: JAX's compile-phase duration events, summed into `TrainResult.compile_s`
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class TrainResult:
    """What one `main`/`train` call did. `loss` is the mean of the last 20
    emitted client losses (the eval loss when a resume left none to run).
    `events_per_s` counts arrivals over the chunks after the first, so it
    leaves out compilation (None when only one chunk ran). `peak_bytes` is
    the device's ``peak_bytes_in_use`` where the backend reports it."""
    loss: float
    t: int
    T: int
    param_count: int
    state_bytes: int
    compile_s: float
    events_per_s: Optional[float] = None
    peak_bytes: Optional[int] = None


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=None,
                    help="depth. With --reduced: the toy model's layers "
                    "(default 4). Without: cut the published model to this "
                    "many layers, keeping whole periods of every stage and "
                    "every width (default: all layers)")
    ap.add_argument("--steps", type=int, default=200,
                    help="server iterations T")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--algo", default="ace")
    ap.add_argument("--n-clients", type=int, default=8)
    ap.add_argument("--lr-scale", type=float, default=0.5)
    ap.add_argument("--beta", type=float, default=5.0)
    ap.add_argument("--speed-skew", type=float, default=0.0)
    ap.add_argument("--driver", choices=("scan", "host"), default="scan",
                    help="scan: chunked device scan (default); host: the "
                    "pinned replay reference loop")
    ap.add_argument("--chunk-events", type=int, default=64,
                    help="events per scanned chunk (checkpoint granularity); "
                    "need not divide the event budget — the final chunk "
                    "runs partial")
    ap.add_argument("--k-batch", type=int, default=1,
                    help="arrivals consumed per server tick (event-batched "
                    "scan engine; 1 = the bit-pinned per-event path)")
    ap.add_argument("--history-dtype", choices=("float32", "int8"),
                    default="float32",
                    help="model-history ring layout; int8 is ~4x smaller "
                    "but leaves the ≤1e-5 host-replay contract")
    ap.add_argument("--cache-dtype", choices=("float32", "bfloat16", "int8"),
                    default="float32",
                    help="aggregator cache dtype (f32 default keeps the "
                    "host replay exact; int8 quantizes per leaf here vs per "
                    "raveled row on the flat reference)")
    ap.add_argument("--mesh", choices=("auto", "none"), default="auto",
                    help="auto: shard over a (data, model) mesh when >1 "
                    "device is visible")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100,
                    help="events between checkpoints (rounded to chunk "
                    "boundaries)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    # --- fault injection / guard pipeline --------------------------------
    ap.add_argument("--clip-norm", type=float, default=0.0,
                    help="global-norm clip threshold for client payloads "
                    "(0 disables; >0 turns the guard pipeline on)")
    ap.add_argument("--fault-nan-rate", type=float, default=0.0,
                    help="fraction of events injected with NaN payloads "
                    "(quarantined by the guard pipeline)")
    ap.add_argument("--fault-explode-rate", type=float, default=0.0,
                    help="fraction of events with norm-exploded payloads")
    ap.add_argument("--fault-byzantine-rate", type=float, default=0.0,
                    help="fraction of events with sign-flipped payloads")
    ap.add_argument("--fault-overstale-rate", type=float, default=0.0,
                    help="fraction of events arriving with tau > tau_max "
                    "(rejected by the guard pipeline)")
    ap.add_argument("--fault-explode-scale", type=float, default=1e4,
                    help="norm multiplier for explode faults")
    ap.add_argument("--resync-every", type=int, default=0,
                    help="emitted updates between exact recomputes of the "
                    "incremental ACED/CA2FL running sums (0 disables)")
    ap.add_argument("--checkify", action="store_true",
                    help="compile the repro.core.sanitize invariant checks "
                    "into the scan step (finite model/payload, ring-cursor "
                    "and owner-ring bounds, resync agreement); equivalent "
                    "to REPRO_CHECKIFY=1. Off is the default and traces "
                    "zero extra ops")
    return ap


def train(**overrides) -> TrainResult:
    """Programmatic entry point: parser defaults + keyword overrides
    (underscored option names, e.g. ``train(reduced=True, d_model=64)``) —
    examples/train_lm.py uses this instead of re-encoding argv."""
    args = _parser().parse_args([])
    for k, v in overrides.items():
        if not hasattr(args, k):
            raise TypeError(f"unknown train option {k!r}")
        setattr(args, k, v)
    return _run(args)


def main(argv=None) -> TrainResult:
    use_compile_cache()
    return _run(_parser().parse_args(argv))


def _run(args) -> TrainResult:
    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event in _COMPILE_EVENTS:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        return _train(args, compile_s)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def model_config(args):
    """The model the options name: the toy `reduced` variant with
    --reduced, else the published config, depth cut by --layers."""
    cfg = get_config(args.arch)
    if args.reduced:
        return cfg.reduced(layers=args.layers or 4, d_model=args.d_model,
                           vocab=args.vocab)
    return cfg.cut_depth(args.layers) if args.layers else cfg


def _train(args, compile_s) -> TrainResult:
    cfg = model_config(args)
    aflc = afl_config(args.arch, algorithm=args.algo,
                      n_clients=args.n_clients, delay_beta=args.beta,
                      cache_dtype=args.cache_dtype, k_batch=args.k_batch)
    print(f"model={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"algo={args.algo} clients={aflc.n_clients} driver={args.driver}")

    agg = make_aggregator(aflc)
    task = make_lm_task(cfg=cfg, n_clients=aflc.n_clients, batch=args.batch,
                        seq=args.seq, seed=args.seed)
    T = args.steps
    server_lr = sqrt_nt_schedule(args.lr_scale, aflc.n_clients, T)
    tau_max = default_tau_max(args.beta)
    fault_rates = {"nan_rate": args.fault_nan_rate,
                   "explode_rate": args.fault_explode_rate,
                   "byzantine_rate": args.fault_byzantine_rate,
                   "overstale_rate": args.fault_overstale_rate}
    any_faults = any(r > 0 for r in fault_rates.values())
    guards = any_faults or args.clip_norm > 0
    n_events = default_n_events(agg, T, True)
    if any_faults:
        # quarantined/rejected events never emit: pad the event budget so
        # the run still reaches T server iterations in expectation
        drop = args.fault_nan_rate + args.fault_overstale_rate
        n_events = int(np.ceil(n_events / max(1.0 - drop, 0.5))) + 16
    C = max(1, args.chunk_events)
    # exact event budget — no rounding up to a chunk multiple: the final
    # chunk runs partial (one extra compile for its shorter shape), so the
    # checkpointed event cursor can never claim events past the schedule and
    # a resume with a different --chunk-events lands on the same stream
    rand = build_staleness_randomness(args.seed, n_events, aflc.n_clients,
                                      args.beta, speed_skew=args.speed_skew,
                                      k_batch=args.k_batch)
    faults = None
    if guards:
        faults = build_fault_schedule(
            args.seed, n_events, explode_scale=args.fault_explode_scale,
            k_batch=args.k_batch, **fault_rates)
        kinds = faults.counts()
        print(f"guards on: clip_norm={args.clip_norm} "
              f"resync_every={args.resync_every or 'off'} "
              f"injected={kinds}")
    resync_every = args.resync_every or None
    state_bytes = (afl_state_bytes(aflc, task.params0, "tree", guards=guards,
                                   resync_every=resync_every)
                   + history_ring_bytes(task.params0, tau_max,
                                        args.history_dtype))

    def result(loss, t, events_per_s=None):
        stats = jax.local_devices()[0].memory_stats() or {}
        res = TrainResult(loss=loss, t=t, T=T,
                          param_count=cfg.param_count(),
                          state_bytes=state_bytes, compile_s=compile_s[0],
                          events_per_s=events_per_s,
                          peak_bytes=stats.get("peak_bytes_in_use"))
        print(f"final loss (mean last 20): {loss:.4f} t={t}/{T} "
              f"state_bytes={state_bytes} compile_s={res.compile_s:.2f} "
              f"ev/s={events_per_s} peak_bytes={res.peak_bytes}")
        return res

    if args.driver == "host":
        sim = StalenessSimulator(
            grad_fn=task.grad_fn, params0=task.params0, aggregator=agg,
            n_clients=aflc.n_clients, server_lr=server_lr, beta=args.beta,
            tau_max=tau_max, speed_skew=args.speed_skew, seed=args.seed,
            replay=rand, faults=faults, clip_norm=args.clip_norm,
            resync_every=resync_every, k_batch=args.k_batch)
        res = sim.run(T)
        if res.faults:
            print(f"guard counters: {res.faults}")
        return result(float(np.mean(res.losses[-20:])),
                      int(res.ts[-1]) + 1 if res.ts else 0)

    mesh = staleness_mesh() if args.mesh == "auto" else None
    runner = make_chunked_staleness_runner(
        mesh=mesh, grad_fn=task.grad_fn, params0=task.params0,
        aggregator=agg, n_clients=aflc.n_clients, T=T, beta=args.beta,
        server_lr=server_lr, tau_max=tau_max, speed_skew=args.speed_skew,
        layout="tree", history_dtype=args.history_dtype,
        guards=guards, resync_every=resync_every,
        checkify_invariants=args.checkify or None, k_batch=args.k_batch)

    lr0 = jnp.float32(0.0)   # schedule baked in; runtime lr unused
    carry = runner.init(jax.random.PRNGKey(args.seed), lr0)
    e0 = 0
    if args.ckpt_dir:
        carry, e0 = restore_train_checkpoint(args.ckpt_dir, carry)
        if e0:
            print(f"resumed from event {e0} (t={int(carry['t'])})")
        e0 = min(e0, n_events)

    losses: list = []
    t0 = time.time()
    events_done, last_log = 0, 0
    steady = None     # (time, events) once the first chunk has compiled
    for lo in range(e0, n_events, C):
        # tail guard: the final chunk is sliced exactly, so the snapshot /
        # checkpoint cursor `hi` never lands past the event schedule even
        # when the chunk size does not divide n_events (or a resume starts
        # mid-chunk after a --chunk-events change)
        hi = min(lo + C, n_events)
        guard_args = ()
        if guards:
            guard_args = (faults.kind[lo:hi], faults.scale[lo:hi],
                          jnp.float32(args.clip_norm))
        # host spans on the profiler's clock, each with the chunk's event
        # offset: under a caller's `jax.profiler.trace`, every device idle
        # gap falls in a dispatch, a readback, a checkpoint save or a log
        with TraceAnnotation("afl.dispatch", lo=lo):
            carry, outs = runner.chunk(carry, rand.gumbels[lo:hi],
                                       rand.tau_raw[lo:hi], rand.leave_at,
                                       rand.rejoin_at, lr0, *guard_args)
        with TraceAnnotation("afl.readback", lo=lo):
            em = np.asarray(outs["emit"])
            losses.extend(np.asarray(outs["loss"])[em].tolist())
            t_now = int(carry["t"])
        events_done += hi - lo
        if steady is None:
            steady = (time.time(), events_done)
        if len(losses) - last_log >= args.log_every or hi >= n_events:
            with TraceAnnotation("afl.log", lo=lo):
                last_log = len(losses)
                dt = time.time() - t0
                print(f"t={t_now:5d}/{T} events={hi} "
                      f"loss={np.mean(losses[-args.log_every:]):.4f} "
                      f"({events_done * args.k_batch / max(dt, 1e-9):.1f}"
                      " ev/s)", flush=True)
        if args.ckpt_dir and (hi // args.ckpt_every != lo // args.ckpt_every
                              or hi >= n_events or t_now >= T):
            with TraceAnnotation("afl.checkpoint", lo=lo):
                save_train_checkpoint(args.ckpt_dir, hi, carry)
        if t_now >= T:
            break

    eps = None
    if steady is not None and events_done > steady[1]:
        eps = ((events_done - steady[1]) * args.k_batch
               / (time.time() - steady[0]))
    ev = task.eval_fn(carry["w"])
    if guards:
        counters = {k: int(v) for k, v in carry["guards"].items()}
        print(f"guard counters: {counters}")
    print(f"eval={ev}")
    # resumed past the event budget => no fresh losses; report eval loss
    final = float(np.mean(losses[-20:])) if losses else ev["loss"]
    return result(final, int(carry["t"]), eps)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Chip smoke test: drive the AFL server's main paths once on a TPU and check
what comes out.

    python chip_smoke.py             # one chip: phases a-c below
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

One chip:
  a. device  -- JAX's first device must be a TPU (no CPU fallback), and
     neither REPRO_NO_PALLAS nor REPRO_NO_FUSED_COMMIT may be set: they
     would take the Pallas kernels out of the path under test.
  b. train   -- `repro.launch.train.main` on mamba2-780m at its published
     widths cut to 2 layers, int8 history ring and int8 cache, 8 clients,
     ACE. The loss must be finite and the server must reach t == T.
  c. flat    -- `run_staleness_scan` on an int8 `FlatCache` of 256 clients x
     2^22 features (1 GiB) for ACE, ACED and CA2FL at K=16 (the fused commit
     kernel) and ACE at K=1 (the `cache_row_update` kernel). Each compiled
     scan program must hold a Pallas kernel (`tpu_custom_call`), every
     trajectory must be finite and reach T, and each K=16 run must match the
     same run through the XLA dispatch chain (`fused_commit=False`). Every
     kernel is then compared with its XLA oracle at these widths: <= 1e-5
     relative, int8 rows bit-identical.

Four chips (`--chips 4`): `run_staleness_scan` on a (data, model) mesh and
the chunked tree runner under the same mesh, each compared with the same run
unsharded on device 0.

No phase catches an exception: any failure exits non-zero before the last
line, which is one JSON object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke: no repro package under {ROOT / 'src'}; run from "
             "a checkout of the repository")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# --- sizes -----------------------------------------------------------------
BETA = 5.0                              # tau_max = 50, the train default
TRAIN_T = 40                            # ACE emits per event: 39 events
TRAIN_ARGV = ["--arch", "mamba2-780m", "--layers", "2",
              "--history-dtype", "int8", "--cache-dtype", "int8",
              "--n-clients", "8", "--algo", "ace", "--beta", str(BETA),
              "--steps", str(TRAIN_T), "--chunk-events", "13",
              "--log-every", "13"]
FLAT_N, FLAT_D, FLAT_T, FLAT_K = 256, 1 << 22, 24, 16
TREE_T, TREE_CHUNK, TREE_BATCH, TREE_SEQ = 16, 5, 8, 256
#: what a compiled Pallas kernel leaves in the program text
KERNEL_MARK = "tpu_custom_call"
#: tolerance of a Pallas kernel or fused run against its XLA reference:
#: max |a − b| / (1 + max |b|) where only f32 reassociation separates them.
#: Runs over an int8 cache are held to it in relative L2 instead: a payload
#: that moved by an ulp can requantize one int8 step the other way, which
#: moves that one coordinate by a whole step over n (≈ 1e-5 absolute at
#: n = 256) while leaving the model's L2 norm all but unchanged.
REL_TOL = 1e-5
#: int8 history ring: every slot is requantized per leaf on each append, so
#: a sharded run is held to the relative L2 tolerance the tests use for the
#: int8 ring against the f32 ring
INT8_RING_REL_TOL = 0.05


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / (1.0 + np.max(np.abs(b))))


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# --- a. device -------------------------------------------------------------
def device_check(chips: int):
    for var in ("REPRO_NO_PALLAS", "REPRO_NO_FUSED_COMMIT"):
        if var in os.environ:
            raise SystemExit(f"chip_smoke: {var} is set; it takes the Pallas "
                             "kernels out of the path under test")
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    log(f"device {devs[0].device_kind} x{len(devs)}")
    return devs


# --- b. train --------------------------------------------------------------
def train_phase():
    from repro.launch.train import main as train_main
    res = train_main(TRAIN_ARGV)
    check(np.isfinite(res.loss), f"train loss not finite: {res.loss}")
    check(res.t == res.T, f"train stopped at t={res.t} < T={res.T}")
    log(f"train params={res.param_count} state_bytes={res.state_bytes} "
        f"peak_bytes_in_use={res.peak_bytes} compile_s={res.compile_s:.2f} "
        f"events_per_s={res.events_per_s} loss={res.loss}")
    return res


# --- c. flat ---------------------------------------------------------------
def flat_task(d: int):
    """Quadratic client objectives 0.5·||w − c_i||² with c_i = b + 0.3·z_i:
    targets are regenerated from fixed keys on each call, so no (n, d) data
    table sits on the device beside the cache."""
    def grad_fn(w, client, key):
        # b's key is folded with a traced zero (client // 2^30), so XLA
        # draws b at run time instead of folding a d-long constant into
        # the program at compile time
        kb = jax.random.fold_in(jax.random.PRNGKey(1), client // (1 << 30))
        base = jax.random.normal(kb, (d,), jnp.float32)
        kc = jax.random.fold_in(jax.random.PRNGKey(2), client)
        diff = w - (base + 0.3 * jax.random.normal(kc, (d,), jnp.float32))
        noise = 0.1 * jax.random.normal(key, (d,), jnp.float32)
        return 0.5 * jnp.mean(jnp.square(diff)), diff + noise
    return grad_fn, jnp.zeros((d,), jnp.float32)


def _aggregator(name: str, k: int, fused):
    from repro.core.aggregators import ACED, ACEIncremental, CA2FL
    if name == "ace":
        return ACEIncremental(cache_dtype="int8", fused_commit=fused)
    if name == "aced":
        return ACED(tau_algo=10, max_cohort=k, cache_dtype="int8",
                    fused_commit=fused)
    return CA2FL(buffer_size=10, cache_dtype="int8", fused_commit=fused)


def _flat_kwargs(name, k, n, d, T, fused=None):
    grad_fn, params0 = flat_task(d)
    # a K=16 tick always emits (ACE/ACED) or crosses CA2FL's buffer of 10,
    # so T ticks (+ slack) cover the run; _to_result raises if they don't
    return dict(grad_fn=grad_fn, params0=params0,
                aggregator=_aggregator(name, k, fused), n_clients=n,
                server_lr=0.1, T=T, beta=BETA, seed=0, k_batch=k,
                n_events=T + 8)


def _scan_program_text(kw) -> str:
    """Compile the program `run_staleness_scan(**kw)` runs and return its
    text. The persistent compile cache hands the same executable to the run
    that follows, so the check costs no second compilation."""
    from repro.core.scan_staleness import (build_staleness_randomness,
                                           make_staleness_runner)
    rand = build_staleness_randomness(kw["seed"], kw["n_events"],
                                      kw["n_clients"], kw["beta"],
                                      k_batch=kw["k_batch"])
    runner = make_staleness_runner(
        grad_fn=kw["grad_fn"], params0=kw["params0"],
        aggregator=kw["aggregator"], n_clients=kw["n_clients"], T=kw["T"],
        beta=kw["beta"], k_batch=kw["k_batch"])
    args = (jax.random.PRNGKey(kw["seed"]), rand.gumbels, rand.tau_raw,
            rand.leave_at, rand.rejoin_at, jnp.float32(kw["server_lr"]))
    return runner.lower(*args).compile().as_text()


def flat_phase():
    from repro.core.distributed import afl_state_bytes, history_ring_bytes
    from repro.core.scan_staleness import run_staleness_scan
    from repro.configs.base import AFLConfig
    from repro.core.staleness_sim import default_tau_max
    n, d, T = FLAT_N, FLAT_D, FLAT_T
    for name, k in (("ace", FLAT_K), ("aced", FLAT_K), ("ca2fl", FLAT_K),
                    ("ace", 1)):
        kw = _flat_kwargs(name, k, n, d, T)
        cfg = AFLConfig(algorithm=name, n_clients=n, cache_dtype="int8",
                        tau_algo=10, buffer_size=10, k_batch=k)
        state_b = afl_state_bytes(cfg, {"w": kw["params0"]}, "flat")
        ring_b = history_ring_bytes({"w": kw["params0"]},
                                    default_tau_max(BETA), layout="flat")
        t0 = time.time()
        text = _scan_program_text(kw)
        compile_s = time.time() - t0
        check(KERNEL_MARK in text,
              f"{name} K={k}: no {KERNEL_MARK} in the program")
        t0 = time.time()
        sr = run_staleness_scan(**kw)
        run_s = time.time() - t0
        check(np.all(np.isfinite(sr.w)) and np.all(np.isfinite(sr.losses)),
              f"{name} K={k}: non-finite trajectory")
        check(int(sr.ts[-1]) + 1 == T, f"{name} K={k}: stopped before T")
        msg = ""
        if k > 1:
            chain = run_staleness_scan(**_flat_kwargs(name, k, n, d, T,
                                                      fused=False))
            err, l2 = rel_err(sr.w, chain.w), rel_l2(sr.w, chain.w)
            check(l2 <= REL_TOL,
                  f"{name} K={k}: fused vs chain rel_l2={l2:.3e}")
            msg = f" fused_vs_chain rel={err:.3e} rel_l2={l2:.3e}"
        log(f"flat {name} K={k} n={n} d={d} state_bytes={state_b} "
            f"ring_bytes={ring_b} compile_s={compile_s:.2f} "
            f"run_s={run_s:.2f} loss0={sr.losses[0]:.4f} "
            f"loss_end={sr.losses[-1]:.4f}{msg}")


def kernel_phase():
    """Every Pallas kernel against its XLA oracle at the flat widths, on
    seeded random inputs with one quarantined (NaN, invalid) lane."""
    from repro.kernels import ops, ref
    n, d, K = FLAT_N, FLAT_D, FLAT_K
    keys = (jax.random.fold_in(jax.random.PRNGKey(7), i)
            for i in itertools.count())
    normal = lambda shape: jax.random.normal(next(keys), shape, jnp.float32)
    valid = jnp.arange(K) != 3
    G = normal((K, d)).at[3].set(jnp.nan)
    new_s = ref.row_scale(jnp.where(valid[:, None], G, 0.0))
    old_q, old_s = ref.quantize_rows_ref(normal((K, d)))
    vf = valid.astype(jnp.float32)
    lane = jnp.where(valid, jax.random.uniform(next(keys), (K,)), 0.0)
    cases = {  # name -> (R, lane_a, lane_b, lane_g): the rules' basis shapes
        "ace": (1, None, None, None),
        "aced": (2, lane, vf, None),
        "ca2fl": (3, vf, None, vf)}
    for name, (R, la, lb, lg) in cases.items():
        for dtype in ("int8", "float32"):
            q = dtype == "int8"
            rows = old_q if q else normal((K, d))
            kw = dict(G=G, old_rows=rows, old_s=old_s if q else None,
                      new_s=new_s if q else None, valid=valid,
                      vecs=normal((R, d)), coef=normal((R, R + 4)),
                      upd_w=normal((R + 4,)), lane_a=la, lane_b=lb,
                      lane_g=lg)
            p = ops.commit_batch(**kw, backend="pallas")
            x = ops.commit_batch(**kw, backend="xla")
            check(np.array_equal(np.asarray(p[0]), np.asarray(x[0])),
                  f"commit_batch {name} {dtype}: rows differ")
            err = max(rel_err(p[1], x[1]), rel_err(p[2], x[2]))
            check(err <= REL_TOL, f"commit_batch {name} {dtype}: {err:.3e}")
            log(f"kernel commit_batch {name} {dtype} K={K} d={d} "
                f"rel={err:.3e} rows bit-identical")
    g, u = normal((d,)), normal((d,))
    c_row, s_old, s_new = old_q[0], old_s[0], ref.row_scale(g)
    for name, fn, a in (
            ("cache_row_update", ops.cache_row_update,
             (u, g, c_row, s_old, s_new, 1.0 / n)),
            ("row_delta", ops.row_delta, (g, c_row, s_old, s_new))):
        # both return (f32 values, int8 row)
        _pair(name, fn(*a, backend="pallas")[::-1],
              fn(*a, backend="xla")[::-1], d)
    cache = jax.random.randint(next(keys), (n, d), -127, 128, jnp.int8)
    scales = jax.random.uniform(next(keys), (n,), jnp.float32, 1e-3, 1e-2)
    mask = jax.random.uniform(next(keys), (n,)) < 0.5
    err = rel_err(ops.masked_agg(cache, scales, mask, backend="pallas"),
                  ops.masked_agg(cache, scales, mask, backend="xla"))
    check(err <= REL_TOL, f"masked_agg: {err:.3e}")
    log(f"kernel masked_agg n={n} d={d} rel={err:.3e}")
    x = normal((K, d))
    pq, ps = ops.quantize_rows(x, backend="pallas")
    xq, xs = ops.quantize_rows(x, backend="xla")
    _pair("quantize_rows", (pq, ps), (xq, xs), d)
    err = rel_err(ops.dequantize_rows(xq, xs, backend="pallas"),
                  ops.dequantize_rows(xq, xs, backend="xla"))
    check(err <= REL_TOL, f"dequantize_rows: {err:.3e}")
    log(f"kernel dequantize_rows K={K} d={d} rel={err:.3e}")


def _pair(name, p, x, d):
    """(int8 rows, f32 values) from a kernel and its oracle."""
    check(np.array_equal(np.asarray(p[0]), np.asarray(x[0])),
          f"{name}: int8 rows differ")
    err = rel_err(p[1], x[1])
    check(err <= REL_TOL, f"{name}: {err:.3e}")
    log(f"kernel {name} d={d} rel={err:.3e} int8 bit-identical")


# --- four chips ------------------------------------------------------------
def _run_chunks(runner, rand, n_events, chunk):
    carry = runner.init(jax.random.PRNGKey(0), jnp.float32(0.0))
    losses = []
    for lo in range(0, n_events, chunk):
        hi = min(lo + chunk, n_events)
        carry, outs = runner.chunk(carry, rand.gumbels[lo:hi],
                                   rand.tau_raw[lo:hi], rand.leave_at,
                                   rand.rejoin_at, jnp.float32(0.0))
        losses.append(np.asarray(outs["loss"])[np.asarray(outs["emit"])])
    w = np.concatenate([np.asarray(x).ravel()
                        for x in jax.tree.leaves(carry["w"])])
    return w, np.concatenate(losses), int(carry["t"])


def tree_config():
    from repro.launch.train import _parser, model_config
    return model_config(_parser().parse_args(TRAIN_ARGV))


def sharded_phase():
    from repro.configs.registry import afl_config
    from repro.core.aggregators import ACEIncremental
    from repro.core.aggregators import make_aggregator
    from repro.core.fl_tasks import make_lm_task
    from repro.core.scan_engine import default_n_events
    from repro.core.scan_sharded import staleness_mesh
    from repro.core.scan_staleness import (build_staleness_randomness,
                                           make_chunked_staleness_runner,
                                           run_staleness_scan)
    from repro.core.staleness_sim import default_tau_max
    from repro.optim import sqrt_nt_schedule
    n, d, T = FLAT_N, FLAT_D, FLAT_T
    mesh = staleness_mesh()
    log(f"mesh {dict(mesh.shape)}")

    # an f32 cache may differ only by reduction order; an int8 cache also
    # requantizes (see REL_TOL)
    for dtype in ("float32", "int8"):
        kw = _flat_kwargs("ace", FLAT_K, n, d, T)
        kw["aggregator"] = ACEIncremental(cache_dtype=dtype)
        t0 = time.time()
        one = run_staleness_scan(**kw)
        many = run_staleness_scan(mesh=mesh, **kw)
        check(many.ts.tolist() == one.ts.tolist(), "sharded flat: ts differ")
        err, l2 = rel_err(many.w, one.w), rel_l2(many.w, one.w)
        check((err if dtype == "float32" else l2) <= REL_TOL,
              f"sharded flat ACE K=16 {dtype}: rel={err:.3e} l2={l2:.3e}")
        log(f"sharded flat ACE K={FLAT_K} {dtype} cache n={n} d={d} "
            f"rel={err:.3e} rel_l2={l2:.3e} ({time.time() - t0:.1f}s)")

    # the chunked tree runner as launch/train.py builds it
    cfg = tree_config()
    aflc = afl_config("mamba2-780m", algorithm="ace", n_clients=8,
                      delay_beta=BETA, cache_dtype="int8")
    task = make_lm_task(cfg=cfg, n_clients=8, batch=TREE_BATCH,
                        seq=TREE_SEQ, seed=0)
    T_tree = TREE_T
    n_events = default_n_events(make_aggregator(aflc), T_tree, True)
    rand = build_staleness_randomness(0, n_events, 8, BETA)
    results = {}
    for label, m in (("unsharded", None), ("sharded", mesh)):
        t0 = time.time()
        runner = make_chunked_staleness_runner(
            mesh=m, grad_fn=task.grad_fn, params0=task.params0,
            aggregator=make_aggregator(aflc), n_clients=8, T=T_tree,
            beta=BETA, server_lr=sqrt_nt_schedule(0.5, 8, T_tree),
            tau_max=default_tau_max(BETA), layout="tree",
            history_dtype="int8")
        results[label] = _run_chunks(runner, rand, n_events, TREE_CHUNK)
        log(f"tree {label} {cfg.name} t={results[label][2]} "
            f"({time.time() - t0:.1f}s)")
    (w1, l1, t1), (w4, l4, t4) = results["unsharded"], results["sharded"]
    check(t1 == t4 == T_tree, f"tree runs stopped at t={t1}, {t4}")
    check(np.all(np.isfinite(w4)) and np.all(np.isfinite(l4)),
          "sharded tree: non-finite")
    rel = rel_l2(w4, w1)
    check(rel < INT8_RING_REL_TOL, f"sharded tree vs unsharded: {rel:.3e}")
    log(f"sharded tree {cfg.name} int8 ring rel_l2={rel:.3e} "
        f"loss_rel={rel_err(l4, l1):.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths and what they are "
                    "compared with")
    args = ap.parse_args(argv)
    use_compile_cache()
    devs = device_check(args.chips)
    t0 = time.time()
    if args.chips == 4:
        sharded_phase()
    else:
        train_phase()
        flat_phase()
        kernel_phase()
    log(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

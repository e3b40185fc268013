"""Distributed (pjit) AFL server step — the paper's technique at pod scale.

One physical step = one server iteration of Algorithm 1 / a.1:
  1. the *arriving* client's gradient is computed by the whole mesh
     (its batch shards over (pod, data); params/optimizer FSDP+TP shard);
  2. the server rule updates the sharded per-client cache + running mean
     (ACE incremental O(d); ACED masked aggregation; baselines likewise);
  3. w ← w − η·scale·u.

Staleness is emergent: a client's cache row was written when it last arrived,
so its age in server iterations is exactly the paper's τ_i^t — no stale model
copies are stored (see DESIGN.md §3). The arrival schedule is precomputed
host-side from the delay model and fed as a scalar per step.

Cache sharding: client axis → `data`, feature dims → `model` (via the leaf's
own sharding), so aggregation adds no collectives beyond the gradient's own
reduce-scatter. The sharded staleness scan (repro/core/scan_sharded.py) uses
the same client/feature layout for its flat cache, and `apply_server_rule`
below delegates to the layout-generic `Aggregator.step` protocol — the rule
implementations exist once, in repro/core/aggregators.py.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import AFLConfig
from repro.core.aggregators import Arrival, make_aggregator
from repro.optim.optim import Optimizer


class AFLTrainState(NamedTuple):
    params: Any
    opt_state: Any
    afl: Any            # algorithm-specific server state (pytree)
    step: jnp.ndarray   # server iteration t


# ---------------------------------------------------------------------------
# Algorithm-specific server states over gradient pytrees
# ---------------------------------------------------------------------------

def init_afl_state(cfg: AFLConfig, grads_like, init_grads=None):
    """Tree-layout server state for `cfg.algorithm` over the params pytree.

    Delegates to the layout-generic `Aggregator.init_state` (the same code
    path the flat simulators and scan engines use, with `d` = the pytree
    template instead of the raveled dimension), so the pjit path cannot
    drift from the rule implementations. `init_grads`, when given, is a
    grads-like pytree with a leading (n,) client axis seeding the cache of
    cache-init rules. asgd/delay_asgd carry no state (empty tuple)."""
    return make_aggregator(cfg).init_state(cfg.n_clients, grads_like,
                                           init_grads)


def apply_server_rule(cfg: AFLConfig, afl_state, grads, client, t, staleness):
    """-> (new_afl_state, update (grads-like), lr_scale scalar).

    Thin adapter over the unified `Aggregator.step` protocol
    (repro/core/aggregators.py): the rule implementations are layout-generic
    — cache access dispatches on the state's cache layout (tree caches here,
    `FlatCache` in the simulators/scan engines) and all other arithmetic is
    per-leaf — so the EXACT same transition serves host sim, single-device
    scan, sharded scan and this pjit path. The `emit` gate folds into the
    update (non-flushing arrivals emit a zero update, w unchanged — the train
    step applies unconditionally)."""
    agg = make_aggregator(cfg)
    state, u, emit, scale = agg.step(
        afl_state, Arrival(client, grads, t, staleness))
    gate = emit.astype(jnp.float32)
    u = jax.tree.map(lambda x: x.astype(jnp.float32) * gate, u)
    return state, u, scale


# ---------------------------------------------------------------------------
# Train step factory
# ---------------------------------------------------------------------------

def make_afl_train_step(loss_fn: Callable, cfg: AFLConfig, opt: Optimizer,
                        remat: str = "full"):
    """loss_fn(params, batch) -> scalar. Returns (init_fn, step_fn).

    step_fn(state, batch, client, staleness) -> (state, metrics)."""

    def init_fn(params):
        grads_like = params
        return AFLTrainState(params=params, opt_state=opt.init(params),
                             afl=init_afl_state(cfg, grads_like),
                             step=jnp.zeros((), jnp.int32))

    def step_fn(state: AFLTrainState, batch, client, staleness):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        new_afl, u, scale = apply_server_rule(cfg, state.afl, grads, client,
                                              state.step, staleness)
        scaled = jax.tree.map(lambda x: (scale * x).astype(jnp.float32), u)
        updates, new_opt = opt.update(scaled, state.opt_state, state.params)
        new_params = jax.tree.map(lambda p, d: (p + d).astype(p.dtype),
                                  state.params, updates)
        metrics = {
            "loss": loss,
            "grad_norm": optax_global_norm(grads),
            "update_norm": optax_global_norm(u),
            "lr_scale": scale,
        }
        return AFLTrainState(new_params, new_opt, new_afl, state.step + 1), metrics

    return init_fn, step_fn


def optax_global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def afl_state_bytes(cfg: AFLConfig, params, layout: str = "flat",
                    guards: bool = False,
                    resync_every: int | None = None) -> int:
    """Analytic server-state memory (paper Table a.3) without allocating —
    exact: matches byte-for-byte what the corresponding init actually
    allocates (pinned per algorithm × cache_dtype by tests/test_distributed
    and benchmarks/table_a3_memory).

    layout="flat": `Aggregator.init_state` over the raveled d — a FlatCache
    always carries an (n,) f32 scale row (even for float dtypes), counts are
    int32 scalars, ACED's t_start is (n,) int32, and u/h_bar/accum are f32.
    layout="tree": `init_afl_state` over the params pytree — per-leaf int8
    caches carry one (n,) f32 scale each (float tree caches carry none), and
    u/h_bar/accum live in cfg.state_dtype.

    ``cfg.k_batch > 1`` sizes ACED's owner-ring for whole-cohort expiry:
    (tau_algo+2, k_batch) int32 instead of (tau_algo+2,).

    ``guards=True`` adds the scan's fault-guard counters (the PR-7
    quarantined/clipped/rejected int32 triple riding the chunked carry —
    checkpointed server state, so the exact accounting must include it).
    ``resync_every`` adds the emitted-update int32 scalar the resync
    cadence is keyed on (likewise checkpointed alongside the rule state)."""
    db = {"float32": 4, "bfloat16": 2, "int8": 1}[cfg.cache_dtype]
    d = sum(int(x.size) for x in jax.tree.leaves(params))
    n = cfg.n_clients
    a = cfg.algorithm
    extra = 0
    if guards:
        extra += 3 * 4        # quarantined / clipped / rejected counters
    if resync_every:
        extra += 4            # n_upd cadence scalar (drives lax.cond resync)
    if layout == "flat":
        cache = n * d * db + n * 4            # data + per-row f32 scale
        vec = d * 4                           # u / h_bar / accum are f32
    elif layout == "tree":
        n_leaves = len(jax.tree.leaves(params))
        cache = n * d * db + (n * 4 * n_leaves if cfg.cache_dtype == "int8"
                              else 0)
        vec = d * jnp.dtype(cfg.state_dtype).itemsize
    else:
        raise ValueError(f"unknown layout {layout!r}")
    count = 4                                 # int32 buffer counter
    if a == "ace":
        return cache + vec + extra
    if a == "ace_direct":
        return cache + extra
    if a == "aced":
        # incremental active-set state: t_start (n,) int32, owner-ring
        # (tau_algo+2,) int32 — (tau_algo+2, k_batch) when event-batched —
        # asum + init_sum running vectors, count/t_prev/init_count int32
        # scalars, init_mask (n,) bool
        cohort = max(1, getattr(cfg, "k_batch", 1))
        return (cache + n * 4 + (cfg.tau_algo + 2) * cohort * 4 + 2 * vec
                + 3 * 4 + n * 1 + extra)
    if a == "aced_direct":
        return cache + n * 4 + extra          # t_start (n,) int32
    if a == "ca2fl":
        return cache + 3 * vec + count + extra  # h + h_bar + h_sum + accum
    if a == "ca2fl_direct":
        return cache + 2 * vec + count + extra  # h + h_bar + accum + count
    if a == "fedbuff":
        return vec + count + extra
    return extra


def history_ring_bytes(params, tau_max: int,
                       history_dtype: str = "float32",
                       layout: str = "tree") -> int:
    """Analytic bytes of the (tau_max+1, ·) model-history ring the scanned
    staleness protocol carries (repro/core/scan_staleness.py) — exact:
    matches byte-for-byte what the corresponding allocation produces
    (allocation-pinned by tests, like `afl_state_bytes`).

    layout="tree": `init_tree_cache(tau_max+1, params, history_dtype)` — a
    per-leaf stacked (S, *shape) buffer; the int8 layout adds one (S,) f32
    scale per leaf. layout="flat": a raw (S, *flat_row_shape(d)) f32 ring,
    S * d values (the flat engines never quantize their history)."""
    S = tau_max + 1
    d = sum(int(x.size) for x in jax.tree.leaves(params))
    if layout == "flat":
        return S * d * 4
    if layout != "tree":
        raise ValueError(f"unknown layout {layout!r}")
    db = {"float32": 4, "bfloat16": 2, "int8": 1}[history_dtype]
    n_leaves = len(jax.tree.leaves(params))
    return S * d * db + (S * 4 * n_leaves if history_dtype == "int8" else 0)

"""Server aggregation rules — the paper's algorithm zoo.

Implemented exactly as specified:
  * Vanilla ASGD            [Mishchenko et al., 2022]     (m=1, immediate)
  * Delay-adaptive ASGD     [Koloskova et al., 2022]      (m=1, lr ∝ 1/τ for stragglers)
  * FedBuff                 [Nguyen et al., 2022]         (buffer M, partial participation)
  * CA²FL                   [Wang et al., 2024]           (buffer M + cached calibration;
                                                           lazy O(d) h_sum — CA2FLDirect
                                                           keeps the literal re-reduction)
  * ACE direct              (paper Alg. 1)                (all-client cache, mean each arrival)
  * ACE incremental         (paper Alg. a.5)              (u += (g_new − g_prev)/n, O(d))
  * ACED                    (paper Alg. a.1)              (bounded-delay active set τ_algo;
                                                           incremental O(d) sum + expiry
                                                           owner-ring — ACEDDirect keeps
                                                           the literal masked mean)

Every rule is a pure, trace-safe transition

    step(state, arr) -> (state', update (d,), emit (bool []), lr_scale (f32 []))

with `jnp.where`-gated emission instead of `None`/Python-int branching, so a
rule can live inside `jax.lax.scan` / `jax.vmap` / `jax.jit` (the scan engine
in repro/core/scan_engine.py runs whole sweeps on device). Buffer counts are
traced int32; ACED's active-set emission is a traced mask (no device→host
sync per arrival). `on_arrival` remains as the host-side wrapper used by the
event-driven simulators: it materialises `emit` and returns `None` when no
update is emitted, preserving the original protocol.

Every rule is **layout-generic**: payloads and state vectors may be flat (d,)
arrays (host simulators, scan engines — caches are `FlatCache`) or gradient
pytrees (the pjit distributed path — caches are tree caches); cache access
routes through the `cache_row`/`cache_set_row`/`cache_mean` dispatchers in
repro/core/cache.py and everything else is per-leaf `jax.tree.map` (a bare
array is its own single leaf). `distributed.apply_server_rule` is a thin
adapter over this same `step` protocol, so host sim, single-device scan,
sharded scan and pod-scale pjit all run ONE rule implementation.
The server applies ``w ← w − η · lr_scale · update``.

**O(d) hot-path contract**: no production rule's `step` may reduce over the
client axis — every per-event transition is O(d) (+O(n) index bookkeeping).
ACE carries its running mean (Alg. a.5), ACED a running active-set sum with
an expiry owner-ring, CA²FL a running calibration sum; all three fold cache
writes through `cache_set_row_delta` (fused int8 `row_delta` kernel on the
flat layout). The literal O(n·d) re-reductions survive only as the pinned
reference rules `ACEDirect`/`ACEDDirect`/`CA2FLDirect`, which every
incremental rule is differentially tested against (≤1e-5 across dropout,
leave/re-join windows, int8 caches and freeze/thaw — see
tests/test_aggregators.py, tests/test_scan_staleness.py,
tests/test_scan_sharded.py).

Step contract addendum for the incremental rules: across the `step` calls a
state actually receives, `arr.t` must be **strictly increasing** (arbitrary
forward jumps allowed — availability-window thaws), because the ACED
owner-ring keys one client per t_start value. The engines guarantee this
while updates are consumed: ACED emits on every processed arrival, so t
advances by ≥1 per step, and frozen events keep the previous state. The one
exception is the scan engines' post-budget tail (t stalled at T with
emission force-gated off): distinct same-t arrivals there can orphan a ring
slot, so the *final* ACED asum/count returned by a scan run is not
meaningful — only emitted updates are, and those all precede the stall.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.cache import (FlatCache, cache_mean, cache_n, cache_row,
                              cache_rows, cache_set_row, cache_set_row_delta,
                              cache_set_rows_delta, cache_sum,
                              flat_commit_batch, init_flat_cache,
                              init_tree_cache)
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref
from repro.sharding.rules import shard


class Arrival(NamedTuple):
    client: int
    payload: Any                # gradient-like descent direction: (d,) or pytree
    t: int                      # server iteration counter
    staleness: int              # server iterations since client got its model
    #: bool []: False for an arrival the scan tick does not process (frozen,
    #: quarantined or refused) — the rule must then leave the per-client
    #: cache bit-exact (see `Aggregator.step`)
    valid: Any = True


class ArrivalBatch(NamedTuple):
    """K simultaneous arrivals consumed by ONE server step (`step_batch`).

    `clients` (K,) int32 must be pairwise distinct among valid lanes (the
    K-batch engine's Gumbel top-k sampling guarantees it); `payloads` carries
    a leading (K,) lane axis on every leaf; `valid` (K,) bool masks out lanes
    quarantined/rejected by the guard pipeline — an invalid lane must be a
    perfect no-op on the state (its cache row stays bit-exact)."""
    clients: Any                # (K,) int32
    payloads: Any               # per-leaf leading (K,) lane axis
    t: int                      # shared server iteration counter
    staleness: Any              # (K,) int32
    valid: Any                  # (K,) bool


_TRUE = jnp.ones((), jnp.bool_)
_ONE = jnp.ones((), jnp.float32)


def wants_cache_init(agg) -> bool:
    """Rules seeded with one gradient per client before the loop (paper
    Alg. 1 line 1) declare ``cache_init = True`` — the single predicate every
    simulator/engine must agree on. Explicit (not sniffed off `cache_dtype`):
    CA²FL keeps a per-client cache dtype too, but its calibration state h_i⁰
    starts at zero (paper Alg. a.3), not at an init gradient."""
    return bool(getattr(agg, "cache_init", False))


def _acc(a, x):
    """``a + x`` per leaf, accumulating in f32 but preserving the state leaf's
    dtype (the distributed path keeps accumulators in cfg.state_dtype; the
    flat engines' f32 state makes the casts identities)."""
    return jax.tree.map(
        lambda a_, x_: (a_.astype(jnp.float32)
                        + x_.astype(jnp.float32)).astype(a_.dtype), a, x)


def _gate(emit, new, old):
    """Per-leaf ``where(emit, new, old)``."""
    return jax.tree.map(lambda n_, o_: jnp.where(emit, n_, o_), new, old)


def _where_sub(a, x, gate):
    """Per-leaf ``a − x`` where `gate` else ``a`` (f32 accumulation, leaf
    dtype preserved) — the expiry primitive of the running-sum rules."""
    return jax.tree.map(
        lambda a_, x_: jnp.where(gate,
                                 a_.astype(jnp.float32)
                                 - x_.astype(jnp.float32),
                                 a_.astype(jnp.float32)).astype(a_.dtype),
        a, x)


def _masked_batch_sum(payloads, mask):
    """Per-leaf ``Σ_{k : mask[k]} p[k]`` over the leading (K,) lane axis, in
    f32 — the segment-sum reduction folding a K-arrival batch into one
    running vector. `where`-gated rather than multiply-gated: a quarantined
    lane's payload may be NaN/inf, and ``NaN · 0`` would poison the sum."""
    def leaf(p):
        m = mask.reshape((-1,) + (1,) * (p.ndim - 1))
        return jnp.sum(jnp.where(m, p.astype(jnp.float32), 0.0), axis=0)
    return jax.tree.map(leaf, payloads)


def _sum_lanes(tree):
    """Per-leaf f32 sum over the leading (K,) lane axis (unmasked — used on
    `cache_set_rows_delta` deltas, which already zero invalid lanes)."""
    return jax.tree.map(lambda x: jnp.sum(x.astype(jnp.float32), axis=0),
                        tree)


def _fused_flat_commit(flag, cache, vecs) -> bool:
    """Trace-time gate for the fused K-arrival commit (ISSUE 10): the flat
    cache layout only (tree layouts keep the dispatch chain), every carried
    running-sum vector in f32 (the kernel's accumulation dtype — non-f32
    `state_dtype` builds stay on the chain), and the wiring enabled
    (`fused_commit` field / REPRO_NO_FUSED_COMMIT env, resolved at trace
    time by `kernels.backend.fused_commit_enabled`)."""
    return (isinstance(cache, FlatCache)
            and all(v.dtype == jnp.float32 for v in vecs)
            and kernel_ops.fused_commit_enabled(flag))


def _shard_vec(vec, cache):
    """Re-assert the feature sharding on running-sum state in the flat (d,)
    layout (cache_d → model axis; no-op outside a mesh context), so the
    sharded scan carries the new O(d) state without all-gathering. Tree
    layouts keep their leaves' own layouts."""
    if isinstance(cache, FlatCache):
        return jax.tree.map(lambda a: shard(a, ("cache_d",)), vec)
    return vec


# --- layout-generic init_state plumbing ------------------------------------
# `init_state` takes `d` either as the raveled dimension (int — flat layout:
# host simulators, scan engines) or as a gradient pytree *template* (tree
# layout: the pjit train step and the real-model scanned path). The step
# implementations are already layout-generic; these helpers make the initial
# state so too, byte-for-byte matching what afl_state_bytes accounts per
# layout (pinned by tests/test_distributed.py and benchmarks/table_a3).

def _is_template(d) -> bool:
    import numpy as _np
    return not isinstance(d, (int, _np.integer))


def _init_cache(n, d, dtype, init_grads):
    if _is_template(d):
        return init_tree_cache(n, d, dtype, init_grads)
    return init_flat_cache(n, int(d), dtype, init_grads)


def _zeros_vec(d, dtype="float32"):
    dt = jnp.dtype(dtype)
    # `d` is trace-time static by contract: a Python int (flat layout) or a
    # params template pytree (tree layout) — never a tracer, so branching on
    # its type and int() on it are safe here.
    if _is_template(d):  # tracecheck: ignore[TRC001]
        return jax.tree.map(lambda g: jnp.zeros(tuple(jnp.shape(g)), dt), d)
    return jnp.zeros((int(d),), dt)  # tracecheck: ignore[TRC001]


def _astate(vec, dtype):
    """Cast a running-sum vector to the rule's state dtype (identity for the
    flat engines' f32 default)."""
    dt = jnp.dtype(dtype)
    return jax.tree.map(lambda a: a.astype(dt), vec)


class Aggregator:
    """Base: subclasses define init_state / step (pure, trace-safe)."""
    name = "base"
    #: server iterations advance only when an update is emitted
    #: whether every buffer flush is certain to emit: a rule whose emission
    #: is data-dependent and genuinely refusable sets this False so the scan
    #: engines budget extra events (see scan_engine.default_n_events)
    guaranteed_emit = True

    def init_state(self, n: int, d, init_grads=None) -> Any:
        """Initial server state. `d` is layout-generic: the raveled dimension
        (int — flat layout; caches are `FlatCache`, running vectors (d,)
        arrays) or a gradient pytree *template* (tree layout; caches are
        stacked tree caches, running vectors grads-like pytrees in
        `state_dtype`). `init_grads` matches: an (n, d) array or a grads-like
        pytree with a leading (n,) client axis."""
        raise NotImplementedError

    def step(self, state, arr: Arrival):
        """Pure transition: -> (state, update (d,), emit (bool), lr_scale).

        Must be trace-safe: no Python branching on traced values, no
        device→host syncs. `update` is always a (d,) array; when `emit`
        is False its value is ignored by the caller.

        The per-arrival scan tick relies on `arr.valid` for the per-client
        cache: it is the cache's only gate. A rule must write the cache only
        through validity-masked row writes (`cache_set_row_delta` /
        `cache_set_row` with `valid`), which put the stored row and scale
        back bit for bit, NaN payloads included, when `arr.valid` is False.
        The engine holds every other leaf of `state` and gates `emit` off on
        such a tick itself (`scan_staleness._select_state`)."""
        raise NotImplementedError

    def on_arrival(self, state, arr: Arrival):
        """Host wrapper: -> (state, update (d,) or None, lr_scale float)."""
        state, update, emit, lr_scale = self.step(state, arr)
        if not bool(emit):
            return state, None, float(lr_scale)
        return state, update, float(lr_scale)

    def step_batch(self, state, batch: ArrivalBatch):
        """K-arrival transition: -> (state, update, emit, lr_scale) — one
        aggregation and one emission decision for the whole batch. Same
        trace-safety contract as `step`; invalid lanes must be perfect
        no-ops. `step` with a singleton batch is the K=1 sanity anchor; the
        scan tick commits through `step` at K = 1 and through `step_batch`
        above it.

        The scan tick at K > 1 relies on this for the per-client cache:
        lane validity is its only gate, as `arr.valid` is `step`'s. The
        cache must be written only through lane-masked row writes that put
        each invalid lane's stored row and scale back bit for bit, NaN
        payloads included, so a batch with zero valid lanes leaves it
        bit-identical. The engine holds every other leaf of `state` and
        gates `emit` off on such a tick itself
        (`scan_staleness._select_state`): ACED's expiry sweep, for one,
        still moves its running sums there."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support K-batched arrivals")

    def on_batch(self, state, batch: ArrivalBatch):
        """Host wrapper over `step_batch` (mirror of `on_arrival`)."""
        state, update, emit, lr_scale = self.step_batch(state, batch)
        if not bool(emit):
            return state, None, float(lr_scale)
        return state, update, float(lr_scale)

    def resync(self, state):
        """Exact self-heal: re-derive every incrementally-maintained running
        aggregate from the authoritative per-client cache. O(n·d) — never on
        the per-event hot path; the engines invoke it every `resync_every`
        emitted steps (`jax.lax.cond` in the scan, so a skipped step costs
        nothing unvmapped), bounding float drift and recovering from any
        corrupted running sum. Must be trace-safe and preserve the state
        pytree's structure/dtypes. Rules without running sums are a no-op."""
        return state

    def nbytes(self, state) -> int:
        import numpy as _np
        return sum(_np.asarray(a).nbytes for a in jax.tree.leaves(state))


# ---------------------------------------------------------------------------

@dataclasses.dataclass
class VanillaASGD(Aggregator):
    name = "asgd"

    def init_state(self, n, d, init_grads=None):
        return ()

    def step(self, state, arr):
        return state, arr.payload, _TRUE, _ONE

    def step_batch(self, state, batch):
        # FedAsync's burst rule: average the simultaneously received
        # contributions into one server step.
        nv = jnp.sum(batch.valid.astype(jnp.float32))
        inv = jnp.where(nv > 0, 1.0 / jnp.maximum(nv, 1.0), 0.0)
        update = jax.tree.map(lambda s_: s_ * inv,
                              _masked_batch_sum(batch.payloads, batch.valid))
        return state, update, jnp.any(batch.valid), _ONE


@dataclasses.dataclass
class DelayAdaptiveASGD(Aggregator):
    """η_t = η if τ ≤ τ_C else η·τ_C/τ (down-weight stale gradients)."""
    tau_c: float = 10.0
    name = "delay_asgd"

    def init_state(self, n, d, init_grads=None):
        return ()

    def step(self, state, arr):
        tau = jnp.maximum(jnp.asarray(arr.staleness, jnp.float32), 0.0)
        scale = jnp.where(tau <= self.tau_c, 1.0,
                          self.tau_c / jnp.maximum(tau, 1.0))
        return state, arr.payload, _TRUE, scale.astype(jnp.float32)

    def step_batch(self, state, batch):
        # Per-lane staleness discounts fold INTO the averaged update (the
        # scalar lr_scale can't carry K different weights), so the K-batch
        # rule returns lr_scale = 1 with s(τ_k)·g_k already applied.
        tau = jnp.maximum(jnp.asarray(batch.staleness, jnp.float32), 0.0)
        scale = jnp.where(tau <= self.tau_c, 1.0,
                          self.tau_c / jnp.maximum(tau, 1.0))
        scaled = jax.tree.map(
            lambda p: p.astype(jnp.float32)
            * scale.reshape((-1,) + (1,) * (p.ndim - 1)),
            batch.payloads)
        nv = jnp.sum(batch.valid.astype(jnp.float32))
        inv = jnp.where(nv > 0, 1.0 / jnp.maximum(nv, 1.0), 0.0)
        update = jax.tree.map(lambda s_: s_ * inv,
                              _masked_batch_sum(scaled, batch.valid))
        return state, update, jnp.any(batch.valid), _ONE


@dataclasses.dataclass
class FedBuff(Aggregator):
    buffer_size: int = 10
    state_dtype: str = "float32"
    name = "fedbuff"

    def init_state(self, n, d, init_grads=None):
        return {"accum": _zeros_vec(d, self.state_dtype),
                "count": jnp.zeros((), jnp.int32)}

    def step(self, state, arr):
        accum = _acc(state["accum"], arr.payload)
        count = state["count"] + 1
        emit = count >= self.buffer_size
        # emit-gated division: buffered (non-flushing) arrivals do no update
        # arithmetic — the scalar reciprocal is zeroed under the gate, so a
        # non-emitting step's "update" is a multiply-by-0, not an O(d) divide
        inv = jnp.where(emit, 1.0 / count.astype(jnp.float32), 0.0)
        update = jax.tree.map(lambda a: a.astype(jnp.float32) * inv, accum)
        new_state = {"accum": _gate(emit, jax.tree.map(jnp.zeros_like, accum),
                                    accum),
                     "count": jnp.where(emit, 0, count)}
        return new_state, update, emit, _ONE

    def step_batch(self, state, batch):
        # The buffer may overshoot `buffer_size` when a batch straddles the
        # flush boundary; the division by the achieved count keeps the flush
        # an exact mean of everything buffered (FedBuff with K concurrent
        # contributions per server step).
        accum = _acc(state["accum"],
                     _masked_batch_sum(batch.payloads, batch.valid))
        count = state["count"] + jnp.sum(batch.valid.astype(jnp.int32))
        emit = count >= self.buffer_size
        inv = jnp.where(emit, 1.0 / jnp.maximum(count, 1).astype(jnp.float32),
                        0.0)
        update = jax.tree.map(lambda a: a.astype(jnp.float32) * inv, accum)
        new_state = {"accum": _gate(emit, jax.tree.map(jnp.zeros_like, accum),
                                    accum),
                     "count": jnp.where(emit, 0, count)}
        return new_state, update, emit, _ONE


@dataclasses.dataclass
class CA2FL(Aggregator):
    """Cache-aided calibration: v = h̄ + Σ_{i∈S}(Δ_i − h_i)/m (paper Alg. a.3)
    with a **lazy calibration mean** — O(d) per arrival.

    The per-client calibration cache h is a real gradient cache (FlatCache /
    tree cache) so the paper's 8-bit compression applies to it exactly like
    ACE's (App. F.3.3); `cache_init` stays False — h_i⁰ = 0 per Alg. a.3.

    The running sum ``h_sum = Σ_i dq(h_i)`` is maintained through the
    `cache_set_row_delta` swap (``h_sum += dq(new) − dq(old)``, exact under
    int8), and ``h̄ = h_sum/n`` folds into the emit-gated refresh only — no
    arrival re-reduces the (n, d) cache the way `CA2FLDirect` does."""
    buffer_size: int = 10
    cache_dtype: str = "float32"
    state_dtype: str = "float32"
    #: fused K-arrival commit (ISSUE 10): None resolves via
    #: REPRO_NO_FUSED_COMMIT (default on); False pins the dispatch chain
    fused_commit: Optional[bool] = None
    name = "ca2fl"

    def init_state(self, n, d, init_grads=None):
        h = _init_cache(n, d, self.cache_dtype, init_grads)
        mean = cache_mean(h)
        h_bar = _astate(mean, self.state_dtype)
        h_sum = _shard_vec(
            _astate(jax.tree.map(lambda m: m * n, mean), self.state_dtype), h)
        return {"h": h, "h_bar": h_bar, "h_sum": h_sum,
                "accum": _zeros_vec(d, self.state_dtype),
                "count": jnp.zeros((), jnp.int32)}

    def step(self, state, arr):
        j = jnp.asarray(arr.client, jnp.int32)
        h, delta, old = cache_set_row_delta(state["h"], j, arr.payload,
                                            arr.valid)
        accum = _acc(state["accum"],
                     jax.tree.map(lambda g, o: g.astype(jnp.float32) - o,
                                  arr.payload, old))
        h_sum = _shard_vec(_acc(state["h_sum"], delta), h)
        count = state["count"] + 1
        emit = count >= self.buffer_size
        # emit-gated O(d) math: scalar reciprocal zeroed under the gate, so
        # buffered arrivals do no division sweep between flushes
        inv = jnp.where(emit, 1.0 / count.astype(jnp.float32), 0.0)
        gate = emit.astype(jnp.float32)
        update = jax.tree.map(
            lambda hb, a: hb.astype(jnp.float32) * gate
            + a.astype(jnp.float32) * inv,
            state["h_bar"], accum)
        inv_n = 1.0 / cache_n(h)
        h_bar = jax.tree.map(
            lambda hb, hs: jnp.where(emit, hs.astype(jnp.float32) * inv_n,
                                     hb.astype(jnp.float32)).astype(hb.dtype),
            state["h_bar"], h_sum)
        new_state = {
            "h": h, "h_bar": h_bar, "h_sum": h_sum,
            "accum": _gate(emit, jax.tree.map(jnp.zeros_like, accum), accum),
            "count": jnp.where(emit, 0, count)}
        return new_state, update, emit, _ONE

    def step_batch(self, state, batch):
        js = jnp.asarray(batch.clients, jnp.int32)
        valid = batch.valid
        vecs = (state["accum"], state["h_sum"], state["h_bar"])
        if _fused_flat_commit(self.fused_commit, state["h"], vecs):
            # fused commit, basis [accum, h_sum, h_bar, S_Δ, S_A, S_B, S_G]
            # with lane_a = lane_g = valid (S_G − S_A = Σ_valid(g − old)):
            #   accum' = (1−g)·(accum + S_G − S_A)
            #   h_sum' = h_sum + S_Δ
            #   h_bar' = g·inv_n·h_sum' + (1−g)·h_bar
            #   update = g·h_bar + inv·(accum + S_G − S_A)
            count = state["count"] + jnp.sum(valid.astype(jnp.int32))
            emit = count >= self.buffer_size
            g = emit.astype(jnp.float32)
            inv = jnp.where(emit,
                            1.0 / jnp.maximum(count, 1).astype(jnp.float32),
                            0.0)
            inv_n = 1.0 / cache_n(state["h"])
            one, zero = jnp.float32(1.0), jnp.float32(0.0)
            keep = 1.0 - g
            coef = jnp.stack([
                jnp.stack([keep, zero, zero, zero, -keep, zero, keep]),
                jnp.stack([zero, one, zero, one, zero, zero, zero]),
                jnp.stack([zero, g * inv_n, keep, g * inv_n,
                           zero, zero, zero])])
            upd_w = jnp.stack([inv, zero, g, zero, -inv, zero, inv])
            vf = valid.astype(jnp.float32)
            h, out, update = flat_commit_batch(
                state["h"], js, batch.payloads, valid, jnp.stack(vecs),
                coef, upd_w, lane_a=vf, lane_g=vf)
            new_state = {"h": h, "h_bar": out[2], "h_sum": out[1],
                         "accum": out[0],
                         "count": jnp.where(emit, 0, count)}
            return new_state, update, emit, _ONE
        h, delta, old = cache_set_rows_delta(state["h"], js, batch.payloads,
                                             valid)
        diff = jax.tree.map(lambda g, o: g.astype(jnp.float32) - o,
                            batch.payloads, old)
        accum = _acc(state["accum"], _masked_batch_sum(diff, valid))
        h_sum = _shard_vec(_acc(state["h_sum"], _sum_lanes(delta)), h)
        count = state["count"] + jnp.sum(valid.astype(jnp.int32))
        emit = count >= self.buffer_size
        inv = jnp.where(emit, 1.0 / jnp.maximum(count, 1).astype(jnp.float32),
                        0.0)
        gate = emit.astype(jnp.float32)
        update = jax.tree.map(
            lambda hb, a: hb.astype(jnp.float32) * gate
            + a.astype(jnp.float32) * inv,
            state["h_bar"], accum)
        inv_n = 1.0 / cache_n(h)
        h_bar = jax.tree.map(
            lambda hb, hs: jnp.where(emit, hs.astype(jnp.float32) * inv_n,
                                     hb.astype(jnp.float32)).astype(hb.dtype),
            state["h_bar"], h_sum)
        new_state = {
            "h": h, "h_bar": h_bar, "h_sum": h_sum,
            "accum": _gate(emit, jax.tree.map(jnp.zeros_like, accum), accum),
            "count": jnp.where(emit, 0, count)}
        return new_state, update, emit, _ONE

    def resync(self, state):
        h = state["h"]
        h_sum = _shard_vec(_astate(cache_sum(h), self.state_dtype), h)
        return {**state, "h_sum": h_sum}


@dataclasses.dataclass
class CA2FLDirect(Aggregator):
    """Paper Alg. a.3, literal: re-reduces ``cache_mean(h)`` over the whole
    (n, d) calibration cache on every arrival — the pinned O(n·d) reference
    the lazy `CA2FL` is differentially tested against (≤1e-5)."""
    buffer_size: int = 10
    cache_dtype: str = "float32"
    state_dtype: str = "float32"
    name = "ca2fl_direct"

    def init_state(self, n, d, init_grads=None):
        h = _init_cache(n, d, self.cache_dtype, init_grads)
        return {"h": h, "h_bar": _astate(cache_mean(h), self.state_dtype),
                "accum": _zeros_vec(d, self.state_dtype),
                "count": jnp.zeros((), jnp.int32)}

    def step(self, state, arr):
        j = jnp.asarray(arr.client, jnp.int32)
        old = cache_row(state["h"], j)
        accum = _acc(state["accum"],
                     jax.tree.map(lambda g, o: g.astype(jnp.float32) - o,
                                  arr.payload, old))
        h = cache_set_row(state["h"], j, arr.payload, arr.valid)
        count = state["count"] + 1
        emit = count >= self.buffer_size
        cf = count.astype(jnp.float32)
        update = jax.tree.map(
            lambda hb, a: hb.astype(jnp.float32) + a.astype(jnp.float32) / cf,
            state["h_bar"], accum)
        h_bar = jax.tree.map(
            lambda hb, hm: jnp.where(emit, hm, hb.astype(jnp.float32)
                                     ).astype(hb.dtype),
            state["h_bar"], cache_mean(h))
        new_state = {
            "h": h, "h_bar": h_bar,
            "accum": _gate(emit, jax.tree.map(jnp.zeros_like, accum), accum),
            "count": jnp.where(emit, 0, count)}
        return new_state, update, emit, _ONE


@dataclasses.dataclass
class ACEDirect(Aggregator):
    """Paper Algorithm 1: cache row j ← g, update = mean over all n rows."""
    cache_dtype: str = "float32"
    name = "ace_direct"
    cache_init = True

    def init_state(self, n, d, init_grads=None):
        return {"cache": _init_cache(n, d, self.cache_dtype, init_grads)}

    def step(self, state, arr):
        cache = cache_set_row(state["cache"], arr.client, arr.payload,
                              arr.valid)
        return {"cache": cache}, cache_mean(cache), _TRUE, _ONE


@dataclasses.dataclass
class ACEIncremental(Aggregator):
    """Paper Algorithm a.5: u ← u + (g − dq(C_j))/n — O(d) per arrival.

    Exact under int8 cache: the subtracted value is the dequantized row that
    was previously added, so ``u == mean_i dq(C_i)`` is invariant. The flat
    int8 path routes through the fused Pallas `cache_row_update` kernel (via
    the backend-aware dispatch in repro/kernels/ops.py); tree caches take the
    generic dequantize-subtract path."""
    cache_dtype: str = "float32"
    state_dtype: str = "float32"
    #: fused K-arrival commit (ISSUE 10): None resolves via
    #: REPRO_NO_FUSED_COMMIT (default on); False pins the dispatch chain
    fused_commit: Optional[bool] = None
    name = "ace"
    cache_init = True

    def init_state(self, n, d, init_grads=None):
        cache = _init_cache(n, d, self.cache_dtype, init_grads)
        return {"cache": cache,
                "u": _astate(cache_mean(cache), self.state_dtype)}

    def step(self, state, arr):
        cache, u = state["cache"], state["u"]
        j = jnp.asarray(arr.client, jnp.int32)
        if isinstance(cache, FlatCache) and cache.data.dtype == jnp.int8:
            # the stored row, (d // 128, 128) or (d,), as the kernel's (d,)
            c_row = jax.lax.dynamic_index_in_dim(cache.data, j, keepdims=False)
            old_scale = jax.lax.dynamic_index_in_dim(cache.scale, j,
                                                     keepdims=False)
            new_scale = kernel_ref.row_scale(arr.payload)
            u, q_row = kernel_ops.cache_row_update(
                u, arr.payload, c_row.reshape(-1), old_scale, new_scale,
                1.0 / cache.n)
            cache = FlatCache(
                jax.lax.dynamic_update_index_in_dim(
                    cache.data,
                    jnp.where(arr.valid, q_row.reshape(c_row.shape), c_row),
                    j, 0),
                jax.lax.dynamic_update_index_in_dim(
                    cache.scale, jnp.where(arr.valid,
                                           new_scale.astype(jnp.float32),
                                           old_scale), j, 0))
        else:
            n = cache_n(cache)
            cache, delta, _old = cache_set_row_delta(cache, j, arr.payload,
                                                     arr.valid)
            u = jax.tree.map(
                lambda u_, d_: (u_.astype(jnp.float32)
                                + d_ / n).astype(u_.dtype),
                u, delta)
        return {"cache": cache, "u": u}, u, _TRUE, _ONE

    def step_batch(self, state, batch):
        # Batched Alg. a.5: u += Σ_k (dq(new_k) − dq(old_k))/n in one O(K·d)
        # pass — the fused commit kernel on the flat layout (basis
        # [u, S_Δ, ...]: u' = u + S_Δ/n), the generic dequantize-subtract
        # chain elsewhere. The fused flat-int8 `cache_row_update` kernel is
        # single-row and stays on the K=1 `step`.
        js = jnp.asarray(batch.clients, jnp.int32)
        cache = state["cache"]
        n = cache_n(cache)
        if _fused_flat_commit(self.fused_commit, cache, (state["u"],)):
            coef = jnp.asarray([[1.0, 1.0 / n, 0.0, 0.0, 0.0]], jnp.float32)
            cache, vecs, u = flat_commit_batch(
                cache, js, batch.payloads, batch.valid,
                state["u"][None], coef, coef[0])
            return {"cache": cache, "u": u}, u, jnp.any(batch.valid), _ONE
        cache, delta, _old = cache_set_rows_delta(cache, js, batch.payloads,
                                                  batch.valid)
        u = jax.tree.map(
            lambda u_, d_: (u_.astype(jnp.float32) + d_ / n).astype(u_.dtype),
            state["u"], _sum_lanes(delta))
        return {"cache": cache, "u": u}, u, jnp.any(batch.valid), _ONE

    def resync(self, state):
        u = _astate(cache_mean(state["cache"]), self.state_dtype)
        return {**state, "u": u}


@dataclasses.dataclass
class ACED(Aggregator):
    """Paper Algorithm a.1 with an **incremental active-set sum** — O(d) per
    event (the ACE-incremental pattern of Alg. a.5 extended to the
    bounded-delay active set A(t) = {i : t − t_start_i ≤ τ_algo}).

    State beyond the cache:
      * ``asum (d,)`` / ``count`` — running Σ_{i∈A} dq(C_i) and |A|. On
        arrival the client's previous dequantized row is swapped out and the
        new one in (exact under int8 — `cache_set_row_delta` subtracts
        exactly the value previously added).
      * ``ring (τ_algo+2,)`` int32 owner-ring keyed on ``t_start mod P`` —
        active t_start values live in [t−τ_algo, t+1], exactly P = τ_algo+2
        residues, and each emitted step hands a new t_start to one client,
        so expiries amortize to ≤1 per event: the step at time t retires the
        slot whose value fell to t−τ_algo−1. A re-arrival before expiry
        *disowns* its old slot; an availability-window thaw jump retires
        min(Δt, P) slots in one sweep (every live owner is expired once
        Δt ≥ P, and the P visited residues cover the whole ring).
        With K-batched arrivals (``max_cohort > 1``) a slot owns a whole
        *cohort* — up to max_cohort clients sharing one t_start — so the
        ring widens to (P, max_cohort) and every expiry sweep retires the
        slot's full cohort at once (the K=1 "≤1 expiring owner per slot"
        assumption would silently drop all but one of them).
      * ``init_sum``/``init_count``/``init_mask`` — the init batch is the one
        case the ring cannot carry (all n clients share t_start = 1): its
        cohort sum is maintained incrementally as members re-arrive and
        subtracted in a single where-gated O(d) correction when t first
        reaches τ_algo+2 (also when a freeze jump leaps straight past it).
      * ``t_prev`` — last processed arrival time, bounding the expiry sweep.

    Emission is a traced mask (`emit = count > 0`) — no per-arrival host
    sync, and no arrival ever reduces over the (n, d) cache (that literal
    form survives as `ACEDDirect`, the pinned differential reference)."""
    tau_algo: int = 10
    cache_dtype: str = "float32"
    state_dtype: str = "float32"
    #: owner-ring cohort width: max distinct clients sharing one t_start
    #: value (= the engine's K). 1 keeps the legacy (P,) ring — and its
    #: checkpoints/bit-identity — intact; > 1 widens it to (P, max_cohort)
    #: and routes K=1 steps through the batched transition too.
    max_cohort: int = 1
    #: fused K-arrival commit (ISSUE 10): None resolves via
    #: REPRO_NO_FUSED_COMMIT (default on); False pins the dispatch chain
    fused_commit: Optional[bool] = None
    name = "aced"
    cache_init = True
    #: emit = count > 0 looks data-dependent, but emission is in fact
    #: guaranteed: the arriving client re-enters the active set before the
    #: count — t_start[j] = t+1 gives t − t_start[j] = −1 ≤ tau_algo — so
    #: every processed arrival flushes (guaranteed_emit stays True; the scan
    #: engines' _to_result raises if an event budget ever starves before T,
    #: pinned by the fig3 50%-dropout regression test)

    @property
    def ring_size(self) -> int:
        return self.tau_algo + 2

    def init_state(self, n, d, init_grads=None):
        cache = _init_cache(n, d, self.cache_dtype, init_grads)
        ring_shape = ((self.ring_size,) if self.max_cohort == 1
                      else (self.ring_size, self.max_cohort))
        # one-time O(n·d) seed of the running active-set sum
        asum = _shard_vec(_astate(cache_sum(cache), self.state_dtype), cache)
        return {"cache": cache,
                "t_start": jnp.ones((n,), jnp.int32),
                "ring": jnp.full(ring_shape, -1, jnp.int32),
                "asum": asum,
                "count": jnp.asarray(n, jnp.int32),
                "t_prev": jnp.zeros((), jnp.int32),
                "init_sum": asum,
                "init_count": jnp.asarray(n, jnp.int32),
                "init_mask": jnp.ones((n,), jnp.bool_)}

    def step(self, state, arr):
        if self.max_cohort > 1:
            # the (P, max_cohort) ring speaks cohorts — route single
            # arrivals through the batched transition as a 1-lane batch
            return self.step_batch(state, ArrivalBatch(
                clients=jnp.asarray(arr.client, jnp.int32)[None],
                payloads=jax.tree.map(lambda g: g[None], arr.payload),
                t=arr.t,
                staleness=jnp.asarray(arr.staleness, jnp.int32)[None],
                valid=jnp.asarray(arr.valid, jnp.bool_)[None]))
        j = jnp.asarray(arr.client, jnp.int32)
        t = jnp.asarray(arr.t, jnp.int32)
        tau, P = self.tau_algo, self.ring_size
        cache, t_start = state["cache"], state["t_start"]
        ring, asum, count = state["ring"], state["asum"], state["count"]

        # 1. expiry sweep bookkeeping: the slot whose t_start fell to t−τ−1
        # (≤1 per emitted step — hoisted; its O(d) subtraction is fused into
        # the single asum expression below). Thaw jumps retire up to Δt−1
        # *older* slots through the fori_loop, which ordinary steps never
        # enter (Δt == 1 → zero iterations).
        dt = jnp.clip(t - state["t_prev"], 0, P)
        s0 = jnp.mod(t - tau - 1, P)
        k0 = jax.lax.dynamic_index_in_dim(ring, s0, keepdims=False)
        dead = jnp.logical_and(dt >= 1, jnp.logical_and(
            k0 >= 0, t_start[jnp.maximum(k0, 0)] <= t - tau - 1))
        dead_row = cache_row(cache, jnp.maximum(k0, 0))
        count = count - dead.astype(jnp.int32)
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, jnp.where(dead, -1, k0), s0, 0)

        def expire(i, val):
            asum, count, ring = val
            s = jnp.mod(t - tau - 1 - i, P)
            k = jax.lax.dynamic_index_in_dim(ring, s, keepdims=False)
            ks = jnp.maximum(k, 0)
            gone = jnp.logical_and(k >= 0, t_start[ks] <= t - tau - 1)
            asum = _where_sub(asum, cache_row(cache, ks), gone)
            count = count - gone.astype(jnp.int32)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.where(gone, -1, k), s, 0)
            return asum, count, ring

        asum, count, ring = jax.lax.fori_loop(1, dt, expire,
                                              (asum, count, ring))

        # 2. init-batch simultaneous-expiry gate at t = τ_algo+2 (one-time;
        # covers jumps that leap past it) — scalar bookkeeping here, the
        # O(d) correction rides the fused expression below
        init_sum, init_count = state["init_sum"], state["init_count"]
        init_mask = state["init_mask"]
        fire = jnp.logical_and(init_count > 0, t >= tau + 2)
        count = count - jnp.where(fire, init_count, 0)
        init_count = jnp.where(fire, 0, init_count)
        init_mask = jnp.logical_and(init_mask, jnp.logical_not(fire))

        # 3. arrival: swap row j in. One fused O(d) pass updates the active
        # sum with the slot-0 expiry, the init correction and the swap (0/1
        # scalar multiplies — bit-identical to the where-gated sequence):
        # an active client contributes its delta, a returning one its whole
        # new row.
        old_ts = t_start[j]
        was_active = old_ts >= t - tau
        was_init = init_mask[j]
        cache, delta, old = cache_set_row_delta(cache, j, arr.payload,
                                                arr.valid)
        g_dead = dead.astype(jnp.float32)
        g_fire = fire.astype(jnp.float32)
        g_ret = 1.0 - was_active.astype(jnp.float32)   # returning client
        asum = _shard_vec(jax.tree.map(
            lambda a, r_, i_, d_, o: (a.astype(jnp.float32) - g_dead * r_
                                      - g_fire * i_.astype(jnp.float32)
                                      + d_ + g_ret * o).astype(a.dtype),
            asum, dead_row, init_sum, delta, old), cache)
        count = count + 1 - was_active.astype(jnp.int32)
        g_wi = was_init.astype(jnp.float32)
        init_sum = _shard_vec(jax.tree.map(
            lambda i_, o: ((1.0 - g_fire) * i_.astype(jnp.float32)
                           - g_wi * o).astype(i_.dtype),
            init_sum, old), cache)
        init_count = init_count - was_init.astype(jnp.int32)
        init_mask = jax.lax.dynamic_update_index_in_dim(
            init_mask, jnp.zeros((), jnp.bool_), j, 0)

        # 4. ring ownership: disown j's previous slot (re-arrival before
        # expiry must not leave a stale owner), then own (t+1) mod P.
        # Claiming assumes no *other* live client holds t_start == t+1 —
        # the strictly-increasing-t step contract (module docstring); a
        # same-t distinct arrival only occurs in the engines' discarded
        # post-budget tail.
        s_old = jnp.mod(old_ts, P)
        cur = jax.lax.dynamic_index_in_dim(ring, s_old, keepdims=False)
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, jnp.where(cur == j, -1, cur), s_old, 0)
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, j, jnp.mod(t + 1, P), 0)
        t_start = jax.lax.dynamic_update_index_in_dim(t_start, t + 1, j, 0)

        inv = 1.0 / jnp.maximum(count, 1).astype(jnp.float32)
        update = jax.tree.map(lambda a: a.astype(jnp.float32) * inv, asum)
        new_state = {"cache": cache, "t_start": t_start, "ring": ring,
                     "asum": asum, "count": count, "t_prev": t,
                     "init_sum": init_sum, "init_count": init_count,
                     "init_mask": init_mask}
        return new_state, update, count > 0, _ONE

    def step_batch(self, state, batch):
        """K simultaneous arrivals sharing one t (hence one t_start = t+1
        cohort). Requires ``max_cohort ≥ K``: the (P, max_cohort) ring row
        at ``(t+1) mod P`` owns the whole cohort, and every expiry sweep
        retires a slot's *entire* cohort — fixing the K=1 ring's "≤1
        expiring owner per slot" assumption, which would silently keep
        all-but-one expired member in asum/count."""
        js = jnp.asarray(batch.clients, jnp.int32)
        K = js.shape[0]
        if self.max_cohort < max(K, 2):
            raise ValueError(
                f"ACED(max_cohort={self.max_cohort}) cannot own a "
                f"{K}-arrival cohort — construct with max_cohort >= "
                "max(K, 2) (the cohort ring is (P, max_cohort))")
        t = jnp.asarray(batch.t, jnp.int32)
        valid = batch.valid
        tau, P = self.tau_algo, self.ring_size
        C = self.max_cohort
        cache, t_start = state["cache"], state["t_start"]
        ring, asum, count = state["ring"], state["asum"], state["count"]

        # 1. expiry sweep: visit the min(Δt, P) slots whose t_start fell to
        # ≤ t−τ−1 and retire each slot's whole surviving cohort (reads are
        # against the pre-arrival cache; the fori_loop collapses to one
        # iteration on an ordinary Δt == 1 step).
        dt = jnp.clip(t - state["t_prev"], 0, P)

        def expire(i, val):
            asum, count, ring = val
            s = jnp.mod(t - tau - 1 - i, P)
            owners = jax.lax.dynamic_index_in_dim(ring, s, keepdims=False)
            ow = jnp.maximum(owners, 0)
            gone = jnp.logical_and(owners >= 0, t_start[ow] <= t - tau - 1)
            dead_sum = _masked_batch_sum(cache_rows(cache, ow), gone)
            asum = jax.tree.map(
                lambda a, d_: (a.astype(jnp.float32) - d_).astype(a.dtype),
                asum, dead_sum)
            count = count - jnp.sum(gone.astype(jnp.int32))
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.where(gone, -1, owners), s, 0)
            return asum, count, ring

        asum, count, ring = jax.lax.fori_loop(0, dt, expire,
                                              (asum, count, ring))

        # 2. init-batch one-shot fire (identical to the K=1 rule)
        init_sum, init_count = state["init_sum"], state["init_count"]
        init_mask = state["init_mask"]
        fire = jnp.logical_and(init_count > 0, t >= tau + 2)
        count = count - jnp.where(fire, init_count, 0)
        init_count = jnp.where(fire, 0, init_count)
        init_mask = jnp.logical_and(init_mask, jnp.logical_not(fire))
        g_fire = fire.astype(jnp.float32)

        # 3. cohort swap-in: one batched cache write; returning (valid,
        # not-active) lanes contribute their whole old rows, active lanes
        # their deltas. Invalid lanes are bit-exact no-ops on the cache and
        # zero in every sum.
        old_ts = t_start[js]
        was_active = old_ts >= t - tau
        was_init = jnp.logical_and(init_mask[js], valid)
        ret = jnp.logical_and(valid, jnp.logical_not(was_active))
        if _fused_flat_commit(self.fused_commit, cache, (asum, init_sum)):
            # fused commit (ISSUE 10), basis [asum, init_sum, S_Δ, S_A,
            # S_B, S_G] with lane_a = ret (a returning lane adds its whole
            # old row back), lane_b = was_init (an init-cohort member's old
            # row leaves init_sum):
            #   asum'     = asum − g_fire·init_sum + S_Δ + S_A
            #   init_sum' = (1−g_fire)·init_sum − S_B
            #   update    = inv·(that same asum' row)
            count = count + jnp.sum(ret.astype(jnp.int32))
            inv = 1.0 / jnp.maximum(count, 1).astype(jnp.float32)
            one, zero = jnp.float32(1.0), jnp.float32(0.0)
            r_asum = jnp.stack([one, -g_fire, one, one, zero, zero])
            coef = jnp.stack([
                r_asum,
                jnp.stack([zero, 1.0 - g_fire, zero, zero, -one, zero])])
            cache, out, update = flat_commit_batch(
                cache, js, batch.payloads, valid,
                jnp.stack((asum, init_sum)), coef, inv * r_asum,
                lane_a=ret.astype(jnp.float32),
                lane_b=was_init.astype(jnp.float32))
            asum, init_sum = out[0], out[1]
        else:
            cache, delta, old = cache_set_rows_delta(cache, js,
                                                     batch.payloads, valid)
            asum = _shard_vec(jax.tree.map(
                lambda a, i_, d_, r_: (a.astype(jnp.float32)
                                       - g_fire * i_.astype(jnp.float32)
                                       + d_ + r_).astype(a.dtype),
                asum, init_sum, _sum_lanes(delta),
                _masked_batch_sum(old, ret)), cache)
            count = count + jnp.sum(ret.astype(jnp.int32))
            init_sum = _shard_vec(jax.tree.map(
                lambda i_, w_: ((1.0 - g_fire) * i_.astype(jnp.float32) - w_
                                ).astype(i_.dtype),
                init_sum, _masked_batch_sum(old, was_init)), cache)
            inv = 1.0 / jnp.maximum(count, 1).astype(jnp.float32)
            update = jax.tree.map(lambda a: a.astype(jnp.float32) * inv, asum)
        init_count = init_count - jnp.sum(was_init.astype(jnp.int32))
        # top-k sampling guarantees pairwise-distinct js, so scatter is safe
        init_mask = init_mask.at[js].set(
            jnp.logical_and(init_mask[js], jnp.logical_not(valid)))
        t_start = t_start.at[js].set(jnp.where(valid, t + 1, old_ts))

        # 4. ring ownership: disown every valid lane's previous slot entry
        # anywhere in the ring, then claim slot (t+1) mod P with the cohort.
        # That slot aliases (t−τ−1) mod P, which sweep iteration i=0 just
        # emptied — live t_start values span [t−τ, t], a width-(τ+1) window
        # that cannot contain t+1 mod P — so the row overwrite is safe.
        hit = jnp.any(jnp.logical_and(ring[..., None] == js, valid), axis=-1)
        ring = jnp.where(hit, -1, ring)
        cohort = jnp.full((C,), -1, jnp.int32).at[:K].set(
            jnp.where(valid, js, -1))
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, cohort, jnp.mod(t + 1, P), 0)

        new_state = {"cache": cache, "t_start": t_start, "ring": ring,
                     "asum": asum, "count": count, "t_prev": t,
                     "init_sum": init_sum, "init_count": init_count,
                     "init_mask": init_mask}
        return new_state, update, count > 0, _ONE

    def resync(self, state):
        """Recompute asum/count (and the init-cohort correction state) from
        the cache: the active set after the step at t_prev is exactly
        {i : t_prev − t_start_i ≤ τ_algo} — init members ride along through
        their shared t_start = 1 until the one-time fire at t = τ_algo+2."""
        cache, t_start = state["cache"], state["t_start"]
        active = (state["t_prev"] - t_start) <= self.tau_algo
        init_mask = state["init_mask"]
        asum = _shard_vec(
            _astate(cache_sum(cache, active), self.state_dtype), cache)
        init_sum = _shard_vec(
            _astate(cache_sum(cache, init_mask), self.state_dtype), cache)
        return {**state, "asum": asum,
                "count": jnp.sum(active.astype(jnp.int32)),
                "init_sum": init_sum,
                "init_count": jnp.sum(init_mask.astype(jnp.int32))}


@dataclasses.dataclass
class ACEDDirect(Aggregator):
    """Paper Algorithm a.1, literal: masked mean over the whole (n, d) cache
    on every arrival — the pinned O(n·d) reference the incremental `ACED` is
    differentially tested against (≤1e-5, all scenarios). The int8 masked
    mean routes through the Pallas `masked_agg` kernel dispatch."""
    tau_algo: int = 10
    cache_dtype: str = "float32"
    name = "aced_direct"
    cache_init = True

    def init_state(self, n, d, init_grads=None):
        return {"cache": _init_cache(n, d, self.cache_dtype, init_grads),
                "t_start": jnp.ones((n,), jnp.int32)}

    def step(self, state, arr):
        j = jnp.asarray(arr.client, jnp.int32)
        cache = cache_set_row(state["cache"], j, arr.payload, arr.valid)
        t = jnp.asarray(arr.t, jnp.int32)
        t_start = jax.lax.dynamic_update_index_in_dim(
            state["t_start"], t + 1, j, 0)
        active = (t - t_start) <= self.tau_algo
        emit = jnp.any(active)
        if isinstance(cache, FlatCache) and cache.data.dtype == jnp.int8:
            update = kernel_ops.masked_agg(cache.data, cache.scale, active)
        else:
            update = cache_mean(cache, active)
        return {"cache": cache, "t_start": t_start}, update, emit, _ONE


ALGORITHMS = {
    "asgd": VanillaASGD,
    "delay_asgd": DelayAdaptiveASGD,
    "fedbuff": FedBuff,
    "ca2fl": CA2FL,
    "ca2fl_direct": CA2FLDirect,
    "ace_direct": ACEDirect,
    "ace": ACEIncremental,
    "aced": ACED,
    "aced_direct": ACEDDirect,
}


def make_aggregator(cfg) -> Aggregator:
    """Build from an AFLConfig."""
    a = cfg.algorithm
    if a == "asgd":
        return VanillaASGD()
    if a == "delay_asgd":
        return DelayAdaptiveASGD(tau_c=cfg.max_delay_scale * cfg.delay_beta)
    if a == "fedbuff":
        return FedBuff(buffer_size=cfg.buffer_size,
                       state_dtype=cfg.state_dtype)
    if a == "ca2fl":
        return CA2FL(buffer_size=cfg.buffer_size, cache_dtype=cfg.cache_dtype,
                     state_dtype=cfg.state_dtype)
    if a == "ca2fl_direct":
        return CA2FLDirect(buffer_size=cfg.buffer_size,
                           cache_dtype=cfg.cache_dtype,
                           state_dtype=cfg.state_dtype)
    if a == "ace_direct":
        return ACEDirect(cache_dtype=cfg.cache_dtype)
    if a == "ace":
        return ACEIncremental(cache_dtype=cfg.cache_dtype,
                              state_dtype=cfg.state_dtype)
    if a == "aced":
        # k_batch>1 sizes the owner-ring for whole-cohort expiry (the
        # event-batched engine hands ACED up to k_batch arrivals per tick)
        return ACED(tau_algo=cfg.tau_algo, cache_dtype=cfg.cache_dtype,
                    state_dtype=cfg.state_dtype,
                    max_cohort=max(1, getattr(cfg, "k_batch", 1)))
    if a == "aced_direct":
        return ACEDDirect(tau_algo=cfg.tau_algo, cache_dtype=cfg.cache_dtype)
    raise ValueError(f"unknown AFL algorithm {a!r}")

"""Device-resident sampled-staleness engine — the paper's Fig. 2 protocol as
one `jax.lax.scan`.

The host `StalenessSimulator` (repro/core/staleness_sim.py) is the pinned
reference for this protocol, but it serializes thousands of arrivals per run
through eager dispatches: at each server iteration it samples an arriving
client, samples τ ~ Exp(β), reads the stale model from a bounded deque of
recent models, and applies the aggregator — all in host Python. The paper's
main experimental surface (the Fig. 2 heterogeneity×delay grid, the Fig. 3
dropout/τ_algo study, Fig. a.1 stability bands, and the lr-tuning grids in
benchmarks/common.py) is thousands of such runs.

This engine scans the full protocol on device:

  1. **Host randomness precompute** — like the event engine's schedule
     (repro/core/delays.py), the protocol's randomness never depends on model
     values, so it is materialised up front as per-event arrays:
     ``gumbels[e]`` (one Gumbel row per event, for categorical client
     sampling via argmax), ``tau_raw[e]`` (Exp(β) staleness draws, pre-cap)
     and per-client **availability windows** ``leave_at``/``rejoin_at``
     (drawn once; permanent dropout = ``rejoin_at = NEVER``, always-on =
     ``leave_at = NEVER``). See `build_staleness_randomness`.
  2. **Device scan** — a ``(tau_max+1, *flat_row_shape(d))`` **ring buffer**
     of recent models (each slot in the cache's row shape) is carried
     through the scan with a write cursor that advances on emitted updates.
     The stale read is ``ring[(cursor − clamp(τ)) mod (tau_max+1)]``,
     exactly `history[-(τ+1)]` in the host deque. Client sampling is a traced
     categorical: ``argmax(logits + gumbels[e])`` with speed-skew
     log-probabilities; **availability is a traced-t window mask** —
     ``leave_at <= t < rejoin_at`` folded into the sampling logits, so both
     the Fig. 3 permanent-dropout study and TimelyFL-style leave/re-join
     dynamics run inside the scan. When *every* client is inside its window
     the protocol freezes (no arrivals are possible): the scan burns one
     event, holds the model and aggregator state, and fast-forwards t to the
     earliest rejoin — the host reference mirrors the same jump, so frozen
     runs stay event-for-event matched through the thaw.
  3. **In-scan eval cadence** — an ``(n_marks, d)`` snapshot buffer carried
     through the scan captures the model whenever an emitted update lands t
     on an eval mark (the host's ``t % eval_every == 0 or t == T`` cadence).
     Arbitrary host `eval_fn`s then run post-scan on the snapshots, so
     `ScanResult.evals`/`eval_ts` match `SimResult` without ever leaving the
     device mid-run.

The runner takes the server learning rate as a *runtime* scalar (unless a
schedule callable is baked in) and the availability windows as *runtime*
arrays, so one compiled runner vmaps over seeds, the lr-tuning grid AND every
dropout/re-join scenario: `run_staleness_seeds` / `run_staleness_grid` batch
whole sweeps into a single XLA computation.

Equivalence contract: `StalenessSimulator(..., replay=rand)` consumes the
same randomness arrays event-for-event, so given the same seed the host and
scanned trajectories match to ≤1e-5 — including dropout, speed-skew,
leave/re-join windows and the eval cadence
(tests/test_scan_staleness.py pins all five algorithms).

Two model layouts share one protocol program (`_staleness_program`):

  * ``layout="flat"`` — the model is carried as the raveled (d,) vector
    (the original engine; host-replay reference layout for the quadratic /
    vision payloads and the sweep drivers below).
  * ``layout="tree"`` — the model is carried as its parameter pytree: client
    gradients are the model's own pjit grads on the (data, model) mesh (no
    ravel on the hot path), the aggregator runs its tree-cache path (same
    layout as the pjit train step in repro/core/distributed.py) and the
    (tau_max+1, ·) model-history ring is a per-leaf stacked tree buffer —
    optionally int8-quantized (``history_dtype="int8"``, ~4x smaller; the
    trajectory then deviates from the f32 host replay by ring quantization
    error, so the ≤1e-5 replay contract holds for the f32 ring only).

Execution comes in two shapes: `make_staleness_runner` (one jitted scan over
all events — the sweep/benchs path) and `make_chunked_staleness_runner`
(explicit ``init``/``chunk`` calls over event slices; the carry between
chunks is a plain pytree holding the FULL protocol state — model, aggregator
cache + running sums, history ring, PRNG key — so `launch/train.py`
checkpoints on chunk boundaries and resumes bit-exactly).

Fault tolerance (``guards=True``): a `FaultSchedule` is one more per-event
runtime array pair — injected NaN payloads, norm explosions, Byzantine sign
flips and over-stale arrivals flow through a traced guard pipeline
(quarantine / global-norm clip / staleness rejection) whose counters ride in
the scan carry; `StalenessSimulator(faults=...)` mirrors it event-for-event,
so the ≤1e-5 replay contract extends to faulted runs. ``resync_every``
periodically recomputes the incremental ACED/CA²FL running sums exactly from
the cache (`Aggregator.resync`) inside the scan — self-healing against
accumulated drift.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core import sanitize
from repro.core.aggregators import (Aggregator, Arrival, ArrivalBatch,
                                    wants_cache_init)
from repro.core.cache import (FlatCache, _take_rows, flat_row_shape,
                              init_tree_cache, is_tree_cache_leaf,
                              tree_cache_row, tree_cache_rows,
                              tree_cache_set_row)
from repro.core.scan_engine import (ScanResult, _payload_chain, _to_result,
                                    default_n_events)
from repro.core.staleness_sim import (FAULT_BYZANTINE, FAULT_EXPLODE,
                                      FAULT_NAN, FAULT_NONE, FAULT_OVERSTALE,
                                      NEVER, default_tau_max,
                                      staleness_client_probs)
from repro.sharding.rules import replicate, shard, use_rules

#: The stages of one scan tick, in code order. Each wraps its part of the
#: tick in a `jax.named_scope`, so the compiled chunk's op metadata —
#: and a profile of it — names the stage every op came from (see
#: `ChunkedStalenessRunner.op_stages`). "afl.guards" is built only with
#: guards and "afl.resync" only with `resync_every`. Outputs, the next t and
#: the guard counters stay outside every stage.
STAGES = ("afl.sample", "afl.stale_read", "afl.client", "afl.guards",
          "afl.commit", "afl.select", "afl.resync", "afl.update", "afl.ring")

#: one instruction of an HLO module's text, with its op_name metadata if any
_HLO_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?(?:op_name="((?:[^"\\]|\\.)*)"|$)')
#: a stage scope as a component of an op_name, also under a transform
#: wrapper such as `transpose(jvp(afl.client))`
_STAGE_COMPONENT = re.compile(r"(?:^|/)(?:\w+\()*(afl\.\w+)")


def _stage(name: str):
    """The named scope of the tick stage `name` (one of `STAGES`)."""
    if name not in STAGES:
        raise ValueError(f"unknown tick stage {name!r}")
    return jax.named_scope(name)


def hlo_op_stages(hlo_text: str) -> Dict[str, str]:
    """Each instruction of an HLO module's text mapped to the outermost stage
    of `STAGES` in its ``op_name`` metadata, or "" when it has none."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        names = _STAGE_COMPONENT.findall(m.group(2) or "")
        out[m.group(1)] = next((n for n in names if n in STAGES), "")
    return out


@dataclasses.dataclass
class StalenessRandomness:
    """Per-event randomness for one run — everything the protocol draws that
    does not depend on model values. Consumed identically by the device scan
    and by `StalenessSimulator(..., replay=...)` (seed-matched replay)."""
    gumbels: jnp.ndarray    # (n_events, n) f32 — categorical sampling noise
    tau_raw: jnp.ndarray    # (n_events,) f32 Exp(β) staleness draws, pre-cap
    #                         ((n_events, k_batch) when built with k_batch > 1
    #                         — one draw per arrival lane per tick)
    leave_at: jnp.ndarray   # (n,) int32 — iteration each client leaves (NEVER: stays)
    rejoin_at: jnp.ndarray  # (n,) int32 — iteration it comes back (NEVER: permanent)

    @property
    def n_events(self) -> int:
        return self.tau_raw.shape[0]

    @property
    def dropped(self) -> jnp.ndarray:
        """(n,) bool — clients that leave at some point (window is armed)."""
        return self.leave_at < NEVER


def build_staleness_randomness(seed: int, n_events: int, n_clients: int,
                               beta: float, dropout_frac: float = 0.0,
                               speed_skew: float = 0.0,
                               dropout_at: Optional[int] = None,
                               rejoin_at: Optional[int] = None,
                               windows=None,
                               k_batch: int = 1) -> StalenessRandomness:
    """Materialise the protocol's random stream from `seed`.

    Availability comes from one of (highest precedence first):
      * ``windows = (leave_at, rejoin_at)`` — explicit (n,) int32 arrays;
      * ``dropout_frac``/``dropout_at`` (+ optional scalar ``rejoin_at``) —
        the dropout set is drawn without replacement weighted by the
        (speed-skew) participation probabilities, mirroring the host
        simulator's `rng.choice(..., p=probs)`; drawn clients leave at
        ``dropout_at`` and rejoin at ``rejoin_at`` (NEVER when omitted —
        the Fig. 3 permanent-dropout scenario);
      * neither — every client is always on.

    ``k_batch > 1`` (the event-batched engine) widens ``tau_raw`` to
    (n_events, k_batch) — one Exp(β) draw per arrival lane per tick. The
    gumbel rows stay (n_events, n): top-k of ONE perturbed logit row yields
    the tick's K distinct clients. ``k_batch=1`` keeps the stream
    bit-identical to every pre-batching build."""
    root = jax.random.PRNGKey(seed)
    kg, kt, kd = (jax.random.fold_in(root, c) for c in (101, 102, 103))
    gumbels = jax.random.gumbel(kg, (n_events, n_clients), jnp.float32)
    tau_shape = ((n_events,) if k_batch == 1 else (n_events, int(k_batch)))
    tau_raw = jax.random.exponential(kt, tau_shape, jnp.float32) * beta
    if windows is not None:
        leave, rejoin = windows
        leave = jnp.asarray(np.asarray(leave), jnp.int32)
        rejoin = jnp.asarray(np.asarray(rejoin), jnp.int32)
        return StalenessRandomness(gumbels, tau_raw, leave, rejoin)
    leave = jnp.full((n_clients,), NEVER, jnp.int32)
    rejoin = jnp.full((n_clients,), NEVER, jnp.int32)
    k = int(dropout_frac * n_clients)
    if k > 0 and dropout_at is not None:
        probs = jnp.asarray(staleness_client_probs(n_clients, speed_skew))
        idx = jax.random.choice(kd, n_clients, (k,), replace=False, p=probs)
        leave = leave.at[idx].set(dropout_at)
        if rejoin_at is not None:
            rejoin = rejoin.at[idx].set(rejoin_at)
    return StalenessRandomness(gumbels, tau_raw, leave, rejoin)


# ---------------------------------------------------------------------------
# Traced client-fault model: per-event fault descriptors as runtime arrays —
# exactly like the availability windows, so fault scenarios vmap across the
# existing seed/lr sweep grid without recompiling.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FaultSchedule:
    """Per-event fault descriptors for one run (runtime arrays — consumed by
    the scan's guard pipeline and, identically, by
    `StalenessSimulator(..., faults=...)`). ``kind[e]`` is a FAULT_* code
    (NONE/NAN/EXPLODE/BYZANTINE/OVERSTALE — see repro/core/staleness_sim.py);
    ``scale[e]`` is the norm multiplier an EXPLODE event applies."""
    kind: jnp.ndarray       # (n_events,) int32 — FAULT_* code per event
    #                         ((n_events, k_batch) per-lane codes when built
    #                         for the K-batched engine)
    scale: jnp.ndarray      # (n_events,) f32 — EXPLODE norm multiplier
    #                         ((n_events, k_batch) with K-batching)

    @property
    def n_events(self) -> int:
        return int(self.kind.shape[0])

    def counts(self):
        """Host-side {kind-name: count} of scheduled (not yet fired) faults."""
        k = np.asarray(self.kind)
        return {"nan": int((k == FAULT_NAN).sum()),
                "explode": int((k == FAULT_EXPLODE).sum()),
                "byzantine": int((k == FAULT_BYZANTINE).sum()),
                "overstale": int((k == FAULT_OVERSTALE).sum())}


def no_faults(n_events: int, k_batch: int = 1) -> FaultSchedule:
    """An all-clean schedule — runs the guard pipeline (clipping, natural
    over-stale rejection) without injected faults. ``k_batch > 1`` shapes
    the arrays per-lane for the K-batched engine."""
    shape = (n_events,) if k_batch == 1 else (n_events, int(k_batch))
    return FaultSchedule(jnp.zeros(shape, jnp.int32),
                         jnp.ones(shape, jnp.float32))


def build_fault_schedule(seed: int, n_events: int, *, k_batch: int = 1,
                         nan_rate: float = 0.0,
                         explode_rate: float = 0.0,
                         byzantine_rate: float = 0.0,
                         overstale_rate: float = 0.0,
                         explode_scale: float = 1e4) -> FaultSchedule:
    """Draw a per-event fault schedule from `seed` (fold_in 201 — disjoint
    from the protocol randomness constants 101–103, so faulted and clean
    runs share their gumbel/τ streams event-for-event). Each event
    independently becomes one fault kind with the given rate: NAN poisons
    the payload non-finite, EXPLODE multiplies its norm by `explode_scale`,
    BYZANTINE flips its sign, OVERSTALE forces the staleness request past
    tau_max. Rates must sum to ≤ 1. With ``k_batch > 1`` every *lane*
    draws independently — arrays are (n_events, k_batch), and the guards
    quarantine lanes individually (a faulty arrival never vetoes its whole
    batch). ``k_batch=1`` draws are bit-identical to pre-batching builds."""
    rates = (nan_rate, explode_rate, byzantine_rate, overstale_rate)
    if min(rates) < 0 or sum(rates) > 1.0:
        raise ValueError(f"fault rates must be ≥0 and sum to ≤1: {rates}")
    shape = (n_events,) if k_batch == 1 else (n_events, int(k_batch))
    u = jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(seed), 201), shape, jnp.float32)
    edges = np.concatenate([[0.0], np.cumsum(rates)])
    kind = jnp.full(shape, FAULT_NONE, jnp.int32)
    for code, lo, hi in zip(
            (FAULT_NAN, FAULT_EXPLODE, FAULT_BYZANTINE, FAULT_OVERSTALE),
            edges[:-1], edges[1:]):
        kind = jnp.where(jnp.logical_and(u >= lo, u < hi), code, kind)
    return FaultSchedule(kind, jnp.full(shape, explode_scale, jnp.float32))


# ---------------------------------------------------------------------------
# Ring-buffer model history: the bounded deque, scannable.
# ---------------------------------------------------------------------------

def ring_read(ring: jnp.ndarray, cursor, tau):
    """``history[-(tau+1)]``: the model τ emitted updates ago, as a (d,)
    vector. The ring is ``(S, *flat_row_shape(d))``: each slot one whole
    block in the cache's row shape, read by one dynamic slice. `cursor` is
    the slot holding the newest model; requires τ ≤ min(t, capacity−1). The
    read row keeps the buffer's feature sharding (history slots are
    replicated, features shard over ``model`` — no-op outside a mesh
    context)."""
    slot = jnp.mod(cursor - tau, ring.shape[0])
    row = jax.lax.dynamic_index_in_dim(ring, slot, keepdims=False)
    return shard(row.reshape(-1), ("cache_d",))


def ring_read_lanes(ring: jnp.ndarray, cursor, taus):
    """`ring_read` for each lane of the (K,) staleness vector `taus`: the K
    models as (K, d) rows, one dynamic slice of a whole slot each (a
    `jnp.take` over the slots lowers to a gather that reads every slot's
    column band)."""
    rows = _take_rows(ring, jnp.mod(cursor - taus, ring.shape[0]))
    return shard(rows.reshape(taus.shape[0], -1), (None, "cache_d"))


def ring_append(ring: jnp.ndarray, cursor, w, emit):
    """``history.append(w)`` gated on `emit`: advance the cursor and write
    the (d,) model `w` into its slot in the stored row shape, one in-place
    dynamic update of a whole slot. When not emitting, cursor stays and `w`
    (unchanged) rewrites its own slot, so the write can be unconditional —
    trace-safe without a select on the full buffer. The written buffer
    re-asserts its (replicated-slots, model-sharded-features, unsharded
    lanes) layout so the scan carry never all-gathers."""
    cursor = jnp.where(emit, jnp.mod(cursor + 1, ring.shape[0]), cursor)
    ring = jax.lax.dynamic_update_index_in_dim(
        ring, w.reshape(ring.shape[1:]), cursor, 0)
    return shard(ring, (None, "cache_d")), cursor


# ---------------------------------------------------------------------------
# In-scan eval cadence: snapshot buffer written on mark crossings.
# ---------------------------------------------------------------------------

def eval_marks_for(T: int, eval_every: Optional[int]) -> Optional[Tuple[int, ...]]:
    """The server iterations the host simulator evaluates at
    (``t % eval_every == 0 or t == T``), as a static sorted tuple."""
    if not eval_every:
        return None
    return tuple(sorted(set(range(eval_every, T + 1, eval_every)) | {T}))


def snapshot_update(snaps, hits, marks, t_new, emit, w):
    """Write `w` into the snapshot row whose mark equals `t_new`, gated on
    `emit` (t only lands on a mark via an emitted update; freeze fast-forward
    jumps skip their marks exactly like the host's modulo cadence does).
    Returns (snaps, hits). Snapshot rows keep mark-replicated, model-sharded
    features (no-op outside a mesh context)."""
    hit = jnp.logical_and(emit, marks == t_new)          # (n_marks,) bool
    snaps = jnp.where(hit[:, None], w[None, :], snaps)
    return shard(snaps, (None, "cache_d")), jnp.logical_or(hits, hit)


def _apply_evals(snaps, hits, marks, eval_fn, unravel):
    """Run the host `eval_fn` over the marks the scan actually reached.
    `unravel=None` means `snaps` is a params pytree with a leading
    (n_marks,) axis (tree layout) rather than an (n_marks, d) array."""
    evals, eval_ts = [], []
    hits = np.asarray(hits)
    snaps = jax.tree.map(np.asarray, snaps)
    for i, m in enumerate(marks):
        if not hits[i]:
            continue
        if unravel is None:
            params = jax.tree.map(lambda s: jnp.asarray(s[i]), snaps)
        else:
            params = unravel(jnp.asarray(snaps[i]))
        evals.append(eval_fn(params))
        eval_ts.append(int(m))
    return evals, eval_ts


def _is_cache(x) -> bool:
    """A per-client cache in the aggregator state: a `FlatCache`, or one
    tree-cache leaf (`cache.is_tree_cache_leaf`)."""
    return isinstance(x, FlatCache) or is_tree_cache_leaf(x)


def _select_state(pred, new, old):
    """The tick's state gate: per-leaf ``where(pred, new, old)`` over the
    aggregator state, with the per-client caches passed through as the
    rule's transition returned them. It holds the state through all-gone
    freezes, so a thawed run continues from the frozen state exactly like
    the host loop (which performs no transitions while frozen), and
    through quarantined or refused arrivals.

    Validity is the only gate on the cache at every K: `Aggregator.step`
    gets `pred` as `Arrival.valid`, `step_batch` the lanes' validity, and
    each writes the cache only through masked row writes that put the
    stored row and scale back bit for bit, so on a tick that `pred` refuses
    the returned cache already equals `old`. Selecting over it anyway reads
    and writes the whole O(n·d) cache every tick, and — reading the old
    cache after the row write — makes XLA copy the loop carry instead of
    updating it in place. Every other leaf (the O(n + d) running sums,
    counters and ACED's owner-ring) keeps the select, and with it the freeze
    and NaN protection."""
    return jax.tree.map(
        lambda a, b: a if _is_cache(a) else jnp.where(pred, a, b),
        new, old, is_leaf=_is_cache)


def _tree_norm(tree, lane_dims: int = 0):
    """‖·‖₂ over all leaves of `tree`, taken over each leaf's axes past its
    leading `lane_dims` lane axes: with none, the global norm (the tree
    layout's `unorm` metric, equal to ``jnp.linalg.norm`` of the raveled
    vector); with a (K,) lane axis, lane k's global norm at k."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                axis=tuple(range(lane_dims, x.ndim)))
                        for x in jax.tree.leaves(tree)))


def _per_lane(x, leaf):
    """Lane values `x`, of the tick's lane shape (``()`` or ``(K,)``), shaped
    to broadcast against `leaf`, which leads with the same lane axes (a
    scalar broadcasts as it is)."""
    return x.reshape(x.shape + (1,) * (leaf.ndim - x.ndim)) if x.ndim else x


def _tree_payload_chain(grad_fn, local_steps: int, local_lr: float):
    """Tree-layout client payload with the SAME PRNG-split chain as
    `_payload_chain` (one split per call, plus one per local step when
    local_steps > 1) but over the model pytree directly — no ravel/unravel
    on the hot path, so the client grad keeps the model's own (data, model)
    pjit layout end-to-end."""
    K = local_steps

    def payload(w, client, key):
        key, sub = jax.random.split(key)
        if K == 1:
            loss, g = grad_fn(w, client, sub)
            return (jax.tree.map(lambda x: x.astype(jnp.float32), g),
                    loss, key)
        w_start = w
        loss = jnp.zeros((), jnp.float32)
        for _ in range(K):
            key, sub = jax.random.split(key)
            loss, g = grad_fn(w, client, sub)
            w = jax.tree.map(lambda a, b: a - local_lr * b.astype(a.dtype),
                             w, g)
        p = jax.tree.map(
            lambda a, b: ((a - b) / (K * local_lr)).astype(jnp.float32),
            w_start, w)
        return p, loss, key
    return payload


# ---------------------------------------------------------------------------

def _staleness_program(*, grad_fn: Callable, params0,
                       aggregator: Aggregator, n_clients: int, T: int,
                       beta: float,
                       server_lr: Optional[Callable] = None,
                       tau_max: Optional[int] = None,
                       speed_skew: float = 0.0,
                       eval_marks: Optional[Sequence[int]] = None,
                       local_steps: int = 1, local_lr: float = 0.05,
                       init_cache_grads: bool = True,
                       record_w: bool = False,
                       layout: str = "flat",
                       history_dtype: str = "float32",
                       guards: bool = False,
                       resync_every: Optional[int] = None,
                       checkify_invariants: bool = False,
                       k_batch: int = 1):
    """The protocol as two pure functions: ``(init_fn, chunk_fn, marks, w0)``.

    ``init_fn(key, lr, w0) -> carry`` builds the initial scan carry
    (init-batch cache seed, ring slot 0, eval snapshot buffer) from the f32
    starting model `w0` (the raveled vector or the params pytree, per
    layout). It takes `w0` as an argument rather than closing over it, so a
    jitted init does not embed a published-width model in its executable as
    a constant. ``chunk_fn(carry,
    gumbels, tau_raw, leave_at, rejoin_at, lr) -> (carry, outs)`` scans any
    slice of the event stream and composes: running it over consecutive
    slices is bit-identical to one scan over their concatenation, because
    the carry holds the FULL protocol state. Past-budget tail events are
    harmless padding (emit is gated on ``t < T``; the model and state
    freeze), so callers may round the stream up to a chunk multiple.

    ``layout`` picks the model representation (see module docstring): "flat"
    carries the raveled (d,) vector with the original byte-identical ops;
    "tree" carries the params pytree, dispatches the aggregator onto its
    tree-cache path and stores the history ring as a per-leaf stacked tree
    buffer in ``history_dtype`` ("int8" opt-in — quantization error then
    breaks the exact host-replay contract, by design).

    ``guards=True`` compiles the in-scan fault-guard pipeline and changes
    the chunk signature to ``chunk_fn(carry, gumbels, tau_raw, leave_at,
    rejoin_at, lr, fault_kind, fault_scale, clip_norm)`` — per-event fault
    descriptors (`FaultSchedule` slices) and a runtime clip threshold ride
    the scan exactly like the availability windows do. Per event: the
    payload is fault-injected, then (1) **quarantine** — a non-finite
    payload consumes the event without touching model, cache, running sums
    or the ACED owner-ring; (2) **over-stale rejection** — a staleness
    request past tau_max (injected or natural) is likewise dropped;
    (3) **global-norm clip** — surviving payloads with ‖g‖ > clip_norm are
    scaled to the threshold (clip_norm ≤ 0 disables). Counters ride the
    carry (``carry["guards"]``) and per-event flags the outs, both gated on
    the in-window live region (t < T and not frozen) so chunked totals
    equal one-shot totals. With guards off the pipeline compiles to
    nothing: signatures, carry and outs are bit-identical to pre-guard
    builds.

    ``resync_every`` (independent of guards) re-derives the aggregator's
    incremental running sums from its cache (`Aggregator.resync`) on every
    `resync_every`-th emitted update, under `jax.lax.cond` — O(n·d) only on
    the cadence when unvmapped, so it belongs to the chunked/long-run path,
    not the vmapped sweep grids (vmap lowers cond to select and would pay
    the recompute every event)."""
    n = n_clients
    agg = aggregator
    k_batch = int(k_batch)
    if not 1 <= k_batch <= n_clients:
        raise ValueError(
            f"k_batch={k_batch} must be in [1, n_clients={n_clients}]")
    mc = getattr(agg, "max_cohort", None)
    if mc is not None and mc < k_batch:
        raise ValueError(
            f"{type(agg).__name__}(max_cohort={mc}) cannot own "
            f"k_batch={k_batch} cohorts — construct the aggregator "
            "with max_cohort >= k_batch")
    tau_max = tau_max if tau_max is not None else default_tau_max(beta)
    S = tau_max + 1
    wants_init = init_cache_grads and wants_cache_init(agg)
    log_probs = jnp.asarray(
        np.log(staleness_client_probs(n, speed_skew)), jnp.float32)
    marks = (jnp.asarray(eval_marks, jnp.int32)
             if eval_marks is not None else None)
    if server_lr is not None and not callable(server_lr):
        raise TypeError("pass constant lrs at call time; server_lr is for "
                        "iteration schedules (callable) only")
    lr_of_t = ((lambda t, lr: server_lr(t)) if server_lr is not None
               else (lambda t, lr: lr))

    if layout == "flat":
        if history_dtype != "float32":
            raise ValueError("quantized history ring is tree-layout only")
        flat0, unravel = ravel_pytree(params0)
        w0 = jnp.asarray(flat0, jnp.float32)
        d_tpl = w0.size
        payload_fn = _payload_chain(grad_fn, unravel, local_steps, local_lr)
        # pin the raveled gradient replicated: the client grad is computed
        # redundantly per device; only server state shards (see
        # sharding/rules.replicate for the CPU-SPMD rationale)
        pin_payload = replicate
        # each slot in the cache's row shape, so a slot is whole tiles
        ring_shape = (S,) + flat_row_shape(d_tpl)
        init_ring = lambda w: shard(
            jnp.zeros(ring_shape, jnp.float32).at[0].set(
                w.reshape(ring_shape[1:])), (None, "cache_d"))
        rd_ring, rd_rings, ap_ring = ring_read, ring_read_lanes, ring_append

        init_snaps = lambda: shard(
            jnp.zeros((marks.shape[0], d_tpl), jnp.float32),
            (None, "cache_d"))
        snap_update = snapshot_update
        # the init scan stacks each client's row in the cache's stored row
        # shape: laying out the (n, d) f32 stack again to seed the cache
        # would take a second copy of it (8 GiB at the flat cell's size)
        init_row = lambda p: p.reshape(flat_row_shape(d_tpl))
        init_mean = lambda rows: jnp.mean(rows, 0).reshape(-1)
        apply_init = lambda w, eta, mean: w - eta * mean
        apply_update = lambda w, u, eta, emit: shard(
            jnp.where(emit, w - eta * u, w), ("cache_d",))
        unorm = jnp.linalg.norm
    elif layout == "tree":
        if record_w:
            raise ValueError("record_w is flat-layout only (a per-event "
                             "model trajectory buffer does not fit the tree "
                             "path's real-model sizes)")
        w0 = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params0)
        d_tpl = w0  # Aggregator.init_state takes the pytree template as d
        payload_fn = _tree_payload_chain(grad_fn, local_steps, local_lr)
        # no replicate pin: tree-layout grads come from the model's own pjit
        # computation and keep its (data, model) layout; the tree-cache row
        # writes inherit it per leaf
        pin_payload = lambda p: p
        init_ring = lambda w: tree_cache_set_row(
            init_tree_cache(S, w, history_dtype), 0, w)

        def rd_ring(ring, cursor, tau):
            return tree_cache_row(ring, jnp.mod(cursor - tau, S))

        def rd_rings(ring, cursor, taus):
            # batched stale reads off the tree ring: a (K,)-lane dequantized
            # gather per leaf (int8 rings dequantize per slot exactly like
            # the single-row read)
            return tree_cache_rows(ring, jnp.mod(cursor - taus, S))

        def ap_ring(ring, cursor, w, emit):
            # same unconditional-write trick as `ring_append`: a non-emitting
            # event rewrites its own slot with the unchanged (re-quantized —
            # deterministic) model
            cursor = jnp.where(emit, jnp.mod(cursor + 1, S), cursor)
            return tree_cache_set_row(ring, cursor, w), cursor

        init_snaps = lambda: jax.tree.map(
            lambda x: jnp.zeros((marks.shape[0],) + x.shape, jnp.float32),
            w0)

        def snap_update(snaps, hits, mk, t_new, emit, w):
            hit = jnp.logical_and(emit, mk == t_new)     # (n_marks,) bool
            snaps = jax.tree.map(
                lambda s, x: jnp.where(hit.reshape((-1,) + (1,) * x.ndim),
                                       x[None], s), snaps, w)
            return snaps, jnp.logical_or(hits, hit)

        init_row = lambda p: p
        init_mean = lambda rows: jax.tree.map(lambda r: jnp.mean(r, 0), rows)
        apply_init = lambda w, eta, mean: jax.tree.map(
            lambda wl, m: wl - eta * m.astype(jnp.float32), w, mean)
        apply_update = lambda w, u, eta, emit: jax.tree.map(
            lambda wl, ul: jnp.where(emit, wl - eta * ul.astype(jnp.float32),
                                     wl), w, u)
        unorm = _tree_norm
    else:
        raise ValueError(f"unknown layout {layout!r}")

    # What a tick's arrival count decides, picked here once; the tick itself
    # is written once over a lane shape of () (K = 1) or (K,). K = 1 samples
    # the Gumbel argmax, reads one ring slot, runs one payload on the carry's
    # key chain and commits one `Arrival`; K > 1 samples the Gumbel top-k
    # (the K distinct clients in sampling order; ties break to the lower
    # index, as the host reference's stable argsort does), reads K slots,
    # runs K payload lanes and commits one `ArrivalBatch`. ``tau_raw`` (and
    # the fault arrays under guards) then carry the (K,) lane axis.
    if k_batch == 1:
        lanes = ()

        def sample(scores, gone, any_alive):
            # the argmax client is alive whenever any client is
            return jnp.argmax(scores).astype(jnp.int32), any_alive

        read, client = rd_ring, payload_fn
        next_key = lambda key: key

        def commit(state, j, p, t, tau, valid):
            return agg.step(state, Arrival(j, p, t, tau, valid)), valid

        tick_loss = lambda losses, valid: losses
        # the guard flags of a tick are booleans
        flag = lambda win, alive, m: jnp.logical_and(win, m)
        lane_checks = lambda *args: None
    else:
        lanes = (k_batch,)

        def sample(scores, gone, any_alive):
            # gone clients sink to -inf; with fewer than K alive the lanes
            # past them are masked off
            _, js = jax.lax.top_k(scores, k_batch)
            js = js.astype(jnp.int32)
            return js, jnp.logical_not(gone[js])

        read = rd_rings

        def client(w_stales, js, key):
            # keys[1 + i] seeds lane i's payload and keys[0] advances the
            # carry's chain (the host reference splits identically)
            keys = jax.random.split(key, k_batch + 1)
            payloads, losses, _ = jax.vmap(payload_fn)(w_stales, js,
                                                       keys[1:])
            return payloads, losses, keys

        # taken where the next carry is built, outside every stage
        next_key = lambda keys: keys[0]

        def commit(state, js, p, t, taus, valid):
            # no valid lane (the all-gone freeze included): no transition
            proc = jnp.any(valid)
            return agg.step_batch(
                state, ArrivalBatch(js, p, t, taus, valid)), proc

        def tick_loss(losses, valid):
            # the mean over the valid lanes
            nv = jnp.sum(valid.astype(jnp.float32))
            return (jnp.sum(jnp.where(valid, losses, 0.0))
                    / jnp.maximum(nv, 1.0))

        def flag(win, alive, m):
            # the guard flags of a tick count its live lanes
            c = jnp.sum(jnp.logical_and(alive, m).astype(jnp.int32))
            return jnp.where(win, c, 0)

        def lane_checks(js, taus, valid, u, state, old):
            sanitize.check_batch_arrivals(js, taus, valid, n, tau_max)
            sanitize.check_commit_batch(u, state, old, valid)

    def init_fn(key, lr, w0):
        lr = jnp.asarray(lr, jnp.float32)
        w = w0
        if wants_init:
            def init_step(key, client):
                p, _, key = payload_fn(w0, client, key)
                return key, init_row(pin_payload(p))
            key, init_rows = jax.lax.scan(init_step, key, jnp.arange(n, dtype=jnp.int32))
            state = agg.init_state(n, d_tpl, init_rows)
            # paper Alg. 1 line 4-5: apply u^0 before the loop
            w = apply_init(w, lr_of_t(0, lr), init_mean(init_rows))
            t0 = 1
        else:
            state = agg.init_state(n, d_tpl, None)
            t0 = 0

        ring = init_ring(w0)
        cursor = jnp.asarray(0, jnp.int32)
        if wants_init:           # history = [w^0, w^1] after the init update
            ring, cursor = ap_ring(ring, cursor, w, True)

        carry = {"w": w, "key": key, "state": state,
                 "t": jnp.asarray(t0, jnp.int32),
                 # emitted-update count: tracks len(history)-1 in the host
                 # deque; diverges from t after a freeze fast-forward jump
                 "n_upd": jnp.asarray(t0, jnp.int32),
                 "ring": ring, "cursor": cursor}
        if marks is not None:
            carry["snaps"] = init_snaps()
            carry["hits"] = jnp.zeros((marks.shape[0],), jnp.bool_)
        if guards:
            carry["guards"] = {k: jnp.zeros((), jnp.int32) for k in
                               ("quarantined", "clipped", "rejected")}
        return carry

    def _chunk_impl(carry, gumbels, tau_raw, leave_at, rejoin_at, lr,
                    fault_kind, fault_scale, clip_norm):
        lr = jnp.asarray(lr, jnp.float32)
        leave_at = jnp.asarray(leave_at, jnp.int32)
        rejoin_at = jnp.asarray(rejoin_at, jnp.int32)

        def tick(carry, ev):
            if guards:
                g_row, traw, f_kind, f_scale = ev
            else:
                g_row, traw = ev
            t = carry["t"]
            with _stage("afl.sample"):
                g_row = shard(g_row, ("cache_clients",))
                # availability: traced-t windows folded into the sampling
                # logits
                gone = jnp.logical_and(leave_at <= t, t < rejoin_at)
                logits = jnp.where(gone, -jnp.inf, log_probs)
                # every client inside its window: no arrival is possible —
                # the protocol freezes (no emission, model and aggregator
                # state held) and t fast-forwards to the earliest rejoin; the
                # host reference performs the same jump (or stops when none
                # rejoins before T)
                any_alive = jnp.any(~gone)
                thaw_t = jnp.minimum(
                    jnp.min(jnp.where(gone, rejoin_at, NEVER)), T)
                js, alive = sample(logits + g_row, gone, any_alive)
                tau_req = jnp.floor(traw).astype(jnp.int32)
                if guards:   # injected over-stale request; clamped for read
                    tau_req = jnp.where(f_kind == FAULT_OVERSTALE,
                                        tau_max + 1, tau_req)
                taus = jnp.minimum(tau_req,
                                   jnp.minimum(tau_max, carry["n_upd"]))
            with _stage("afl.stale_read"):
                w_stale = read(carry["ring"], carry["cursor"], taus)
            with _stage("afl.client"):
                payload, losses, key = client(w_stale, js, carry["key"])
                payload = pin_payload(payload)
            if guards:
                with _stage("afl.guards"):
                    # fault injection: one multiplier per lane covers NAN
                    # (payload goes non-finite), EXPLODE (norm blow-up by
                    # f_scale) and BYZANTINE (sign flip); clean lanes
                    # multiply by 1.0 — an f32 identity, so a no-fault
                    # guarded run tracks the unguarded trajectory exactly,
                    # and a faulty lane never vetoes its batch
                    mult = jnp.where(f_kind == FAULT_NAN, jnp.float32(jnp.nan),
                                     jnp.float32(1.0))
                    mult = mult * jnp.where(f_kind == FAULT_EXPLODE, f_scale,
                                            jnp.float32(1.0))
                    mult = jnp.where(f_kind == FAULT_BYZANTINE, -mult, mult)
                    payload = jax.tree.map(lambda p: p * _per_lane(mult, p),
                                           payload)
                    finite = jnp.ones(lanes, jnp.bool_)
                    for leaf in jax.tree.leaves(payload):
                        finite = jnp.logical_and(finite, jnp.all(
                            jnp.isfinite(leaf),
                            axis=tuple(range(len(lanes), leaf.ndim))))
                    gnorm = _tree_norm(payload, len(lanes))
                    # NaN gnorm compares False: a quarantined payload is never
                    # also counted as clipped
                    do_clip = jnp.logical_and(clip_norm > 0, gnorm > clip_norm)
                    cscale = jnp.where(
                        do_clip, clip_norm / jnp.maximum(gnorm, 1e-12),
                        jnp.float32(1.0))
                    payload = jax.tree.map(lambda p: p * _per_lane(cscale, p),
                                           payload)
                    reject = tau_req > tau_max
                    ok = jnp.logical_and(finite, jnp.logical_not(reject))
                    valid = jnp.logical_and(alive, ok)
            else:
                valid = alive
            with _stage("afl.commit"):
                (state, u, emit, lr_scale), proc = commit(
                    carry["state"], js, payload, t, taus, valid)
                emit = jnp.logical_and(emit, jnp.logical_and(t < T, proc))
            # frozen events perform no aggregator transition on the host —
            # and neither do quarantined/rejected ones: the masked row writes
            # keep the cache, the select the running sums and the ACED
            # owner-ring untouched (jnp.where also stops any NaN from
            # leaking out of the unselected branch)
            with _stage("afl.select"):
                state = _select_state(proc, state, carry["state"])
            n_upd_new = carry["n_upd"] + emit.astype(jnp.int32)
            if resync_every:
                # periodic exact self-heal of the incremental running sums
                # (lax.cond: the O(n·d) recompute only runs on the cadence)
                resync_fn = agg.resync
                if checkify_invariants:
                    def resync_fn(s):
                        s2 = agg.resync(s)
                        sanitize.check_resync_agreement(s, s2)
                        return s2
                with _stage("afl.resync"):
                    state = jax.lax.cond(
                        jnp.logical_and(
                            emit, jnp.mod(n_upd_new, resync_every) == 0),
                        resync_fn, lambda s: s, state)
            with _stage("afl.update"):
                eta = lr_of_t(t, lr) * lr_scale
                w = apply_update(carry["w"], u, eta, emit)
            with _stage("afl.ring"):
                ring, cursor = ap_ring(carry["ring"], carry["cursor"], w,
                                       emit)
            t_new = jnp.where(any_alive, t + emit.astype(jnp.int32), thaw_t)
            out = {"loss": tick_loss(losses, valid), "emit": emit, "t": t,
                   "unorm": unorm(u), "alive": any_alive}
            if record_w:
                out["w"] = w
            new_carry = {"w": w, "key": next_key(key), "state": state,
                         "t": t_new, "n_upd": n_upd_new,
                         "ring": ring, "cursor": cursor}
            if marks is not None:
                new_carry["snaps"], new_carry["hits"] = snap_update(
                    carry["snaps"], carry["hits"], marks, t_new, emit, w)
            if guards:
                # flags gated on the live window (t < T, not frozen) and the
                # live lanes, so the padding tail and freezes never count and
                # chunked totals equal the host loop's
                win = jnp.logical_and(t < T, any_alive)
                flags = {
                    "quarantined": flag(win, alive, jnp.logical_not(finite)),
                    "rejected": flag(win, alive, jnp.logical_and(finite, reject)),
                    "clipped": flag(win, alive, jnp.logical_and(ok, do_clip))}
                out.update(flags)
                new_carry["guards"] = {
                    k: carry["guards"][k] + flags[k].astype(jnp.int32)
                    for k in flags}
            if checkify_invariants:
                # debug-build value invariants (repro/core/sanitize.py);
                # the static flag means an off build traces ZERO extra ops
                sanitize.check_model_finite(w)
                # quarantined lanes legitimately carry NaN — check only the
                # lanes the tick applied
                applied = jax.tree.map(
                    lambda p: jnp.where(_per_lane(valid, p), p, 0.0), payload)
                sanitize.check_payload_finite(applied, applied=emit)
                sanitize.check_cursor_bounds(cursor, S)
                sanitize.check_aggregator_state(state, n)
                lane_checks(js, taus, valid, u, state, carry["state"])
            return new_carry, out

        xs = ((gumbels, tau_raw, fault_kind, fault_scale) if guards
              else (gumbels, tau_raw))
        return jax.lax.scan(tick, carry, xs)

    if guards:
        def chunk_fn(carry, gumbels, tau_raw, leave_at, rejoin_at, lr,
                     fault_kind, fault_scale, clip_norm):
            return _chunk_impl(carry, gumbels, tau_raw, leave_at, rejoin_at,
                               lr, jnp.asarray(fault_kind, jnp.int32),
                               jnp.asarray(fault_scale, jnp.float32),
                               jnp.asarray(clip_norm, jnp.float32))
    else:
        def chunk_fn(carry, gumbels, tau_raw, leave_at, rejoin_at, lr):
            return _chunk_impl(carry, gumbels, tau_raw, leave_at, rejoin_at,
                               lr, None, None, None)

    return init_fn, chunk_fn, marks, w0


def make_staleness_runner(*, grad_fn: Callable, params0,
                          aggregator: Aggregator, n_clients: int, T: int,
                          beta: float,
                          server_lr: Optional[Callable] = None,
                          tau_max: Optional[int] = None,
                          speed_skew: float = 0.0,
                          eval_marks: Optional[Sequence[int]] = None,
                          local_steps: int = 1, local_lr: float = 0.05,
                          init_cache_grads: bool = True,
                          record_w: bool = False,
                          layout: str = "flat",
                          history_dtype: str = "float32",
                          guards: bool = False,
                          resync_every: Optional[int] = None,
                          checkify_invariants: Optional[bool] = None,
                          k_batch: int = 1):
    """Build the jitted runner
    ``run(key, gumbels, tau_raw, leave_at, rejoin_at, lr)
          -> (w, state, outs, extras)``.

    `lr` is a traced f32 scalar (constant server lr) so one compiled runner
    serves the whole lr-tuning grid; pass a callable `server_lr` to bake an
    iteration schedule instead (the runtime `lr` is then ignored).
    ``leave_at``/``rejoin_at`` are traced (n,) int32 availability windows
    (see `build_staleness_randomness`), so the same executable serves every
    dropout fraction, trigger iteration and re-join scenario. `grad_fn` must
    be trace-safe in `client`. The event count is the leading axis of the
    ``gumbels``/``tau_raw`` inputs. With `eval_marks` (a static sorted tuple
    of server iterations, see `eval_marks_for`), ``extras`` carries
    ``snaps`` / ``hits (n_marks,)`` — the model at each reached mark, for
    post-scan host evaluation. vmap the runner over stacked
    ``(key, gumbels, tau_raw, leave_at, rejoin_at, lr)`` for seed/grid/
    scenario sweeps. With ``layout="tree"``, `w` and the snapshots are
    params pytrees instead of raveled vectors (see `_staleness_program`).
    With ``guards=True`` the runner takes three trailing arguments
    ``(..., fault_kind, fault_scale, clip_norm)`` (the `FaultSchedule`
    arrays and a traced f32 clip threshold) and ``outs`` carries the
    per-event quarantined/clipped/rejected flags.

    ``checkify_invariants`` (default: the ``REPRO_CHECKIFY`` env var)
    compiles the debug value sanitizers into the step (repro/core/sanitize):
    the returned runner then raises on the first violated invariant and is
    not vmappable (the sweep helpers always build with the flag off). Off
    (the default) traces no check at all — bit-identical program.

    ``k_batch > 1`` builds the event-batched engine: every scan tick
    consumes K arrivals (Gumbel top-k sampling, one `step_batch`
    aggregation, one ring append + model update), so ``tau_raw`` — and the
    fault arrays under guards — must carry a trailing (k_batch,) lane axis
    (`build_staleness_randomness(..., k_batch=...)`). ``k_batch=1``
    commits one `Arrival` a tick through `step`."""
    do_checkify = sanitize.enabled(checkify_invariants)
    init_fn, chunk_fn, marks, w0 = _staleness_program(
        grad_fn=grad_fn, params0=params0, aggregator=aggregator,
        n_clients=n_clients, T=T, beta=beta, server_lr=server_lr,
        tau_max=tau_max, speed_skew=speed_skew, eval_marks=eval_marks,
        local_steps=local_steps, local_lr=local_lr,
        init_cache_grads=init_cache_grads, record_w=record_w,
        layout=layout, history_dtype=history_dtype,
        guards=guards, resync_every=resync_every,
        checkify_invariants=do_checkify, k_batch=k_batch)

    def _run(key, gumbels, tau_raw, leave_at, rejoin_at, lr, *guard_args):
        carry = init_fn(key, lr, w0)
        carry, outs = chunk_fn(carry, gumbels, tau_raw, leave_at, rejoin_at,
                               lr, *guard_args)
        extras = {}
        if marks is not None:
            extras = {"snaps": carry["snaps"], "hits": carry["hits"]}
        return carry["w"], carry["state"], outs, extras

    if do_checkify:
        return sanitize.wrap_checked(_run)
    return jax.jit(_run)


@dataclasses.dataclass
class ChunkedStalenessRunner:
    """Chunked execution of the scanned protocol (`launch/train.py` driver).

    ``init(key, lr) -> carry`` then repeatedly ``chunk(carry, gumbels,
    tau_raw, leave_at, rejoin_at, lr) -> (carry, outs)`` over consecutive
    event slices — bit-identical to one scan over the whole stream. The
    carry is a plain pytree of arrays holding the FULL protocol state
    (model, aggregator cache + running sums + owner-ring, model-history
    ring, PRNG key, eval snapshots), so it checkpoints/restores with the
    generic pytree saver (repro/checkpoint) and a resumed run continues
    exactly. ``marks`` mirrors the baked `eval_marks` static (None without
    an eval cadence); with marks the carry holds ``snaps``/``hits`` for
    `_apply_evals`. `chunk` consumes (donates) the carry it is given, except
    in the checkify build."""
    init: Callable
    chunk: Callable
    marks: Optional[jnp.ndarray]
    tau_max: int
    layout: str
    mesh: object = None
    #: guard statics baked into `chunk` — with guards, chunk takes the three
    #: trailing (fault_kind, fault_scale, clip_norm) arguments and the carry
    #: holds the ``guards`` counter dict (checkpointed with the rest)
    guards: bool = False
    resync_every: Optional[int] = None
    #: True when the debug value sanitizers are compiled into `chunk`
    #: (repro/core/sanitize) — chunk then raises on a violated invariant
    checkify_invariants: bool = False
    #: arrivals consumed per scan tick (1: one `Arrival` through `step`);
    #: the chunked event slices must carry the matching tau_raw/fault lane
    #: axis — see `_staleness_program`
    k_batch: int = 1
    #: the jitted program `chunk` calls (under `use_rules(mesh)` with a
    #: mesh); `op_stages` lowers it, which the checkify build cannot
    jit_chunk: Optional[Callable] = None

    def op_stages(self, *args) -> Dict[str, str]:
        """The stage map of the compiled chunk: each instruction name
        (``fusion.729``, ``copy.257``, ``commit_batch.7``, …) mapped to the
        outermost `STAGES` scope of its ``op_name`` metadata, or "" when it
        has none — a profile of `chunk` names its ops by these instructions.

        `args` are `chunk`'s arguments as arrays or `jax.ShapeDtypeStruct`s.
        The chunk is lowered and compiled for them and nothing runs: lowering
        reads only shapes, dtypes and shardings, so no buffer is read or
        donated, a carry that `chunk` already consumed included. The
        persistent compilation cache keys a program without its metadata
        by default and may hand back an executable built with other scopes;
        this compile keys it with its metadata, so the map is this program's.
        The instructions are the same either way: metadata does not change
        what XLA compiles.

        A fusion carries the metadata of its root, so all the ops fused into
        it count under the root's stage, even those traced in another
        stage."""
        key_flag = "jax_compilation_cache_include_metadata_in_key"
        keyed = getattr(jax.config, key_flag)
        jax.config.update(key_flag, True)
        try:
            with (use_rules(self.mesh) if self.mesh is not None
                  else contextlib.nullcontext()):
                text = self.jit_chunk.lower(*args).compile().as_text()
        finally:
            jax.config.update(key_flag, keyed)
        return hlo_op_stages(text)


def make_chunked_staleness_runner(*, mesh=None, **kwargs
                                  ) -> ChunkedStalenessRunner:
    """`_staleness_program` with jitted init/chunk entry points; with `mesh`
    (a (data, model) jax Mesh) every call runs under `use_rules(mesh)` so
    the model's own logical-axis constraints and the server rules' cache
    layout (clients → data, features → model) apply — the chunked analogue
    of `make_sharded_staleness_runner`. ``checkify_invariants`` (default:
    the ``REPRO_CHECKIFY`` env var) compiles the debug value sanitizers
    into `chunk` — see `make_staleness_runner`."""
    do_checkify = sanitize.enabled(kwargs.pop("checkify_invariants", None))
    kwargs["checkify_invariants"] = do_checkify
    init_fn, chunk_fn, marks, w0 = _staleness_program(**kwargs)
    tau_max = kwargs.get("tau_max")
    if tau_max is None:
        tau_max = default_tau_max(kwargs["beta"])
    guards = kwargs.get("guards", False)
    resync_every = kwargs.get("resync_every")
    k_batch = kwargs.get("k_batch", 1)
    jit_init = jax.jit(init_fn)
    # only `chunk` carries checks (init traces none), so only it needs the
    # checkify functionalization + throw wrapper. The production chunk
    # donates its carry: the old and new carries (ring, cache, model) are
    # never live together, which is what lets a published-width model fit
    # one chip. A caller must not read a carry after passing it to `chunk`.
    jit_chunk = (sanitize.wrap_checked(chunk_fn) if do_checkify
                 else jax.jit(chunk_fn, donate_argnums=0))
    if mesh is None:
        return ChunkedStalenessRunner(
            lambda key, lr: jit_init(key, lr, w0), jit_chunk, marks, tau_max,
            kwargs.get("layout", "flat"), guards=guards,
            resync_every=resync_every, checkify_invariants=do_checkify,
            k_batch=k_batch, jit_chunk=jit_chunk)

    def init(key, lr):
        with use_rules(mesh):
            return jit_init(key, lr, w0)

    def chunk(carry, *args):
        with use_rules(mesh):
            return jit_chunk(carry, *args)

    return ChunkedStalenessRunner(init, chunk, marks, tau_max,
                                  kwargs.get("layout", "flat"), mesh,
                                  guards=guards, resync_every=resync_every,
                                  checkify_invariants=do_checkify,
                                  k_batch=k_batch, jit_chunk=jit_chunk)


def _window_slack(n_clients: int, rejoin_at, windows) -> int:
    """Extra events for freeze fast-forward jumps: each all-gone freeze burns
    exactly one event and jumps to a strictly later rejoin, so at most
    `n_clients` events are ever lost to freezes."""
    return n_clients if (rejoin_at is not None or windows is not None) else 0


def _make_runner(mesh, **kwargs):
    """Dispatch runner construction on `mesh`: None -> the plain jitted
    runner; a Mesh -> the sharded GSPMD variant (lazy import — scan_sharded
    imports this module)."""
    if mesh is None:
        return make_staleness_runner(**kwargs)
    from repro.core.scan_sharded import make_sharded_staleness_runner
    return make_sharded_staleness_runner(mesh=mesh, **kwargs)


def run_staleness_scan(*, grad_fn: Callable, params0, aggregator: Aggregator,
                       n_clients: int, server_lr, T: int, beta: float = 5.0,
                       tau_max: Optional[int] = None, speed_skew: float = 0.0,
                       dropout_frac: float = 0.0,
                       dropout_at: Optional[int] = None,
                       rejoin_at: Optional[int] = None, windows=None,
                       eval_fn: Optional[Callable] = None,
                       eval_every: Optional[int] = None,
                       n_events: Optional[int] = None, local_steps: int = 1,
                       local_lr: float = 0.05, init_cache_grads: bool = True,
                       seed: int = 0, record_w: bool = False,
                       mesh=None, layout: str = "flat",
                       history_dtype: str = "float32",
                       faults: Optional[FaultSchedule] = None,
                       clip_norm: float = 0.0,
                       resync_every: Optional[int] = None,
                       k_batch: int = 1) -> ScanResult:
    """One device-resident run, trajectory-equivalent to
    ``StalenessSimulator(..., replay=build_staleness_randomness(seed, ...))``
    given the same arguments — including the eval cadence: with `eval_fn` and
    `eval_every`, `ScanResult.evals`/`eval_ts` match `SimResult` exactly.
    With `mesh` (a (data, model) jax Mesh), the run executes the sharded
    GSPMD variant (repro/core/scan_sharded.py) — same trajectory ≤1e-5.
    With ``layout="tree"``, `grad_fn` takes the params pytree (no ravel on
    the hot path) and `ScanResult.w` is the raveled final model — the same
    ≤1e-5 contract vs the flat/host paths holds for the f32 history ring.
    ``faults`` (a `FaultSchedule`) / ``clip_norm`` turn on the guard
    pipeline (same semantics as `StalenessSimulator(faults=..., ...)` — the
    ≤1e-5 replay contract extends to faulted runs); ``resync_every``
    enables the periodic exact recompute of incremental aggregator sums."""
    guards = faults is not None or clip_norm > 0
    if faults is not None:
        if n_events is not None and n_events != faults.n_events:
            raise ValueError(
                f"n_events={n_events} != faults.n_events={faults.n_events}")
        fault_lanes = (faults.kind.shape[1] if faults.kind.ndim == 2 else 1)
        if fault_lanes != k_batch:
            raise ValueError(
                f"faults built for k_batch={fault_lanes} but the engine "
                f"runs k_batch={k_batch} — rebuild the schedule with "
                "build_fault_schedule(..., k_batch=k_batch)")
        n_events = faults.n_events
    if n_events is None:
        # each tick still emits ≤1 server update, so the K=1 tick budget
        # remains sufficient for K>1 (a batch never emits more than once)
        n_events = (default_n_events(aggregator, T, init_cache_grads)
                    + _window_slack(n_clients, rejoin_at, windows))
    rand = build_staleness_randomness(seed, n_events, n_clients, beta,
                                      dropout_frac, speed_skew,
                                      dropout_at=dropout_at,
                                      rejoin_at=rejoin_at, windows=windows,
                                      k_batch=k_batch)
    marks = (eval_marks_for(T, eval_every or T)
             if eval_fn is not None else None)
    runner = _make_runner(
        mesh, grad_fn=grad_fn, params0=params0, aggregator=aggregator,
        n_clients=n_clients, T=T, beta=beta,
        server_lr=server_lr if callable(server_lr) else None,
        tau_max=tau_max, speed_skew=speed_skew, eval_marks=marks,
        local_steps=local_steps, local_lr=local_lr,
        init_cache_grads=init_cache_grads, record_w=record_w,
        layout=layout, history_dtype=history_dtype,
        guards=guards, resync_every=resync_every, k_batch=k_batch)
    lr = jnp.float32(0.0 if callable(server_lr) else server_lr)
    guard_args = ()
    if guards:
        fa = faults if faults is not None else no_faults(n_events, k_batch)
        guard_args = (fa.kind, fa.scale, jnp.float32(clip_norm))
    w, _, outs, extras = runner(jax.random.PRNGKey(seed), rand.gumbels,
                                rand.tau_raw, rand.leave_at, rand.rejoin_at,
                                lr, *guard_args)
    if layout == "tree":
        w = ravel_pytree(w)[0]
    evals, eval_ts = [], []
    if marks is not None:
        unravel = None if layout == "tree" else ravel_pytree(params0)[1]
        evals, eval_ts = _apply_evals(extras["snaps"], extras["hits"], marks,
                                      eval_fn, unravel)
    wants_init = init_cache_grads and wants_cache_init(aggregator)
    return _to_result(w, outs, T, n_clients if wants_init else 0,
                      evals=evals, eval_ts=eval_ts)


def _staleness_batch(seeds: Sequence[int], *, n_events: int, n_clients: int,
                     beta: float, dropout_frac: float, speed_skew: float,
                     dropout_at: Optional[int] = None,
                     rejoin_at: Optional[int] = None, windows=None,
                     k_batch: int = 1):
    """Stack per-seed randomness and PRNG keys on host (pure precompute)."""
    keys, gum, tau, leave, rejoin = [], [], [], [], []
    for s in seeds:
        r = build_staleness_randomness(s, n_events, n_clients, beta,
                                       dropout_frac, speed_skew,
                                       dropout_at=dropout_at,
                                       rejoin_at=rejoin_at, windows=windows,
                                       k_batch=k_batch)
        keys.append(jax.random.PRNGKey(s))
        gum.append(r.gumbels)
        tau.append(r.tau_raw)
        leave.append(r.leave_at)
        rejoin.append(r.rejoin_at)
    return (jnp.stack(keys), jnp.stack(gum), jnp.stack(tau),
            jnp.stack(leave), jnp.stack(rejoin))


def _staleness_results(ws, outs, extras, n_runs: int, T: int, n_init: int,
                       marks, eval_fn, unravel) -> List[ScanResult]:
    jax.block_until_ready(ws)
    results = []
    for i in range(n_runs):
        evals, eval_ts = [], []
        if marks is not None and eval_fn is not None and "snaps" in extras:
            evals, eval_ts = _apply_evals(extras["snaps"][i],
                                          extras["hits"][i], marks,
                                          eval_fn, unravel)
        results.append(_to_result(ws[i], jax.tree.map(lambda o: o[i], outs),
                                  T, n_init, evals=evals, eval_ts=eval_ts))
    return results


def run_staleness_seeds(*, grad_fn: Callable, params0,
                        aggregator: Aggregator, n_clients: int, server_lr,
                        T: int, seeds: Sequence[int], beta: float = 5.0,
                        tau_max: Optional[int] = None, speed_skew: float = 0.0,
                        dropout_frac: float = 0.0,
                        dropout_at: Optional[int] = None,
                        rejoin_at: Optional[int] = None, windows=None,
                        eval_fn: Optional[Callable] = None,
                        eval_every: Optional[int] = None,
                        n_events: Optional[int] = None, local_steps: int = 1,
                        local_lr: float = 0.05, init_cache_grads: bool = True,
                        runner=None, mesh=None,
                        fault_rates: Optional[Dict[str, float]] = None,
                        clip_norm: float = 0.0,
                        resync_every: Optional[int] = None,
                        k_batch: int = 1) -> List[ScanResult]:
    """vmap one compiled runner over seeds — the whole batch of staleness
    trajectories is one XLA computation. Pass `runner` (a
    `make_staleness_runner` result with matching statics, including
    `eval_marks` when `eval_fn`/`eval_every` are given) to reuse a compiled
    runner across calls, e.g. across an lr grid. With `mesh`, the runner is
    the sharded variant (repro/core/scan_sharded.py) and every per-run cache/
    ring/snapshot buffer lays out over the (data, model) mesh.
    ``fault_rates`` (kwargs for `build_fault_schedule`, per-seed schedules) /
    ``clip_norm`` turn on the guard pipeline; ``resync_every`` the periodic
    incremental-state recompute. A passed-in `runner` must have matching
    `guards`/`resync_every` statics."""
    guards = bool(fault_rates) or clip_norm > 0
    if n_events is None:
        n_events = (default_n_events(aggregator, T, init_cache_grads)
                    + _window_slack(n_clients, rejoin_at, windows))
    batch = _staleness_batch(seeds, n_events=n_events, n_clients=n_clients,
                             beta=beta, dropout_frac=dropout_frac,
                             speed_skew=speed_skew, dropout_at=dropout_at,
                             rejoin_at=rejoin_at, windows=windows,
                             k_batch=k_batch)
    marks = (eval_marks_for(T, eval_every or T)
             if eval_fn is not None else None)
    if runner is None:
        runner = _make_runner(
            mesh, grad_fn=grad_fn, params0=params0, aggregator=aggregator,
            n_clients=n_clients, T=T, beta=beta,
            server_lr=server_lr if callable(server_lr) else None,
            tau_max=tau_max, speed_skew=speed_skew, eval_marks=marks,
            local_steps=local_steps, local_lr=local_lr,
            init_cache_grads=init_cache_grads,
            guards=guards, resync_every=resync_every, k_batch=k_batch,
            # vmapped sweeps are never checkified: a batched checkify error
            # can't throw per-lane (use the single/chunked runners to debug)
            checkify_invariants=False)
    lr = 0.0 if callable(server_lr) else float(server_lr)
    lrs = jnp.full((len(seeds),), lr, jnp.float32)
    guard_batch = ()
    if guards:
        # per-seed fault schedules: seed s draws its own schedule, so the
        # sweep covers schedule variation exactly like the randomness streams
        fas = [build_fault_schedule(s, n_events, k_batch=k_batch,
                                    **(fault_rates or {}))
               for s in seeds]
        guard_batch = (jnp.stack([f.kind for f in fas]),
                       jnp.stack([f.scale for f in fas]),
                       jnp.full((len(seeds),), clip_norm, jnp.float32))
    ws, _, outs, extras = jax.vmap(runner)(*batch, lrs, *guard_batch)
    wants_init = init_cache_grads and wants_cache_init(aggregator)
    return _staleness_results(ws, outs, extras, len(seeds), T,
                              n_clients if wants_init else 0,
                              marks, eval_fn, ravel_pytree(params0)[1])


def run_staleness_grid(*, grad_fn: Callable, params0, aggregator: Aggregator,
                       n_clients: int, lrs: Sequence[float], T: int,
                       seeds: Sequence[int], beta: float = 5.0,
                       tau_max: Optional[int] = None, speed_skew: float = 0.0,
                       dropout_frac: float = 0.0,
                       dropout_at: Optional[int] = None,
                       rejoin_at: Optional[int] = None, windows=None,
                       eval_fn: Optional[Callable] = None,
                       eval_every: Optional[int] = None,
                       n_events: Optional[int] = None, local_steps: int = 1,
                       local_lr: float = 0.05, init_cache_grads: bool = True,
                       runner=None, mesh=None,
                       fault_rates: Optional[Dict[str, float]] = None,
                       clip_norm: float = 0.0,
                       resync_every: Optional[int] = None,
                       k_batch: int = 1) -> List[List[ScanResult]]:
    """The lr-tuning grid × seed sweep as ONE vmapped computation: per-seed
    randomness is tiled across the lr axis (same trajectories, different
    step sizes — exactly the host grid in benchmarks/common.py `tuned`).
    Returns ``results[i_lr][i_seed]``. `mesh` picks the sharded runner.
    ``fault_rates``/``clip_norm``/``resync_every`` as in
    `run_staleness_seeds` — per-seed schedules broadcast across the lr axis
    like the rest of the randomness."""
    guards = bool(fault_rates) or clip_norm > 0
    if n_events is None:
        n_events = (default_n_events(aggregator, T, init_cache_grads)
                    + _window_slack(n_clients, rejoin_at, windows))
    batch = _staleness_batch(seeds, n_events=n_events, n_clients=n_clients,
                             beta=beta, dropout_frac=dropout_frac,
                             speed_skew=speed_skew, dropout_at=dropout_at,
                             rejoin_at=rejoin_at, windows=windows,
                             k_batch=k_batch)
    marks = (eval_marks_for(T, eval_every or T)
             if eval_fn is not None else None)
    L, ns = len(lrs), len(seeds)
    if runner is None:
        runner = _make_runner(
            mesh, grad_fn=grad_fn, params0=params0, aggregator=aggregator,
            n_clients=n_clients, T=T, beta=beta,
            tau_max=tau_max, speed_skew=speed_skew, eval_marks=marks,
            local_steps=local_steps, local_lr=local_lr,
            init_cache_grads=init_cache_grads,
            guards=guards, resync_every=resync_every, k_batch=k_batch,
            checkify_invariants=False)   # vmapped: see run_staleness_seeds
    guard_batch, g_in, g_out = (), (), ()
    if guards:
        fas = [build_fault_schedule(s, n_events, k_batch=k_batch,
                                    **(fault_rates or {}))
               for s in seeds]
        guard_batch = (jnp.stack([f.kind for f in fas]),
                       jnp.stack([f.scale for f in fas]),
                       jnp.full((ns,), clip_norm, jnp.float32))
        g_in, g_out = (0, 0, 0), (None, None, None)
    # nested vmap: the lr axis broadcasts the per-seed randomness
    # (in_axes=None) instead of host-materialising L copies of the
    # (ns, n_events, n) gumbel stack — the (n_events, n) rows are stored
    # once per seed, not once per (lr, seed) grid cell
    grid_run = jax.vmap(
        jax.vmap(runner, in_axes=(0, 0, 0, 0, 0, None) + g_in),
        in_axes=(None, None, None, None, None, 0) + g_out)
    ws, _, outs, extras = grid_run(*batch, jnp.asarray(lrs, jnp.float32),
                                   *guard_batch)
    # flatten (L, ns, ...) -> (L*ns, ...): cell i*ns+j is (lr i, seed j)
    flat2 = lambda x: x.reshape((L * ns,) + x.shape[2:])
    ws = flat2(ws)
    outs = jax.tree.map(flat2, outs)
    extras = jax.tree.map(flat2, extras)
    wants_init = init_cache_grads and wants_cache_init(aggregator)
    flat = _staleness_results(ws, outs, extras, L * ns, T,
                              n_clients if wants_init else 0,
                              marks, eval_fn, ravel_pytree(params0)[1])
    return [flat[i * ns:(i + 1) * ns] for i in range(L)]

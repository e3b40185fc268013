"""Event-batched server steps (ISSUE 9): K arrivals consumed per scan tick.

Pins the tentpole contracts:
  * the scan's trajectories at K = 1 and K = 4 are bit for bit those
    recorded in `tests/data/k_batch_golden.npz` (see `GOLDEN`);
  * K>1 device scans replay the host K-batch `StalenessSimulator` reference
    ≤1e-5 for all five production algorithms (Gumbel top-k sampling, per-lane
    payload keys, one aggregated server update per tick), on the flat
    quadratic, the tree-layout LM task and the 8-device sharded three-way;
  * ACED's (P, max_cohort) cohort owner-ring retires same-step cohorts
    whole and disowns re-arrivals anywhere in the ring — pinned against the
    exact `resync` recompute, the K=1 thaw-jump path included (satellite:
    the 1-D ring's "≤1 expiring owner per slot" assumption silently kept
    all-but-one expired cohort member in asum/count);
  * chunked K-batch execution composes bit-identically with the one-shot
    scan, including a chunk size that does NOT divide the event budget (the
    train driver's partial-final-chunk path);
  * constructor/validation guards: K > n_clients, an undersized max_cohort
    and a mis-shaped fault schedule are rejected up front.
"""
import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core.aggregators import (ACED, ACEIncremental, Arrival,
                                    ArrivalBatch, CA2FL, FedBuff,
                                    VanillaASGD)
from repro.core.scan_engine import default_n_events
from repro.core.scan_staleness import (build_fault_schedule,
                                       build_staleness_randomness,
                                       make_chunked_staleness_runner,
                                       make_staleness_runner,
                                       run_staleness_scan)
from repro.core.staleness_sim import StalenessSimulator

N, D, T, BETA, LR, SEED = 6, 16, 30, 3.0, 0.05, 1
K = 4


def _agg(algo, k=1):
    return {
        "asgd": lambda: VanillaASGD(),
        "fedbuff": lambda: FedBuff(buffer_size=4),
        "ca2fl": lambda: CA2FL(buffer_size=3),
        "ace": lambda: ACEIncremental(),
        "aced": lambda: ACED(tau_algo=5, max_cohort=max(k, 1)),
    }[algo]()


@functools.lru_cache(maxsize=2)
def _quad(n=N):
    rng = np.random.default_rng(0)
    C = jnp.asarray(rng.normal(size=(n, D)) * 2.0, jnp.float32)

    def grad_fn(params, client, key):
        g = params - C[client] + 0.2 * jax.random.normal(key, params.shape)
        return 0.5 * jnp.sum((params - C[client]) ** 2), g
    return grad_fn, jnp.zeros((D,), jnp.float32)


def _pair(algo, k, n=N, t=T, faults=None, clip_norm=0.0, resync_every=None,
          mesh=None):
    """One (host reference, device scan) run pair on the shared stream."""
    grad_fn, params0 = _quad(n)
    n_events = default_n_events(_agg(algo, k), t)
    if faults is not None:
        n_events = faults.kind.shape[0]
    rand = build_staleness_randomness(SEED, n_events, n, BETA, k_batch=k)
    sim = StalenessSimulator(
        grad_fn=grad_fn, params0=params0, aggregator=_agg(algo, k),
        n_clients=n, server_lr=LR, beta=BETA, seed=SEED, replay=rand,
        k_batch=k, faults=faults, clip_norm=clip_norm,
        resync_every=resync_every)
    hr = sim.run(t)
    sr = run_staleness_scan(
        grad_fn=grad_fn, params0=params0, aggregator=_agg(algo, k),
        n_clients=n, server_lr=LR, T=t, beta=BETA, seed=SEED, k_batch=k,
        n_events=n_events, faults=faults, clip_norm=clip_norm,
        resync_every=resync_every, mesh=mesh)
    return sim, hr, sr


# ---------------------------------------------------------------------------
# K=1 bit-identity + K>1 host parity
# ---------------------------------------------------------------------------

ALGOS = ["asgd", "fedbuff", "ca2fl", "ace", "aced"]


#: Trajectories recorded once from the source of commit 833ab96, whose scan
#: still ran K = 1 and K > 1 on two separate tick bodies: running this module
#: as a script (``PYTHONPATH=<that commit's src> python tests/test_k_batch.py``)
#: writes every case of `GOLDEN_CASES` through `_golden_run` into this file.
#: The flat K = 4 cases were recorded again when the K-lane stale read became
#: whole-slot dynamic slices (`ring_read_lanes`): the rows read are the same
#: values, but without the gather's out-of-range fill the CPU compiler rounds
#: the fused payload arithmetic differently in the last bit. 833ab96's source
#: with its `jnp.take` in ``mode="clip"`` gives these cases bit for bit.
GOLDEN = Path(__file__).parent / "data" / "k_batch_golden.npz"
#: the five rules on the flat layout at K = 1 (named by the rule alone) and
#: K = 4, ACE on the int8 tree layout (int8 cache and history ring), and a
#: guarded, faulted ACED build with a resync cadence, each at both K
GOLDEN_CASES = (ALGOS + [f"{a}-k{K}" for a in ALGOS]
                + ["ace-tree-int8", f"ace-tree-int8-k{K}",
                   "aced-guarded", f"aced-guarded-k{K}"])
GOLDEN_KEYS = ("w", "losses", "ts", "update_norms", "emit", "faults")


def _golden_run(case):
    """The scan of one golden case: {key: array} over `GOLDEN_KEYS`, the
    guard counters as (quarantined, clipped, rejected)."""
    name, lanes, _ = case.partition(f"-k{K}")
    k = K if lanes else 1
    algo = name.split("-")[0]
    grad_fn, params0 = _quad()
    kw = dict(grad_fn=grad_fn, params0=params0, n_clients=N, server_lr=LR,
              T=T, beta=BETA, seed=SEED, k_batch=k)
    if name.endswith("tree-int8"):
        agg = ACEIncremental(cache_dtype="int8")
        tree0 = {"a": jnp.zeros((D // 2,), jnp.float32),
                 "b": jnp.zeros((2, D // 4), jnp.float32)}
        unravel = ravel_pytree(tree0)[1]

        def tree_grad(params, client, key):
            loss, g = grad_fn(ravel_pytree(params)[0], client, key)
            return loss, unravel(g)
        kw.update(grad_fn=tree_grad, params0=tree0, layout="tree",
                  history_dtype="int8")
    elif name.endswith("guarded"):
        agg = _agg(algo, k)
        n_events = default_n_events(agg, T) + 40
        kw.update(faults=build_fault_schedule(
            7, n_events, k_batch=k, nan_rate=0.1, explode_rate=0.08,
            byzantine_rate=0.08, overstale_rate=0.08),
            clip_norm=5.0, resync_every=8)
    else:
        agg = _agg(algo, k)
    r = run_staleness_scan(aggregator=agg, **kw)
    return {"w": np.asarray(r.w), "losses": np.asarray(r.losses),
            "ts": np.asarray(r.ts), "update_norms": np.asarray(r.update_norms),
            "emit": np.asarray(r.emit),
            "faults": np.asarray([r.faults.get(f, -1) for f in
                                  ("quarantined", "clipped", "rejected")])}


@pytest.mark.parametrize("algo", GOLDEN_CASES)
def test_k1_is_bit_identical_to_unbatched_engine(algo):
    """At K = 1 and K = 4 the one tick body reproduces, bit for bit, the
    trajectories recorded from the two separate tick bodies it replaced:
    model, losses, emit cadence, update norms and guard counters, on both
    layouts, with faults, guards and resync (`GOLDEN`)."""
    got = _golden_run(algo)
    with np.load(GOLDEN) as golden:
        for key in GOLDEN_KEYS:
            np.testing.assert_array_equal(got[key], golden[f"{algo}/{key}"],
                                          err_msg=f"{algo}: {key}")
    if algo.startswith("aced-guarded"):
        assert got["faults"].sum() > 0, "schedule injected nothing"


@pytest.mark.parametrize("algo", ALGOS)
def test_k4_matches_host_reference(algo):
    """Tentpole contract: the K-batched scan replays the host K-batch
    reference ≤1e-5 — trajectory, emit cadence and per-tick masked-mean
    losses — for every production algorithm."""
    sim, hr, sr = _pair(algo, K)
    assert list(np.asarray(sr.ts)) == list(hr.ts)
    assert np.max(np.abs(np.asarray(sr.w) - sim.w)) <= 1e-5
    np.testing.assert_allclose(sr.losses, hr.losses, rtol=1e-4, atol=1e-4)


def test_k16_wide_pool_matches_host_reference():
    """A wide batch (K=16 of 20 clients, most of the pool per tick) keeps
    the parity: collision-heavy sampling, near-full cohorts."""
    sim, hr, sr = _pair("aced", 16, n=20, t=12)
    assert list(np.asarray(sr.ts)) == list(hr.ts)
    assert np.max(np.abs(np.asarray(sr.w) - sim.w)) <= 1e-5


def test_k4_faulted_matches_host_reference():
    """Per-lane guards: a faulted K-batch run (NaN quarantine, explode/
    Byzantine clipping, over-stale rejection, periodic resync) replays the
    host ≤1e-5 with IDENTICAL per-kind guard counters."""
    agg = _agg("aced", K)
    n_events = default_n_events(agg, T) + 40
    faults = build_fault_schedule(7, n_events, k_batch=K, nan_rate=0.1,
                                  explode_rate=0.08, byzantine_rate=0.08,
                                  overstale_rate=0.08)
    sim, hr, sr = _pair("aced", K, faults=faults, clip_norm=5.0,
                        resync_every=8)
    assert np.isfinite(np.asarray(sr.w)).all()
    assert np.max(np.abs(np.asarray(sr.w) - sim.w)) <= 1e-5
    assert sr.faults == hr.faults
    assert sum(sr.faults.values()) > 0, "schedule injected nothing"


def test_tree_layout_k_batch_matches_host_on_lm_task():
    """The real-model path: tree payload lanes, batched tree-cache writes
    and the tree history ring under K=3 arrivals per tick replay the host
    reference ≤1e-5 on the reduced yi LM task."""
    from repro.configs.registry import get_config
    from repro.core.fl_tasks import make_lm_task
    cfg = get_config("yi-9b").reduced(layers=2, d_model=64, vocab=128)
    task = make_lm_task(cfg=cfg, n_clients=4, batch=2, seq=32,
                        n_tokens=1 << 14, seed=0)
    k, t = 3, 12
    agg = lambda: ACED(tau_algo=5, max_cohort=k)
    n_events = default_n_events(agg(), t)
    rand = build_staleness_randomness(SEED, n_events, 4, BETA, k_batch=k)
    sim = StalenessSimulator(
        grad_fn=task.grad_fn, params0=task.params0, aggregator=agg(),
        n_clients=4, server_lr=LR, beta=BETA, seed=SEED, replay=rand,
        k_batch=k)
    hr = sim.run(t)
    sr = run_staleness_scan(
        grad_fn=task.grad_fn, params0=task.params0, aggregator=agg(),
        n_clients=4, server_lr=LR, T=t, beta=BETA, seed=SEED, k_batch=k,
        layout="tree")
    assert list(np.asarray(sr.ts)) == list(hr.ts)
    assert np.max(np.abs(sr.w - np.asarray(sim.w))) <= 1e-5
    np.testing.assert_allclose(sr.losses, hr.losses, rtol=1e-4, atol=1e-4)


@pytest.mark.multidevice
@pytest.mark.parametrize("algo", ALGOS)
def test_sharded_k_batch_three_way(algo, device_mesh):
    """host K-batch reference vs unsharded vs 8-device sharded K-batch scan
    on one stream: the (data, model) mesh may only reorder reductions."""
    sim, hr, sr = _pair(algo, K)
    _, _, shr = _pair(algo, K, mesh=device_mesh)
    np.testing.assert_allclose(shr.w, sr.w, rtol=1e-5, atol=1e-5)
    assert list(np.asarray(shr.ts)) == list(np.asarray(sr.ts)) == list(hr.ts)
    assert np.max(np.abs(np.asarray(shr.w) - sim.w)) <= 1e-5


# ---------------------------------------------------------------------------
# chunked execution with K>1 (incl. the non-dividing tail)
# ---------------------------------------------------------------------------

def test_chunked_k_batch_composes_bit_identically():
    """Chunked K-batch execution == the one-shot K-batch scan, with a chunk
    size that does NOT divide the event budget: the final partial chunk is
    real protocol state, not padding (the train driver's tail path)."""
    grad_fn, params0 = _quad()
    n_events = default_n_events(_agg("aced", K), T)
    C = 7
    assert n_events % C != 0, "pick C so the tail chunk is partial"
    rand = build_staleness_randomness(SEED, n_events, N, BETA, k_batch=K)
    kw = dict(grad_fn=grad_fn, params0=params0,
              aggregator=_agg("aced", K), n_clients=N, T=T, beta=BETA,
              k_batch=K)
    one = make_staleness_runner(**kw)
    w1, _, outs1, _ = one(jax.random.PRNGKey(SEED), rand.gumbels,
                          rand.tau_raw, rand.leave_at, rand.rejoin_at,
                          jnp.float32(LR))
    runner = make_chunked_staleness_runner(**kw)
    carry = runner.init(jax.random.PRNGKey(SEED), jnp.float32(LR))
    losses = []
    for lo in range(0, n_events, C):
        hi = min(lo + C, n_events)
        carry, outs = runner.chunk(carry, rand.gumbels[lo:hi],
                                   rand.tau_raw[lo:hi], rand.leave_at,
                                   rand.rejoin_at, jnp.float32(LR))
        losses.append(np.asarray(outs["loss"]))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(carry["w"]))
    np.testing.assert_array_equal(np.concatenate(losses),
                                  np.asarray(outs1["loss"]))


# ---------------------------------------------------------------------------
# ACED cohort owner-ring (satellite: same-step collision expiry)
# ---------------------------------------------------------------------------

def _aced_batch(agg, state, clients, t, valid=None):
    js = jnp.asarray(clients, jnp.int32)
    k = js.shape[0]
    rng = np.random.default_rng(100 + int(t))
    payloads = jnp.asarray(rng.normal(size=(k, D)), jnp.float32)
    if valid is None:
        valid = jnp.ones((k,), jnp.bool_)
    return agg.step_batch(state, ArrivalBatch(
        clients=js, payloads=payloads, t=jnp.asarray(t, jnp.int32),
        staleness=jnp.zeros((k,), jnp.int32), valid=jnp.asarray(valid)))


def _assert_matches_resync(agg, state):
    healed = agg.resync(state)
    assert int(state["count"]) == int(healed["count"]), \
        (int(state["count"]), int(healed["count"]))
    np.testing.assert_allclose(np.asarray(state["asum"]),
                               np.asarray(healed["asum"]),
                               rtol=1e-5, atol=1e-5)


def test_aced_cohort_expires_whole_not_one_member():
    """Regression for the 1-D ring bug: clients {0,1,2} arrive as ONE
    cohort (shared t_start), then never again — when their slot ages out,
    ALL three must leave asum/count in the same sweep. The old ring kept a
    single owner per slot, so two of the three stayed active forever."""
    n, tau = 8, 2
    agg = ACED(tau_algo=tau, max_cohort=3)
    rng = np.random.default_rng(0)
    state = agg.init_state(n, D, jnp.asarray(rng.normal(size=(n, D)),
                                             jnp.float32))
    state, _, _, _ = _aced_batch(agg, state, [0, 1, 2], t=1)
    for t in range(2, 2 + tau + 3):     # cohort {0,1,2} must age out
        state, _, _, _ = _aced_batch(agg, state, [3 + (t % 3), 6, 7], t=t)
        _assert_matches_resync(agg, state)
    # after the sweep at t = t_start + tau + 1 NONE of {0,1,2} may linger:
    # not in the ring, not counted active (the 1-D ring retired only one of
    # them — the exact-recompute agreement above catches the stale asum)
    ring = np.asarray(state["ring"])
    t_prev, t_start = int(state["t_prev"]), np.asarray(state["t_start"])
    for j in (0, 1, 2):
        assert not np.any(ring == j), (j, ring)
        assert t_prev - t_start[j] > tau, (j, t_prev, t_start[j])


def test_aced_rearrival_disowns_old_cohort_slot():
    """A cohort member that re-arrives in a LATER cohort must be disowned
    from its old slot (anywhere in the ring): when the old slot expires,
    the re-arrived client stays active and the running sums stay exact."""
    n, tau = 8, 3
    agg = ACED(tau_algo=tau, max_cohort=3)
    rng = np.random.default_rng(1)
    state = agg.init_state(n, D, jnp.asarray(rng.normal(size=(n, D)),
                                             jnp.float32))
    state, _, _, _ = _aced_batch(agg, state, [0, 1, 2], t=1)
    # client 0 re-arrives at t=2 inside another cohort; 1 and 2 do not
    state, _, _, _ = _aced_batch(agg, state, [0, 3, 4], t=2)
    for t in range(3, 3 + tau + 3):
        state, _, _, _ = _aced_batch(agg, state, [5, 6, 7], t=t)
        _assert_matches_resync(agg, state)
        # client 0's fresher t_start must survive the {1,2} slot expiry
        active0 = int(state["t_prev"]) - int(state["t_start"][0]) <= tau
        ring_has_0 = bool(np.any(np.asarray(state["ring"]) == 0))
        assert active0 == ring_has_0


def test_aced_mixed_validity_cohort_is_partially_applied():
    """Invalid lanes of a cohort are perfect no-ops: the cache rows stay
    bit-exact, only valid lanes join the active set, and the running sums
    match the exact recompute."""
    n, tau = 8, 3
    agg = ACED(tau_algo=tau, max_cohort=3)
    rng = np.random.default_rng(2)
    state = agg.init_state(n, D, jnp.asarray(rng.normal(size=(n, D)),
                                             jnp.float32))
    cache_before = np.asarray(state["cache"].data).copy()
    state, _, _, _ = _aced_batch(agg, state, [0, 1, 2], t=1,
                                 valid=[True, False, True])
    np.testing.assert_array_equal(np.asarray(state["cache"].data)[1],
                                  cache_before[1])
    assert int(state["t_start"][1]) == 1        # lane 1 never arrived
    assert int(state["t_start"][0]) == 2
    _assert_matches_resync(agg, state)
    assert not np.any(np.asarray(state["ring"]) == 1)


def test_aced_k1_thaw_jump_through_cohort_ring():
    """max_cohort > 1 routes single arrivals through the batched transition:
    a frozen stretch (t jumping by more than one) must retire every aged
    slot — cohort ring and legacy ring agree with the exact recompute."""
    n, tau = 8, 2
    agg = ACED(tau_algo=tau, max_cohort=2)
    rng = np.random.default_rng(3)
    init = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    state = agg.init_state(n, D, init)
    for t, j in [(1, 0), (2, 1), (3, 2), (9, 3), (10, 4)]:   # 3 -> 9 jump
        payload = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
        state, _, _, _ = agg.step(state, Arrival(
            client=jnp.asarray(j, jnp.int32), payload=payload,
            t=jnp.asarray(t, jnp.int32),
            staleness=jnp.zeros((), jnp.int32)))
        _assert_matches_resync(agg, state)
    # after the jump only the t=9 and t=10 arrivals are active
    assert int(state["count"]) == 2


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------

def test_k_batch_validation():
    grad_fn, params0 = _quad()
    kw = dict(grad_fn=grad_fn, params0=params0, aggregator=VanillaASGD(),
              n_clients=N, server_lr=LR, T=T, beta=BETA, seed=SEED)
    with pytest.raises(ValueError, match="k_batch"):
        run_staleness_scan(k_batch=N + 1, **kw)
    # undersized cohort ring, both at engine-build and aggregator level
    with pytest.raises(ValueError, match="max_cohort"):
        run_staleness_scan(k_batch=2, **{
            **kw, "aggregator": ACED(tau_algo=5, max_cohort=1)})
    agg = ACED(tau_algo=5, max_cohort=1)
    state = agg.init_state(N, D, jnp.zeros((N, D), jnp.float32))
    with pytest.raises(ValueError, match="max_cohort"):
        _aced_batch(agg, state, [0, 1], t=1)


def test_host_k_batch_requires_replay_and_matching_faults():
    grad_fn, params0 = _quad()
    kw = dict(grad_fn=grad_fn, params0=params0, aggregator=VanillaASGD(),
              n_clients=N, server_lr=LR, beta=BETA, seed=SEED)
    with pytest.raises(ValueError, match="replay"):
        StalenessSimulator(k_batch=K, **kw)
    n_events = default_n_events(VanillaASGD(), T)
    rand = build_staleness_randomness(SEED, n_events, N, BETA, k_batch=K)
    flat_faults = build_fault_schedule(0, n_events, nan_rate=0.1)
    sim = StalenessSimulator(k_batch=K, replay=rand, faults=flat_faults,
                             clip_norm=5.0, **kw)
    with pytest.raises(ValueError, match="fault schedule"):
        sim.run(T)


if __name__ == "__main__":
    GOLDEN.parent.mkdir(exist_ok=True)
    np.savez(GOLDEN, **{f"{case}/{key}": value
                        for case in GOLDEN_CASES
                        for key, value in _golden_run(case).items()})

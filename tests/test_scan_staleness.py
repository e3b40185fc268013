"""Scanned-staleness engine: trajectory equivalence against the host
`StalenessSimulator` under seed-matched RNG replay (all five algorithms,
with/without dropout, leave/re-join availability windows, speed-skew, both
τ-cap regimes, in-scan eval cadence), ring-buffer vs deque semantics, and
the seed/lr-grid vmap paths."""
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregators import (ACED, ACEDDirect, ACEDirect,
                                    ACEIncremental, CA2FL, CA2FLDirect,
                                    FedBuff, VanillaASGD)
from repro.core.cache import flat_row_shape
from repro.core.scan_engine import default_n_events
from repro.core.scan_staleness import (NEVER, build_staleness_randomness,
                                       eval_marks_for, make_staleness_runner,
                                       ring_append, ring_read,
                                       ring_read_lanes,
                                       run_staleness_grid,
                                       run_staleness_scan,
                                       run_staleness_seeds)
from repro.core.staleness_sim import StalenessSimulator


def quad_grad_fn(n, d, zeta=2.0, sigma=0.2, seed=0):
    rng = np.random.default_rng(seed)
    C = jnp.asarray(rng.normal(size=(n, d)) * zeta)

    def grad_fn(params, client, key):
        g = params - C[client] + sigma * jax.random.normal(key, (d,))
        return 0.5 * jnp.sum((params - C[client]) ** 2), g
    return grad_fn


AGGS = {
    "asgd": lambda: VanillaASGD(),
    "fedbuff": lambda: FedBuff(buffer_size=4),
    "ca2fl": lambda: CA2FL(buffer_size=4),
    "ace": lambda: ACEIncremental(),
    "aced": lambda: ACED(tau_algo=5),
}


def _quad_eval_fn(params):
    return {"dist": float(jnp.sqrt(jnp.sum(params ** 2)))}


def _host_and_scan(algo, *, n=8, d=6, T=40, beta=2.0, seed=0, tau_max=None,
                   speed_skew=0.0, dropout_frac=0.0, dropout_at=None,
                   rejoin_at=None, windows=None, eval_every=None,
                   server_lr=0.05, k_batch=1):
    """Run host (replay mode) and scan on the same random stream."""
    grad_fn = quad_grad_fn(n, d)

    def agg():
        if algo == "aced" and k_batch > 1:
            return ACED(tau_algo=5, max_cohort=k_batch)
        return AGGS[algo]()
    n_events = default_n_events(agg(), T)
    if rejoin_at is not None or windows is not None:
        n_events += n                       # freeze fast-forward slack
    rand = build_staleness_randomness(seed, n_events, n, beta, dropout_frac,
                                      speed_skew, dropout_at=dropout_at,
                                      rejoin_at=rejoin_at, windows=windows,
                                      k_batch=k_batch)
    eval_fn = _quad_eval_fn if eval_every else None
    sim = StalenessSimulator(
        grad_fn=grad_fn, params0=jnp.zeros(d), aggregator=agg(),
        n_clients=n, server_lr=server_lr, beta=beta, tau_max=tau_max,
        speed_skew=speed_skew, dropout_frac=dropout_frac,
        dropout_at=dropout_at, rejoin_at=rejoin_at, windows=windows,
        eval_fn=eval_fn, eval_every=eval_every or T, seed=seed, replay=rand,
        k_batch=k_batch)
    hr = sim.run(T)
    sr = run_staleness_scan(
        grad_fn=grad_fn, params0=jnp.zeros(d), aggregator=agg(),
        n_clients=n, server_lr=server_lr, T=T, beta=beta, tau_max=tau_max,
        speed_skew=speed_skew, dropout_frac=dropout_frac,
        dropout_at=dropout_at, rejoin_at=rejoin_at, windows=windows,
        eval_fn=eval_fn, eval_every=eval_every, seed=seed, k_batch=k_batch,
        n_events=n_events if k_batch > 1 else None)
    return sim, hr, sr


def _assert_equivalent(sim, hr, sr, comms=True):
    assert np.max(np.abs(sr.w - np.asarray(sim.w))) <= 1e-5
    assert len(sr.losses) == len(hr.losses)
    np.testing.assert_allclose(sr.losses, hr.losses, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sr.update_norms, hr.update_norms,
                               rtol=1e-4, atol=1e-5)
    assert sr.ts.tolist() == hr.ts
    if comms:
        assert sr.total_comms == hr.total_comms
    assert sr.eval_ts == hr.eval_ts
    for se, he in zip(sr.evals, hr.evals):
        assert set(se) == set(he)
        for k in se:
            np.testing.assert_allclose(se[k], he[k], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("algo", sorted(AGGS))
def test_staleness_scan_matches_host(algo):
    """Same seed => host replay and scan trajectories agree to <= 1e-5."""
    _assert_equivalent(*_host_and_scan(algo))


@pytest.mark.parametrize("algo", ["aced", "asgd", "fedbuff"])
def test_staleness_scan_matches_host_with_dropout(algo):
    """Permanent dropout at T/2: traced-t logits mask == host dropped set."""
    sim, hr, sr = _host_and_scan(algo, n=10, T=60, dropout_frac=0.5,
                                 dropout_at=30)
    _assert_equivalent(sim, hr, sr)


@pytest.mark.parametrize("algo", ["ace", "ca2fl", "asgd"])
def test_staleness_scan_matches_host_speed_skew(algo):
    """speed_skew>0: weighted categorical sampling (participation imbalance)."""
    _assert_equivalent(*_host_and_scan(algo, speed_skew=2.0))


def test_staleness_scan_dropout_plus_skew():
    """The Fig. 3 worst case: imbalanced sampling AND a skew-weighted dropout
    set drawn from the same stream."""
    sim, hr, sr = _host_and_scan("aced", n=10, T=60, speed_skew=1.5,
                                 dropout_frac=0.3, dropout_at=20)
    _assert_equivalent(sim, hr, sr)
    assert len(sr.losses) == 59          # cache init consumes iteration 0


def test_staleness_scan_all_dropped_freezes_like_host_stop():
    """dropout_frac=1.0: the host reference breaks out of the loop; the scan
    gates every later emission, so the final model still matches."""
    sim, hr, sr = _host_and_scan("asgd", n=6, T=40, dropout_frac=1.0,
                                 dropout_at=15)
    assert len(hr.losses) == 15                  # host stopped at the trigger
    _assert_equivalent(sim, hr, sr)              # incl. comms: frozen events
    assert sr.total_comms == 15                  # are not counted as popped


def test_staleness_scan_tau_capped_at_tau_max():
    """beta >> tau_max: nearly every draw hits the tau_max clamp."""
    _assert_equivalent(*_host_and_scan("asgd", beta=50.0, tau_max=7, T=30))


def test_staleness_scan_tau_capped_by_history_length():
    """Early iterations: tau is clamped to the t models that exist, i.e. the
    deque's len(history)-1 — the ring must never read unwritten slots."""
    _assert_equivalent(*_host_and_scan("ace", beta=30.0, T=25))


def test_staleness_dropout_shrinks_participation():
    """After dropout_at, dropped clients never arrive again in the scan."""
    n, d, T = 10, 5, 80
    grad_fn = quad_grad_fn(n, d)
    n_events = default_n_events(VanillaASGD(), T)
    rand = build_staleness_randomness(3, n_events, n, 2.0, 0.5, 0.0,
                                      dropout_at=T // 2)
    runner = make_staleness_runner(
        grad_fn=grad_fn, params0=jnp.zeros(d), aggregator=VanillaASGD(),
        n_clients=n, T=T, beta=2.0, record_w=True)
    w, _, outs, _ = runner(jax.random.PRNGKey(3), rand.gumbels, rand.tau_raw,
                           rand.leave_at, rand.rejoin_at, jnp.float32(0.05))
    # recover arrivals from the logits the scan used
    dropped = np.asarray(rand.dropped)
    logp = np.log(np.full(n, 1.0 / n)).astype(np.float32)
    g = np.asarray(rand.gumbels)
    ts = np.asarray(outs["t"])
    late = ts >= T // 2
    arrive_late = np.argmax(np.where(dropped, -np.inf, logp) + g[late], axis=1)
    assert not set(arrive_late.tolist()) & set(np.flatnonzero(dropped))


# ---------------------------------------------------------------------------
# Availability windows (leave / re-join) and the in-scan eval cadence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", sorted(AGGS))
def test_staleness_scan_matches_host_with_windows(algo):
    """Staggered per-client leave/re-join windows (a mid-run absence, a late
    joiner, a permanent dropout) for every algorithm."""
    n, T = 10, 60
    leave = np.full(n, NEVER, np.int64)
    rejoin = np.full(n, NEVER, np.int64)
    leave[2], rejoin[2] = 10, 30           # mid-run absence
    leave[5], rejoin[5] = 0, 20            # late joiner
    leave[7] = 25                          # permanent dropout
    sim, hr, sr = _host_and_scan(algo, n=n, T=T, windows=(leave, rejoin))
    _assert_equivalent(sim, hr, sr)


@pytest.mark.parametrize("algo", sorted(AGGS))
def test_staleness_scan_freeze_thaw_all_left(algo):
    """Every client inside its window at once: the run freezes (model and
    aggregator state held), fast-forwards to the earliest rejoin, and resumes
    — event-for-event matched to the host jump."""
    n, T = 8, 50
    leave = np.full(n, 12, np.int64)
    rejoin = np.full(n, 22, np.int64)
    rejoin[3] = 30                          # one client stays away longer
    sim, hr, sr = _host_and_scan(algo, n=n, T=T, windows=(leave, rejoin),
                                 eval_every=10)
    _assert_equivalent(sim, hr, sr)
    # no server iterations happen inside the frozen gap
    assert not [t for t in hr.ts if 12 < t < 22]
    if hr.ts:                               # the run resumes after the thaw
        assert max(hr.ts) >= 22


@pytest.mark.parametrize("algo", ["ace", "aced", "ca2fl"])
def test_staleness_scan_freeze_thaw_all_left_k4(algo):
    """The freeze at K = 4 arrivals a tick: a frozen tick has no valid lane,
    so the per-client cache is held by its lanes alone and every other
    leaf by the tick's select — event for event matched to the host K-batch
    reference's jump."""
    n, T = 8, 50
    leave = np.full(n, 12, np.int64)
    rejoin = np.full(n, 22, np.int64)
    rejoin[3] = 30                          # one client stays away longer
    sim, hr, sr = _host_and_scan(algo, n=n, T=T, windows=(leave, rejoin),
                                 eval_every=10, k_batch=4)
    # at K > 1 the scan counts a tick as one communication, the host each
    # live lane
    _assert_equivalent(sim, hr, sr, comms=False)
    assert not [t for t in hr.ts if 12 < t < 22]
    assert max(hr.ts) >= 22


def test_staleness_scan_legacy_rejoin_scalar():
    """dropout_frac/dropout_at + scalar rejoin_at: the drawn set leaves and
    comes back — the fig3 re-join scenario."""
    sim, hr, sr = _host_and_scan("aced", n=10, T=60, dropout_frac=0.5,
                                 dropout_at=20, rejoin_at=40, eval_every=15)
    _assert_equivalent(sim, hr, sr)


@pytest.mark.parametrize("algo", ["asgd", "fedbuff", "aced"])
def test_staleness_scan_eval_cadence_matches_host(algo):
    """In-scan snapshots evaluated post-scan == host SimResult.evals at the
    identical cadence (incl. the t == T mark)."""
    sim, hr, sr = _host_and_scan(algo, T=40, eval_every=7)
    _assert_equivalent(sim, hr, sr)
    assert sr.eval_ts == [7, 14, 21, 28, 35, 40]
    assert len(sr.evals) == 6
    assert sr.final_eval() == sr.evals[-1]


def test_eval_marks_for_cadence():
    assert eval_marks_for(40, 7) == (7, 14, 21, 28, 35, 40)
    assert eval_marks_for(40, 10) == (10, 20, 30, 40)
    assert eval_marks_for(5, 100) == (5,)
    assert eval_marks_for(40, None) is None


# ---------------------------------------------------------------------------
# Incremental O(d) rules vs their pinned O(n·d) direct references, at the
# scan level (the other two zoo members, asgd/fedbuff, have no cache to
# re-reduce; their host/scan equivalence is pinned above)
# ---------------------------------------------------------------------------

_PAIRS = {
    "ace": (lambda dt: ACEIncremental(cache_dtype=dt),
            lambda dt: ACEDirect(cache_dtype=dt)),
    "aced": (lambda dt: ACED(tau_algo=5, cache_dtype=dt),
             lambda dt: ACEDDirect(tau_algo=5, cache_dtype=dt)),
    "ca2fl": (lambda dt: CA2FL(buffer_size=4, cache_dtype=dt),
              lambda dt: CA2FLDirect(buffer_size=4, cache_dtype=dt)),
}

_DIFF_SCENARIOS = {
    "dropout": ("float32", dict(n=10, T=60, dropout_frac=0.5, dropout_at=30)),
    "rejoin": ("float32", dict(n=10, T=60, dropout_frac=0.5, dropout_at=20,
                               rejoin_at=40)),
    "freeze_thaw": ("float32", "windows"),
    "int8": ("int8", {}),
}


def _diff_incremental_vs_direct(pair, scenario):
    """scan(incremental) == scan(direct) == host-replay(direct), one random
    stream. Both rules emit identically, so the trajectories are comparable
    event-for-event; any O(d)-state drift from the masked/whole-cache
    re-reduction shows up here."""
    dtype, kw = _DIFF_SCENARIOS[scenario]
    inc_f, dir_f = _PAIRS[pair]
    n, T, beta, seed = 8, 40, 2.0, 0
    if kw == "windows":
        leave = np.full(n, 12, np.int64)
        rejoin = np.full(n, 22, np.int64)
        rejoin[3] = 30
        kw = dict(n=n, T=50, windows=(leave, rejoin))
    n = kw.get("n", n)
    T = kw.get("T", T)
    grad_fn = quad_grad_fn(n, 6)
    n_events = default_n_events(dir_f(dtype), T)
    if kw.get("rejoin_at") is not None or kw.get("windows") is not None:
        n_events += n
    rand = build_staleness_randomness(
        seed, n_events, n, beta, kw.get("dropout_frac", 0.0), 0.0,
        dropout_at=kw.get("dropout_at"), rejoin_at=kw.get("rejoin_at"),
        windows=kw.get("windows"))
    run_kw = dict(grad_fn=grad_fn, params0=jnp.zeros(6), n_clients=n,
                  server_lr=0.05, T=T, beta=beta, seed=seed,
                  dropout_frac=kw.get("dropout_frac", 0.0),
                  dropout_at=kw.get("dropout_at"),
                  rejoin_at=kw.get("rejoin_at"), windows=kw.get("windows"))
    sr_inc = run_staleness_scan(aggregator=inc_f(dtype), **run_kw)
    sr_dir = run_staleness_scan(aggregator=dir_f(dtype), **run_kw)
    sim = StalenessSimulator(
        grad_fn=grad_fn, params0=jnp.zeros(6), aggregator=dir_f(dtype),
        n_clients=n, server_lr=0.05, beta=beta,
        dropout_frac=kw.get("dropout_frac", 0.0),
        dropout_at=kw.get("dropout_at"), rejoin_at=kw.get("rejoin_at"),
        windows=kw.get("windows"), seed=seed, replay=rand)
    hr_dir = sim.run(T)
    assert sr_inc.ts.tolist() == sr_dir.ts.tolist() == hr_dir.ts
    assert np.max(np.abs(sr_inc.w - sr_dir.w)) <= 1e-5
    assert np.max(np.abs(sr_dir.w - np.asarray(sim.w))) <= 1e-5
    np.testing.assert_allclose(sr_inc.update_norms, sr_dir.update_norms,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sr_inc.losses, sr_dir.losses,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("scenario", sorted(_DIFF_SCENARIOS))
@pytest.mark.parametrize("pair", sorted(_PAIRS))
def test_incremental_rule_matches_direct_scan(pair, scenario):
    _diff_incremental_vs_direct(pair, scenario)


def test_aced_event_budget_survives_heavy_dropout():
    """Regression for the fig3 50%-dropout ACED cell: ACED's emission is
    guaranteed (the arriving client re-enters the active set before the
    any()), so the default budget must reach T exactly — _to_result raises
    RuntimeError if a scan's budget ever starves while clients remain, so
    this test fails the moment that guarantee breaks."""
    T = 60
    sim, hr, sr = _host_and_scan("aced", n=10, T=T, dropout_frac=0.5,
                                 dropout_at=T // 2)
    _assert_equivalent(sim, hr, sr)
    assert sr.ts[-1] == T - 1               # full trajectory, no starvation


def test_default_n_events_headroom_for_non_guaranteed_emitters():
    """ACED's emission is guaranteed (documented in aggregators.py), so it
    gets no headroom; the budget mechanism serves rules that declare
    guaranteed_emit = False."""
    assert ACED(tau_algo=5).guaranteed_emit
    assert (default_n_events(ACED(tau_algo=5), 40)
            == default_n_events(ACEIncremental(), 40))

    class Flaky(VanillaASGD):
        guaranteed_emit = False

    assert default_n_events(Flaky(), 40) > default_n_events(VanillaASGD(), 40)


# ---------------------------------------------------------------------------
# Ring buffer == deque semantics
# ---------------------------------------------------------------------------

def _ring_vs_deque(emits, taus, tau_max, d=3):
    """Drive ring_read/ring_append and a deque(maxlen=tau_max+1) through the
    same emit/τ sequence; every read must match history[-(τ+1)]. The ring
    stores each slot in the row shape `flat_row_shape(d)`, as the scan's."""
    S = tau_max + 1
    val = lambda k: np.full(d, float(k), np.float32)   # model after k updates
    ring = jnp.zeros((S,) + flat_row_shape(d), jnp.float32).at[0].set(
        val(0).reshape(flat_row_shape(d)))
    cursor = jnp.asarray(0, jnp.int32)
    history = deque(maxlen=S)
    history.append(val(0))
    t = 0
    for emit, tau in zip(emits, taus):
        tau_eff = min(tau, tau_max, len(history) - 1)
        got = np.asarray(ring_read(ring, cursor, jnp.asarray(tau_eff)))
        np.testing.assert_array_equal(got, history[-(tau_eff + 1)])
        if emit:
            t += 1
            history.append(val(t))
            ring, cursor = ring_append(ring, cursor, jnp.asarray(val(t)),
                                       jnp.asarray(True))
        else:
            ring, cursor = ring_append(
                ring, cursor, jnp.asarray(val(t)), jnp.asarray(False))


#: a row width 128 does not divide, stored (S, d), and one it does, stored
#: (S, d // 128, 128)
RING_WIDTHS = [3, 256]


@pytest.mark.parametrize("d", RING_WIDTHS)
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_ring_buffer_matches_deque_random_sequences(seed, d):
    rng = np.random.default_rng(seed)
    tau_max = int(rng.integers(1, 9))
    n_steps = 60
    emits = rng.random(n_steps) < 0.7
    taus = rng.integers(0, 3 * tau_max, size=n_steps)
    _ring_vs_deque(emits.tolist(), taus.tolist(), tau_max, d)


@pytest.mark.parametrize("d", RING_WIDTHS)
def test_ring_read_lanes_equals_take(d):
    """The K-lane stale read, one dynamic slice of a whole slot per lane,
    returns bit for bit what `jnp.take` over the slots of the same values
    laid out (S, d) returns, repeated and wrapping slots included."""
    S, K = 7, 5
    rng = np.random.default_rng(d)
    flat = rng.normal(size=(S, d)).astype(np.float32)
    ring = jnp.asarray(flat.reshape((S,) + flat_row_shape(d)))
    for cursor in range(S):
        taus = jnp.asarray(rng.integers(0, S, size=K), jnp.int32)
        got = ring_read_lanes(ring, jnp.int32(cursor), taus)
        want = jnp.take(jnp.asarray(flat), jnp.mod(cursor - taus, S), axis=0)
        assert got.shape == (K, d)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# vmap over seeds and the lr grid
# ---------------------------------------------------------------------------

def test_staleness_vmap_seeds_matches_single_runs():
    n, d, T = 6, 5, 20
    grad_fn = quad_grad_fn(n, d)
    seeds = [1, 2, 3]
    batch = run_staleness_seeds(grad_fn=grad_fn, params0=jnp.zeros(d),
                                aggregator=ACEIncremental(), n_clients=n,
                                server_lr=0.05, T=T, seeds=seeds, beta=2.0)
    for s, br in zip(seeds, batch):
        single = run_staleness_scan(grad_fn=grad_fn, params0=jnp.zeros(d),
                                    aggregator=ACEIncremental(), n_clients=n,
                                    server_lr=0.05, T=T, beta=2.0, seed=s)
        np.testing.assert_allclose(br.w, single.w, rtol=1e-6, atol=1e-6)
        assert br.total_comms == single.total_comms


def test_staleness_grid_matches_per_lr_runs():
    """One vmapped grid call == independent per-lr seed sweeps."""
    n, d, T = 6, 5, 20
    grad_fn = quad_grad_fn(n, d)
    lrs, seeds = [0.02, 0.05, 0.1], [1, 2]
    grid = run_staleness_grid(grad_fn=grad_fn, params0=jnp.zeros(d),
                              aggregator=FedBuff(buffer_size=3), n_clients=n,
                              lrs=lrs, T=T, seeds=seeds, beta=2.0)
    assert len(grid) == len(lrs) and all(len(g) == len(seeds) for g in grid)
    for lr, results in zip(lrs, grid):
        singles = run_staleness_seeds(grad_fn=grad_fn, params0=jnp.zeros(d),
                                      aggregator=FedBuff(buffer_size=3),
                                      n_clients=n, server_lr=lr, T=T,
                                      seeds=seeds, beta=2.0)
        for br, sr in zip(results, singles):
            np.testing.assert_allclose(br.w, sr.w, rtol=1e-6, atol=1e-6)

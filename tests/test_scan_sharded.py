"""Sharded staleness scan (repro/core/scan_sharded.py): differential
equivalence on a forced 8-device host mesh.

Three-way contract, pinned for the whole zoo (all five production
algorithms plus the O(n·d) direct references): the **sharded** scan
(cache rows over ``data``, features over ``model``), the **unsharded** scan
and the **host** `StalenessSimulator` replay consume the identical random
stream, so trajectories must agree to ≤1e-5 — including permanent dropout,
speed-skew, availability windows (freeze/thaw) and int8 caches. Runs skip
cleanly without the mesh: ``REPRO_FORCE_DEVICES=8 python -m pytest
tests/test_scan_sharded.py`` (see tests/conftest.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregators import (ACED, ACEDDirect, ACEIncremental, CA2FL,
                                    CA2FLDirect, FedBuff, VanillaASGD)
from repro.core.scan_engine import default_n_events
from repro.core.scan_sharded import (make_sharded_staleness_runner,
                                     staleness_mesh)
from repro.core.scan_staleness import (build_staleness_randomness,
                                       run_staleness_grid,
                                       run_staleness_scan,
                                       run_staleness_seeds)
from repro.core.staleness_sim import StalenessSimulator

pytestmark = pytest.mark.multidevice

AGGS = {
    "asgd": lambda: VanillaASGD(),
    "fedbuff": lambda: FedBuff(buffer_size=4),
    "ca2fl": lambda: CA2FL(buffer_size=4),
    "ca2fl_direct": lambda: CA2FLDirect(buffer_size=4),
    "ace": lambda: ACEIncremental(),
    "aced": lambda: ACED(tau_algo=5),
    "aced_direct": lambda: ACEDDirect(tau_algo=5),
}


def quad_grad_fn(n, d, zeta=2.0, sigma=0.2, seed=0):
    rng = np.random.default_rng(seed)
    C = jnp.asarray(rng.normal(size=(n, d)) * zeta)

    def grad_fn(params, client, key):
        g = params - C[client] + sigma * jax.random.normal(key, (d,))
        return 0.5 * jnp.sum((params - C[client]) ** 2), g
    return grad_fn


def _quad_eval_fn(params):
    return {"dist": float(jnp.sqrt(jnp.sum(params ** 2)))}


def _three_way(agg_factory, mesh, *, n=8, d=6, T=40, beta=2.0, seed=0,
               speed_skew=0.0, dropout_frac=0.0, dropout_at=None,
               rejoin_at=None, windows=None, eval_every=None, server_lr=0.05):
    """host replay / unsharded scan / sharded scan on one random stream."""
    grad_fn = quad_grad_fn(n, d)
    n_events = default_n_events(agg_factory(), T)
    if rejoin_at is not None or windows is not None:
        n_events += n                       # freeze fast-forward slack
    rand = build_staleness_randomness(seed, n_events, n, beta, dropout_frac,
                                      speed_skew, dropout_at=dropout_at,
                                      rejoin_at=rejoin_at, windows=windows)
    eval_fn = _quad_eval_fn if eval_every else None
    sim = StalenessSimulator(
        grad_fn=grad_fn, params0=jnp.zeros(d), aggregator=agg_factory(),
        n_clients=n, server_lr=server_lr, beta=beta, speed_skew=speed_skew,
        dropout_frac=dropout_frac, dropout_at=dropout_at,
        rejoin_at=rejoin_at, windows=windows, eval_fn=eval_fn,
        eval_every=eval_every or T, seed=seed, replay=rand)
    hr = sim.run(T)
    kw = dict(grad_fn=grad_fn, params0=jnp.zeros(d),
              n_clients=n, server_lr=server_lr, T=T, beta=beta,
              speed_skew=speed_skew, dropout_frac=dropout_frac,
              dropout_at=dropout_at, rejoin_at=rejoin_at, windows=windows,
              eval_fn=eval_fn, eval_every=eval_every, seed=seed)
    sr = run_staleness_scan(aggregator=agg_factory(), **kw)
    shr = run_staleness_scan(aggregator=agg_factory(), mesh=mesh, **kw)
    return sim, hr, sr, shr


def _assert_matches(a, b, host=None):
    """ScanResult `b` (sharded) == ScanResult `a` (unsharded) ≤1e-5; when
    `host` is given, also ≤1e-5 against the host SimResult trajectory."""
    np.testing.assert_allclose(b.w, a.w, rtol=1e-5, atol=1e-5)
    assert b.ts.tolist() == a.ts.tolist()
    assert b.total_comms == a.total_comms
    np.testing.assert_allclose(b.losses, a.losses, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(b.update_norms, a.update_norms,
                               rtol=1e-4, atol=1e-5)
    assert b.eval_ts == a.eval_ts
    for be, ae in zip(b.evals, a.evals):
        for k in ae:
            np.testing.assert_allclose(be[k], ae[k], rtol=1e-4, atol=1e-5)
    if host is not None:
        assert b.ts.tolist() == host.ts
        np.testing.assert_allclose(b.losses, host.losses,
                                   rtol=1e-4, atol=1e-5)
        assert b.eval_ts == host.eval_ts


@pytest.mark.parametrize("algo", sorted(AGGS))
def test_sharded_scan_matches_unsharded_and_host(algo, device_mesh):
    """Base protocol: all five algorithms, three-way ≤1e-5."""
    sim, hr, sr, shr = _three_way(AGGS[algo], device_mesh)
    _assert_matches(sr, shr, host=hr)
    np.testing.assert_allclose(shr.w, np.asarray(sim.w), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("algo", ["aced", "fedbuff", "asgd"])
def test_sharded_scan_with_dropout(algo, device_mesh):
    """Permanent dropout at T/2 under sharded client sampling."""
    sim, hr, sr, shr = _three_way(AGGS[algo], device_mesh, n=8, T=60,
                                  dropout_frac=0.5, dropout_at=30)
    _assert_matches(sr, shr, host=hr)


@pytest.mark.parametrize("algo", ["ace", "ca2fl"])
def test_sharded_scan_with_speed_skew(algo, device_mesh):
    """Participation imbalance: the weighted categorical argmax must pick
    identical clients when the gumbel rows are sharded over `data`."""
    sim, hr, sr, shr = _three_way(AGGS[algo], device_mesh, speed_skew=2.0)
    _assert_matches(sr, shr, host=hr)


@pytest.mark.parametrize("algo", sorted(AGGS))
def test_sharded_scan_windows_freeze_thaw(algo, device_mesh):
    """Availability windows incl. an all-gone freeze/thaw: the fast-forward
    jump and the frozen aggregator state must shard transparently."""
    n, T = 8, 50
    leave = np.full(n, 12, np.int64)
    rejoin = np.full(n, 22, np.int64)
    rejoin[3] = 30
    sim, hr, sr, shr = _three_way(AGGS[algo], device_mesh, n=n, T=T,
                                  windows=(leave, rejoin), eval_every=10)
    _assert_matches(sr, shr, host=hr)
    assert not [t for t in hr.ts if 12 < t < 22]


@pytest.mark.parametrize("algo,factory", [
    ("ace", lambda: ACEIncremental(cache_dtype="int8")),
    ("aced", lambda: ACED(tau_algo=5, cache_dtype="int8")),
    ("aced_direct", lambda: ACEDDirect(tau_algo=5, cache_dtype="int8")),
    ("ca2fl", lambda: CA2FL(buffer_size=4, cache_dtype="int8")),
    ("ca2fl_direct", lambda: CA2FLDirect(buffer_size=4, cache_dtype="int8")),
])
def test_sharded_scan_int8_cache(algo, factory, device_mesh):
    """int8 caches: quantize/dequantize must commute with the (clients →
    data, features → model) cache sharding."""
    sim, hr, sr, shr = _three_way(factory, device_mesh, T=30)
    _assert_matches(sr, shr, host=hr)


@pytest.mark.parametrize("algo,factory", [
    ("ace", lambda: ACEIncremental(cache_dtype="int8")),
    ("aced", lambda: ACED(tau_algo=5, cache_dtype="int8")),
    ("aced_direct", lambda: ACEDDirect(tau_algo=5, cache_dtype="int8")),
    ("ca2fl", lambda: CA2FL(buffer_size=4, cache_dtype="int8")),
])
def test_sharded_scan_tiled_int8_cache(algo, factory, device_mesh):
    """d = 256 stores each client's row as (2, 128) tiles: the cache's
    constraint (clients → data, the row's tiles → model, lanes unsharded)
    keeps the trajectories of the unsharded scan and the host replay."""
    sim, hr, sr, shr = _three_way(factory, device_mesh, d=256, T=30)
    _assert_matches(sr, shr, host=hr)


@pytest.mark.parametrize("inc,dr", [
    (lambda dt: ACED(tau_algo=5, cache_dtype=dt),
     lambda dt: ACEDDirect(tau_algo=5, cache_dtype=dt)),
    (lambda dt: CA2FL(buffer_size=4, cache_dtype=dt),
     lambda dt: CA2FLDirect(buffer_size=4, cache_dtype=dt)),
], ids=["aced", "ca2fl"])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_sharded_incremental_matches_direct(inc, dr, dtype, device_mesh):
    """The O(d) running-sum state (asum/h_sum, sharded over ``model`` via
    the cache_d constraint) must reproduce the direct O(n·d) re-reduction's
    trajectory on the mesh — including a freeze/thaw window, where the thaw
    jump retires several ring slots in one sharded sweep."""
    n, T = 8, 50
    leave = np.full(n, 12, np.int64)
    rejoin = np.full(n, 22, np.int64)
    rejoin[3] = 30
    grad_fn = quad_grad_fn(n, 6)
    kw = dict(grad_fn=grad_fn, params0=jnp.zeros(6), n_clients=n,
              server_lr=0.05, T=T, beta=2.0, windows=(leave, rejoin),
              seed=0, mesh=device_mesh)
    ri = run_staleness_scan(aggregator=inc(dtype), **kw)
    rd = run_staleness_scan(aggregator=dr(dtype), **kw)
    assert ri.ts.tolist() == rd.ts.tolist()
    np.testing.assert_allclose(ri.w, rd.w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ri.update_norms, rd.update_norms,
                               rtol=1e-4, atol=1e-5)


def test_sharded_scan_nondividing_shapes(device_mesh):
    """n=7 clients (∤ data=4) and d=5 features (∤ model=2): the divisibility
    guard drops those constraints and the run must still match."""
    sim, hr, sr, shr = _three_way(AGGS["ace"], device_mesh, n=7, d=5, T=30)
    _assert_matches(sr, shr, host=hr)


def test_sharded_seeds_vmap_matches_unsharded(device_mesh):
    """The vmapped seed sweep with mesh= equals per-seed unsharded runs."""
    n, d, T = 8, 6, 20
    grad_fn = quad_grad_fn(n, d)
    seeds = [1, 2, 3]
    kw = dict(grad_fn=grad_fn, params0=jnp.zeros(d), n_clients=n,
              server_lr=0.05, T=T, beta=2.0)
    batch = run_staleness_seeds(aggregator=ACEIncremental(), seeds=seeds,
                                mesh=device_mesh, **kw)
    for s, br in zip(seeds, batch):
        single = run_staleness_scan(aggregator=ACEIncremental(), seed=s, **kw)
        np.testing.assert_allclose(br.w, single.w, rtol=1e-5, atol=1e-5)
        assert br.total_comms == single.total_comms


def test_sharded_grid_matches_unsharded_grid(device_mesh):
    """lr-grid × seed sweep, sharded == unsharded (one vmapped computation
    each)."""
    n, d, T = 8, 6, 20
    grad_fn = quad_grad_fn(n, d)
    lrs, seeds = [0.02, 0.1], [1, 2]
    kw = dict(grad_fn=grad_fn, params0=jnp.zeros(d),
              aggregator=FedBuff(buffer_size=3), n_clients=n, lrs=lrs, T=T,
              seeds=seeds, beta=2.0)
    sharded = run_staleness_grid(mesh=device_mesh, **kw)
    plain = run_staleness_grid(**kw)
    for row_s, row_p in zip(sharded, plain):
        for rs, rp in zip(row_s, row_p):
            np.testing.assert_allclose(rs.w, rp.w, rtol=1e-5, atol=1e-5)


def test_sharded_scan_mlp_task_matches_unsharded(device_mesh):
    """Regression for the CPU-SPMD payload miscompile: a raveled MLP gradient
    is concat(reshape(dot), ...), and without the replicated payload pin
    (sharding/rules.replicate) a model-axis constraint propagating into that
    pattern scales gradients by the data-axis replica count. The quadratic
    task can't catch this (no dots) — this MLP task can."""
    from repro.core.fl_tasks import make_vision_task
    n, T = 8, 25
    task = make_vision_task(n_clients=n, alpha=0.5, n_train=400, n_test=100,
                            dim=8, hidden=(12,), n_classes=4, noise=1.0,
                            batch=4, seed=0)
    kw = dict(grad_fn=task.grad_fn, params0=task.params0, n_clients=n,
              server_lr=0.05, T=T, beta=2.0, seed=0)
    sr = run_staleness_scan(aggregator=ACEIncremental(), **kw)
    shr = run_staleness_scan(aggregator=ACEIncremental(), mesh=device_mesh,
                             **kw)
    _assert_matches(sr, shr)


def test_cache_rows_actually_sharded(device_mesh):
    """Not just numerics: the compiled sharded runner must lay the (n, d)
    aggregator cache out over the mesh — catch silent constraint dropping."""
    n, d, T = 8, 6, 10
    grad_fn = quad_grad_fn(n, d)
    runner = make_sharded_staleness_runner(
        mesh=device_mesh, grad_fn=grad_fn, params0=jnp.zeros(d),
        aggregator=ACEIncremental(), n_clients=n, T=T, beta=2.0)
    rand = build_staleness_randomness(
        0, default_n_events(ACEIncremental(), T), n, 2.0)
    w, state, _, _ = runner(jax.random.PRNGKey(0), rand.gumbels, rand.tau_raw,
                            rand.leave_at, rand.rejoin_at, jnp.float32(0.05))
    sharding = state["cache"].data.sharding
    # client rows split over data, features over model (dims that don't
    # divide their axis stay replicated — the divisibility guard)
    dd, dm = device_mesh.shape["data"], device_mesh.shape["model"]
    expect = (n // dd if n % dd == 0 else n, d // dm if d % dm == 0 else d)
    assert sharding.shard_shape(state["cache"].data.shape) == expect
    assert expect != (n, d)           # something actually sharded


def test_tiled_cache_rows_actually_sharded(device_mesh):
    """A cache whose rows are whole (d // 128, 128) tiles shards its
    clients over ``data`` and its tile rows over ``model``."""
    n, d, T = 8, 512, 10
    runner = make_sharded_staleness_runner(
        mesh=device_mesh, grad_fn=quad_grad_fn(n, d), params0=jnp.zeros(d),
        aggregator=ACEIncremental(cache_dtype="int8"), n_clients=n, T=T,
        beta=2.0)
    rand = build_staleness_randomness(
        0, default_n_events(ACEIncremental(), T), n, 2.0)
    w, state, _, _ = runner(jax.random.PRNGKey(0), rand.gumbels, rand.tau_raw,
                            rand.leave_at, rand.rejoin_at, jnp.float32(0.05))
    data = state["cache"].data
    dd, dm = device_mesh.shape["data"], device_mesh.shape["model"]
    assert data.shape == (n, d // 128, 128)
    assert data.sharding.shard_shape(data.shape) == (n // dd,
                                                     d // 128 // dm, 128)


def test_staleness_mesh_helper(device_mesh):
    ndev = jax.device_count()
    mesh = staleness_mesh()                    # auto: (ndev/2, 2) when even
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.size == ndev
    if ndev % 4 == 0:
        assert staleness_mesh(model=4).shape == {"data": ndev // 4,
                                                 "model": 4}
    if ndev % 3 != 0:
        with pytest.raises(ValueError):
            staleness_mesh(model=3)
    with pytest.raises(ValueError):
        make_sharded_staleness_runner(mesh=None, grad_fn=None, params0=None,
                                      aggregator=None, n_clients=1, T=1,
                                      beta=1.0)

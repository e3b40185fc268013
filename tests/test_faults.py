"""Fault-injected AFL (ISSUE 7): traced client-fault model, in-scan guard
pipeline, self-healing incremental state and crash-safe checkpointing.

Pins the tentpole contracts:
  * guards compile to no-ops — a guarded runner on an all-clean schedule is
    bit-identical to the unguarded runner;
  * under injected NaN / explode / Byzantine / over-stale faults the host
    `StalenessSimulator` and the scanned engine replay each other ≤1e-5 for
    all five production algorithms, with identical guard counters, and every
    run finishes with a finite model, and the tree layout follows the flat
    one through faults and an all-gone freeze;
  * periodic `Aggregator.resync` keeps the incremental ACED / CA²FL running
    sums matched to their O(n·d) direct references under faults, and heals
    injected state corruption between chunks;
  * guard counters survive chunking and checkpoint/resume exactly (flat and
    tree layouts);
  * checkpoints are atomic + checksummed: truncation/corruption falls back
    to the last verified checkpoint, transient save IO retries, legacy
    sidecar-less files stay restorable.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregators import (ACED, ACEDDirect, ACEDirect,
                                    ACEIncremental, CA2FL, CA2FLDirect,
                                    FedBuff, VanillaASGD)
from repro.core.scan_engine import default_n_events
from repro.core.scan_staleness import (build_fault_schedule,
                                       build_staleness_randomness,
                                       make_chunked_staleness_runner,
                                       make_staleness_runner, no_faults,
                                       run_staleness_scan,
                                       run_staleness_seeds)
from repro.core.staleness_sim import StalenessSimulator

pytestmark = pytest.mark.faults

N, D, T, BETA, LR, SEED = 6, 16, 30, 3.0, 0.05, 1
RATES = dict(nan_rate=0.08, explode_rate=0.05, byzantine_rate=0.05,
             overstale_rate=0.08)
CLIP = 5.0

AGGS = {
    "asgd": lambda: VanillaASGD(),
    "fedbuff": lambda: FedBuff(buffer_size=4),
    "ca2fl": lambda: CA2FL(buffer_size=3),
    "ace": lambda: ACEIncremental(),
    "aced": lambda: ACED(tau_algo=6),
}


@functools.lru_cache(maxsize=1)
def _quad():
    rng = np.random.default_rng(0)
    C = jnp.asarray(rng.normal(size=(N, D)) * 2.0, jnp.float32)

    def grad_fn(params, client, key):
        g = params - C[client] + 0.2 * jax.random.normal(key, params.shape)
        return 0.5 * jnp.sum((params - C[client]) ** 2), g
    return grad_fn, jnp.ones((D,), jnp.float32)


def _n_events(agg_factory):
    # quarantined/rejected events never emit: generous slack over the
    # guaranteed-emit budget so every faulted run still reaches T
    return default_n_events(agg_factory(), T) + 60


def _schedule(n_events, seed=SEED):
    return build_fault_schedule(seed, n_events, **RATES)


def _scan_kw(algo, **over):
    grad_fn, params0 = _quad()
    kw = dict(grad_fn=grad_fn, params0=params0, aggregator=AGGS[algo](),
              n_clients=N, server_lr=LR, T=T, beta=BETA, seed=SEED,
              n_events=_n_events(AGGS[algo]))
    kw.update(over)
    return kw


def _host_run(algo, faults, **over):
    grad_fn, params0 = _quad()
    n_events = over.pop("n_events", _n_events(AGGS[algo]))
    rand = build_staleness_randomness(SEED, n_events, N, BETA)
    sim = StalenessSimulator(
        grad_fn=grad_fn, params0=params0, aggregator=AGGS[algo](),
        n_clients=N, server_lr=LR, beta=BETA, seed=SEED, replay=rand,
        faults=faults, clip_norm=CLIP, **over)
    return sim, sim.run(T)


# ---------------------------------------------------------------------------
# fault schedule
# ---------------------------------------------------------------------------

def test_fault_schedule_counts_and_validation():
    fa = _schedule(4000)
    counts = fa.counts()
    assert set(counts) == {"nan", "explode", "byzantine", "overstale"}
    for kind, rate in (("nan", 0.08), ("explode", 0.05),
                       ("byzantine", 0.05), ("overstale", 0.08)):
        assert abs(counts[kind] / 4000 - rate) < 0.03, (kind, counts)
    assert no_faults(8).counts() == {"nan": 0, "explode": 0,
                                     "byzantine": 0, "overstale": 0}
    with pytest.raises(ValueError):
        build_fault_schedule(0, 10, nan_rate=0.7, byzantine_rate=0.6)
    with pytest.raises(ValueError):
        build_fault_schedule(0, 10, nan_rate=-0.1)


def test_schedule_mismatch_rejected():
    fa = _schedule(50)
    with pytest.raises(ValueError, match="n_events"):
        run_staleness_scan(**_scan_kw("asgd", faults=fa))


# ---------------------------------------------------------------------------
# guards compile to no-ops
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["aced", "ca2fl"])
def test_clean_schedule_is_bit_exact(algo):
    """Guarded runner + all-clean schedule + clip off == unguarded runner,
    bit for bit — the guard pipeline is a no-op unless a fault fires."""
    grad_fn, params0 = _quad()
    n_events = _n_events(AGGS[algo])
    rand = build_staleness_randomness(SEED, n_events, N, BETA)
    kw = dict(grad_fn=grad_fn, params0=params0, aggregator=AGGS[algo](),
              n_clients=N, T=T, beta=BETA)
    base_args = (jax.random.PRNGKey(SEED), rand.gumbels, rand.tau_raw,
                 rand.leave_at, rand.rejoin_at, jnp.float32(LR))
    w_off, _, outs_off, _ = make_staleness_runner(**kw)(*base_args)
    fa = no_faults(n_events)
    w_on, _, outs_on, _ = make_staleness_runner(guards=True, **kw)(
        *base_args, fa.kind, fa.scale, jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(w_on), np.asarray(w_off))
    np.testing.assert_array_equal(np.asarray(outs_on["emit"]),
                                  np.asarray(outs_off["emit"]))
    for k in ("quarantined", "clipped", "rejected"):
        assert int(np.asarray(outs_on[k]).sum()) == 0


# ---------------------------------------------------------------------------
# host/scan parity + survival under injected faults
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", sorted(AGGS))
def test_host_scan_parity_under_faults(algo):
    """Tentpole contract: the ≤1e-5 replay equivalence extends to faulted
    runs — same trajectory, same guard counters, finite final model."""
    fa = _schedule(_n_events(AGGS[algo]))
    sim, hr = _host_run(algo, fa)
    sr = run_staleness_scan(**_scan_kw(algo, faults=fa, clip_norm=CLIP))
    assert np.isfinite(sr.w).all()
    assert np.max(np.abs(sr.w - np.asarray(sim.w, np.float32))) <= 1e-5
    assert sr.ts.tolist() == hr.ts
    np.testing.assert_allclose(sr.losses, hr.losses, rtol=1e-4, atol=1e-5)
    assert sr.faults == hr.faults
    assert sum(sr.faults.values()) > 0, "schedule injected nothing"


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
@pytest.mark.parametrize("algo", ["ace", "aced", "ca2fl"])
def test_tree_layout_matches_flat_under_faults_and_freeze(algo, cache_dtype):
    """The per-arrival tick on the tree layout follows the flat one through
    quarantined, refused and frozen arrivals: on both, the rule's masked
    row write is the per-client cache's only gate (the flat run is held to
    the host by the parity tests above)."""
    grad_fn, params0 = _quad()

    def tree_grad_fn(params, client, key):
        loss, g = grad_fn(params["w"], client, key)
        return loss, {"w": g}
    rules = {"ace": lambda: ACEIncremental(cache_dtype=cache_dtype),
             "aced": lambda: ACED(tau_algo=6, cache_dtype=cache_dtype),
             "ca2fl": lambda: CA2FL(buffer_size=3, cache_dtype=cache_dtype)}
    fa = _schedule(_n_events(rules[algo]))
    # every client away over [8, 14): the run freezes, then thaws
    windows = (np.full(N, 8, np.int64), np.full(N, 14, np.int64))
    kw = dict(faults=fa, clip_norm=CLIP, windows=windows,
              n_events=_n_events(rules[algo]))
    flat = run_staleness_scan(**_scan_kw(algo, aggregator=rules[algo](),
                                         **kw))
    tree = run_staleness_scan(**_scan_kw(
        algo, aggregator=rules[algo](), grad_fn=tree_grad_fn,
        params0={"w": params0}, layout="tree", **kw))
    w_tree = np.asarray(jax.tree.leaves(tree.w)[0]).reshape(flat.w.shape)
    assert np.isfinite(flat.w).all()
    assert np.max(np.abs(w_tree - flat.w)) <= 1e-5
    assert tree.ts.tolist() == flat.ts.tolist()
    assert tree.faults == flat.faults
    assert sum(flat.faults.values()) > 0, "schedule injected nothing"


def test_seed_sweep_surfaces_fault_counters():
    """run_staleness_seeds with fault_rates: per-seed schedules, every
    ScanResult carries its own counters, every model finite."""
    grad_fn, params0 = _quad()
    results = run_staleness_seeds(
        grad_fn=grad_fn, params0=params0, aggregator=ACEIncremental(),
        n_clients=N, server_lr=LR, T=T, seeds=(1, 2), beta=BETA,
        n_events=_n_events(lambda: ACEIncremental()),
        fault_rates=RATES, clip_norm=CLIP)
    assert len(results) == 2
    for r in results:
        assert np.isfinite(r.w).all()
        assert set(r.faults) == {"quarantined", "clipped", "rejected"}
    # different seeds draw different schedules
    assert not np.array_equal(results[0].w, results[1].w)


def test_mixed_clean_nan_batch_quarantines_per_lane():
    """Satellite (ISSUE 9): with K-batched arrivals a NaN lane is
    quarantined ALONE — its clean batch-mates still apply and the tick
    still emits. Every tick carries the lane pattern [clean, NaN, clean]:
    the run must reach T updates (a whole-batch veto would starve it),
    quarantine exactly one lane per tick, and replay the host ≤1e-5."""
    from repro.core.scan_staleness import FAULT_NAN, FaultSchedule
    k = 3
    grad_fn, params0 = _quad()
    n_events = _n_events(AGGS["ace"])
    kind = np.zeros((n_events, k), np.int32)
    kind[:, 1] = FAULT_NAN
    fa = FaultSchedule(jnp.asarray(kind),
                       jnp.ones((n_events, k), jnp.float32))
    rand = build_staleness_randomness(SEED, n_events, N, BETA, k_batch=k)
    sim = StalenessSimulator(
        grad_fn=grad_fn, params0=params0, aggregator=AGGS["ace"](),
        n_clients=N, server_lr=LR, beta=BETA, seed=SEED, replay=rand,
        k_batch=k, faults=fa, clip_norm=CLIP)
    hr = sim.run(T)
    sr = run_staleness_scan(**_scan_kw("ace", faults=fa, clip_norm=CLIP,
                                       k_batch=k))
    assert len(sr.ts) == len(hr.ts) == T - 1    # cache-init consumes
    assert np.isfinite(sr.w).all()              # iteration 0; every other
    assert np.max(np.abs(sr.w - np.asarray(sim.w, np.float32))) <= 1e-5
    assert sr.faults == hr.faults       # tick emitted despite its NaN lane
    assert sr.faults["quarantined"] == len(sr.ts)   # one lane per tick


def test_per_lane_fault_schedule_mismatch_rejected():
    """A flat (E,) schedule cannot drive the K-batched engine (and vice
    versa): the lane-count check rejects it before tracing."""
    fa = _schedule(_n_events(AGGS["asgd"]))
    with pytest.raises(ValueError, match="k_batch"):
        run_staleness_scan(**_scan_kw("asgd", faults=fa, k_batch=3))


# ---------------------------------------------------------------------------
# self-healing incremental state
# ---------------------------------------------------------------------------

RESYNC_PAIRS = [
    ("ace", lambda: ACEIncremental(), lambda: ACEDirect()),
    ("aced", lambda: ACED(tau_algo=6), lambda: ACEDDirect(tau_algo=6)),
    ("ca2fl", lambda: CA2FL(buffer_size=3),
     lambda: CA2FLDirect(buffer_size=3)),
]


@pytest.mark.parametrize("name,inc,direct", RESYNC_PAIRS,
                         ids=[p[0] for p in RESYNC_PAIRS])
def test_resync_matches_direct_under_faults(name, inc, direct):
    """Incremental rule + periodic exact resync == O(n·d) direct reference
    ≤1e-5 on the same faulted stream (the differential the self-healing
    path is pinned against)."""
    n_events = _n_events(direct)
    fa = _schedule(n_events)
    kw = _scan_kw("asgd", faults=fa, clip_norm=CLIP, n_events=n_events)
    r_inc = run_staleness_scan(**{**kw, "aggregator": inc(),
                                  "resync_every": 5})
    r_dir = run_staleness_scan(**{**kw, "aggregator": direct()})
    assert np.max(np.abs(r_inc.w - r_dir.w)) <= 1e-5
    assert r_inc.faults == r_dir.faults


def test_resync_heals_corrupted_running_sum():
    """Corrupt the incremental ACED active-set sum between chunks: with
    `resync_every` the periodic exact recompute restores it from the cache;
    without, the corruption persists to the end of the run."""
    grad_fn, params0 = _quad()
    agg = ACED(tau_algo=6)
    C = 20
    n_pad = -(-_n_events(lambda: ACED(tau_algo=6)) // C) * C
    rand = build_staleness_randomness(SEED, n_pad, N, BETA)
    fa = _schedule(n_pad)
    final_states = {}
    for resync_every in (None, 4):
        runner = make_chunked_staleness_runner(
            grad_fn=grad_fn, params0=params0, aggregator=agg, n_clients=N,
            T=T, beta=BETA, guards=True, resync_every=resync_every)
        carry = runner.init(jax.random.PRNGKey(SEED), jnp.float32(LR))
        for i, lo in enumerate(range(0, n_pad, C)):
            if i == 1:      # corrupt the O(d) running sum between chunks
                state = dict(carry["state"])
                state["asum"] = state["asum"] + jnp.float32(100.0)
                carry = {**carry, "state": state}
            carry, _ = runner.chunk(
                carry, rand.gumbels[lo:lo + C], rand.tau_raw[lo:lo + C],
                rand.leave_at, rand.rejoin_at, jnp.float32(LR),
                fa.kind[lo:lo + C], fa.scale[lo:lo + C], jnp.float32(CLIP))
        final_states[resync_every] = carry["state"]
    # ground truth: the exact recompute from the (never-corrupted) cache
    for resync_every, state in final_states.items():
        healed = jax.jit(agg.resync)(state)
        drift = float(np.max(np.abs(np.asarray(state["asum"])
                                    - np.asarray(healed["asum"]))))
        if resync_every:
            assert drift <= 1e-4, drift
        else:
            assert drift > 50.0, drift   # the +100 never got cleaned up


# ---------------------------------------------------------------------------
# counters across chunking + checkpoint/resume (flat and tree layouts)
# ---------------------------------------------------------------------------

def _counter_harness(layout, tmp_path):
    from repro.checkpoint import (restore_train_checkpoint,
                                  save_train_checkpoint)
    if layout == "tree":
        from repro.configs.registry import get_config
        from repro.core.fl_tasks import make_lm_task
        cfg = get_config("yi-9b").reduced(layers=2, d_model=64, vocab=128)
        task = make_lm_task(cfg=cfg, n_clients=4, batch=2, seq=32,
                            n_tokens=1 << 14, seed=0)
        grad_fn, params0, n, t_final = task.grad_fn, task.params0, 4, 16
    else:
        (grad_fn, params0), n, t_final = _quad(), N, T
    agg_f = lambda: ACED(tau_algo=6)
    C = 16
    n_pad = -(-(default_n_events(agg_f(), t_final) + 32) // C) * C
    rand = build_staleness_randomness(SEED, n_pad, n, BETA)
    fa = _schedule(n_pad)
    kw = dict(grad_fn=grad_fn, params0=params0, aggregator=agg_f(),
              n_clients=n, T=t_final, beta=BETA, layout=layout,
              guards=True, resync_every=4)
    lr = jnp.float32(LR)
    gargs = lambda lo, hi: (fa.kind[lo:hi], fa.scale[lo:hi],
                            jnp.float32(CLIP))

    # one-shot reference
    one = make_staleness_runner(**kw)
    _, _, outs1, _ = one(jax.random.PRNGKey(SEED), rand.gumbels,
                         rand.tau_raw, rand.leave_at, rand.rejoin_at, lr,
                         *gargs(0, n_pad))
    want = {k: int(np.asarray(outs1[k]).sum())
            for k in ("quarantined", "clipped", "rejected")}

    # chunked with a checkpoint round-trip in the middle
    runner = make_chunked_staleness_runner(**kw)

    def chunks(carry, lo, hi):
        for o in range(lo, hi, C):
            carry, _ = runner.chunk(carry, rand.gumbels[o:o + C],
                                    rand.tau_raw[o:o + C], rand.leave_at,
                                    rand.rejoin_at, lr, *gargs(o, o + C))
        return carry

    mid = (n_pad // C // 2) * C
    carry = chunks(runner.init(jax.random.PRNGKey(SEED), lr), 0, mid)
    save_train_checkpoint(tmp_path, mid, carry)
    template = runner.init(jax.random.PRNGKey(SEED), lr)
    restored, e0 = restore_train_checkpoint(tmp_path, template)
    assert e0 == mid
    carry = chunks(restored, mid, n_pad)
    got = {k: int(v) for k, v in carry["guards"].items()}
    assert got == want
    assert sum(got.values()) > 0, "schedule injected nothing in-window"


@pytest.mark.parametrize("layout", ["flat", "tree"])
def test_fault_counters_survive_chunk_and_resume(layout, tmp_path):
    """Satellite: guard-counter totals after a chunked run with a mid-run
    checkpoint/restore equal the one-shot scan's, for both model layouts —
    the counters are protocol state, not logging."""
    _counter_harness(layout, tmp_path)


@pytest.mark.multidevice
def test_sharded_faulted_scan_three_way(device_mesh):
    """host replay vs unsharded vs 8-device sharded scan on one faulted
    stream: guards + counters shard transparently, trajectories ≤1e-5."""
    fa = _schedule(_n_events(AGGS["aced"]))
    sim, hr = _host_run("aced", fa)
    kw = _scan_kw("aced", faults=fa, clip_norm=CLIP)
    sr = run_staleness_scan(**kw)
    shr = run_staleness_scan(mesh=device_mesh, **kw)
    np.testing.assert_allclose(shr.w, sr.w, rtol=1e-5, atol=1e-5)
    assert np.max(np.abs(shr.w - np.asarray(sim.w, np.float32))) <= 1e-5
    assert shr.faults == sr.faults == hr.faults


# ---------------------------------------------------------------------------
# crash-safe checkpointing
# ---------------------------------------------------------------------------

def _toy_carry(x=0.0):
    return {"w": jnp.arange(8, dtype=jnp.float32) + x,
            "t": jnp.asarray(int(x), jnp.int32)}


def _ckpt_path(tmp_path, step):
    return str(tmp_path / f"afl_{step:08d}.npz")


def test_truncated_checkpoint_falls_back(tmp_path):
    """Killing a run mid-save (simulated truncation of the newest payload)
    must not lose the run: restore warns and falls back to the last
    verified checkpoint."""
    from repro.checkpoint import (restore_train_checkpoint,
                                  save_train_checkpoint)
    save_train_checkpoint(tmp_path, 10, _toy_carry(1.0))
    save_train_checkpoint(tmp_path, 20, _toy_carry(2.0))
    with open(_ckpt_path(tmp_path, 20), "r+b") as f:
        f.truncate(f.seek(0, 2) // 2)
    with pytest.warns(RuntimeWarning, match="corrupt"):
        carry, step = restore_train_checkpoint(tmp_path, _toy_carry())
    assert step == 10
    np.testing.assert_array_equal(np.asarray(carry["w"]),
                                  np.asarray(_toy_carry(1.0)["w"]))


def test_checksum_flip_detected(tmp_path):
    """A single flipped byte fails sidecar verification even when the file
    still parses; latest_step(verified=True) skips it too."""
    from repro.checkpoint import latest_step, verify_checkpoint
    from repro.checkpoint import save_train_checkpoint
    save_train_checkpoint(tmp_path, 5, _toy_carry(1.0))
    save_train_checkpoint(tmp_path, 6, _toy_carry(2.0))
    p = _ckpt_path(tmp_path, 6)
    assert verify_checkpoint(p)
    with open(p, "r+b") as f:
        f.seek(-1, 2)
        last = f.read(1)
        f.seek(-1, 2)
        f.write(bytes([last[0] ^ 0xFF]))
    assert not verify_checkpoint(p)
    assert latest_step(tmp_path, prefix="afl") == 6
    assert latest_step(tmp_path, prefix="afl", verified=True) == 5


def test_all_checkpoints_bad_returns_template(tmp_path):
    from repro.checkpoint import (restore_train_checkpoint,
                                  save_train_checkpoint)
    save_train_checkpoint(tmp_path, 3, _toy_carry(1.0))
    with open(_ckpt_path(tmp_path, 3), "wb") as f:
        f.write(b"not an npz")
    template = _toy_carry()
    with pytest.warns(RuntimeWarning):
        carry, step = restore_train_checkpoint(tmp_path, template)
    assert step == 0
    assert carry is template


def test_legacy_checkpoint_without_sidecar_restores(tmp_path):
    """Pre-ISSUE-7 checkpoints have no .sha256 sidecar: they verify via the
    parse path and restore normally."""
    import os
    from repro.checkpoint import (restore_train_checkpoint,
                                  save_train_checkpoint, verify_checkpoint)
    save_train_checkpoint(tmp_path, 7, _toy_carry(3.0))
    os.remove(_ckpt_path(tmp_path, 7) + ".sha256")
    assert verify_checkpoint(_ckpt_path(tmp_path, 7))
    carry, step = restore_train_checkpoint(tmp_path, _toy_carry())
    assert step == 7
    np.testing.assert_array_equal(np.asarray(carry["w"]),
                                  np.asarray(_toy_carry(3.0)["w"]))


def test_save_retries_transient_io(tmp_path, monkeypatch):
    """The first two os.replace calls fail (flaky filesystem): the save
    retries with backoff and the published checkpoint verifies."""
    import repro.checkpoint.checkpoint as ck
    real_replace = ck.os.replace
    fails = {"left": 2}

    def flaky(src, dst):
        if fails["left"] > 0:
            fails["left"] -= 1
            raise OSError("transient")
        return real_replace(src, dst)

    monkeypatch.setattr(ck.os, "replace", flaky)
    path = ck.save_checkpoint(str(tmp_path), 1, _toy_carry(), prefix="afl",
                              backoff=0.001)
    assert fails["left"] == 0
    assert ck.verify_checkpoint(path)


def test_failed_save_leaves_no_partial(tmp_path, monkeypatch):
    """A save that exhausts its retries raises and leaves neither a partial
    payload nor a stale temp file under the final name."""
    import repro.checkpoint.checkpoint as ck

    def broken(src, dst):
        raise OSError("disk full")

    monkeypatch.setattr(ck.os, "replace", broken)
    with pytest.raises(OSError):
        ck.save_checkpoint(str(tmp_path), 2, _toy_carry(), prefix="afl",
                           retries=2, backoff=0.001)
    leftover = [p for p in tmp_path.iterdir()
                if p.name.endswith((".npz", ".tmp"))]
    assert leftover == []


def test_rotation_removes_sidecars(tmp_path):
    import os
    from repro.checkpoint import save_checkpoint
    for step in range(5):
        save_checkpoint(str(tmp_path), step, _toy_carry(float(step)),
                        prefix="ck", keep=2)
    files = sorted(os.listdir(tmp_path))
    npz = [f for f in files if f.endswith(".npz")]
    sidecars = [f for f in files if f.endswith(".sha256")]
    assert npz == ["ck_00000003.npz", "ck_00000004.npz"]
    assert sidecars == ["ck_00000003.npz.sha256", "ck_00000004.npz.sha256"]

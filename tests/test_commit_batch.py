"""Fused arrival-commit path (ISSUE 10): scan-level fused-vs-chain parity
for every running-sum rule, the ``REPRO_NO_PALLAS`` / ``REPRO_NO_FUSED_COMMIT``
escape hatches, and the `check_commit_batch` sanitizer tripwires. The
kernel-vs-oracle shape sweeps live in test_kernels.py; the hypothesis
differential in test_properties.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sanitize
from repro.core.aggregators import ACED, ACEIncremental, ArrivalBatch, CA2FL
from repro.kernels import backend, ops, ref
from repro.kernels.backend import fused_commit_enabled

_RULES = {
    "ace": lambda dt, f: ACEIncremental(cache_dtype=dt, fused_commit=f),
    "aced": lambda dt, f: ACED(tau_algo=5, max_cohort=4, cache_dtype=dt,
                               fused_commit=f),
    "ca2fl": lambda dt, f: CA2FL(buffer_size=3, cache_dtype=dt,
                                 fused_commit=f),
}


def _run_stream(agg, T=40, n=30, d=64, K=4, seed=0):
    """Drive `step_batch` over a deterministic synthetic arrival stream and
    return (final_state, (T, d) update trajectory)."""
    rng = np.random.default_rng(seed)
    clients = jnp.asarray(np.stack(
        [rng.choice(n, size=K, replace=False) for _ in range(T)]), jnp.int32)
    payloads = jnp.asarray(rng.normal(size=(T, K, d)), jnp.float32)
    valid = jnp.asarray(rng.random((T, K)) < 0.85)
    init = (jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
            if getattr(agg, "cache_init", False) else None)
    ts = jnp.arange(T, dtype=jnp.int32)
    zk = jnp.zeros((K,), jnp.int32)
    state0 = agg.init_state(n, d, init_grads=init)

    @jax.jit
    def run(state):
        def step(st, ev):
            js, g, v, t = ev
            st, u, _, _ = agg.step_batch(st, ArrivalBatch(js, g, t, zk, v))
            return st, u
        return jax.lax.scan(step, state, (clients, payloads, valid, ts))
    state, us = run(state0)
    return state, np.asarray(us)


@pytest.mark.parametrize("dt", ["int8", "float32"])
@pytest.mark.parametrize("name", sorted(_RULES))
def test_fused_commit_matches_dispatch_chain(name, dt):
    """The fused one-pass commit tracks the pinned dispatch chain: the cache
    (data AND scale) stays BIT-identical — the int8 exactness contract —
    and the running sums / update trajectory differ only by f32
    reassociation (≤1e-5)."""
    sf, uf = _run_stream(_RULES[name](dt, True))
    sc, uc = _run_stream(_RULES[name](dt, False))
    cf = sf.get("cache", sf.get("h"))          # CA²FL's cache is `h`
    cc = sc.get("cache", sc.get("h"))
    for a, b in zip(jax.tree.leaves(cf), jax.tree.leaves(cc)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.max(np.abs(uf - uc)) <= 1e-5
    for a, b in zip(jax.tree.leaves(sf), jax.tree.leaves(sc)):
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(a.dtype, np.floating):
            tol = 1e-5 * (1.0 + np.max(np.abs(b)))
            assert np.max(np.abs(a - b)) <= tol
        else:
            assert np.array_equal(a, b)


def test_k1_batch_fused_matches_chain():
    """K=1 through `step_batch` (the max_cohort>1 ACED route) is the
    degenerate fused batch — same parity contract."""
    sf, uf = _run_stream(ACED(tau_algo=5, max_cohort=2, fused_commit=True),
                         K=1)
    sc, uc = _run_stream(ACED(tau_algo=5, max_cohort=2, fused_commit=False),
                         K=1)
    assert np.max(np.abs(uf - uc)) <= 1e-5
    for a, b in zip(jax.tree.leaves(sf["cache"]),
                    jax.tree.leaves(sc["cache"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dt", ["int8", "float32"])
def test_disabled_env_is_bit_identical_to_chain(monkeypatch, dt):
    """``REPRO_NO_FUSED_COMMIT=1`` with the default `fused_commit=None`
    resolves to the dispatch chain at trace time: EVERY output leaf must be
    bit-identical to an explicit `fused_commit=False` build (dev == 0.0,
    the BENCH `max_dev_disabled` gate)."""
    monkeypatch.setenv("REPRO_NO_FUSED_COMMIT", "1")
    sd, ud = _run_stream(_RULES["ace"](dt, None))
    monkeypatch.delenv("REPRO_NO_FUSED_COMMIT")
    sc, uc = _run_stream(_RULES["ace"](dt, False))
    assert np.array_equal(ud, uc)
    for a, b in zip(jax.tree.leaves(sd), jax.tree.leaves(sc)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_commit_enabled_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_NO_FUSED_COMMIT", raising=False)
    assert fused_commit_enabled() is True
    for val in ("1", "true", "on", "yes"):
        monkeypatch.setenv("REPRO_NO_FUSED_COMMIT", val)
        assert fused_commit_enabled() is False
        assert fused_commit_enabled(True) is True      # explicit override wins
    monkeypatch.setenv("REPRO_NO_FUSED_COMMIT", "0")
    assert fused_commit_enabled() is True
    monkeypatch.delenv("REPRO_NO_FUSED_COMMIT")
    assert fused_commit_enabled(False) is False


def test_no_pallas_env_forces_xla(monkeypatch):
    """``REPRO_NO_PALLAS=1`` routes every dispatcher to the XLA oracle —
    the uniform runtime escape hatch — while an explicit `backend=` still
    wins."""
    monkeypatch.delenv("REPRO_NO_PALLAS", raising=False)
    assert backend.no_pallas() is False
    monkeypatch.setenv("REPRO_NO_PALLAS", "1")
    assert backend.no_pallas() is True
    assert ops.default_backend() == "xla"
    # explicit backend= overrides the hatch: interpret mode still runs the
    # Pallas kernel body and must match the oracle
    rng = np.random.default_rng(5)
    G = jnp.asarray(rng.normal(size=(3, 150)), jnp.float32)
    old = jnp.asarray(rng.normal(size=(3, 150)), jnp.float32)
    valid = jnp.asarray([True, False, True])
    vecs = jnp.asarray(rng.normal(size=(1, 150)), jnp.float32)
    coef = jnp.asarray([[1.0, 0.5, 0.0, 0.0, 0.0]], jnp.float32)
    kw = dict(G=G, old_rows=old, old_s=None, new_s=None, valid=valid,
              vecs=vecs, coef=coef, upd_w=coef[0])
    rows1, vecs1, upd1 = ops.commit_batch(**kw, backend="interpret")
    rows2, vecs2, upd2 = ref.commit_batch_ref(**kw)
    assert jnp.array_equal(rows1, rows2)
    np.testing.assert_allclose(np.asarray(upd1), np.asarray(upd2),
                               rtol=1e-5, atol=1e-5)


def test_default_backend_policy(monkeypatch):
    """Pallas on a TPU; the XLA oracle off it, under ``REPRO_NO_PALLAS``,
    and under a multi-device mesh (the SPMD partitioner cannot partition a
    Mosaic kernel). A one-device mesh keeps the kernels."""
    import types
    from repro.sharding.rules import use_rules
    monkeypatch.delenv("REPRO_NO_PALLAS", raising=False)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "cpu")
    assert ops.default_backend() == "xla"
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.default_backend() == "pallas"
    with use_rules(types.SimpleNamespace(size=1)):
        assert ops.default_backend() == "pallas"
    with use_rules(types.SimpleNamespace(size=4)):
        assert ops.default_backend() == "xla"
    monkeypatch.setenv("REPRO_NO_PALLAS", "1")
    assert ops.default_backend() == "xla"


# --- check_commit_batch sanitizer tripwires --------------------------------

def _checked(fn):
    return sanitize.wrap_checked(
        lambda *a: fn(*a) or jnp.zeros(()))


def test_check_commit_batch_clean_pass():
    fn = _checked(sanitize.check_commit_batch)
    fn(jnp.ones(4),
       {"u": jnp.ones(3), "count": jnp.asarray(3)},
       {"u": jnp.zeros(3), "count": jnp.asarray(2)},
       jnp.asarray([True, False, True]))


def test_check_commit_batch_trips_on_nonfinite_update():
    fn = _checked(sanitize.check_commit_batch)
    with pytest.raises(Exception, match="non-finite commit update"):
        fn(jnp.asarray([1.0, jnp.nan]), {}, {}, jnp.asarray([True]))


def test_check_commit_batch_trips_on_nonfinite_sum():
    fn = _checked(sanitize.check_commit_batch)
    with pytest.raises(Exception, match="non-finite running sum"):
        fn(jnp.ones(2), {"asum": jnp.asarray([jnp.inf, 0.0])}, {},
           jnp.asarray([True]))


def test_check_commit_batch_trips_on_count_violation():
    fn = _checked(sanitize.check_commit_batch)
    with pytest.raises(Exception, match="count conservation"):
        fn(jnp.ones(2),
           {"count": jnp.asarray(5)}, {"count": jnp.asarray(2)},
           jnp.asarray([True, False]))

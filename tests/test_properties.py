"""Hypothesis property tests (optional dependency: the whole module skips
cleanly when `hypothesis` is not installed — tier-1 collection must never
die on it)."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.data.partition import dirichlet_partition
from repro.kernels import ref


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 20), st.floats(0.05, 10.0))
def test_dirichlet_partition_is_a_partition(n_clients, alpha):
    labels = np.random.default_rng(0).integers(0, 5, size=500)
    parts = dirichlet_partition(labels, n_clients, alpha, seed=1)
    allidx = np.concatenate(parts)
    assert len(allidx) == 500
    assert len(np.unique(allidx)) == 500          # exactly once
    assert min(len(p) for p in parts) >= 2


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 8), st.integers(1, 300), st.floats(0.01, 100.0))
def test_quant_roundtrip_error_bound(n, d, scale):
    """|x - dq(q(x))| <= scale/2 per element (symmetric rounding bound)."""
    rng = np.random.default_rng(n * 1000 + d)
    x = jnp.asarray(rng.normal(size=(n, d)) * scale, jnp.float32)
    q, s = ref.quantize_rows_ref(x)
    back = ref.dequantize_rows_ref(q, s)
    bound = np.asarray(s)[:, None] * 0.5 + 1e-6
    assert np.all(np.abs(np.asarray(back - x)) <= bound)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.integers(2, 200))
def test_masked_agg_full_mask_is_mean(n, d):
    rng = np.random.default_rng(d)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q, s = ref.quantize_rows_ref(x)
    u = ref.masked_agg_ref(q, s, jnp.ones(n, bool))
    np.testing.assert_allclose(np.asarray(u),
                               np.asarray(ref.dequantize_rows_ref(q, s).mean(0)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d", [2, 256])
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 10),
       st.lists(st.tuples(st.booleans(), st.integers(0, 40)),
                min_size=1, max_size=80))
def test_ring_buffer_reads_equal_deque_semantics(d, tau_max, steps):
    """The scanned-staleness ring buffer (repro/core/scan_staleness.py) must
    serve history[-(tau+1)] for arbitrary emit/τ sequences — including τ
    beyond both caps (clamped to min(tau_max, len(history)-1)) and cursor
    wraparound after more than tau_max+1 emissions — in both stored shapes:
    (S, d) where 128 does not divide d, (S, d // 128, 128) where it does."""
    from collections import deque

    import jax
    from repro.core.cache import flat_row_shape
    from repro.core.scan_staleness import ring_append, ring_read

    S = tau_max + 1
    ring = jnp.zeros((S,) + flat_row_shape(d), jnp.float32)
    cursor = jnp.asarray(0, jnp.int32)
    history = deque(maxlen=S)
    history.append(np.zeros(d, np.float32))
    t = 0
    for emit, tau in steps:
        tau_eff = min(tau, tau_max, len(history) - 1)
        got = np.asarray(ring_read(ring, cursor, jnp.asarray(tau_eff)))
        np.testing.assert_array_equal(got, history[-(tau_eff + 1)])
        if emit:
            t += 1
            history.append(np.full(d, float(t), np.float32))
        w = jnp.full((d,), float(t), jnp.float32)
        ring, cursor = ring_append(ring, cursor, w, jnp.asarray(emit))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 7), st.integers(5, 40),
       st.lists(st.tuples(st.booleans(), st.integers(0, 6)),
                min_size=1, max_size=60))
def test_snapshot_marks_match_host_cadence(every, T, steps):
    """The in-scan eval snapshot (repro/core/scan_staleness.py
    snapshot_update) must capture the model exactly at the host's
    ``t % eval_every == 0 or t == T`` marks under a *gated* t: t advances
    only on emitted updates, and freeze fast-forward jumps skip their marks
    (no update lands on them) — matching the host, whose jump performs no
    eval either."""
    import jax
    from repro.core.scan_staleness import eval_marks_for, snapshot_update

    marks_t = eval_marks_for(T, every)
    marks = jnp.asarray(marks_t, jnp.int32)
    snaps = jnp.zeros((len(marks_t), 2), jnp.float32)
    hits = jnp.zeros((len(marks_t),), jnp.bool_)
    t, ref = 0, {}
    for emit, jump in steps:
        if jump and not emit:               # freeze fast-forward: no update
            t_new, emitted = min(t + jump, T), False
        else:
            t_new, emitted = t + int(emit), bool(emit)
        w = jnp.full((2,), float(t_new), jnp.float32)
        snaps, hits = snapshot_update(snaps, hits, marks,
                                      jnp.asarray(t_new, jnp.int32),
                                      jnp.asarray(emitted), w)
        if emitted and t_new in marks_t:
            ref[t_new] = float(t_new)       # host evals right after t += 1
        t = t_new
        if t >= T:
            break
    for i, m in enumerate(marks_t):
        assert bool(hits[i]) == (m in ref)
        if m in ref:
            assert float(snaps[i][0]) == ref[m]


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(["ace", "ace_direct", "aced", "fedbuff", "ca2fl"]),
       st.integers(2, 5), st.integers(1, 3), st.integers(4, 10),
       st.integers(0, 10**6))
def test_apply_server_rule_equals_unified_step(algo, n, M, steps, seed):
    """`distributed.apply_server_rule` (tree caches, pjit path) must be the
    SAME transition as the flat `Aggregator.step` (simulators, scan engines)
    on random pytrees / client sequences / flush points — the adapter now
    delegates to one rule implementation, and this property keeps the
    de-duplication from silently drifting. float32 caches: int8 quantizes at
    different granularity per layout (per raveled row vs per leaf row) by
    design."""
    import jax
    from jax.flatten_util import ravel_pytree

    from repro.configs.base import AFLConfig
    from repro.core.aggregators import Arrival, make_aggregator
    from repro.core.distributed import apply_server_rule, init_afl_state

    rng = np.random.default_rng(seed)
    grads_like = {"a": jnp.zeros((3, 4)), "b": jnp.zeros(5)}
    d = 17
    cfg = AFLConfig(algorithm=algo, n_clients=n, buffer_size=M, tau_algo=3)
    tree_state = init_afl_state(cfg, grads_like)
    flat_agg = make_aggregator(cfg)
    flat_state = flat_agg.init_state(n, d, None)
    for t in range(steps):
        j = int(rng.integers(n))
        tau = int(rng.integers(0, 6))
        flat = jnp.asarray(rng.normal(size=d), jnp.float32)
        g = {"a": jnp.asarray(flat[:12].reshape(3, 4)),
             "b": jnp.asarray(flat[12:])}
        assert np.allclose(ravel_pytree(g)[0], flat)    # same payload bits
        tree_state, u_tree, sc_tree = apply_server_rule(
            cfg, tree_state, g, jnp.int32(j), jnp.int32(t), jnp.int32(tau))
        flat_state, u_flat, emit, sc_flat = flat_agg.step(
            flat_state, Arrival(j, flat, t, tau))
        gated = np.asarray(u_flat) * float(np.asarray(emit))
        np.testing.assert_allclose(np.asarray(ravel_pytree(u_tree)[0]),
                                   gated, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(sc_tree), float(sc_flat),
                                   rtol=1e-6, atol=0)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(0, 4),
       st.sampled_from(["float32", "int8"]),
       st.lists(st.tuples(st.integers(0, 9), st.integers(0, 8)),
                min_size=1, max_size=40),
       st.integers(0, 10**6))
def test_aced_incremental_active_sum_matches_direct(n, tau, dtype, steps,
                                                    seed):
    """incremental-ACED running active sum == direct masked ``cache_mean``
    over random arrival/expiry/re-join sequences (flat layout, f32 + int8):
    every emitted update agrees ≤1e-5, and after the sequence the carried
    count equals the direct rule's active-set size — pinning the owner-ring
    expiry sweep, the init-cohort correction and re-arrival disowning under
    arbitrary t advances (including freeze-thaw jumps)."""
    from repro.core.aggregators import ACED, ACEDDirect, Arrival

    rng = np.random.default_rng(seed)
    d = 12
    init = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    inc = ACED(tau_algo=tau, cache_dtype=dtype)
    dr = ACEDDirect(tau_algo=tau, cache_dtype=dtype)
    s1, s2 = inc.init_state(n, d, init), dr.init_state(n, d, init)
    t, t_last = 1, 1
    for c, jump in steps:
        g = jnp.asarray(rng.normal(size=d), jnp.float32)
        arr = Arrival(c % n, g, t, 1)
        s1, u1, e1, _ = inc.step(s1, arr)
        s2, u2, e2, _ = dr.step(s2, arr)
        assert bool(e1) == bool(e2)
        np.testing.assert_allclose(np.asarray(u1), np.asarray(u2),
                                   rtol=1e-5, atol=1e-5)
        t_last = t
        t += 1 + (jump if jump > 5 else 0)      # mostly +1; sometimes a thaw
    # count reflects the active set at the last *processed* arrival time
    active = (t_last - np.asarray(s2["t_start"])) <= tau
    assert int(s1["count"]) == int(active.sum())


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 5), st.integers(0, 3),
       st.sampled_from(["float32", "int8"]),
       st.integers(3, 12), st.integers(0, 10**6))
def test_aced_incremental_matches_direct_tree_layout(n, tau, dtype, steps,
                                                     seed):
    """Same property on the tree-cache layout (pjit path): `aced` vs
    `aced_direct` through `apply_server_rule` on pytree gradients — the
    running-sum state must be layout-generic, not a FlatCache special."""
    import jax

    from repro.configs.base import AFLConfig
    from repro.core.distributed import apply_server_rule, init_afl_state

    rng = np.random.default_rng(seed)
    grads_like = {"a": jnp.zeros((3, 4)), "b": jnp.zeros(5)}
    kw = dict(n_clients=n, tau_algo=tau, cache_dtype=dtype)
    cfg_i = AFLConfig(algorithm="aced", **kw)
    cfg_d = AFLConfig(algorithm="aced_direct", **kw)
    s1, s2 = init_afl_state(cfg_i, grads_like), init_afl_state(cfg_d,
                                                               grads_like)
    for t in range(steps):
        j = int(rng.integers(n))
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.normal(size=x.shape), jnp.float32),
            grads_like)
        s1, u1, _ = apply_server_rule(cfg_i, s1, g, jnp.int32(j),
                                      jnp.int32(t), jnp.int32(1))
        s2, u2, _ = apply_server_rule(cfg_d, s2, g, jnp.int32(j),
                                      jnp.int32(t), jnp.int32(1))
        for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 8), st.integers(8, 200), st.floats(0.05, 20.0),
       st.integers(0, 10**6))
def test_row_delta_is_exact_swap(n, d, scale, seed):
    """row_delta's delta == dq(new row) − dq(old row) exactly: a running sum
    that adds delta and later subtracts dq(new row) returns to its previous
    value to fp rounding (the incremental-rule invariant)."""
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(size=(n, d)) * scale, jnp.float32)
    q, s = ref.quantize_rows_ref(rows)
    g = jnp.asarray(rng.normal(size=d) * scale, jnp.float32)
    nsc = ref.row_scale(g)
    delta, q_new = ref.row_delta_ref(g, q[0], s[0], nsc)
    old = ref.dequantize_rows_ref(q[:1], s[:1])[0]
    new = q_new.astype(jnp.float32) * nsc
    np.testing.assert_allclose(np.asarray(delta), np.asarray(new - old),
                               rtol=1e-6, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.integers(8, 128), st.integers(0, 10**6))
def test_cache_update_invariant(n, d, seed):
    """After any update sequence, u == mean(dq(cache)) exactly (Alg. a.5)."""
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q, s = ref.quantize_rows_ref(rows)
    u = ref.dequantize_rows_ref(q, s).mean(0)
    for t in range(5):
        j = int(rng.integers(n))
        g = jnp.asarray(rng.normal(size=d) * rng.uniform(0.1, 10), jnp.float32)
        nsc = ref.row_scale(g)
        u, newrow = ref.cache_row_update_ref(u, g, q[j], s[j], nsc, 1.0 / n)
        q = q.at[j].set(newrow)
        s = s.at[j].set(nsc)
    # invariant holds to f32 accumulation error: ~1e-7 * |row| per update,
    # rows can reach |g|~scale*127 with the drawn scales => atol O(1e-3)
    np.testing.assert_allclose(np.asarray(u),
                               np.asarray(ref.dequantize_rows_ref(q, s).mean(0)),
                               rtol=1e-3, atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from(["asgd", "fedbuff", "ca2fl", "ace", "aced"]),
       st.integers(2, 5), st.integers(1, 3), st.integers(4, 12),
       st.integers(0, 10**6))
def test_aggregator_step_tree_matches_flat_on_ravel(algo, n, M, steps, seed):
    """`Aggregator.step` with pytree payloads + tree-cache state (the scanned
    real-model train path) is the SAME transition as the flat (d,) layout on
    ravel/unravel round-trips of random payload sequences — state init
    included (`init_state` takes the pytree template as `d`). float32 caches:
    int8 quantizes per leaf vs per raveled row by design."""
    import jax
    from jax.flatten_util import ravel_pytree

    from repro.configs.base import AFLConfig
    from repro.core.aggregators import Arrival, make_aggregator

    rng = np.random.default_rng(seed)
    template = {"a": jnp.zeros((2, 3)), "b": jnp.zeros(4)}
    _, unravel = ravel_pytree(template)
    d = 10
    cfg = AFLConfig(algorithm=algo, n_clients=n, buffer_size=M, tau_algo=3)
    agg = make_aggregator(cfg)
    init_flat = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    init_tree = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[unravel(r) for r in init_flat])
    s_flat = agg.init_state(n, d, init_flat)
    s_tree = agg.init_state(n, template, init_tree)
    t = 1
    for _ in range(steps):
        j = int(rng.integers(n))
        tau = int(rng.integers(0, 5))
        flat = jnp.asarray(rng.normal(size=d), jnp.float32)
        s_flat, u_flat, e_flat, sc_flat = agg.step(
            s_flat, Arrival(j, flat, t, tau))
        s_tree, u_tree, e_tree, sc_tree = agg.step(
            s_tree, Arrival(j, unravel(flat), t, tau))
        assert bool(e_flat) == bool(e_tree)
        np.testing.assert_allclose(np.asarray(ravel_pytree(u_tree)[0]),
                                   np.asarray(u_flat), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(sc_tree), float(sc_flat),
                                   rtol=1e-6, atol=0)
        t += int(np.asarray(e_flat))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 12),                       # K
       st.integers(1, 400),                      # d (non-dividing tiles)
       st.booleans(),                            # quantized cache
       st.integers(1, 3),                        # R running-sum vectors
       st.integers(0, 7),                        # lane_a/b/g presence bits
       st.sampled_from([128, 256]),              # block_d
       st.sampled_from(["dense", "zero", "tiny", "huge", "allmask"]))
def test_commit_batch_fused_matches_oracle(K, d, quantized, R, lanes, blk,
                                           mode):
    """ISSUE 10 differential: the Pallas fused-commit kernel (interpret
    mode) vs the exact XLA oracle over random shapes/dtypes, non-dividing
    feature tiles, K=1, all-masked batches, zero payload rows and int8
    scale edges (tiny rows hit the 1e-12 row_scale clamp, huge rows the
    f32 range). Cache rows must be BIT-equal; sums/update ≤1e-5 relative."""
    from repro.kernels.commit_batch import commit_batch

    rng = np.random.default_rng(K * 7919 + d * 13 + lanes)
    scale = {"dense": 3.0, "zero": 0.0, "tiny": 1e-30, "huge": 1e30}
    G = jnp.asarray(rng.normal(size=(K, d)) * scale.get(mode, 1.0),
                    jnp.float32)
    valid = (np.zeros(K, bool) if mode == "allmask"
             else rng.random(K) < 0.75)
    valid = jnp.asarray(valid)
    if bool(np.any(~np.asarray(valid))):         # NaN-poison invalid lanes
        Gn = np.asarray(G).copy()
        Gn[~np.asarray(valid)] = np.nan
        G = jnp.asarray(Gn)
    rows_f = jnp.asarray(rng.normal(size=(K, d)), jnp.float32)
    if quantized:
        old_rows, old_s = ref.quantize_rows_ref(rows_f)
        new_s = ref.row_scale(jnp.where(valid[:, None], G, 0.0))
    else:
        old_rows, old_s, new_s = rows_f, None, None
    vf = valid.astype(jnp.float32)
    kw = dict(G=G, old_rows=old_rows, old_s=old_s, new_s=new_s, valid=valid,
              vecs=jnp.asarray(rng.normal(size=(R, d)), jnp.float32),
              coef=jnp.asarray(rng.normal(size=(R, R + 4)), jnp.float32),
              upd_w=jnp.asarray(rng.normal(size=(R + 4,)), jnp.float32))
    for i, name in enumerate("abg"):
        if lanes & (1 << i):
            kw[f"lane_{name}"] = jnp.asarray(rng.random(K), jnp.float32) * vf
    rows1, vecs1, upd1 = commit_batch(**kw, block_d=blk, interpret=True)
    rows2, vecs2, upd2 = ref.commit_batch_ref(**kw)
    assert jnp.array_equal(rows1, rows2)
    tol = 1e-5 * (1.0 + float(np.max(np.abs(np.asarray(vecs2)))))
    assert np.max(np.abs(np.asarray(vecs1) - np.asarray(vecs2))) <= tol
    tol_u = 1e-5 * (1.0 + float(np.max(np.abs(np.asarray(upd2)))))
    assert np.max(np.abs(np.asarray(upd1) - np.asarray(upd2))) <= tol_u

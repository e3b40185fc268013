"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.
Hypothesis property tests live in test_properties.py (optional dependency)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.cache_update import cache_row_update
from repro.kernels.commit_batch import commit_batch
from repro.kernels.masked_agg import masked_agg
from repro.kernels.quant import dequantize_rows, quantize_rows
from repro.kernels.row_delta import row_delta


@pytest.mark.parametrize("n,d", [(2, 128), (8, 1000), (16, 4096), (3, 2049),
                                 (1, 257)])
def test_quantize_matches_ref(n, d):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, d)) * rng.uniform(0.1, 30), jnp.float32)
    q1, s1 = quantize_rows(x, interpret=True, block_d=512)
    q2, s2 = ref.quantize_rows_ref(x)
    assert jnp.array_equal(q1, q2)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    x1 = dequantize_rows(q1, s1, interpret=True, block_d=512)
    np.testing.assert_allclose(np.asarray(x1),
                               np.asarray(ref.dequantize_rows_ref(q2, s2)),
                               rtol=1e-6)


@pytest.mark.parametrize("n,d,blk", [(4, 512, 128), (16, 3000, 1024),
                                     (2, 127, 256)])
def test_masked_agg_matches_ref(n, d, blk):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q, s = ref.quantize_rows_ref(x)
    for frac in (0.0, 0.5, 1.0):
        mask = jnp.asarray(rng.random(n) >= frac)
        u1 = masked_agg(q, s, mask, interpret=True, block_d=blk)
        u2 = ref.masked_agg_ref(q, s, mask)
        np.testing.assert_allclose(np.asarray(u1), np.asarray(u2),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,d,blk", [(4, 512, 128), (16, 3072, 1024)])
def test_masked_agg_takes_stored_rows(n, d, blk):
    """The masked mean over a cache stored as (n, d // 128, 128) is the
    mean over the same cache laid out (n, d)."""
    rng = np.random.default_rng(4)
    q, s = ref.quantize_rows_ref(
        jnp.asarray(rng.normal(size=(n, d)), jnp.float32))
    mask = jnp.asarray(rng.random(n) >= 0.5)
    tiles = q.reshape(n, d // 128, 128)
    u1 = masked_agg(tiles, s, mask, interpret=True, block_d=blk)
    u2 = masked_agg(q, s, mask, interpret=True, block_d=blk)
    assert u1.shape == (d,)
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u2),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.masked_agg_ref(tiles, s, mask)),
                               np.asarray(ref.masked_agg_ref(q, s, mask)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("d,blk", [(512, 128), (4096, 2048), (1000, 512),
                                   (129, 128)])
def test_cache_row_update_matches_ref(d, blk):
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=d), jnp.float32)
    g = jnp.asarray(rng.normal(size=d) * 5, jnp.float32)
    crow_f = jnp.asarray(rng.normal(size=d), jnp.float32)
    q, s = ref.quantize_rows_ref(crow_f[None])
    crow, osc = q[0], s[0]
    nsc = ref.row_scale(g)
    a1, b1 = cache_row_update(u, g, crow, osc, nsc, 0.125, interpret=True,
                              block_d=blk)
    a2, b2 = ref.cache_row_update_ref(u, g, crow, osc, nsc, 0.125)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                               rtol=1e-5, atol=1e-5)
    assert jnp.array_equal(b1, b2)


@pytest.mark.parametrize("d,blk", [(512, 128), (4096, 2048), (1000, 512),
                                   (129, 128)])
def test_row_delta_matches_ref(d, blk):
    rng = np.random.default_rng(4)
    g = jnp.asarray(rng.normal(size=d) * 5, jnp.float32)
    crow_f = jnp.asarray(rng.normal(size=d), jnp.float32)
    q, s = ref.quantize_rows_ref(crow_f[None])
    crow, osc = q[0], s[0]
    nsc = ref.row_scale(g)
    d1, q1 = row_delta(g, crow, osc, nsc, interpret=True, block_d=blk)
    d2, q2 = ref.row_delta_ref(g, crow, osc, nsc)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-5)
    assert jnp.array_equal(q1, q2)
    # the swap invariant: delta == dq(new) − dq(old) exactly
    np.testing.assert_allclose(
        np.asarray(d2),
        np.asarray(q2.astype(jnp.float32) * nsc - crow.astype(jnp.float32)
                   * osc), rtol=1e-6, atol=1e-6)


def commit_inputs(seed, K, d, R, quantized, lanes, valid=None):
    """Random inputs for the fused K-arrival commit, in the aggregator
    calling convention: lane weights are zero on invalid lanes and `new_s`
    scales the sanitized payloads (NaN-free), exactly as
    `repro.core.cache.flat_commit_batch` prepares them."""
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.normal(size=(K, d)) * 3, jnp.float32)
    if valid is None:
        valid = rng.random(K) < 0.8
    valid = jnp.asarray(valid, bool)
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
    for name in lanes:
        kw[f"lane_{name}"] = jnp.asarray(rng.random(K), jnp.float32) * vf
    return kw


@pytest.mark.parametrize("K,d,blk,quantized,R,lanes", [
    (1, 257, 128, True, 1, ()),                    # K=1, non-dividing tile
    (4, 1000, 512, True, 2, ("a", "b")),           # ACED lane shape
    (16, 2048, 1024, False, 3, ("a", "g")),        # float cache
    (3, 129, 128, True, 3, ("a", "b", "g")),       # every lane weight
])
def test_commit_batch_matches_ref(K, d, blk, quantized, R, lanes):
    kw = commit_inputs(7 * K + d, K, d, R, quantized, lanes)
    rows1, vecs1, upd1 = commit_batch(**kw, block_d=blk, interpret=True)
    rows2, vecs2, upd2 = ref.commit_batch_ref(**kw)
    assert jnp.array_equal(rows1, rows2)           # cache rows bit-exact
    np.testing.assert_allclose(np.asarray(vecs1), np.asarray(vecs2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(upd1), np.asarray(upd2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,d,blk,quantized,R,lanes", [
    (16, 4096, 2048, True, 1, ()),                 # ACE at the tile block
    (4, 1280, 512, True, 2, ("a", "b")),           # non-dividing: padded
    (3, 512, 256, False, 3, ("a", "g")),           # float cache
])
def test_commit_batch_takes_stored_rows(K, d, blk, quantized, R, lanes):
    """Rows in the cache's stored (d // 128, 128) shape go in and come out
    in it: the same bits as the (K, d) rows, and the oracle's sums."""
    kw = commit_inputs(5 * K + d, K, d, R, quantized, lanes)
    flat_rows = kw["old_rows"]
    kw["old_rows"] = flat_rows.reshape(K, d // 128, 128)
    rows1, vecs1, upd1 = commit_batch(**kw, block_d=blk, interpret=True)
    rows2, vecs2, upd2 = ref.commit_batch_ref(**kw)
    rows3, _, _ = commit_batch(**{**kw, "old_rows": flat_rows}, block_d=blk,
                               interpret=True)
    assert rows1.shape == (K, d // 128, 128)
    assert jnp.array_equal(rows1, rows2)
    assert jnp.array_equal(rows1.reshape(K, d), rows3)
    np.testing.assert_allclose(np.asarray(vecs1), np.asarray(vecs2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(upd1), np.asarray(upd2),
                               rtol=1e-5, atol=1e-5)


def test_commit_batch_invalid_lanes_are_noops():
    """Invalid lanes keep their stored rows bit-exact even when the payload
    is NaN-poisoned, and the sums/update stay finite (the guard-quarantine
    contract the scan engines rely on)."""
    valid = np.array([True, False, True, False])
    kw = commit_inputs(11, 4, 300, 2, True, ("a",), valid=valid)
    G = np.asarray(kw["G"]).copy()
    G[~valid] = np.nan
    kw["G"] = jnp.asarray(G)
    kw["new_s"] = ref.row_scale(jnp.where(kw["valid"][:, None], kw["G"], 0.0))
    rows, vecs, upd = commit_batch(**kw, block_d=128, interpret=True)
    assert jnp.array_equal(rows[~valid], kw["old_rows"][~valid])
    assert np.isfinite(np.asarray(vecs)).all()
    assert np.isfinite(np.asarray(upd)).all()
    rows2, vecs2, upd2 = ref.commit_batch_ref(**kw)
    assert jnp.array_equal(rows, rows2)
    np.testing.assert_allclose(np.asarray(vecs), np.asarray(vecs2),
                               rtol=1e-5, atol=1e-5)


def test_commit_batch_all_masked_batch():
    """An all-invalid batch is a perfect no-op on the cache and reduces the
    output to the pure affine recombination of the running-sum vectors."""
    kw = commit_inputs(13, 4, 200, 2, True, ("a", "b"),
                       valid=np.zeros(4, bool))
    rows, vecs, upd = commit_batch(**kw, block_d=128, interpret=True)
    assert jnp.array_equal(rows, kw["old_rows"])
    expect = np.asarray(kw["coef"])[:, :2] @ np.asarray(kw["vecs"])
    np.testing.assert_allclose(np.asarray(vecs), expect,
                               rtol=1e-5, atol=1e-5)


def test_ops_dispatch_xla_equals_interpret():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 300)), jnp.float32)
    qa, sa = ops.quantize_rows(x, backend="xla")
    qb, sb = ops.quantize_rows(x, backend="interpret")
    assert jnp.array_equal(qa, qb)
    mask = jnp.asarray([True, False, True, True])
    np.testing.assert_allclose(
        np.asarray(ops.masked_agg(qa, sa, mask, backend="xla")),
        np.asarray(ops.masked_agg(qa, sa, mask, backend="interpret")),
        rtol=1e-5, atol=1e-5)

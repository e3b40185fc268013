"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth).

Semantics (all f32 accumulation):
  * cache_row_update: fused ACE incremental rule on one cache row
        u' = u + (g − c_row·old_scale)·(1/n)
        c_row' = clip(round(g / new_scale))  (int8)
  * masked_agg: ACED bounded-delay aggregation over the whole cache
        u = Σ_i m_i·(C[i]·s_i) / max(Σ_i m_i, 1)
  * row_delta: fused cache-row swap for the incremental running-sum rules
        delta  = dq(q(g)) − dq(c_row)     (what a running sum gains)
        c_row' = q(g)                     (int8)
  * quantize_rows / dequantize_rows: symmetric per-row int8.
  * commit_batch: the whole K-arrival server commit as one affine pass —
        rows' = requantized payloads on valid lanes (old rows bit-exact
                elsewhere), running-sum vectors and the model update are
                rows of  mats @ [V; S_Δ; S_A; S_B; S_G]
    where the segment sums S_* are lane-weighted matvecs over the old /
    new dequantized rows (see `commit_batch_ref`).
"""
from __future__ import annotations

import jax.numpy as jnp

INT8_MAX = 127.0


def row_scale(g: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(jnp.max(jnp.abs(g), axis=-1), 1e-12) / INT8_MAX


def cache_row_update_ref(u, g, c_row, old_scale, new_scale, inv_n):
    """u,g (d,) f32; c_row (d,) int8; scalars old_scale,new_scale,inv_n.

    u is updated with the *dequantized* new row (not raw g) so that
    ``u == mean_i dq(C[i])`` stays an exact invariant (paper Alg. a.5
    under F.3.3 compression)."""
    old = c_row.astype(jnp.float32) * old_scale
    q = jnp.clip(jnp.round(g / new_scale), -127, 127)
    u_new = u + (q * new_scale - old) * inv_n
    return u_new, q.astype(jnp.int8)


def row_delta_ref(g, c_row, old_scale, new_scale):
    """g (d,) f32; c_row (d,) int8; scalars old_scale,new_scale
    -> (delta (d,) f32, c_row' (d,) int8).

    ``delta`` is the exact change a running sum of dequantized rows sees when
    row j is overwritten: dq(new) − dq(old). The incremental ACED/CA²FL rules
    add it to their O(d) running state instead of re-reducing the (n, d)
    cache, and subtract exactly ``dq(c_row')`` when the row later expires —
    the ACE-incremental invariant (paper Alg. a.5) under F.3.3 compression."""
    old = c_row.astype(jnp.float32) * old_scale
    q = jnp.clip(jnp.round(g / new_scale), -127, 127)
    return q * new_scale - old, q.astype(jnp.int8)


def masked_agg_ref(cache, scales, mask):
    """cache (n, *row) int8; scales (n,) f32; mask (n,) bool -> (d,) f32."""
    m = mask.astype(jnp.float32)
    w = m * scales
    # contract the clients in the stored shape, then flatten the (…) result
    acc = jnp.tensordot(w, cache.astype(jnp.float32), axes=1).reshape(-1)
    return acc / jnp.maximum(jnp.sum(m), 1.0)


def quantize_rows_ref(x):
    """x (n,d) f32 -> (q (n,d) int8, scales (n,) f32)."""
    s = row_scale(x)
    q = jnp.clip(jnp.round(x / s[:, None]), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_rows_ref(q, s):
    return q.astype(jnp.float32) * s[:, None]


def commit_batch_ref(G, old_rows, old_s, new_s, valid, vecs, coef, upd_w,
                     lane_a=None, lane_b=None, lane_g=None):
    """The fused K-arrival commit (ISSUE 10) — exact XLA oracle.

    Inputs
      G        (K, d) f32   arriving payloads (invalid lanes may be NaN)
      old_rows (K, *row)    the cache's K rows in its stored row shape
                            (`cache.flat_row_shape`): int8 (with `old_s`/
                            `new_s` (K,) f32 scales) or a float dtype
                            (scales None)
      valid    (K,) bool    guard mask — invalid lanes are perfect no-ops
      vecs     (R, d) f32   stacked running-sum state vectors, R ∈ {1, 2, 3}
      coef     (R, R+4) f32 affine recombination, one row per output vector
      upd_w    (R+4,) f32   the model-update row
      lane_a/b (K,) f32     optional weights on the OLD dequantized rows
                            (must be 0 on invalid lanes); None skips the sum
      lane_g   (K,) f32     optional weights on the (sanitized) payloads

    The basis is ``[vecs_0..vecs_{R-1}, S_Δ, S_A, S_B, S_G]`` with
      S_Δ = Σ_k valid_k·(dq(new_k) − dq(old_k))   (the running-sum delta,
            exact under int8: subtracts exactly what was previously added)
      S_A = Σ_k lane_a_k·dq(old_k),  S_B analogous
      S_G = Σ_k lane_g_k·Ĝ_k        (Ĝ = payloads zeroed on invalid lanes)

    Returns ``(new_rows (K, *row), vecs' (R, d) f32, update (d,) f32)``.
    `new_rows` is bit-identical to `FlatCache.set_rows_delta`'s write: valid
    lanes quantize with `new_s`, invalid lanes keep the stored row bit-exact.
    The sums are lane-weighted broadcast-multiply-reduces (NOT dot_general):
    XLA fuses them into the dequantize/requantize producers in one pass over
    the (K, d) rows — the whole oracle lowers to a single fused loop, which
    is what makes this the CPU fast path. The Pallas kernel computes the
    same sums as MXU matvecs on its feature tiles.
    """
    vf = valid.astype(jnp.float32)
    vcol = valid[:, None]
    G = G.astype(jnp.float32)
    row = old_rows.shape[1:]
    old_rows = old_rows.reshape(G.shape)
    # single sanitization point: quarantined lanes may carry NaN/inf, and
    # every downstream product must see a finite 0 there instead
    Gs = jnp.where(vcol, G, 0.0)
    if old_s is not None:
        old = old_rows.astype(jnp.float32) * old_s[:, None]
        q = jnp.clip(jnp.round(Gs / new_s[:, None]), -127, 127)
        new_rows = jnp.where(vcol, q.astype(jnp.int8), old_rows)
        dq_new = q * new_s[:, None]
    else:
        old = old_rows.astype(jnp.float32)
        stored = Gs.astype(old_rows.dtype)
        new_rows = jnp.where(vcol, stored, old_rows)
        dq_new = stored.astype(jnp.float32)

    def wsum(w, rows):                       # lane-weighted segment sum
        return jnp.sum(w.astype(jnp.float32)[:, None] * rows, axis=0)

    # one masked pass for S_Δ (vf ∈ {0,1} and dq_new/old are finite, so the
    # where-form equals the vf-weighted sum the Pallas kernel computes) and
    # only the *present* basis columns — absent lane sums are structural
    # zeros, so their mats columns are dropped instead of materialised
    sd = jnp.sum(jnp.where(vcol, dq_new - old, 0.0), axis=0)
    R = vecs.shape[0]
    parts = [vecs.astype(jnp.float32), sd[None]]
    cols = list(range(R + 1))
    for lane, rows_, col in ((lane_a, old, R + 1), (lane_b, old, R + 2),
                             (lane_g, Gs, R + 3)):
        if lane is not None:
            parts.append(wsum(lane, rows_)[None])
            cols.append(col)
    basis = jnp.concatenate(parts, 0)
    mats = jnp.concatenate([coef, upd_w[None]], 0)[:, jnp.asarray(cols)]
    out = jnp.sum(mats[:, :, None] * basis[None, :, :], axis=1)
    return new_rows.reshape(new_rows.shape[:1] + row), out[:-1], out[-1]

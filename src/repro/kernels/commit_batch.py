"""Pallas TPU kernel: the fused K-arrival server commit (ISSUE 10).

One pass per feature tile of d performs the whole batched commit that
`Aggregator.step_batch` otherwise spells as a five-op XLA chain
(`cache_set_rows_delta` + masked segment sums + running-sum/update maps):

    dequantize K old int8 cache rows          old_k = C[k]·old_s_k
    requantize + write the K new rows         C'[k] = q(Ĝ_k)   (valid lanes)
    masked segment sums as lane matvecs       S_Δ, S_A, S_B, S_G
    running sums + model update as one GEMM   [V'; u] = mats @ [V; S_*]

so every O(K·d) and O(d) intermediate lives in VMEM for the tile instead of
round-tripping HBM between ops. Exactness contract: a valid lane's delta
subtracts exactly the previously-added dequantized row, and an invalid
lane's stored row/scale stays bit-exact (`cache_set_rows_delta` semantics).

Operand layout per tile: payloads/old rows (K, block_d), state vectors
(R, block_d), the per-lane scalars packed as one (6, K) f32 block
[old_s, new_s, valid, w_a, w_b, w_g] and the affine recombination as one
(R+1, R+4) f32 block [coef; upd_w]. Statically absent lane weights skip
their matvec entirely. Block size is lane-aligned (multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import default_interpret

BLOCK_D = 2048


def _kernel(lanes_ref, mats_ref, g_ref, c_ref, v_ref,
            rows_ref, vecs_ref, upd_ref, *,
            quantized, has_a, has_b, has_g, n_vecs):
    lanes = lanes_ref[...]                       # (6, K) f32
    old_s = lanes[0][:, None]
    new_s = lanes[1][:, None]
    vf = lanes[2]                                # (K,) 1.0/0.0 valid mask
    G = g_ref[...]                               # (K, bd) f32
    vcol = vf[:, None] > 0.0
    # single sanitization point: a quarantined lane's payload may be NaN,
    # and the lane weights are 0 there by construction, so zeroing Ĝ makes
    # every downstream product finite
    Gs = jnp.where(vcol, G, 0.0)
    c = c_ref[...]
    if quantized:
        old = c.astype(jnp.float32) * old_s
        q = jnp.clip(jnp.round(Gs / new_s), -127.0, 127.0)
        rows_ref[...] = jnp.where(vcol, q.astype(jnp.int8), c)
        dq_new = q * new_s
    else:
        old = c.astype(jnp.float32)
        stored = Gs.astype(c.dtype)
        rows_ref[...] = jnp.where(vcol, stored, c)
        dq_new = stored.astype(jnp.float32)
    s_old = _matvec(lanes[2:3], old)
    sd = _matvec(lanes[2:3], dq_new) - s_old
    z = jnp.zeros_like(sd)
    sa = _matvec(lanes[3:4], old) if has_a else z
    sb = _matvec(lanes[4:5], old) if has_b else z
    sg = _matvec(lanes[5:6], Gs) if has_g else z
    basis = jnp.concatenate([v_ref[...], sd, sa, sb, sg], axis=0)
    out = _matvec(mats_ref[...], basis)
    vecs_ref[...] = out[:n_vecs]
    upd_ref[...] = out[n_vecs]


def _matvec(w, x):
    # (r, K) @ (K, bd) on the MXU. Both operands stay 2-D: the TPU compiler
    # refuses a rank-1 LHS. HIGHEST multiplies in full f32, as the XLA
    # oracle's broadcast-multiply-reduce does; the default rounds to bf16.
    return jnp.dot(w, x, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def commit_batch(G, old_rows, old_s, new_s, valid, vecs, coef, upd_w,
                 lane_a=None, lane_b=None, lane_g=None, *,
                 block_d: int = BLOCK_D, interpret: bool | None = None):
    """Fused batched commit; same signature/semantics as `ref.commit_batch_ref`
    -> ``(new_rows (K, d), vecs' (R, d) f32, update (d,) f32)``.

    `old_s`/`new_s` are (K,) f32 for an int8 cache, None for float caches;
    `lane_a`/`lane_b`/`lane_g` are optional (K,) f32 lane weights (zero on
    invalid lanes) — passing None statically removes that segment sum.
    `interpret=None` resolves backend-aware: compiled on TPU, interpreter
    elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    K, d = G.shape
    R = vecs.shape[0]
    quantized = old_rows.dtype == jnp.int8
    ones = jnp.ones((K,), jnp.float32)
    zk = jnp.zeros((K,), jnp.float32)
    lanes = jnp.stack([
        old_s.astype(jnp.float32) if quantized else ones,
        new_s.astype(jnp.float32) if quantized else ones,
        valid.astype(jnp.float32),
        lane_a.astype(jnp.float32) if lane_a is not None else zk,
        lane_b.astype(jnp.float32) if lane_b is not None else zk,
        lane_g.astype(jnp.float32) if lane_g is not None else zk])
    mats = jnp.concatenate([coef, upd_w[None]], axis=0).astype(jnp.float32)
    G = G.astype(jnp.float32)
    V = vecs.astype(jnp.float32)
    pad = (-d) % block_d
    if pad:
        G = jnp.pad(G, ((0, 0), (0, pad)))
        old_rows = jnp.pad(old_rows, ((0, 0), (0, pad)))
        V = jnp.pad(V, ((0, 0), (0, pad)))
    dp = d + pad
    row_spec = pl.BlockSpec((K, block_d), lambda i: (0, i))
    vec_spec = pl.BlockSpec((R, block_d), lambda i: (0, i))
    kern = functools.partial(
        _kernel, quantized=quantized, has_a=lane_a is not None,
        has_b=lane_b is not None, has_g=lane_g is not None, n_vecs=R)
    rows, vecs_out, upd = pl.pallas_call(
        kern,
        grid=(dp // block_d,),
        in_specs=[pl.BlockSpec((6, K), lambda i: (0, 0)),
                  pl.BlockSpec((R + 1, R + 4), lambda i: (0, 0)),
                  row_spec, row_spec, vec_spec],
        out_specs=[row_spec, vec_spec,
                   pl.BlockSpec((block_d,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((K, dp), old_rows.dtype),
                   jax.ShapeDtypeStruct((R, dp), jnp.float32),
                   jax.ShapeDtypeStruct((dp,), jnp.float32)],
        interpret=interpret,
    )(lanes, mats, G, old_rows, V)
    return rows[:, :d], vecs_out[:, :d], upd[:d]

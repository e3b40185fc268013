"""Pallas TPU kernel: symmetric per-row int8 quantization (paper F.3.3).

Two-phase: row scales from a blocked |max| reduction (phase 1 grid over
(n, d-blocks) with an output accumulator), then a blocked scale-and-round
pass. Dequantization is the trivial inverse, also blocked."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import default_interpret

BLOCK_D = 2048
INT8_MAX = 127.0


def _absmax_kernel(x_ref, out_ref):
    i = pl.program_id(1)
    blk = jnp.max(jnp.abs(x_ref[...]), axis=-1)     # (n_blk,)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
    out_ref[...] = jnp.maximum(out_ref[...], blk)


def _quant_kernel(x_ref, s_ref, q_ref):
    s = s_ref[...]                                   # (n_blk,)
    q = jnp.round(x_ref[...] / s[:, None])
    q_ref[...] = jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...][:, None]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def quantize_rows(x, *, block_d: int = BLOCK_D, interpret: bool | None = None):
    """x (n, d) f32 -> (q (n, d) int8, scales (n,) f32).

    `interpret=None` resolves backend-aware: compiled on TPU, interpreter
    elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    n, d = x.shape
    pad = (-d) % block_d
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    dp = d + pad
    grid = (1, dp // block_d)
    absmax = pl.pallas_call(
        _absmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, block_d), lambda r, i: (r, i))],
        out_specs=pl.BlockSpec((n,), lambda r, i: (r,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
        name="quantize_rows_absmax",
    )(xp)
    scales = jnp.maximum(absmax, 1e-12) / INT8_MAX
    q = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, block_d), lambda r, i: (r, i)),
                  pl.BlockSpec((n,), lambda r, i: (r,))],
        out_specs=pl.BlockSpec((n, block_d), lambda r, i: (r, i)),
        out_shape=jax.ShapeDtypeStruct((n, dp), jnp.int8),
        interpret=interpret,
        name="quantize_rows",
    )(xp, scales)
    return q[:, :d], scales


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def dequantize_rows(q, scales, *, block_d: int = BLOCK_D,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    n, d = q.shape
    pad = (-d) % block_d
    qp = jnp.pad(q, ((0, 0), (0, pad))) if pad else q
    dp = d + pad
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(1, dp // block_d),
        in_specs=[pl.BlockSpec((n, block_d), lambda r, i: (r, i)),
                  pl.BlockSpec((n,), lambda r, i: (r,))],
        out_specs=pl.BlockSpec((n, block_d), lambda r, i: (r, i)),
        out_shape=jax.ShapeDtypeStruct((n, dp), jnp.float32),
        interpret=interpret,
        name="dequantize_rows",
    )(qp, scales)
    return x[:, :d]

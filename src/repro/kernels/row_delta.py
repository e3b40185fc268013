"""Pallas TPU kernel: fused int8 cache-row swap for the incremental
running-sum server rules (paper Alg. a.5 generalised to ACED/CA²FL state).

Per d-block, one VMEM-resident tile each of g and the int8 cache row:
    delta  = q(g)·new_scale − dq(c_row)·old_scale
    c_row' = q(g)                                   (int8)
Unfused XLA emits separate dequantize, quantize and subtract sweeps over the
row; the fusion reads 5 bytes/element and writes 5 in one HBM pass. The
caller folds ``delta`` into its O(d) running sum (ACED active-set sum S,
CA²FL calibration sum h_sum) so no rule ever re-reduces the (n, d) cache.

Block size is lane-aligned (multiple of 128); scalars ride in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret

BLOCK_D = 2048  # 2048 f32 = 8 KiB/operand tile; 4 operands << 16 MiB VMEM


def _kernel(scalars_ref, g_ref, c_ref, delta_ref, c_out_ref):
    old_scale = scalars_ref[0]
    new_scale = scalars_ref[1]
    g = g_ref[...]
    old = c_ref[...].astype(jnp.float32) * old_scale
    q = jnp.clip(jnp.round(g / new_scale), -127.0, 127.0)
    # delta carries the *dequantized* new row so a running sum that later
    # subtracts dq(c_row') stays exact to fp rounding
    delta_ref[...] = q * new_scale - old
    c_out_ref[...] = q.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def row_delta(g, c_row, old_scale, new_scale, *,
              block_d: int = BLOCK_D, interpret: bool | None = None):
    """g (d,) f32; c_row (d,) int8; scalars -> (delta (d,) f32, c_row' int8).

    `interpret=None` resolves backend-aware: compiled on TPU, interpreter
    elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    d = g.shape[0]
    pad = (-d) % block_d
    if pad:
        g = jnp.pad(g, (0, pad))
        c_row = jnp.pad(c_row, (0, pad))
    dp = d + pad
    scalars = jnp.stack([jnp.asarray(old_scale, jnp.float32),
                         jnp.asarray(new_scale, jnp.float32)])
    grid = (dp // block_d,)
    spec = pl.BlockSpec((block_d,), lambda i: (i,))
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    delta, c_new = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[sspec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((dp,), jnp.float32),
                   jax.ShapeDtypeStruct((dp,), jnp.int8)],
        interpret=interpret,
        name="row_delta",
    )(scalars, g, c_row)
    return delta[:d], c_new[:d]

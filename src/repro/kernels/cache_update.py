"""Pallas TPU kernel: fused ACE incremental cache-row update (paper Alg. a.5
+ App. F.3.3 int8 compression, in one HBM pass).

Per d-block, one VMEM-resident tile each of u, g and the int8 cache row:
    u'     = u + (g − dq(c_row)) · (1/n)
    c_row' = q(g)
Unfused XLA emits three separate sweeps (dequant-subtract, axpy, quantize);
the fusion reads 9 bytes/element and writes 5 instead of ~21 moved — the
server-side aggregation is purely memory-bound, so bytes == time on TPU.

Block size is lane-aligned (multiple of 128); scalars ride in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret

BLOCK_D = 2048  # 2048 f32 = 8 KiB/operand tile; 5 operands << 16 MiB VMEM


def _kernel(scalars_ref, u_ref, g_ref, c_ref, u_out_ref, c_out_ref):
    old_scale = scalars_ref[0]
    new_scale = scalars_ref[1]
    inv_n = scalars_ref[2]
    g = g_ref[...]
    old = c_ref[...].astype(jnp.float32) * old_scale
    q = jnp.clip(jnp.round(g / new_scale), -127.0, 127.0)
    # u tracks the *dequantized* row so mean(dq(cache)) stays exact
    u_out_ref[...] = u_ref[...] + (q * new_scale - old) * inv_n
    c_out_ref[...] = q.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cache_row_update(u, g, c_row, old_scale, new_scale, inv_n, *,
                     block_d: int = BLOCK_D, interpret: bool | None = None):
    """u,g (d,) f32; c_row (d,) int8; scalars -> (u' (d,) f32, c_row' int8).

    `interpret=None` resolves backend-aware: compiled on TPU, interpreter
    elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    d = u.shape[0]
    pad = (-d) % block_d
    if pad:
        u = jnp.pad(u, (0, pad))
        g = jnp.pad(g, (0, pad))
        c_row = jnp.pad(c_row, (0, pad))
    dp = d + pad
    scalars = jnp.stack([jnp.asarray(old_scale, jnp.float32),
                         jnp.asarray(new_scale, jnp.float32),
                         jnp.asarray(inv_n, jnp.float32)])
    grid = (dp // block_d,)
    spec = pl.BlockSpec((block_d,), lambda i: (i,))
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    u_new, c_new = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[sspec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((dp,), jnp.float32),
                   jax.ShapeDtypeStruct((dp,), jnp.int8)],
        interpret=interpret,
    )(scalars, u, g, c_row)
    return u_new[:d], c_new[:d]

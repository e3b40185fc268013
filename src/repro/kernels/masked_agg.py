"""Pallas TPU kernel: ACED bounded-delay aggregation over the int8 cache.

    u = Σ_i m_i · dq(C[i]) / max(Σ_i m_i, 1)       (paper Alg. a.1 line 7)

One pass over the (n, d) cache: the grid tiles d; each program reads the full
client column block (n is small — the client axis always fits VMEM), applies
the mask·scale weights and reduces. Fuses the App. F.3.3 dequantization into
the reduction so the cache is read once as int8 (4× fewer HBM bytes than a
dequantize-then-mean graph)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import default_interpret

BLOCK_D = 2048


def _kernel(w_ref, c_ref, out_ref):
    # w_ref (n,) f32 = mask*scale/denominator ; c_ref (n, bd) int8. The LHS
    # is lifted to (1, n): the TPU compiler refuses a rank-1 dot operand.
    w = w_ref[...][None]
    c = c_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.dot(w, c, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)[0]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def masked_agg(cache, scales, mask, *, block_d: int = BLOCK_D,
               interpret: bool | None = None):
    """cache (n,d) int8; scales (n,) f32; mask (n,) bool -> u (d,) f32.

    `interpret=None` resolves backend-aware: compiled on TPU, interpreter
    elsewhere (the fused int8 path actually compiles where it can)."""
    if interpret is None:
        interpret = default_interpret()
    n, d = cache.shape
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    w = mask.astype(jnp.float32) * scales / denom
    pad = (-d) % block_d
    if pad:
        cache = jnp.pad(cache, ((0, 0), (0, pad)))
    dp = d + pad
    out = pl.pallas_call(
        _kernel,
        grid=(dp // block_d,),
        in_specs=[pl.BlockSpec((n,), lambda i: (0,)),
                  pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block_d,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((dp,), jnp.float32),
        interpret=interpret,
    )(w, cache)
    return out[:d]

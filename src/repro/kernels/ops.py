"""Jit'd dispatch wrappers over the Pallas kernels.

`backend` selection:
  * "pallas"  — pl.pallas_call with the backend-aware interpret default:
    compiled on TPU (the advertised fused int8 path), interpreter elsewhere
  * "interpret" — pl.pallas_call(interpret=True): kernel body executed in
    Python, forced even on TPU (debugging)
  * "xla"     — the pure-jnp oracle from ref.py (default on CPU: fastest here,
    and what the distributed train step lowers on the dry-run)

The default (`backend=None`) routes to "pallas" on TPU — where the kernels
actually compile — and "xla" elsewhere, so the scanned ACE/ACED steps get the
fused kernels exactly when the hardware supports them. Under a multi-device
mesh (an enclosing `use_rules` context) it routes to "xla" too: the SPMD
partitioner cannot partition a Mosaic kernel, and it can partition the
oracle. ``REPRO_NO_PALLAS=1``
(backend.no_pallas, read at trace time) forces "xla" everywhere — the
runtime escape hatch selecting the oracle path uniformly across every
kernel without editing call sites; an explicit ``backend=`` still wins.
"""
from __future__ import annotations

import jax

from repro.kernels import cache_update as _cu
from repro.kernels import commit_batch as _cb
from repro.kernels import masked_agg as _ma
from repro.kernels import quant as _q
from repro.kernels import ref
from repro.kernels import row_delta as _rd
from repro.kernels.backend import fused_commit_enabled, no_pallas
from repro.sharding.rules import active_mesh

__all__ = [
    "cache_row_update", "commit_batch", "default_backend",
    "dequantize_rows", "fused_commit_enabled", "masked_agg", "no_pallas",
    "quantize_rows", "row_delta",
]


def default_backend() -> str:
    mesh = active_mesh()
    sharded = mesh is not None and mesh.size > 1
    tpu = jax.default_backend() == "tpu"
    return "pallas" if tpu and not (sharded or no_pallas()) else "xla"


def _interpret(backend: str):
    # "pallas" defers to the kernel's backend-aware default (compiled on TPU)
    return True if backend == "interpret" else None


def cache_row_update(u, g, c_row, old_scale, new_scale, inv_n, backend=None):
    backend = backend or default_backend()
    if backend == "xla":
        return ref.cache_row_update_ref(u, g, c_row, old_scale, new_scale, inv_n)
    return _cu.cache_row_update(u, g, c_row, old_scale, new_scale, inv_n,
                                interpret=_interpret(backend))


def row_delta(g, c_row, old_scale, new_scale, backend=None):
    backend = backend or default_backend()
    if backend == "xla":
        return ref.row_delta_ref(g, c_row, old_scale, new_scale)
    return _rd.row_delta(g, c_row, old_scale, new_scale,
                         interpret=_interpret(backend))


def masked_agg(cache, scales, mask, backend=None):
    backend = backend or default_backend()
    if backend == "xla":
        return ref.masked_agg_ref(cache, scales, mask)
    return _ma.masked_agg(cache, scales, mask, interpret=_interpret(backend))


def quantize_rows(x, backend=None):
    backend = backend or default_backend()
    if backend == "xla":
        return ref.quantize_rows_ref(x)
    return _q.quantize_rows(x, interpret=_interpret(backend))


def dequantize_rows(q, s, backend=None):
    backend = backend or default_backend()
    if backend == "xla":
        return ref.dequantize_rows_ref(q, s)
    return _q.dequantize_rows(q, s, interpret=_interpret(backend))


def commit_batch(G, old_rows, old_s, new_s, valid, vecs, coef, upd_w,
                 lane_a=None, lane_b=None, lane_g=None, backend=None):
    """Fused K-arrival commit (ISSUE 10): requantize+write the K cache rows,
    fold the masked segment sums into the running-sum vectors and produce
    the model update in one pass. See `ref.commit_batch_ref` for the exact
    semantics; `repro.core.cache.flat_commit_batch` is the cache-level
    wrapper the aggregators call."""
    backend = backend or default_backend()
    if backend == "xla":
        return ref.commit_batch_ref(G, old_rows, old_s, new_s, valid, vecs,
                                    coef, upd_w, lane_a=lane_a, lane_b=lane_b,
                                    lane_g=lane_g)
    return _cb.commit_batch(G, old_rows, old_s, new_s, valid, vecs, coef,
                            upd_w, lane_a=lane_a, lane_b=lane_b,
                            lane_g=lane_g, interpret=_interpret(backend))

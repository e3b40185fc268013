"""Server-side per-client gradient cache — the O(nd) state that makes ACE's
all-client aggregation possible (paper §3.4, Table a.3), with the paper's
8-bit compression (App. F.3.3) as a first-class dtype.

Two layouts:
  * flat  — n rows over raveled params (simulators, scan engines), each
            stored in the row shape `flat_row_shape(d)`: whole (d // 128,
            128) tiles behind the client index, so a row moves as one block
            (the scan's f32 model-history ring stores its slots the same way)
  * tree  — pytree of stacked leaves {q: (n, *s), scale: (n,)} (distributed)

Quantization is symmetric per-row int8: scale = max|row| / 127. The ACE
incremental rule stays *exact* under quantization because the server subtracts
exactly the dequantized value it previously added: the invariant
``u == mean_i dq(C[i])`` holds to fp rounding.

The layout-generic ``cache_row`` / ``cache_set_row`` / ``cache_mean`` /
``cache_n`` dispatchers at the bottom let one `Aggregator.step` implementation
(repro/core/aggregators.py) serve both layouts — the host simulators and scan
engines on `FlatCache`, the pjit distributed path on tree caches — so the
server rules exist exactly once.

Sharding: flat-cache writes carry logical (cache_clients, cache_d, None)
constraints (repro/sharding/rules.shard — a no-op outside a mesh context), so
inside `use_rules(mesh)` the cache lays out client rows over the ``data``
axis and each row's leading dimension over ``model`` (the sharded staleness
scan, repro/core/scan_sharded.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref
from repro.sharding.rules import shard

INT8_MAX = 127.0


def quantize_rows(x, axis=-1):
    """x (..., d) -> (q int8, scale (...,)); `axis` may be a tuple, for a
    row stored over several dimensions.

    Scale formula (clamp |max| before dividing) must match
    repro/kernels/ref.row_scale and the quant/tree-cache kernels — all int8
    cache writers share one quantizer."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis), 1e-12) / INT8_MAX
    q = jnp.clip(jnp.round(x / jnp.expand_dims(scale, axis)), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_rows(q, scale, axis=-1):
    return q.astype(jnp.float32) * jnp.expand_dims(scale, axis)


#: lanes of a TPU vector register: the minor dimension of a stored row
LANES = 128


def flat_row_shape(d: int) -> tuple:
    """The shape one client's row of d values is stored in: whole
    ``(d // 128, 128)`` tiles when 128 divides d, else ``(d,)``.

    With the client index a major dimension over whole tiles, one row is one
    contiguous block, so reading or writing K rows moves K blocks. A
    row-tiled ``(n, d)`` int8 layout packs 32 clients into each tile: a row
    write rewrites a band of 32 rows, and a gather of K rows reads every
    client's column band."""
    return (d // LANES, LANES) if d % LANES == 0 else (d,)


def _shard_data(data):
    """The cache's logical sharding: client rows over ``cache_clients``,
    the row's leading dimension over ``cache_d``, its lanes unsharded."""
    return shard(data, ("cache_clients", "cache_d")
                 + (None,) * (data.ndim - 2))


def _take_rows(data, idx):
    """Rows ``data[idx[k]]`` (K, *row), one dynamic slice of a whole row
    each: `jnp.take` over the clients lowers to a gather that reads every
    client's column band of the cache."""
    return jnp.stack([jax.lax.dynamic_index_in_dim(data, idx[k],
                                                   keepdims=False)
                      for k in range(idx.shape[0])])


def _put_rows(data, idx, rows):
    """``data`` with ``data[idx[k]] ← rows[k]`` (K, *row), one in-place
    dynamic update of a whole row each, in lane order."""
    for k in range(idx.shape[0]):
        data = jax.lax.dynamic_update_index_in_dim(data, rows[k], idx[k], 0)
    return data


class FlatCache(NamedTuple):
    """Per-client cache over raveled params; data is int8 (with scale) or
    float. Client i's row of d values is ``data[i]``, stored in the row
    shape `flat_row_shape(d)` and read and written as (…, d) vectors."""
    data: jax.Array              # (n, *flat_row_shape(d)) int8|bf16|f32
    scale: jax.Array             # (n,) f32 (unused for float dtypes)

    @property
    def n(self):
        return self.data.shape[0]

    def _stored(self, rows):
        """(…, d) rows in the stored row shape."""
        return rows.reshape(rows.shape[:-1] + self.data.shape[1:])

    def _dq(self):
        """The whole cache dequantized to f32, in the stored shape."""
        x = self.data.astype(jnp.float32)
        if self.data.dtype == jnp.int8:
            x = x * self.scale.reshape((-1,) + (1,) * (x.ndim - 1))
        return x

    def row(self, i):
        i = jnp.asarray(i, jnp.int32)
        r = jax.lax.dynamic_index_in_dim(self.data, i,
                                         keepdims=False).reshape(-1)
        if self.data.dtype == jnp.int8:
            s = jax.lax.dynamic_index_in_dim(self.scale, i, keepdims=False)
            return r.astype(jnp.float32) * s
        return r.astype(jnp.float32)

    def set_row(self, i, g, valid=True):
        """Write row i ← g where `valid`; where not, write the stored row
        and scale back bit for bit (a NaN `g` included)."""
        i = jnp.asarray(i, jnp.int32)
        old_raw = jax.lax.dynamic_index_in_dim(self.data, i, keepdims=False)
        if self.data.dtype == jnp.int8:
            q, s = quantize_rows(g)
            old_s = jax.lax.dynamic_index_in_dim(self.scale, i,
                                                 keepdims=False)
            return FlatCache(
                _shard_data(jax.lax.dynamic_update_index_in_dim(
                    self.data, jnp.where(valid, self._stored(q), old_raw),
                    i, 0)),
                shard(jax.lax.dynamic_update_index_in_dim(
                    self.scale, jnp.where(valid, s, old_s), i, 0),
                    ("cache_clients",)))
        return FlatCache(
            _shard_data(jax.lax.dynamic_update_index_in_dim(
                self.data,
                jnp.where(valid, self._stored(g.astype(self.data.dtype)),
                          old_raw), i, 0)),
            self.scale)

    def set_row_delta(self, i, g, valid=True):
        """Write row i and return ``(cache', delta, old)`` where
        ``old = dq(row_i)`` before the write and ``delta = dq(row_i') − old``
        — the exact change a running sum of dequantized rows sees. The int8
        path routes through the fused `row_delta` kernel dispatch (one HBM
        pass: dequantize-old + quantize-new + delta); float paths are a read
        + write. Row outputs keep the feature sharding (``cache_d``). Where
        not `valid` the stored row and scale are written back bit for bit (a
        NaN `g` included) and `delta` is zero, as in `set_rows_delta`."""
        i = jnp.asarray(i, jnp.int32)
        c_stored = jax.lax.dynamic_index_in_dim(self.data, i, keepdims=False)
        c_row = c_stored.reshape(-1)
        if self.data.dtype == jnp.int8:
            old_scale = jax.lax.dynamic_index_in_dim(self.scale, i,
                                                     keepdims=False)
            new_scale = kernel_ref.row_scale(g)
            delta, q = kernel_ops.row_delta(g, c_row, old_scale, new_scale)
            cache = FlatCache(
                _shard_data(jax.lax.dynamic_update_index_in_dim(
                    self.data, jnp.where(valid, self._stored(q), c_stored),
                    i, 0)),
                shard(jax.lax.dynamic_update_index_in_dim(
                    self.scale, jnp.where(valid, new_scale.astype(jnp.float32),
                                          old_scale), i, 0),
                    ("cache_clients",)))
            # dequantize the old row directly — reconstructing it as
            # q·new_scale − delta would cancel catastrophically when the
            # client's successive gradients differ by orders of magnitude
            old = c_row.astype(jnp.float32) * old_scale
            return (cache, shard(jnp.where(valid, delta, 0.0), ("cache_d",)),
                    shard(old, ("cache_d",)))
        old = c_row.astype(jnp.float32)
        new_raw = g.astype(self.data.dtype)
        cache = FlatCache(
            _shard_data(jax.lax.dynamic_update_index_in_dim(
                self.data, jnp.where(valid, self._stored(new_raw), c_stored),
                i, 0)),
            self.scale)
        delta = jnp.where(valid, new_raw.astype(jnp.float32) - old, 0.0)
        return cache, shard(delta, ("cache_d",)), shard(old, ("cache_d",))

    def rows(self, idx):
        """Dequantized f32 gather of rows ``idx`` (K,) — the batched read
        behind the K-arrival engine (ACED cohort expiry)."""
        idx = jnp.asarray(idx, jnp.int32)
        r = _take_rows(self.data, idx).reshape(idx.shape[0], -1
                                               ).astype(jnp.float32)
        if self.data.dtype == jnp.int8:
            r = r * jnp.take(self.scale, idx, axis=0)[:, None]
        return shard(r, (None, "cache_d"))

    def set_rows_delta(self, idx, G, valid=None):
        """Batched `set_row_delta`: write rows ``idx[k] ← G[k]`` for the
        lanes where ``valid[k]`` (all lanes when `valid` is None); returns
        ``(cache', delta (K, d), old (K, d))``. Indices must be pairwise
        distinct among valid lanes (the K-batch engine's top-k sampling
        guarantees it). Invalid lanes write back their ORIGINAL stored
        row/scale bit-exactly (re-quantizing a dequantized row is NOT an
        identity under int8) and contribute a zero `delta`, so a running
        sum folding ``Σ_k delta_k`` stays exact under quantization."""
        idx = jnp.asarray(idx, jnp.int32)
        K = idx.shape[0]
        if valid is None:
            valid = jnp.ones((K,), jnp.bool_)
        vcol = valid[:, None]
        old_raw = _take_rows(self.data, idx).reshape(K, -1)
        if self.data.dtype == jnp.int8:
            old_s = jnp.take(self.scale, idx, axis=0)
            old = old_raw.astype(jnp.float32) * old_s[:, None]
            new_s = jnp.maximum(jnp.max(jnp.abs(G), axis=-1), 1e-12) / INT8_MAX
            new_q = jnp.clip(jnp.round(G / new_s[:, None]), -127, 127
                             ).astype(jnp.int8)
            dq_new = new_q.astype(jnp.float32) * new_s[:, None]
            delta = jnp.where(vcol, dq_new - old, 0.0)
            cache = FlatCache(
                _shard_data(_put_rows(self.data, idx, self._stored(
                    jnp.where(vcol, new_q, old_raw)))),
                shard(self.scale.at[idx].set(
                    jnp.where(valid, new_s.astype(jnp.float32), old_s)),
                    ("cache_clients",)))
            return (cache, shard(delta, (None, "cache_d")),
                    shard(old, (None, "cache_d")))
        old = old_raw.astype(jnp.float32)
        new_raw = G.astype(self.data.dtype)
        delta = jnp.where(vcol, new_raw.astype(jnp.float32) - old, 0.0)
        cache = FlatCache(
            _shard_data(_put_rows(self.data, idx, self._stored(
                jnp.where(vcol, new_raw, old_raw)))),
            self.scale)
        return (cache, shard(delta, (None, "cache_d")),
                shard(old, (None, "cache_d")))

    def dequant(self):
        """(n, d) f32 view."""
        return self._dq().reshape(self.n, -1)

    def mean(self, mask=None):
        """Direct aggregation (paper Alg. 1 line 10 / Alg. a.1 line 7),
        reduced over the clients in the stored shape."""
        rows = self._dq()
        if mask is None:
            return jnp.mean(rows, axis=0).reshape(-1)
        m = mask.astype(jnp.float32)
        s = jnp.sum(rows * m.reshape((-1,) + (1,) * (rows.ndim - 1)), 0)
        return s.reshape(-1) / jnp.maximum(jnp.sum(m), 1.0)

    def nbytes(self) -> int:
        return self.data.size * self.data.dtype.itemsize + self.scale.nbytes


def init_flat_cache(n: int, d: int, dtype: str = "float32",
                    init_rows=None) -> FlatCache:
    """An (n, d) cache stored as (n, *flat_row_shape(d)); `init_rows`,
    (n, d) or already (n, *flat_row_shape(d)), seeds the rows (int8:
    quantized per row)."""
    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.int8}[dtype]
    stored = (n,) + flat_row_shape(d)
    if init_rows is not None:
        if dt == jnp.int8:
            # quantize in the stored shape: rows that come stacked in it
            # are read once and the int8 cache is written once
            q, s = quantize_rows(init_rows.reshape(stored),
                                 axis=tuple(range(1, len(stored))))
            return FlatCache(_shard_data(q), shard(s, ("cache_clients",)))
        return FlatCache(_shard_data(init_rows.astype(dt).reshape(stored)),
                         jnp.ones((n,), jnp.float32))
    return FlatCache(_shard_data(jnp.zeros(stored, dt)),
                     jnp.ones((n,), jnp.float32))


def flat_commit_batch(cache: FlatCache, idx, G, valid, vecs, coef, upd_w,
                      lane_a=None, lane_b=None, lane_g=None):
    """The whole K-arrival commit as ONE fused pass: read the
    K old rows, requantize+write the new ones, fold the masked segment
    sums into the stacked running-sum vectors ``vecs (R, d)`` via the
    ``coef (R, R+4)`` recombination and emit the ``upd_w``-weighted model
    update — `kernels/ops.commit_batch` behind the backend-aware dispatch
    (Pallas megakernel on TPU, exact XLA oracle elsewhere).

    The K rows move as whole rows in the stored row shape, which the
    kernel takes as it is: K dynamic slices on the client dimension in, K
    dynamic updates out, in place on a donated cache.

    Returns ``(cache', vecs' (R, d) f32, update (d,) f32)``. The written
    rows are bit-identical to `FlatCache.set_rows_delta` (valid lanes
    requantized with the same `row_scale`, invalid lanes bit-exact no-ops);
    only the running sums differ from the op chain by f32 reassociation
    (≤1e-5, BENCH-gated). Lane weights must be zero on invalid lanes.
    Sharding: writes carry the (cache_clients, cache_d) constraints, vector
    outputs the feature (cache_d) constraint — the TRC004 contract, so the
    sharded scan consumes this path unchanged."""
    idx = jnp.asarray(idx, jnp.int32)
    G = G.astype(jnp.float32)
    old_rows = _take_rows(cache.data, idx)
    if cache.data.dtype == jnp.int8:
        old_s = jnp.take(cache.scale, idx, axis=0)
        # scale the *sanitized* payloads: an invalid lane's NaN must not
        # poison new_s (its q/scale are never written, but NaN·0 would
        # taint the kernel's products); valid lanes match set_rows_delta's
        # scale formula exactly
        new_s = kernel_ref.row_scale(jnp.where(valid[:, None], G, 0.0))
        new_rows, vecs_out, update = kernel_ops.commit_batch(
            G, old_rows, old_s, new_s, valid, vecs, coef, upd_w,
            lane_a=lane_a, lane_b=lane_b, lane_g=lane_g)
        new_cache = FlatCache(
            _shard_data(_put_rows(cache.data, idx, new_rows)),
            shard(cache.scale.at[idx].set(
                jnp.where(valid, new_s.astype(jnp.float32), old_s)),
                ("cache_clients",)))
    else:
        new_rows, vecs_out, update = kernel_ops.commit_batch(
            G, old_rows, None, None, valid, vecs, coef, upd_w,
            lane_a=lane_a, lane_b=lane_b, lane_g=lane_g)
        new_cache = FlatCache(
            _shard_data(_put_rows(cache.data, idx, new_rows)),
            cache.scale)
    return (new_cache, shard(vecs_out, (None, "cache_d")),
            shard(update, ("cache_d",)))


# ---------------------------------------------------------------------------
# Tree cache (distributed path): one stacked cache per param leaf.
# ---------------------------------------------------------------------------

def init_tree_cache(n: int, grads_like,  # tracecheck: ignore[TRC004]
                    dtype: str = "float32", init_rows=None):
    # TRC004 suppressed: tree-cache leaves inherit their sharding from the
    # enclosing pjit'd train step via the params template (GSPMD propagates
    # from `grads_like`); only the flat (n, d) cache needs the explicit
    # logical-axis constraint that FlatCache routes through shard().
    """Per-leaf stacked cache {q: (n, *s), scale?: (n,)} over `grads_like`.

    `init_rows` (a grads-like pytree with a leading (n,) client axis — e.g.
    the stacked init-batch gradients of a cache-init rule) seeds the rows;
    the int8 path quantizes each row with the same per-leaf scalar scale
    `tree_cache_set_row` uses (reduced over every axis but the client one),
    so a seeded cache is bit-identical to n successive row writes."""
    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.int8}[dtype]

    def leaf(g):
        data = jnp.zeros((n,) + tuple(jnp.shape(g)), dt)
        if dt == jnp.int8:
            return {"q": data, "scale": jnp.ones((n,), jnp.float32)}
        return {"q": data}

    def seeded(rows):
        rows = rows.astype(jnp.float32)
        if dt == jnp.int8:
            ax = tuple(range(1, rows.ndim))
            s = jnp.maximum(jnp.max(jnp.abs(rows), axis=ax), 1e-12) / INT8_MAX
            q = jnp.clip(jnp.round(rows / s.reshape((-1,) + (1,) * len(ax))),
                         -127, 127).astype(jnp.int8)
            return {"q": q, "scale": s.astype(jnp.float32)}
        return {"q": rows.astype(dt)}

    if init_rows is None:
        return jax.tree.map(leaf, grads_like)
    return jax.tree.map(lambda g, rows: seeded(rows), grads_like, init_rows)


def tree_cache_row(cache, i):
    def leaf(c):
        r = jax.lax.dynamic_index_in_dim(c["q"], i, keepdims=False)
        if c["q"].dtype == jnp.int8:
            s = jax.lax.dynamic_index_in_dim(c["scale"], i, keepdims=False)
            return r.astype(jnp.float32) * s
        return r.astype(jnp.float32)
    return jax.tree.map(leaf, cache, is_leaf=lambda x: isinstance(x, dict) and "q" in x)


def tree_cache_set_row(cache, i, grads, valid=True):
    """Write row i ← `grads` where `valid` (one per-leaf scalar scale under
    int8); where not, the stored row and scale are written back bit for
    bit. The write of `tree_cache_set_row_delta`, its delta unused."""
    return tree_cache_set_row_delta(cache, i, grads, valid)[0]


def tree_cache_rows(cache, idx):
    """Batched `tree_cache_row`: dequantized gather of rows ``idx`` (K,) —
    returns a grads-like pytree with a leading (K,) lane axis per leaf."""
    idx = jnp.asarray(idx, jnp.int32)

    def leaf(c):
        r = jnp.take(c["q"], idx, axis=0).astype(jnp.float32)
        if c["q"].dtype == jnp.int8:
            s = jnp.take(c["scale"], idx, axis=0)
            r = r * s.reshape((-1,) + (1,) * (r.ndim - 1))
        return r
    return jax.tree.map(leaf, cache, is_leaf=is_tree_cache_leaf)


def tree_cache_set_rows_delta(cache, idx, grads,  # tracecheck: ignore[TRC004]
                              valid=None):
    # TRC004 suppressed: like init_tree_cache above, per-leaf .at[idx].set
    # writes inherit each leaf's (data, model) sharding from the enclosing
    # pjit'd step; only the flat (n, d) layout needs FlatCache's explicit
    # shard() constraint.
    """Tree-cache analogue of `FlatCache.set_rows_delta`: `grads` is a
    grads-like pytree with a leading (K,) lane axis; per-leaf per-lane scalar
    scales match `tree_cache_set_row` (reduced over every axis but the lane
    one). Invalid lanes write back their original q/scale bit-exactly and
    zero their `delta` leaves."""
    idx = jnp.asarray(idx, jnp.int32)
    K = idx.shape[0]
    if valid is None:
        valid = jnp.ones((K,), jnp.bool_)

    deltas, olds = [], []

    def leaf(c, g):
        g = g.astype(jnp.float32)
        vshape = (-1,) + (1,) * (g.ndim - 1)
        vmask = valid.reshape(vshape)
        old_raw = jnp.take(c["q"], idx, axis=0)
        if c["q"].dtype == jnp.int8:
            old_s = jnp.take(c["scale"], idx, axis=0)
            old = old_raw.astype(jnp.float32) * old_s.reshape(vshape)
            ax = tuple(range(1, g.ndim))
            s = jnp.maximum(jnp.max(jnp.abs(g), axis=ax), 1e-12) / INT8_MAX
            q = jnp.clip(jnp.round(g / s.reshape(vshape)), -127, 127
                         ).astype(jnp.int8)
            dq_new = q.astype(jnp.float32) * s.reshape(vshape)
            delta = jnp.where(vmask, dq_new - old, 0.0)
            out = {"q": c["q"].at[idx].set(jnp.where(vmask, q, old_raw)),
                   "scale": c["scale"].at[idx].set(
                       jnp.where(valid, s.astype(jnp.float32), old_s))}
        else:
            old = old_raw.astype(jnp.float32)
            new_raw = g.astype(c["q"].dtype)
            delta = jnp.where(vmask, new_raw.astype(jnp.float32) - old, 0.0)
            out = {"q": c["q"].at[idx].set(jnp.where(vmask, new_raw,
                                                     old_raw))}
        deltas.append(delta)
        olds.append(old)
        return out

    new_cache = jax.tree.map(leaf, cache, grads, is_leaf=is_tree_cache_leaf)
    treedef = jax.tree.structure(grads)
    return (new_cache, jax.tree.unflatten(treedef, deltas),
            jax.tree.unflatten(treedef, olds))


def tree_cache_set_row_delta(cache, i, grads, valid=True):
    """Tree-cache analogue of `FlatCache.set_row_delta`: returns
    ``(cache', delta, old)`` with `delta`/`old` grads-like f32 pytrees.
    Per-leaf generic path (the pjit train step fuses these elementwise ops
    itself; the Pallas fusion targets the flat scan layout). The new row's
    dequantized value comes from the quantized payload, not from a read of
    the written cache. Where not `valid` each leaf writes its stored row
    and scale back bit for bit (a NaN payload included) and zeroes its
    `delta`."""
    deltas, olds = [], []

    def leaf(c, g):
        g = g.astype(jnp.float32)
        old_raw = jax.lax.dynamic_index_in_dim(c["q"], i, keepdims=False)
        if c["q"].dtype == jnp.int8:
            old_s = jax.lax.dynamic_index_in_dim(c["scale"], i,
                                                 keepdims=False)
            old = old_raw.astype(jnp.float32) * old_s
            # axis-preserving scale reduction: flattening (reshape(-1))
            # would destroy the leaf's 2-D (data, model) sharding and force
            # XLA to all-gather the full gradient — ~2x params of ICI
            # traffic per step at 405B scale (see EXPERIMENTS.md §Perf
            # iteration 1).
            s = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / INT8_MAX
            q = jnp.clip(jnp.round(g / s), -127, 127).astype(jnp.int8)
            new = q.astype(jnp.float32) * s
            out = {"q": jax.lax.dynamic_update_index_in_dim(
                       c["q"], jnp.where(valid, q, old_raw), i, 0),
                   "scale": jax.lax.dynamic_update_index_in_dim(
                       c["scale"], jnp.where(valid, s, old_s), i, 0)}
        else:
            old = old_raw.astype(jnp.float32)
            new_raw = g.astype(c["q"].dtype)
            new = new_raw.astype(jnp.float32)
            out = {"q": jax.lax.dynamic_update_index_in_dim(
                c["q"], jnp.where(valid, new_raw, old_raw), i, 0)}
        deltas.append(jnp.where(valid, new - old, 0.0))
        olds.append(old)
        return out

    new_cache = jax.tree.map(leaf, cache, grads, is_leaf=is_tree_cache_leaf)
    treedef = jax.tree.structure(grads)
    return (new_cache, jax.tree.unflatten(treedef, deltas),
            jax.tree.unflatten(treedef, olds))


def tree_cache_mean(cache, mask=None):
    def leaf(c):
        rows = c["q"].astype(jnp.float32)
        if c["q"].dtype == jnp.int8:
            s = c["scale"].reshape((-1,) + (1,) * (rows.ndim - 1))
            rows = rows * s
        if mask is None:
            return jnp.mean(rows, axis=0)
        m = mask.astype(jnp.float32).reshape((-1,) + (1,) * (rows.ndim - 1))
        return jnp.sum(rows * m, 0) / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return jax.tree.map(leaf, cache, is_leaf=lambda x: isinstance(x, dict) and "q" in x)


def tree_cache_nbytes(cache) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))


# ---------------------------------------------------------------------------
# Layout-generic dispatch: one Aggregator.step implementation for both the
# flat (simulator / scan) and tree (pjit distributed) cache layouts.
# ---------------------------------------------------------------------------

def is_tree_cache_leaf(x) -> bool:
    """A tree-cache *leaf*: the {"q": ..., "scale"?: ...} dict one param leaf
    stacks into (see init_tree_cache)."""
    return isinstance(x, dict) and "q" in x


def cache_n(cache) -> int:
    """Number of client rows, either layout."""
    if isinstance(cache, FlatCache):
        return cache.n
    leaf = jax.tree.leaves(cache, is_leaf=is_tree_cache_leaf)[0]
    return leaf["q"].shape[0]


def cache_row(cache, i):
    """Dequantized f32 row i: (d,) for FlatCache, grads-like pytree for a
    tree cache."""
    if isinstance(cache, FlatCache):
        return cache.row(i)
    return tree_cache_row(cache, i)


def cache_rows(cache, idx):
    """Dequantized f32 gather of rows ``idx`` (K,): a (K, d) array for
    FlatCache, a grads-like pytree with a leading (K,) lane axis for a tree
    cache — the batched read behind the K-arrival engine."""
    if isinstance(cache, FlatCache):
        return cache.rows(idx)
    return tree_cache_rows(cache, idx)


def cache_set_row(cache, i, g, valid=True):
    """Write (re-quantizing as needed) row i where `valid`; an invalid write
    leaves the stored row and scale bit-exact. Returns the same layout."""
    if isinstance(cache, FlatCache):
        return cache.set_row(i, g, valid)
    return tree_cache_set_row(cache, i, g, valid)


def cache_set_row_delta(cache, i, g, valid=True):
    """Write row i, returning ``(cache', delta, old)`` — the running-sum
    primitive behind the O(d) server rules: ``delta = dq(new) − dq(old)``
    folds into an incremental aggregate (ACED's active-set sum, CA²FL's
    h_sum) and ``old`` is exactly the dequantized value previously added, so
    those aggregates stay exact under int8 (paper Alg. a.5 invariant).
    Where not `valid` the stored row and scale stay bit-exact and `delta`
    is zero — the single-row form of `cache_set_rows_delta`'s lane mask."""
    if isinstance(cache, FlatCache):
        return cache.set_row_delta(i, g, valid)
    return tree_cache_set_row_delta(cache, i, g, valid)


def cache_set_rows_delta(cache, idx, G, valid=None):
    """Batched `cache_set_row_delta`: write rows ``idx[k] ← G[k]`` for the
    lanes where ``valid[k]`` (`G` carries a leading (K,) lane axis; indices
    must be pairwise distinct among valid lanes). Returns
    ``(cache', delta, old)`` with per-lane leading axes; invalid lanes leave
    their stored row/scale bit-exact and zero their `delta`, so running sums
    folding ``Σ_k delta_k`` (ACED's asum, CA²FL's h_sum) stay exact under
    int8 — the K-arrival analogue of the Alg. a.5 invariant."""
    if isinstance(cache, FlatCache):
        return cache.set_rows_delta(idx, G, valid)
    return tree_cache_set_rows_delta(cache, idx, G, valid)


def cache_mean(cache, mask=None):
    """(Masked) mean over client rows — Alg. 1 line 10 / Alg. a.1 line 7."""
    if isinstance(cache, FlatCache):
        return cache.mean(mask)
    return tree_cache_mean(cache, mask)


def cache_sum(cache, mask=None):
    """Σ over dequantized client rows (optionally ``mask``-gated, an (n,)
    bool/float row selector) — the one-time O(n·d) seed of the incremental
    rules' running sums (ACED's asum/init_sum) and the periodic
    `Aggregator.resync` exact recompute; never on a per-event hot path."""
    if isinstance(cache, FlatCache):
        rows = cache._dq()
        if mask is None:
            return rows.sum(0).reshape(-1)
        m = mask.astype(jnp.float32).reshape((-1,) + (1,) * (rows.ndim - 1))
        return jnp.sum(rows * m, 0).reshape(-1)

    def leaf(c):
        rows = c["q"].astype(jnp.float32)
        if c["q"].dtype == jnp.int8:
            rows = rows * c["scale"].reshape((-1,) + (1,) * (rows.ndim - 1))
        if mask is None:
            return jnp.sum(rows, 0)
        m = mask.astype(jnp.float32).reshape((-1,) + (1,) * (rows.ndim - 1))
        return jnp.sum(rows * m, 0)
    return jax.tree.map(leaf, cache, is_leaf=is_tree_cache_leaf)

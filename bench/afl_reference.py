"""Plain reference of the asynchronous-FL server protocol the cells run, and
the readings that compare the program with it.

It follows the protocol of the paper (Fig. 2 sampled staleness, Alg. 1 / a.5
ACE) step by step in straightforward `jax.numpy`, one tick and one arrival at
a time, with nothing taken from the program:

  * init: every client's gradient at w0 seeds its cache row; the model takes
    one step along the mean of those raw gradients; the history holds
    [w0, w1]; t = 1.
  * each tick: K distinct clients by Gumbel top-k over the participation
    log-weights; each lane's staleness is min(floor(tau_raw), tau_max, number
    of updates so far); each client computes its payload at the model that
    many updates old; ACE replaces the client's cache row (int8: symmetric
    absmax per row of each leaf, scale max|x|/127) and moves the running mean
    by the change of the dequantized rows over n; the model steps by
    eta · u with eta = lr_scale·sqrt(n/T); the new model is appended to the
    history (int8 per leaf when the history is int8).

`dtype` is the precision of every float the reference computes and keeps:
float32 for the reference (matmuls at "highest"), bfloat16 for the control.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from traffic import participation_log_probs


def quantize(x):
    """Symmetric int8 of one row (the whole array): (q, scale)."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def dequantize(q, s):
    return q.astype(jnp.float32) * s


_quantize = jax.jit(lambda tree: jax.tree.map(quantize, tree))


@dataclasses.dataclass
class Trace:
    """What the reference (or the program) did over the first chunks."""
    losses: np.ndarray          # (ticks,) per-tick mean client loss
    u_step1: list               # host leaves of the running mean after chunk 1
    w_last: list                # host leaves of the model after the last chunk
    t: int                      # server iteration after the last chunk


def run(*, w0, payload: Callable, n_clients: int, traffic, cache_dtype: str,
        history_dtype: str, key, gumbels: np.ndarray, tau_raw: np.ndarray,
        n_chunks: int, dtype=jnp.float32, fault=None) -> Trace:
    """Follow the protocol for `n_chunks` chunks of the stream.

    `payload(w, client, key) -> (loss, grad)` is the client computation,
    given the stale model in `dtype`; `gumbels`/`tau_raw` are the stream's
    leading rows on the host; `key` is the key the program's init gets.

    `fault` plants one of the faults the comparison must catch, to read
    what it does to the numbers: "half_batch" commits only the first half
    of a tick's K arrivals (K > 1; at K = 1 the family's payload halves the
    client batch); "altered" negates the largest element of the first leaf
    of every payload where the client produces it."""
    if traffic.algorithm != "ace":
        raise ValueError(f"the reference implements ACE only, not {traffic.algorithm!r}")
    n, K, C = n_clients, traffic.k_batch, traffic.chunk_events
    S = traffic.tau_max + 1
    eta = jnp.asarray(traffic.lr_scale * (n / traffic.T) ** 0.5, dtype)
    log_p = participation_log_probs(n, traffic.speed_skew)
    cast = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(dtype), t))

    def store(w):   # a history slot: the model's leaves, int8 or as kept
        leaves = jax.tree.leaves(w)
        return _quantize(leaves) if history_dtype == "int8" else cast(leaves)

    def load(slot):
        if history_dtype == "int8":
            slot = [dequantize(q, s).astype(dtype) for q, s in slot]
        return jax.tree.unflatten(treedef, slot)

    # --- init: one gradient per client at w0 -------------------------------
    w = cast(w0)
    leaves, treedef = jax.tree.flatten(w)
    rows_q = [jnp.zeros((n,) + x.shape, jnp.int8 if cache_dtype == "int8" else dtype)
              for x in leaves]
    rows_s = [jnp.ones((n,), jnp.float32) for _ in leaves]
    raw_sum = [jnp.zeros(x.shape, dtype) for x in leaves]
    for j in range(n):
        key, sub = jax.random.split(key)
        _, g = payload(w, j, sub)
        if fault == "altered":
            g = _alter(g)
        rows_q, rows_s, raw_sum = _seed_rows(rows_q, rows_s, raw_sum, j,
                                             jax.tree.leaves(g), cache_dtype)
    u = [(_row_mean(q, s, cache_dtype)).astype(dtype) for q, s in zip(rows_q, rows_s)]
    w = jax.tree.unflatten(treedef, [x - eta * (r / n) for x, r in
                                     zip(leaves, raw_sum)])
    history: List = [store(cast(w0)), store(w)]
    t = 1   # server iteration; every tick emits, so also the updates so far

    losses, u_step1 = [], None
    for c in range(n_chunks):
        for e in range(c * C, (c + 1) * C):
            scores = log_p + gumbels[e]
            js = np.argsort(-scores, kind="stable")[:K]
            taus = np.floor(np.atleast_1d(tau_raw[e])).astype(np.int64)
            taus = np.minimum(taus, min(traffic.tau_max, t))
            if K == 1:
                key, sub = jax.random.split(key)
                lane_keys = [sub]
            else:
                ks = jax.random.split(key, K + 1)
                key = ks[0]
                lane_keys = [jax.random.split(k)[1] for k in ks[1:]]
            lane_loss, delta = [], [jnp.zeros(x.shape, dtype) for x in leaves]
            lanes = K // 2 if fault == "half_batch" and K > 1 else K
            for lane in range(lanes):
                j, tau = int(js[lane]), int(taus[lane])
                stale = load(history[-(tau + 1)])
                loss, g = payload(stale, j, lane_keys[lane])
                if fault == "altered":
                    g = _alter(g)
                lane_loss.append(loss)
                rows_q, rows_s, delta = _commit_rows(rows_q, rows_s, delta, j,
                                                     jax.tree.leaves(g), cache_dtype)
            u = [a + d / n for a, d in zip(u, delta)]
            leaves = [x - eta * a for x, a in zip(jax.tree.leaves(w), u)]
            w = jax.tree.unflatten(treedef, leaves)
            history = (history + [store(w)])[-S:]
            t += 1
            losses.append(float(np.mean([float(x) for x in lane_loss])))
        if c == 0:
            u_step1 = [np.asarray(a, np.float32) for a in u]
    return Trace(np.asarray(losses), u_step1,
                 [np.asarray(x, np.float32) for x in jax.tree.leaves(w)], t)


@jax.jit
def _alter(g):
    """Negate the largest-magnitude element of the first leaf."""
    leaves, treedef = jax.tree.flatten(g)
    x = leaves[0].reshape(-1)
    i = jnp.argmax(jnp.abs(x))
    leaves[0] = x.at[i].set(-x[i]).reshape(leaves[0].shape)
    return jax.tree.unflatten(treedef, leaves)


def _get_row(q, s, j, cache_dtype):
    if cache_dtype == "int8":
        return dequantize(q[j], s[j])
    return q[j].astype(jnp.float32)


def _put_row(q, s, j, x, cache_dtype):
    if cache_dtype == "int8":
        rq, rs = quantize(x)
        return q.at[j].set(rq), s.at[j].set(rs)
    return q.at[j].set(x.astype(q.dtype)), s


@functools.partial(jax.jit, static_argnums=5, donate_argnums=(0, 1, 2))
def _seed_rows(rows_q, rows_s, raw_sum, j, g, cache_dtype):
    """Client j's first payload `g` (one array per leaf) fills its cache
    rows and is added to the raw sum."""
    out = [_put_row(q, s, j, x, cache_dtype) for q, s, x in zip(rows_q, rows_s, g)]
    return ([q for q, _ in out], [s for _, s in out],
            [r + x.astype(r.dtype) for r, x in zip(raw_sum, g)])


@functools.partial(jax.jit, static_argnums=5, donate_argnums=(0, 1, 2))
def _commit_rows(rows_q, rows_s, delta, j, g, cache_dtype):
    """Client j's payload `g` replaces its cache rows, leaf by leaf; `delta`
    gains the change of the rows as read back."""
    qs, ss, ds = [], [], []
    for q, s, dl, x in zip(rows_q, rows_s, delta, g):
        old = _get_row(q, s, j, cache_dtype)
        q, s = _put_row(q, s, j, x, cache_dtype)
        qs.append(q)
        ss.append(s)
        ds.append(dl + (_get_row(q, s, j, cache_dtype) - old).astype(dl.dtype))
    return qs, ss, ds


@functools.partial(jax.jit, static_argnums=2)
def _row_mean(q, s, cache_dtype):
    """Mean of the dequantized rows, one row at a time."""
    def add(j, total):
        row = q[j].astype(jnp.float32)
        return total + (row * s[j] if cache_dtype == "int8" else row)
    return jax.lax.fori_loop(0, q.shape[0], add,
                             jnp.zeros(q.shape[1:], jnp.float32)) / q.shape[0]


# --- readings ---------------------------------------------------------------

def readings(prog: Trace, ref: Trace, w0_leaves: list) -> dict:
    """Every number the comparison can hold against a limit.

    loss_gap     largest relative gap of a tick's mean client loss
    u_norm_gap   worst leaf: |‖u_p‖ − ‖u_r‖| over max(‖u_r‖, median leaf's),
                 u being the running mean after the first chunk — the first
                 update as the model step receives it
    dw_norm_gap  the same for the model's change w − w0 after the last chunk
    u_max_gap    largest element gap of u over the largest |u_r|
    dw_max_gap   largest element gap of w − w0 over the largest |Δw_r|
    t_gap        |t_p − t_r| after the last chunk (exact)

    Leaves whose reference u norm is under a thousandth of the median leaf's
    (nought to rounding) are left out of the norm gaps."""
    ref_u = [np.linalg.norm(x) for x in ref.u_step1]
    med_u = float(np.median(ref_u))
    keep = [i for i, v in enumerate(ref_u) if v >= 1e-3 * med_u]

    def norm_gap(p_leaves, r_leaves):
        pn = [float(np.linalg.norm(p_leaves[i])) for i in keep]
        rn = [float(np.linalg.norm(r_leaves[i])) for i in keep]
        med = float(np.median(rn))
        return max(abs(a - b) / max(b, med) for a, b in zip(pn, rn))

    def max_gap(p_leaves, r_leaves):
        top = max(float(np.max(np.abs(r))) for r in r_leaves)
        return max(float(np.max(np.abs(p - r))) for p, r in
                   zip(p_leaves, r_leaves)) / top

    dw_p = [p - w for p, w in zip(prog.w_last, w0_leaves)]
    dw_r = [r - w for r, w in zip(ref.w_last, w0_leaves)]
    return {
        "loss_gap": float(np.max(np.abs(prog.losses - ref.losses)
                                 / np.abs(ref.losses))),
        "u_norm_gap": norm_gap(prog.u_step1, ref.u_step1),
        "dw_norm_gap": norm_gap(dw_p, dw_r),
        "u_max_gap": max_gap(prog.u_step1, ref.u_step1),
        "dw_max_gap": max_gap(dw_p, dw_r),
        "t_gap": float(abs(prog.t - ref.t)),
    }

"""The flat-layout server's client task and its byte counts, for the `flat`
configurations of the benchmark.

A flat configuration benchmarks the AFL server alone: the model is one raveled
float32 vector of d parameters, and the clients, which on a real deployment
compute elsewhere, hand in a payload at next to no cost — the stale model
they were sent minus their target row. Client j's target is row j mod R of a
table of R rows. The table is never stored: each element comes from an
integer hash of (row, index, table_seed), so the payload costs a few integer
operations per element and no read from device memory, and the server's own
work — stale ring reads, cache commit, running mean, model update, ring
append — is what a tick spends its time on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def target_rows(rows, d: int, table_seed: int):
    """Target rows (…, d) float32 in [-1, 1) for table row indices `rows`
    (any int32 shape): a multiply-xorshift hash of the element index, salted
    by row and seed."""
    rows = jnp.asarray(rows, jnp.uint32)[..., None]
    i = jnp.arange(d, dtype=jnp.uint32)
    h = i * jnp.uint32(0x9E3779B1) + (rows + jnp.uint32(1)) * jnp.uint32(0x85EBCA77)
    h = h ^ jnp.uint32(table_seed & 0xFFFFFFFF)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> 12)
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) - 1.0


def client_payload(w, client, model: dict):
    """(loss, payload) of client `client` at model `w` (d,): the payload is
    w − target, the loss half its mean square."""
    c = target_rows(jnp.asarray(client) % model["table_rows"], w.shape[-1],
                    model["table_seed"]).astype(w.dtype)
    diff = w - c
    return 0.5 * jnp.mean(diff * diff, axis=-1), diff


def init_params(key, model: dict):
    """The starting model: d standard normals, float32."""
    return jax.jit(lambda k: jax.random.normal(k, (model["d"],), jnp.float32))(key)


def reference_payload(config: dict, dtype=jnp.float32, fault=None):
    """payload(w, client, key) -> (loss, payload), in the dtype of `w`. A
    payload has no batch, so the fault "half_batch" leaves it as it is."""
    model = config["model"]
    fn = jax.jit(lambda w, client: client_payload(w, client, model))
    return lambda w, client, key: fn(w, jnp.int32(client))


def build_program(config: dict, traffic, weights, mesh):
    """The scanned AFL server on the flat layout, chunked as the trainer
    runs it: `weights` is the raveled float32 model, the cache and the
    running mean follow the file's `clients` settings, and each client's
    gradient is its payload above."""
    from repro.configs.base import AFLConfig
    from repro.core.aggregators import make_aggregator
    from repro.core.scan_staleness import make_chunked_staleness_runner
    from repro.optim import sqrt_nt_schedule

    model, clients = config["model"], config["clients"]
    n = clients["n_clients"]
    aflc = AFLConfig(algorithm=traffic.algorithm, n_clients=n,
                     cache_dtype=clients["cache_dtype"], k_batch=traffic.k_batch)

    def grad_fn(w, client, key):
        return client_payload(w, client, model)

    return make_chunked_staleness_runner(
        mesh=mesh, grad_fn=grad_fn, params0=weights,
        aggregator=make_aggregator(aflc), n_clients=n, T=traffic.T,
        beta=traffic.beta,
        server_lr=sqrt_nt_schedule(traffic.lr_scale, n, traffic.T),
        tau_max=traffic.tau_max, speed_skew=traffic.speed_skew, layout="flat",
        history_dtype=clients["history_dtype"], guards=False,
        resync_every=None, checkify_invariants=False, k_batch=traffic.k_batch)


def tick_work(config: dict, traffic) -> dict:
    return {"flops": 0.0,
            "bytes": tick_bytes(config["model"], traffic.k_batch)}


def tick_bytes(model: dict, k_batch: int) -> float:
    """Bytes one server tick must move at the least, once each: the K stale
    models read from the float32 ring, the K old int8 cache rows read and
    the K new ones written (with their scales), the running mean read and
    written, the model read and written, and one ring slot written. The
    targets are computed, not read."""
    d = model["d"]
    f32, i8 = 4, 1
    return float(k_batch * d * f32            # stale ring rows
                 + 2 * k_batch * (d * i8 + 4)  # cache rows + scales, old and new
                 + 2 * d * f32                 # running mean u
                 + 2 * d * f32                 # model w
                 + d * f32)                    # ring append


def kernel_bytes(config: dict, traffic) -> dict:
    """Bytes per call of each Pallas kernel the cell's server tick runs."""
    model, K = config["model"], traffic.k_batch
    if K > 1:
        return {"commit_batch": commit_batch_bytes(model, K)}
    return {"cache_row_update": cache_row_update_bytes(model)}


def commit_batch_bytes(model: dict, k_batch: int) -> float:
    """Bytes of one fused `commit_batch` call for ACE (one running vector):
    K payload rows (float32) and K old int8 rows read, K int8 rows written,
    the running mean read and written, the update written, and the lane and
    coefficient blocks."""
    d, K, R = model["d"], k_batch, 1
    return float(K * d * 4 + K * d + K * d + R * d * 4 + R * d * 4 + d * 4
                 + 6 * K * 4 + (R + 1) * (R + 4) * 4)


def cache_row_update_bytes(model: dict) -> float:
    """Bytes of one `cache_row_update` call: u, the payload and the old int8
    row read (4 + 4 + 1 per element), u and the new int8 row written
    (4 + 1), and three scalars."""
    d = model["d"]
    return float(d * (4 + 4 + 1) + d * (4 + 1) + 3 * 4)

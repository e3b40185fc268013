"""Sharded staleness scan — the device-resident sampled-staleness engine
(repro/core/scan_staleness.py) laid out over a ``(data, model)`` mesh.

ACE/ACED pay for their participation-imbalance robustness with an O(n·d)
per-client cache (paper Table a.3), and the scanned engine adds a
``(tau_max+1, d)`` ring-buffer model history plus an ``(n_marks, d)`` eval
snapshot buffer. On one chip those buffers bound the reachable
(n_clients × model-size) corner of the Fig. 2/3 sweeps; this module shards
them so the same scan spans a pod:

  * **aggregator cache** ``(n_clients, d)`` — client rows over ``data``,
    features over ``model`` (logical axes ``cache_clients``/``cache_d``,
    repro/sharding/rules.py) — the exact layout the pjit train step in
    repro/core/distributed.py uses, so the scan and the pod-scale path fuse;
  * **ring buffer** ``(tau_max+1, d)`` and **snapshot buffer**
    ``(n_marks, d)`` — history/mark slots replicated, features over
    ``model``;
  * **gumbel rows** ``(n_clients,)`` — over ``data`` (client sampling).

Mechanically this is the GSPMD flavour of pjit: `make_staleness_runner`
already threads logical sharding constraints through `ring_read` /
`ring_append` / `snapshot_update` and the `FlatCache` writers (no-ops
without a mesh), so the sharded runner is the SAME traced program compiled
under an active `use_rules(mesh)` context — one rule implementation
(`Aggregator.step`) serves host sim, single-device scan, sharded scan and
the distributed train step. XLA partitions the scan body across the mesh
and inserts the collectives (the cache mean's psum over ``data``, the
categorical argmax's gather over client shards).

Equivalence contract: sharded and unsharded runs consume identical
randomness and differ only by reduction order, so trajectories match to
≤1e-5 — tests/test_scan_sharded.py pins sharded vs unsharded vs host replay
for all five algorithms under dropout, speed-skew, availability windows and
int8 caches on a forced 8-device host mesh (see tests/conftest.py).

Usage::

    mesh = staleness_mesh()                  # (data, model) over all devices
    runner = make_sharded_staleness_runner(mesh=mesh, grad_fn=..., ...)
    # or: run_staleness_seeds(..., mesh=mesh) / run_staleness_grid(..., mesh=mesh)

`benchmarks/common.py` picks the sharded runner automatically whenever more
than one device is visible (``mesh="auto"``), so
``XLA_FLAGS=--xla_force_host_platform_device_count=8 python -m benchmarks.run
--suites fig2`` runs the Fig. 2 sweep sharded end-to-end.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import AxisType

from repro.core.scan_staleness import make_staleness_runner
from repro.sharding.rules import use_rules


def staleness_mesh(*, model: Optional[int] = None):
    """A ``(data, model)`` mesh over every visible device, or None when only
    one device exists (callers then fall back to the unsharded runner).

    `model` defaults to 2 when the device count is even (features of the
    cache/ring/snapshot buffers split once, client rows take the rest) and 1
    otherwise; pass it explicitly to bias toward feature sharding for large
    models. The client axis gets the larger factor because the O(n·d) cache
    dominates state and n_clients is the axis that scales with fleet size."""
    n = jax.device_count()
    if n < 2:
        return None
    if model is None:
        model = 2 if n % 2 == 0 else 1
    if n % model != 0:
        raise ValueError(f"model={model} does not divide device count {n}")
    # Auto axes: `shard()` places logical constraints, which Explicit axes
    # (the jax.make_mesh default) refuse
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_sharded_staleness_runner(*, mesh, **kwargs):
    """Build the sharded runner: `make_staleness_runner(**kwargs)` traced and
    compiled under ``use_rules(mesh)`` so its logical sharding constraints
    (cache/ring/snapshot/client-row layouts, see module docstring) become
    real GSPMD annotations.

    Same call signature as the unsharded runner —
    ``run(key, gumbels, tau_raw, leave_at, rejoin_at, lr)`` — and composes
    with `jax.vmap` for the seed/lr-grid sweeps (the batch axis stays
    unsharded; each run's buffers shard). The mesh context wraps every call:
    entering it is cheap, tracing only happens once per shape."""
    if mesh is None:
        raise ValueError("make_sharded_staleness_runner needs a mesh; use "
                         "make_staleness_runner for single-device runs")
    base = make_staleness_runner(**kwargs)

    @functools.wraps(base)
    def run(key, gumbels, tau_raw, leave_at, rejoin_at, lr, *guard_args):
        with use_rules(mesh):
            return base(key, gumbels, tau_raw, leave_at, rejoin_at, lr,
                        *guard_args)

    run.mesh = mesh
    run.base = base
    return run


def make_sharded_chunked_staleness_runner(*, mesh, **kwargs):
    """Chunked flavour (`ChunkedStalenessRunner`) under ``use_rules(mesh)``
    — the checkpointable executor `launch/train.py` drives when more than
    one device is visible. Thin alias: `make_chunked_staleness_runner`
    already wraps every init/chunk call in the mesh context when one is
    given; this entry point exists for symmetry and the explicit
    mesh-required contract."""
    if mesh is None:
        raise ValueError("make_sharded_chunked_staleness_runner needs a "
                         "mesh; use make_chunked_staleness_runner for "
                         "single-device runs")
    from repro.core.scan_staleness import make_chunked_staleness_runner
    return make_chunked_staleness_runner(mesh=mesh, **kwargs)

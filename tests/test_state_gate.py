"""The state gate of the scan tick.

At every K the per-client cache is gated by validity alone: on a
K-batched tick `Aggregator.step_batch` writes it only through lane-masked
row writes, and on a per-arrival tick (K = 1) `Aggregator.step` writes it
only through a row write masked by `Arrival.valid`. So the tick's
``where(proc, new, old)`` keeps every other leaf of the aggregator state and
passes the cache through (`scan_staleness._select_state`). Pinned here:

  * every cache rule with a `step_batch` (ACE, CA²FL, ACED), in both
    layouts, every cache dtype and with the fused commit on and off, leaves
    each cache leaf bit-identical under a batch of invalid NaN lanes; every
    cache rule's `step` does the same under an invalid NaN arrival;
  * the compiled K-batched chunk has no select over the whole cache and,
    for ACE and CA²FL, no copy of it: the row scatter writes the loop carry
    in place. The per-arrival chunk, compiled for a TPU v5e, has neither
    either.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AFLConfig
from repro.core.aggregators import (ACED, ACEDDirect, ACEDirect,
                                    ACEIncremental, Arrival, ArrivalBatch,
                                    CA2FL, CA2FLDirect, make_aggregator)
from repro.core.scan_staleness import (build_fault_schedule,
                                       build_staleness_randomness,
                                       make_chunked_staleness_runner)

N, K, C = 8, 4, 4
PARAMS0 = {"a": jnp.zeros((64,), jnp.float32),
           "b": jnp.zeros((4, 8), jnp.float32)}
D = 96                                       # PARAMS0 raveled
RULES = {
    "ace": lambda dt, fused: ACEIncremental(cache_dtype=dt,
                                            fused_commit=fused),
    "ca2fl": lambda dt, fused: CA2FL(buffer_size=3, cache_dtype=dt,
                                     fused_commit=fused),
    "aced": lambda dt, fused: ACED(tau_algo=3, cache_dtype=dt,
                                   max_cohort=K, fused_commit=fused),
}
#: every rule with a per-client cache, as its `step` runs at K = 1
RULES_K1 = {
    "ace": lambda dt: ACEIncremental(cache_dtype=dt),
    "ca2fl": lambda dt: CA2FL(buffer_size=3, cache_dtype=dt),
    "aced": lambda dt: ACED(tau_algo=3, cache_dtype=dt),
    "ace_direct": lambda dt: ACEDirect(cache_dtype=dt),
    "ca2fl_direct": lambda dt: CA2FLDirect(buffer_size=3, cache_dtype=dt),
    "aced_direct": lambda dt: ACEDDirect(tau_algo=3, cache_dtype=dt),
}
DTYPES = ["int8", "bfloat16", "float32"]
#: (layout, fused commit): the tree layout has only the dispatch chain
LAYOUTS = [("flat", True), ("flat", False), ("tree", False)]
#: an HLO instruction: its name, result shape without layout, and opcode
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+?)(?:\{[^}]*\})? "
                   r"([\w\-]+)\(")


def _cache(state):
    return state["cache"] if "cache" in state else state["h"]   # CA²FL: h


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


def _init_state(agg, layout, rng):
    """The rule's state with its cache seeded from random rows."""
    if layout == "flat":
        init = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
        return agg.init_state(N, D, init_grads=init)
    init = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=(N,) + p.shape), jnp.float32),
        PARAMS0)
    return agg.init_state(N, PARAMS0, init_grads=init)


def _lanes(layout, rng, scale=1.0):
    """K lanes of payload in the layout: (K, d) flat, PARAMS0-shaped tree
    leaves with a leading (K,) axis."""
    if layout == "flat":
        return jnp.asarray(rng.normal(size=(K, D)) * scale, jnp.float32)
    return jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=(K,) + p.shape) * scale,
                              jnp.float32), PARAMS0)


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("layout,fused", LAYOUTS)
@pytest.mark.parametrize("algo", sorted(RULES))
def test_invalid_nan_lanes_leave_the_cache_bit_identical(algo, layout, fused,
                                                         dt):
    """A batch with no valid lane, its payloads NaN, leaves every cache leaf
    bit for bit as it was; in a batch that mixes valid and invalid NaN
    lanes, the invalid lanes' rows and every row outside the batch keep
    their bits."""
    rng = np.random.default_rng(0)
    agg = RULES[algo](dt, fused)
    state = _init_state(agg, layout, rng)
    step = jax.jit(agg.step_batch)
    state, *_ = step(state, ArrivalBatch(
        jnp.asarray([5, 0, 2, 7], jnp.int32), _lanes(layout, rng), 1,
        jnp.zeros((K,), jnp.int32), jnp.ones((K,), jnp.bool_)))
    before = _cache(state)
    js = jnp.asarray([1, 3, 4, 6], jnp.int32)
    nan = jax.tree.map(lambda p: jnp.full_like(p, jnp.nan),
                       _lanes(layout, rng))
    out, *_ = step(state, ArrivalBatch(js, nan, 2, jnp.zeros((K,), jnp.int32),
                                       jnp.zeros((K,), jnp.bool_)))
    assert _bits(_cache(out)) == _bits(before)

    valid = jnp.asarray([True, False, True, False])
    mixed = jax.tree.map(
        lambda g, b: jnp.where(valid.reshape((-1,) + (1,) * (g.ndim - 1)),
                               g, b), _lanes(layout, rng), nan)
    out, *_ = step(state, ArrivalBatch(js, mixed, 2,
                                       jnp.zeros((K,), jnp.int32), valid))
    kept = np.asarray([0, 2, 3, 5, 7])           # js[~valid] and the rest
    for a, b in zip(jax.tree.leaves(_cache(out)), jax.tree.leaves(before)):
        a, b = np.asarray(a), np.asarray(b)
        assert a[kept].tobytes() == b[kept].tobytes()


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("layout", ["flat", "tree"])
@pytest.mark.parametrize("algo", sorted(RULES_K1))
def test_invalid_nan_arrival_leaves_the_cache_bit_identical(algo, layout,
                                                            dt):
    """K = 1: an arrival the tick does not process (frozen, quarantined or
    refused, so `Arrival.valid` is False), its payload NaN, leaves every
    cache leaf bit for bit as it was through `Aggregator.step`; the same
    arrival made valid rewrites its client's row and no other."""
    rng = np.random.default_rng(0)
    agg = RULES_K1[algo](dt)
    state = _init_state(agg, layout, rng)
    step = jax.jit(agg.step)
    one = lambda lanes: jax.tree.map(lambda x: x[0], lanes)  # noqa: E731
    state, *_ = step(state, Arrival(5, one(_lanes(layout, rng)), 1, 0))
    before = _cache(state)
    nan = jax.tree.map(lambda p: jnp.full_like(p, jnp.nan),
                       one(_lanes(layout, rng)))
    out, *_ = step(state, Arrival(3, nan, 2, 0, jnp.asarray(False)))
    assert _bits(_cache(out)) == _bits(before)

    out, *_ = step(state, Arrival(3, one(_lanes(layout, rng)), 2, 0,
                                  jnp.asarray(True)))
    rest = np.asarray([0, 1, 2, 4, 5, 6, 7])
    moved = False
    for a, b in zip(jax.tree.leaves(_cache(out)), jax.tree.leaves(before)):
        a, b = np.asarray(a), np.asarray(b)
        assert a[rest].tobytes() == b[rest].tobytes()
        moved = moved or a[3].tobytes() != b[3].tobytes()
    assert moved


def _grad_fn(w, client, key):
    def loss(w):
        c = client.astype(jnp.float32)
        return sum(jnp.mean((x - c) ** 2) for x in jax.tree.leaves(w))
    return jax.value_and_grad(loss)(w)


def _compiled_cache_ops(algo, layout, k, guarded, sharding=None):
    """Compile the tiny chunk from shapes, for the default device or for
    `sharding`'s, and return the opcodes of its instructions whose result
    has an int8 cache leaf's shape."""
    aflc = AFLConfig(algorithm=algo, n_clients=N, cache_dtype="int8",
                     k_batch=k)
    runner = make_chunked_staleness_runner(
        grad_fn=_grad_fn, params0=PARAMS0, aggregator=make_aggregator(aflc),
        n_clients=N, T=1000, beta=5.0, speed_skew=3.0, layout=layout,
        history_dtype="int8" if layout == "tree" else "float32",
        guards=guarded, resync_every=2 if guarded else None, k_batch=k)
    lr = jnp.float32(0.0)
    carry = jax.eval_shape(runner.init, jax.random.PRNGKey(0), lr)
    rand = build_staleness_randomness(0, C, N, 5.0, speed_skew=3.0,
                                      k_batch=k)
    args = [carry, rand.gumbels, rand.tau_raw, rand.leave_at, rand.rejoin_at,
            lr]
    if guarded:
        faults = build_fault_schedule(0, C, k_batch=k, nan_rate=0.25)
        args += [faults.kind, faults.scale, jnp.float32(1.0)]
    if sharding is not None:
        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                           sharding=sharding), args)
    shapes = {"s8[%s]" % ",".join(map(str, x.shape))
              for x in jax.tree.leaves(_cache(carry["state"]))
              if x.dtype == jnp.int8}
    assert shapes == ({"s8[8,96]"} if layout == "flat"
                      else {"s8[8,64]", "s8[8,4,8]"})
    text = runner.jit_chunk.lower(*args).compile().as_text()
    return [(m.group(3), m.group(1)) for m in map(INSTR.match,
                                                  text.splitlines())
            if m and m.group(2) in shapes]


def _selects(ops):
    return [name for op, name in ops
            if op == "select" or (op == "fusion" and "select" in name)]


@pytest.mark.parametrize("guarded", [False, True])
@pytest.mark.parametrize("layout", ["flat", "tree"])
@pytest.mark.parametrize("algo", ["ace", "ca2fl"])
def test_k_batched_tick_updates_the_cache_in_place(algo, layout, guarded):
    """The K-batched chunk neither selects over the whole cache nor copies
    it: the row scatter takes the loop carry directly."""
    ops = _compiled_cache_ops(algo, layout, K, guarded)
    assert any(op in ("scatter", "fusion") for op, _ in ops)
    assert _selects(ops) == []
    assert [name for op, name in ops if op == "copy"] == []


def test_aced_k_batched_tick_drops_the_cache_select():
    """ACED's K-batched chunk has no whole-cache select either. It keeps
    cache copies, which its expiry sweep forces: XLA threads the cache
    through the sweep's `fori_loop`, before the row scatter writes it."""
    ops = _compiled_cache_ops("aced", "flat", K, False)
    assert _selects(ops) == []
    assert [name for op, name in ops if op == "copy"]


@pytest.mark.parametrize("guarded", [False, True])
@pytest.mark.parametrize("layout", ["flat", "tree"])
@pytest.mark.parametrize("algo", ["ace", "ca2fl"])
def test_per_arrival_tick_updates_the_cache_in_place(one_chip, algo, layout,
                                                     guarded):
    """The per-arrival chunk (K = 1), compiled for one chip of a described
    TPU v5e, neither selects over the whole cache nor copies it: the rule's
    masked row write takes the loop carry directly.

    The CPU compiler still copies the cache here (two copies flat, four
    tree): it fuses the old row's read into the consumers of the running
    mean, which it schedules after the row write. So the chip's compiler is
    the one this checks."""
    ops = _compiled_cache_ops(algo, layout, 1, guarded, one_chip)
    assert any(op in ("dynamic-update-slice", "fusion") for op, _ in ops)
    assert _selects(ops) == []
    assert [name for op, name in ops if op == "copy"] == []


@pytest.mark.parametrize("layout", ["flat", "tree"])
def test_aced_per_arrival_tick_updates_the_cache_in_place(one_chip, layout):
    """ACED's `step` honours `Arrival.valid` as well, so its per-arrival
    chunk passes the cache through the gate too. Compiled for a v5e it
    keeps neither a whole-cache select nor a copy: the K = 1 expiry sweep
    reads the cache before the row write. (Its K-batched chunk on the CPU
    keeps copies; see above.)"""
    ops = _compiled_cache_ops("aced", layout, 1, False, one_chip)
    assert _selects(ops) == []
    assert [name for op, name in ops if op == "copy"] == []

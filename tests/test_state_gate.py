"""The state gate of the K-batched scan tick.

On a K-batched tick (`step_k`) the per-client cache is gated by lane
validity alone: `Aggregator.step_batch` writes it only through lane-masked
row writes, so the tick's ``where(any(valid), new, old)`` keeps every other
leaf of the aggregator state and passes the cache through
(`scan_staleness._select_batch_state`). Pinned here:

  * every cache rule with a `step_batch` (ACE, CA²FL, ACED), in both
    layouts, every cache dtype and with the fused commit on and off, leaves
    each cache leaf bit-identical under a batch of invalid NaN lanes;
  * the compiled K-batched chunk has no select over the whole cache and,
    for ACE and CA²FL, no copy of it: the row scatter writes the loop carry
    in place. The per-arrival tick (`step`) still copies the cache.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AFLConfig
from repro.core.aggregators import (ACED, ACEIncremental, ArrivalBatch,
                                    CA2FL, make_aggregator)
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
#: (layout, fused commit): the tree layout has only the dispatch chain
LAYOUTS = [("flat", True), ("flat", False), ("tree", False)]
#: an HLO instruction: its name, result shape without layout, and opcode
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+?)(?:\{[^}]*\})? "
                   r"([\w\-]+)\(")


def _cache(state):
    return state["cache"] if "cache" in state else state["h"]   # CA²FL: h


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


def _lanes(layout, rng, scale=1.0):
    """K lanes of payload in the layout: (K, d) flat, PARAMS0-shaped tree
    leaves with a leading (K,) axis."""
    if layout == "flat":
        return jnp.asarray(rng.normal(size=(K, D)) * scale, jnp.float32)
    return jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=(K,) + p.shape) * scale,
                              jnp.float32), PARAMS0)


@pytest.mark.parametrize("dt", ["int8", "bfloat16", "float32"])
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
    if layout == "flat":
        init = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
        state = agg.init_state(N, D, init_grads=init)
    else:
        init = jax.tree.map(
            lambda p: jnp.asarray(rng.normal(size=(N,) + p.shape),
                                  jnp.float32), PARAMS0)
        state = agg.init_state(N, PARAMS0, init_grads=init)
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


def _grad_fn(w, client, key):
    def loss(w):
        c = client.astype(jnp.float32)
        return sum(jnp.mean((x - c) ** 2) for x in jax.tree.leaves(w))
    return jax.value_and_grad(loss)(w)


def _compiled_cache_ops(algo, layout, k, guarded):
    """Compile the tiny chunk from shapes and return the opcodes of its
    instructions whose result has an int8 cache leaf's shape."""
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


@pytest.mark.parametrize("layout", ["flat", "tree"])
def test_per_arrival_tick_still_copies_the_cache(layout):
    """`step` (K = 1) keeps its select over the cache and copies it: the
    ACE transition reads the old row after the row write, so the write
    cannot go to the loop carry in place."""
    ops = _compiled_cache_ops("ace", layout, 1, False)
    assert _selects(ops)
    assert [name for op, name in ops if op == "copy"]

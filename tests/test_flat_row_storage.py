"""The flat cache's stored row shape.

`FlatCache.data` holds client i's row of d values as ``data[i]``, in the row
shape `cache.flat_row_shape(d)`: whole ``(d // 128, 128)`` tiles when 128
divides d, ``(d,)`` otherwise. Rows are read and written as whole rows on
the client dimension and handed to callers as (…, d) vectors. Pinned here:

  * a chunk of the flat scan, for every rule with a fused K-arrival commit
    (ACE, ACED, CA²FL) at K = 1 and K = 16, with int8 and f32 caches, at
    d = 4096 (tiles) and d = 1000 (the ``(d,)`` row), leaves the cache rows,
    their scales, the rule's running sums and the model bit for bit as the
    ``(n, d)`` layout left them: the digests below were recorded from the
    code that stored the cache as ``(n, d)``;
  * `FlatCache.row` / `rows` / `dequant` / `mean` / `set_rows_delta` and
    `cache_sum` on the stored cache against the same rows laid out
    ``(n, d)`` (the methods read the row shape from ``data.shape[1:]``).
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AFLConfig
from repro.core.aggregators import make_aggregator
from repro.core.cache import FlatCache, cache_sum, flat_row_shape, \
    init_flat_cache
from repro.core.scan_staleness import (build_staleness_randomness,
                                       make_chunked_staleness_runner)

N, TICKS, LR = 32, 4, 0.05

#: sha256 prefixes of (cache rows, scales, the rest of the rule's state, w)
#: after init and one chunk, recorded from the (n, d) layout's code
PARENT = {
    "ace-k1-int8-d4096": {"rows": "bdbfd8ff53e7ecb5", "scale": "2f4febc7561e6643",
                         "state": "e88e055a0cd1c1b7", "w": "ddc3d5d773865858"},
    "ace-k1-int8-d1000": {"rows": "0967a9ef69c5603d", "scale": "066c3a4c0cb0f192",
                         "state": "cbe04603e47ae0e7", "w": "74d2079ce7a66790"},
    "ace-k1-float32-d4096": {"rows": "caad8cf6522ce3f9", "scale": "b638277a8690e175",
                            "state": "91729d05ab4f8fb4", "w": "e23dd4db6cc337ba"},
    "ace-k1-float32-d1000": {"rows": "88e165dc38bade66", "scale": "b638277a8690e175",
                            "state": "b2f450aa0f8c1062", "w": "2e6f8566adb0dd92"},
    "ace-k16-int8-d4096": {"rows": "91132e632632b608", "scale": "934b5f565eb62237",
                          "state": "7e17dd3d4793fc29", "w": "582706e26ff5acbb"},
    "ace-k16-int8-d1000": {"rows": "da4a5eecc5a8b858", "scale": "e23ee1b8bdfb6818",
                          "state": "4b8145ee39e30a5d", "w": "f54e9422ee9f2336"},
    "ace-k16-float32-d4096": {"rows": "2e8db7a6a079a68c", "scale": "b638277a8690e175",
                             "state": "ceb547a619c7623a", "w": "d03af88156cf0d42"},
    "ace-k16-float32-d1000": {"rows": "81790144a3e95925", "scale": "b638277a8690e175",
                             "state": "0726cc2890313a7b", "w": "3ad7f2b858cc55cc"},
    "aced-k1-int8-d4096": {"rows": "bdbfd8ff53e7ecb5", "scale": "2f4febc7561e6643",
                          "state": "108417eaea97db5b", "w": "ddc3d5d773865858"},
    "aced-k1-int8-d1000": {"rows": "0967a9ef69c5603d", "scale": "066c3a4c0cb0f192",
                          "state": "2516a294cd52b4c7", "w": "74d2079ce7a66790"},
    "aced-k1-float32-d4096": {"rows": "caad8cf6522ce3f9", "scale": "b638277a8690e175",
                             "state": "533623f346598b2c", "w": "e23dd4db6cc337ba"},
    "aced-k1-float32-d1000": {"rows": "88e165dc38bade66", "scale": "b638277a8690e175",
                             "state": "58fbda282392d701", "w": "2e6f8566adb0dd92"},
    "aced-k16-int8-d4096": {"rows": "91132e632632b608", "scale": "934b5f565eb62237",
                           "state": "270f09b1f5875aef", "w": "582706e26ff5acbb"},
    "aced-k16-int8-d1000": {"rows": "da4a5eecc5a8b858", "scale": "e23ee1b8bdfb6818",
                           "state": "5980817c8e701a7f", "w": "f54e9422ee9f2336"},
    "aced-k16-float32-d4096": {"rows": "2e8db7a6a079a68c", "scale": "b638277a8690e175",
                              "state": "dfe4eafaae88b43b", "w": "d03af88156cf0d42"},
    "aced-k16-float32-d1000": {"rows": "81790144a3e95925", "scale": "b638277a8690e175",
                              "state": "78c2c54094b31a45", "w": "3ad7f2b858cc55cc"},
    "ca2fl-k1-int8-d4096": {"rows": "9309fe85f1f04d3d", "scale": "0937594fcaf10453",
                           "state": "6e4409dcd8665c49", "w": "afb9825a6df7150e"},
    "ca2fl-k1-int8-d1000": {"rows": "6748979830a49c28", "scale": "3297fc5d31dc2e34",
                           "state": "7899434a6158ceae", "w": "14c4586f97430e31"},
    "ca2fl-k1-float32-d4096": {"rows": "78ce549275e9b3f2", "scale": "b638277a8690e175",
                              "state": "674bcd1d6c842272", "w": "afb9825a6df7150e"},
    "ca2fl-k1-float32-d1000": {"rows": "55714b46272c6c13", "scale": "b638277a8690e175",
                              "state": "65d955b4a529329a", "w": "14c4586f97430e31"},
    "ca2fl-k16-int8-d4096": {"rows": "e2f2541b3e66e94a", "scale": "8684738624d733ca",
                            "state": "604488a95ed14f6c", "w": "2f2e1d211f15bdc1"},
    "ca2fl-k16-int8-d1000": {"rows": "51e4a629e1c675c4", "scale": "1dfb71e34a951980",
                            "state": "84a87c2d69cc5e91", "w": "8e2697fd1a04409c"},
    "ca2fl-k16-float32-d4096": {"rows": "12b6aa916375ab7e", "scale": "b638277a8690e175",
                               "state": "819489e9f8ec5aac", "w": "8c9046377464e86d"},
    "ca2fl-k16-float32-d1000": {"rows": "eebe70a90943d485", "scale": "b638277a8690e175",
                               "state": "60a30a959d8b4927", "w": "5f71d4aba8d79e57"},
}


def _grad_fn(d):
    lanes = jnp.arange(d, dtype=jnp.float32)

    def grad_fn(w, client, key):
        c = jnp.sin(lanes * 0.01 * (client.astype(jnp.float32) + 1.0))
        g = w - c + 0.1 * jax.random.normal(key, w.shape)
        return 0.5 * jnp.sum((w - c) ** 2), g
    return grad_fn


def _digest(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest()[:16]


def _chunk_digests(algo, k, dtype, d):
    """Digests of the flat scan's state after init and one chunk."""
    params0 = jnp.asarray(np.random.default_rng(0).normal(size=d),
                          jnp.float32)
    agg = make_aggregator(AFLConfig(algorithm=algo, n_clients=N,
                                    cache_dtype=dtype, k_batch=k))
    runner = make_chunked_staleness_runner(
        grad_fn=_grad_fn(d), params0=params0, aggregator=agg, n_clients=N,
        T=1000, beta=5.0, speed_skew=3.0, layout="flat", k_batch=k)
    lr = jnp.float32(LR)
    rand = build_staleness_randomness(3, TICKS, N, 5.0, speed_skew=3.0,
                                      k_batch=k)
    carry = runner.init(jax.random.PRNGKey(7), lr)
    carry, _ = runner.chunk(carry, rand.gumbels, rand.tau_raw,
                            rand.leave_at, rand.rejoin_at, lr)
    state = carry["state"]
    key = "cache" if "cache" in state else "h"
    cache = state[key]
    rest = {k_: v for k_, v in state.items() if k_ != key}
    return {"rows": _digest(np.asarray(cache.data).reshape(N, d)),
            "scale": _digest(cache.scale),
            "state": _digest(np.concatenate(
                [np.asarray(x).astype(np.float32).ravel()
                 for x in jax.tree.leaves(rest)])),
            "w": _digest(carry["w"])}


@pytest.mark.parametrize("d", [4096, 1000])
@pytest.mark.parametrize("dtype", ["int8", "float32"])
@pytest.mark.parametrize("k", [1, 16])
@pytest.mark.parametrize("algo", ["ace", "aced", "ca2fl"])
def test_flat_chunk_is_bit_identical_to_the_nd_layout(algo, k, dtype, d):
    """The stored row shape moves no bit of a chunk's result."""
    got = _chunk_digests(algo, k, dtype, d)
    assert got == PARENT[f"{algo}-k{k}-{dtype}-d{d}"]


@pytest.mark.parametrize("dtype", ["int8", "bfloat16", "float32"])
@pytest.mark.parametrize("d", [4096, 1000])
def test_flat_cache_reads_and_writes_match_the_nd_layout(d, dtype):
    """Row reads, whole-cache reductions and a batched row write give the
    same bits on the stored cache as on the cache laid out (n, d)."""
    rng = np.random.default_rng(1)
    init = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    stored = init_flat_cache(N, d, dtype, init)
    assert stored.data.shape == (N,) + flat_row_shape(d)
    assert flat_row_shape(d) == ((d // 128, 128) if d == 4096 else (d,))
    flat = FlatCache(stored.data.reshape(N, d), stored.scale)
    same = lambda a, b: np.asarray(a).tobytes() == np.asarray(b).tobytes()

    assert same(stored.row(5), flat.row(5))
    idx = jnp.asarray([3, 0, 17, 30], jnp.int32)
    assert same(stored.rows(idx), flat.rows(idx))
    assert same(stored.dequant(), flat.dequant())
    mask = jnp.asarray(rng.random(N) < 0.5)
    assert same(stored.mean(), flat.mean())
    assert same(stored.mean(mask), flat.mean(mask))
    assert same(cache_sum(stored, mask), cache_sum(flat, mask))

    G = jnp.asarray(rng.normal(size=(4, d)), jnp.float32)
    valid = jnp.asarray([True, False, True, True])
    a, da, oa = stored.set_rows_delta(idx, G, valid)
    b, db, ob = flat.set_rows_delta(idx, G, valid)
    assert a.data.shape == stored.data.shape
    assert same(a.data, b.data) and same(a.scale, b.scale)
    assert same(da, db) and same(oa, ob)
    a, da, oa = stored.set_row_delta(9, G[0])
    b, db, ob = flat.set_row_delta(9, G[0])
    assert same(a.data, b.data) and same(da, db) and same(oa, ob)

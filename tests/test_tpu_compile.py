"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: each test lowers one kernel at a real width
(d = 2^20) for one chip of a described v5e:2x2 topology and asserts that
the compiled program holds the Mosaic kernel (`tpu_custom_call`). Nothing
runs. This catches what interpret mode cannot: operand shapes, tilings and
VMEM use that the chip's compiler refuses.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file. The persistent compilation cache is off around these tests,
because an executable compiled for a described chip cannot be read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cache_update, commit_batch, masked_agg, quant
from repro.kernels import row_delta

D = 1 << 20
K = 16
N = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


#: the rules' fused-commit basis shapes: (R running-sum vectors, which of
#: the lane-weight sums lane_a / lane_b / lane_g are present)
_BASES = {"ace": (1, ()), "aced": (2, ("a", "b")), "ca2fl": (3, ("a", "g"))}


@pytest.mark.parametrize("rule", sorted(_BASES))
@pytest.mark.parametrize("dtype", ["int8", "float32"])
def test_commit_batch_compiles(one_chip, dtype, rule):
    R, lanes = _BASES[rule]
    q = dtype == "int8"
    f32 = jnp.float32

    def fn(G, rows, old_s, new_s, valid, vecs, coef, upd_w, w):
        lw = {f"lane_{x}": w for x in lanes}
        return commit_batch.commit_batch(
            G, rows, old_s if q else None, new_s if q else None, valid, vecs,
            coef, upd_w, interpret=False, **lw)

    _compile(fn, one_chip, ((K, D), f32), ((K, D), jnp.dtype(dtype)),
             ((K,), f32), ((K,), f32), ((K,), jnp.bool_), ((R, D), f32),
             ((R, R + 4), f32), ((R + 4,), f32), ((K,), f32))


def test_masked_agg_compiles(one_chip):
    _compile(lambda c, s, m: masked_agg.masked_agg(c, s, m, interpret=False),
             one_chip, ((N, D), jnp.int8), ((N,), jnp.float32),
             ((N,), jnp.bool_))


def test_row_delta_compiles(one_chip):
    _compile(lambda g, c, a, b: row_delta.row_delta(g, c, a, b,
                                                    interpret=False),
             one_chip, ((D,), jnp.float32), ((D,), jnp.int8),
             ((), jnp.float32), ((), jnp.float32))


def test_cache_row_update_compiles(one_chip):
    _compile(lambda u, g, c, a, b, n: cache_update.cache_row_update(
                 u, g, c, a, b, n, interpret=False),
             one_chip, ((D,), jnp.float32), ((D,), jnp.float32),
             ((D,), jnp.int8), ((), jnp.float32), ((), jnp.float32),
             ((), jnp.float32))


def test_quantize_rows_compiles(one_chip):
    _compile(lambda x: quant.quantize_rows(x, interpret=False), one_chip,
             ((K, D), jnp.float32))


def test_dequantize_rows_compiles(one_chip):
    _compile(lambda q, s: quant.dequantize_rows(q, s, interpret=False),
             one_chip, ((K, D), jnp.int8), ((K,), jnp.float32))

"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: each test lowers one kernel at a real width
(d = 2^20) for one chip of a described v5e:2x2 topology and asserts that
the compiled program holds the Mosaic kernel (`tpu_custom_call`). Nothing
runs. This catches what interpret mode cannot: operand shapes, tilings and
VMEM use that the chip's compiler refuses.

The topology is described inside the `one_chip` fixture (tests/conftest.py),
never at import: only one process at a time may load the TPU library, and
test workers import every test file. The persistent compilation cache is off
around these tests, because an executable compiled for a described chip
cannot be read back.
"""
import importlib.util
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.cache import FlatCache, flat_commit_batch, flat_row_shape
from repro.core.scan_staleness import ring_append, ring_read_lanes
from repro.kernels import cache_update, commit_batch, masked_agg, ops, quant
from repro.kernels import row_delta

D = 1 << 20
K = 16
N = 256


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


#: each kernel's pinned op names, its jitted entry point and its operands
#: at a small width
_W = 4096
_PINNED = {
    "commit_batch": (
        {"commit_batch"}, commit_batch.commit_batch,
        (((K, _W), jnp.float32), ((K, _W), jnp.int8), ((K,), jnp.float32),
         ((K,), jnp.float32), ((K,), jnp.bool_), ((1, _W), jnp.float32),
         ((1, 5), jnp.float32), ((5,), jnp.float32))),
    "masked_agg": (
        {"masked_agg"}, masked_agg.masked_agg,
        (((N, _W), jnp.int8), ((N,), jnp.float32), ((N,), jnp.bool_))),
    "row_delta": (
        {"row_delta"}, row_delta.row_delta,
        (((_W,), jnp.float32), ((_W,), jnp.int8), ((), jnp.float32),
         ((), jnp.float32))),
    "cache_row_update": (
        {"cache_row_update"}, cache_update.cache_row_update,
        (((_W,), jnp.float32), ((_W,), jnp.float32), ((_W,), jnp.int8),
         ((), jnp.float32), ((), jnp.float32), ((), jnp.float32))),
    "quantize_rows": (
        {"quantize_rows_absmax", "quantize_rows"}, quant.quantize_rows,
        (((K, _W), jnp.float32),)),
    "dequantize_rows": (
        {"dequantize_rows"}, quant.dequantize_rows,
        (((K, _W), jnp.int8), ((K,), jnp.float32))),
}


@pytest.mark.parametrize("kernel", sorted(_PINNED))
def test_kernel_op_name_is_pinned(one_chip, kernel):
    """A kernel's compiled op keeps the kernel's pinned name when the call
    sits inside a stage scope and a jitted wrapper of another name, so a
    profile finds it by that name; the fused commit's op matches the pattern the
    benchmark's `commit_batch_roofline` reader looks for."""
    names, kernel_fn, shapes = _PINNED[kernel]

    @jax.jit
    def renamed_wrapper(*a):
        # the body under the kernel's own jit, whose name the op would
        # otherwise take
        return kernel_fn.__wrapped__(*a, interpret=False)

    def outer(*a):
        with jax.named_scope("afl.commit"):
            return renamed_wrapper(*a)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(outer).lower(*args).compile().as_text()
    ops = [re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ", ln).group(1)
           for ln in text.splitlines() if "tpu_custom_call" in ln
           and re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+ = ", ln)]
    assert ops
    assert {re.sub(r"\.\d+$", "", op) for op in ops} == names
    if kernel == "commit_batch":
        path = (Path(__file__).resolve().parents[1] / "bench" / "metrics"
                / "commit_batch_roofline.py")
        spec = importlib.util.spec_from_file_location("cb_roofline", path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert all(reader.NAME.match(op) for op in ops)


#: the flat cell's cache: 512 clients of d = 2^22 int8 values (2 GiB).
#: At this width XLA writes the K rows as one in-place update each; its
#: cost model counts the stack of K row reads at six times their bytes, so
#: at a narrower d the payload's f32 reads would fill the budget below
N_CACHE, D_FLAT = 512, 1 << 22
#: an HLO instruction: its name, result shape without layout, and opcode
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+?)(?:\{[^}]*\})? "
                   r"([\w\-]+)\(")


def _cache_copies(text, n_elems, dtype="s8"):
    """Names of the `copy` instructions whose `dtype` result holds as many
    elements as the whole cache (or ring), in whatever shape."""
    out = []
    for m in map(INSTR.match, text.splitlines()):
        if not m or m.group(3) != "copy" or not m.group(2).startswith(
                dtype + "["):
            continue
        dims = m.group(2)[len(dtype) + 1:-1].split(",")
        if dims != [""] and math.prod(map(int, dims)) == n_elems:
            out.append(m.group(1))
    return out


def test_flat_commit_moves_only_the_committed_rows(one_chip, monkeypatch):
    """ACE's fused K = 16 commit on the flat cell's int8 cache, the carry
    donated, compiled for one v5e chip: the K old rows are read as whole
    rows, with no XLA gather over every client's column band
    (`mini-gather`), the cache is never copied whole, and the program
    accesses under half of the cache's bytes (about a quarter). Reading
    the rows with `jnp.take` from an (n, d) cache accesses 2.7 times
    them."""
    # steer the kernel dispatch to the compiled kernel: the code sees a CPU
    monkeypatch.setattr(ops, "default_backend", lambda: "pallas")
    monkeypatch.setattr(commit_batch, "default_interpret", lambda: False)
    coef = jnp.asarray([[1.0, 1.0 / N_CACHE, 0.0, 0.0, 0.0]], jnp.float32)

    def fn(data, scale, idx, G, valid, u):
        cache, _, update = flat_commit_batch(
            FlatCache(data, scale), idx, G, valid, u[None], coef, coef[0])
        return cache, update

    f32 = jnp.float32
    shapes = (((N_CACHE,) + flat_row_shape(D_FLAT), jnp.int8),
              ((N_CACHE,), f32), ((K,), jnp.int32), ((K, D_FLAT), f32),
              ((K,), jnp.bool_), ((D_FLAT,), f32))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "mini-gather" not in text
    assert _cache_copies(text, N_CACHE * D_FLAT) == []
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 0.5 * N_CACHE * D_FLAT


def test_flat_ring_moves_only_whole_slots(one_chip):
    """The flat cell's f32 history ring, 51 slots of d = 2^22 stored
    (51, *flat_row_shape(d)) and donated, compiled for one v5e chip: the
    K = 16 stale models are read as whole slots, with no XLA gather over
    every slot's column band (`mini-gather`), one slot is appended in
    place, the ring is never copied whole, and the program accesses under
    half of the ring's bytes (the K slots and the appended one are about
    a third). Reading the slots with `jnp.take` from an (S, d) ring
    accesses about seven times the ring's bytes."""
    S = 51
    shapes = (((S,) + flat_row_shape(D_FLAT), jnp.float32),
              ((), jnp.int32), ((K,), jnp.int32),
              ((D_FLAT,), jnp.float32), ((), jnp.bool_))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]

    def fn(ring, cursor, taus, w, emit):
        # the lanes' rows are read once, as the client payload reads them
        rows = ring_read_lanes(ring, cursor, taus)
        ring, cursor = ring_append(ring, cursor, w, emit)
        return ring, cursor, jnp.sum(rows, axis=0)

    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert "mini-gather" not in text
    assert _cache_copies(text, S * D_FLAT, "f32") == []
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 0.5 * S * D_FLAT * 4


def test_masked_agg_reads_the_stored_cache_in_place(one_chip):
    """The masked mean over the whole int8 cache (ACED's direct reference)
    takes the cache in its stored row shape, so the compiled kernel reads
    it where it lies: no copy of the whole cache lays it out (n, d)."""
    shape = (N_CACHE,) + flat_row_shape(D_FLAT)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in ((shape, jnp.int8), ((N_CACHE,), jnp.float32),
                          ((N_CACHE,), jnp.bool_))]
    text = jax.jit(lambda c, s, m: masked_agg.masked_agg(
        c, s, m, interpret=False)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert _cache_copies(text, N_CACHE * D_FLAT) == []

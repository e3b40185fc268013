"""The benchmark's FLOP and byte counts against what they count."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

import run  # noqa: E402
import traffic as traffic_mod  # noqa: E402

flat = run.load_module(BENCH / "configs" / "flat.py", "test_flat_family")
mamba2 = run.load_module(BENCH / "configs" / "mamba2.py", "test_mamba2_family")


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("layers,batch,seq", [(2, 8, 256), (6, 8, 256), (2, 4, 512)])
def test_mamba2_flops_match_analytic(layers, batch, seq):
    """The program's analytic count (repro.launch.analytic) on the same
    shapes, plus the tied unembedding it leaves out (its matmul term
    subtracts the embedding table, which a tied model also multiplies by
    at the output) and minus the elementwise parameters it counts as matmul
    weights (norm gains, A_log, D, dt_bias)."""
    from repro.configs.registry import get_config
    from repro.launch.analytic import forward_flops
    model = dict(config("mamba2-780m-2l")["model"], num_hidden_layers=layers)
    cfg = get_config("mamba2-780m").cut_depth(layers)
    s = mamba2.dims(model)
    tokens = batch * seq
    elementwise = layers * (s["d"] + 3 * s["H"] + s["di"]) + s["d"]
    expected = (forward_flops(cfg, batch, seq) + 2 * tokens * s["V"] * s["d"]
                - 2 * tokens * elementwise)
    assert mamba2.forward_flops(model, batch, seq) == pytest.approx(expected, rel=1e-12)
    assert mamba2.train_flops(model, batch, seq) == 3 * mamba2.forward_flops(model, batch, seq)


def test_mamba2_weights_match_program_parameters():
    """The benchmark's weights have the program model's leaves, and as many
    parameters as its analytic count plus the conv biases that count leaves
    out."""
    import jax
    from repro.configs.registry import get_config
    from repro.models import build_model
    model = dict(config("mamba2-780m-2l")["model"])
    cfg = get_config("mamba2-780m").cut_depth(2)
    mine = jax.eval_shape(lambda: mamba2.init_params(jax.random.PRNGKey(0), model))
    theirs = jax.eval_shape(lambda: build_model(cfg).init(jax.random.PRNGKey(0)))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(mine)] == [x.shape for x in jax.tree.leaves(theirs)]
    conv_bias = 2 * mamba2.dims(model)["conv"]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(mine)) == (
        cfg.param_count() + conv_bias)


def _nbytes(*shapes_dtypes):
    return sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in shapes_dtypes)


@pytest.mark.parametrize("k", [1, 16])
def test_flat_tick_bytes_match_arrays(k):
    """Each term of a tick's bytes is an array the tick touches once."""
    model = config("flat-n512-d2p22")["model"]
    d = model["d"]
    expected = _nbytes(((k, d), np.float32),                       # stale ring rows
                       ((k, d), np.int8), ((k,), np.float32),       # old cache rows, scales
                       ((k, d), np.int8), ((k,), np.float32),       # new cache rows, scales
                       ((d,), np.float32), ((d,), np.float32),      # u read, written
                       ((d,), np.float32), ((d,), np.float32),      # w read, written
                       ((d,), np.float32))                          # ring slot written
    assert flat.tick_bytes(model, k) == expected


def test_kernel_bytes_match_kernel_operands():
    """commit_batch and cache_row_update: the bytes of the arrays each call
    reads and writes, from the kernels' own shapes."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import commit_batch as cb
    from repro.kernels import cache_update as cu
    model = {"d": 8192}
    K, d, R = 4, model["d"], 1
    f32 = jax.ShapeDtypeStruct
    args = (f32((K, d), jnp.float32), f32((K, d), jnp.int8), f32((K,), jnp.float32),
            f32((K,), jnp.float32), f32((K,), jnp.bool_), f32((R, d), jnp.float32),
            f32((R, R + 4), jnp.float32), f32((R + 4,), jnp.float32))
    outs = jax.eval_shape(lambda *a: cb.commit_batch(*a, interpret=True), *args)
    moved = (_nbytes(((K, d), np.float32), ((K, d), np.int8), ((R, d), np.float32))
             + sum(int(np.prod(o.shape)) * o.dtype.itemsize for o in outs)
             + 6 * K * 4 + (R + 1) * (R + 4) * 4)   # the packed lane and matrix blocks
    assert flat.commit_batch_bytes(model, K) == moved
    outs = jax.eval_shape(lambda *a: cu.cache_row_update(*a, interpret=True),
                          f32((d,), jnp.float32), f32((d,), jnp.float32),
                          f32((d,), jnp.int8), 1.0, 1.0, 1.0)
    moved = (_nbytes(((d,), np.float32), ((d,), np.float32), ((d,), np.int8))
             + sum(int(np.prod(o.shape)) * o.dtype.itemsize for o in outs) + 3 * 4)
    assert flat.cache_row_update_bytes(model) == moved
    tr = traffic_mod.load(BENCH / "traffic" / "ace-k16-c16.json")
    assert flat.kernel_bytes({"model": model}, tr) == {
        "commit_batch": flat.commit_batch_bytes(model, 16)}

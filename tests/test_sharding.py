"""Sharding rule inference: divisibility guards, spec shapes, no-mesh no-op."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.sharding.auto import _guard, infer_batch_shardings, param_spec
from repro.sharding.rules import logical_to_spec, shard, use_rules


@pytest.fixture
def mesh():
    if jax.device_count() < 1:
        pytest.skip("no devices")
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_shard_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = shard(x, ("batch", None))
    assert y is x


def test_guard_drops_nondivisible(mesh):
    spec = _guard(mesh, (3, 5), ("data", "model"))
    # axis sizes are 1 => divisible, names kept
    assert spec == P("data", "model")
    big = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                            ("data", "model"))
    assert _guard(big, (4, 4), ("data", "model")) == P("data", "model")


def test_param_spec_rules(mesh):
    path = (jax.tree_util.DictKey("embed"), jax.tree_util.DictKey("embedding"))
    assert param_spec(path, jnp.ones((64, 32)), mesh) == P("model", "data")
    path = (jax.tree_util.DictKey("attn"), jax.tree_util.DictKey("wq"))
    assert param_spec(path, jnp.ones((32, 64)), mesh) == P("data", "model")
    path = (jax.tree_util.DictKey("attn"), jax.tree_util.DictKey("wo"))
    assert param_spec(path, jnp.ones((64, 32)), mesh) == P("model", "data")
    # stacked layer dim gets None
    path = (jax.tree_util.DictKey("stages"), jax.tree_util.DictKey("wq"))
    assert param_spec(path, jnp.ones((4, 32, 64)), mesh) == \
        P(None, "data", "model")
    # 1-D replicated (PartitionSpec(None) ≡ PartitionSpec())
    path = (jax.tree_util.DictKey("ln1"),)
    assert tuple(param_spec(path, jnp.ones(32), mesh)) in ((), (None,))


def test_infer_batch_shardings(mesh):
    batch = {"tokens": jnp.ones((8, 16), jnp.int32), "pos": jnp.int32(0)}
    sh = infer_batch_shardings(batch, mesh)
    assert sh["tokens"].spec[0] == "data"
    assert all(s is None for s in sh["tokens"].spec[1:])
    assert tuple(sh["pos"].spec) == ()


def test_logical_rules_mapping(mesh):
    with use_rules(mesh):
        spec = logical_to_spec(("batch", "seq", "heads", None))
        assert spec == P(("data",), None, "model", None) or \
            spec == P("data", None, "model", None)


def test_shard_applies_constraint_under_mesh(mesh):
    with use_rules(mesh):
        x = jnp.ones((4, 8))
        y = shard(x, ("batch", "embed"))
        assert y.shape == x.shape

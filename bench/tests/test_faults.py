"""`correct` must come out false when the timed path is broken, and the
control must fail the limits.

Each test drives a whole run of a tiny cell (bench/tests/tiny.py) on the CPU
— set-up, first chunks, window, reference, comparison — with the program
under test broken underneath, and sees `correct` false. The faults are those
a training cell can have on one chip: a step that returns its model
unchanged, half of a batch left out with the mean taken over the rest, and an
answer (a client's payload) altered where it is produced. The limits are the
committed limits of the real cell of the same kind."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("root"))


@pytest.fixture(autouse=True)
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def result(root, cell, seed=11):
    return run.run(run.parse(["--workload", cell, "--seed", str(seed),
                              "--seconds", "0.2", "--trace", "0"]),
                   require_tpu=False, root=root)


def unchanged(monkeypatch):
    """Each chunk hands back the model it was given."""
    import repro.core.scan_staleness as ss
    real = ss.make_chunked_staleness_runner

    def make(**kw):
        runner = real(**kw)
        chunk = runner.chunk

        def stuck(carry, *args):
            w = jax.tree.map(jnp.copy, carry["w"])
            carry, outs = chunk(carry, *args)
            return dict(carry, w=w), outs
        runner.chunk = stuck
        return runner
    monkeypatch.setattr(ss, "make_chunked_staleness_runner", make)


def half_batch(monkeypatch):
    """K > 1: a tick commits only the first half of its arrivals. K = 1
    (the LM cell): each client gradient takes half its sequences."""
    import repro.core.aggregators as agg
    import repro.core.fl_tasks as fl
    real_step = agg.ACEIncremental.step_batch

    def step_batch(self, state, batch):
        K = batch.valid.shape[0]
        return real_step(self, state, batch._replace(
            valid=batch.valid & (jnp.arange(K) < K // 2)))
    monkeypatch.setattr(agg.ACEIncremental, "step_batch", step_batch)
    real_task = fl.make_lm_task
    monkeypatch.setattr(fl, "make_lm_task",
                        lambda **kw: real_task(**dict(kw, batch=kw["batch"] // 2)))


def altered(monkeypatch):
    """Every client payload has its largest element negated."""
    import repro.core.scan_staleness as ss

    def alter(g):
        leaves, treedef = jax.tree.flatten(g)
        x = leaves[0].reshape(-1)
        i = jnp.argmax(jnp.abs(x))
        leaves[0] = x.at[i].set(-x[i]).reshape(leaves[0].shape)
        return jax.tree.unflatten(treedef, leaves)

    for name in ("_payload_chain", "_tree_payload_chain"):
        real = getattr(ss, name)

        def chain(*a, _real=real, **kw):
            fn = _real(*a, **kw)

            def payload(w, client, key):
                p, loss, key = fn(w, client, key)
                return alter(p), loss, key
            return payload
        monkeypatch.setattr(ss, name, chain)


@pytest.mark.parametrize("cell,fault", [
    ("flat-tiny-k4", unchanged), ("flat-tiny-k4", half_batch), ("flat-tiny-k4", altered),
    ("flat-tiny-k1", unchanged), ("flat-tiny-k1", altered),
    ("lm-tiny-k1", unchanged), ("lm-tiny-k1", half_batch), ("lm-tiny-k1", altered)])
def test_broken_program_is_not_correct(root, monkeypatch, cell, fault):
    fault(monkeypatch)
    res = result(root, cell)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", ["flat-tiny-k4", "flat-tiny-k1", "lm-tiny-k1"])
def test_sound_program_is_correct(root, cell):
    res = result(root, cell, seed=2 ** 31 + 7)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("cell", ["flat-tiny-k4", "flat-tiny-k1", "lm-tiny-k1"])
def test_control_fails_the_limits(root, monkeypatch, cell):
    """The control — the reference in bfloat16 — put in the program's place:
    the whole run goes on as usual, and what it compares with the float32
    reference is the control's trace of the first chunks."""
    real = run.Cell.first_steps

    def first_steps(self, seed):
        chunk, carry, _ = real(self, seed)
        control, _ = self.reference(seed, dtype=jnp.bfloat16)
        return chunk, carry, control
    monkeypatch.setattr(run.Cell, "first_steps", first_steps)
    res = result(root, cell, seed=13)
    assert res["correct"] is False, res["checks"]

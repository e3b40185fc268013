"""Stage scopes of the scan tick and the trainer's host spans.

Every stage of the scan tick sits in a `jax.named_scope` named in
`scan_staleness.STAGES`; `ChunkedStalenessRunner.op_stages` maps each
instruction of the compiled chunk to its stage. The scopes are metadata
only: the compiled program is the same instruction for instruction with
them as without. The trainer's chunk loop writes `afl.*` host spans that a
profile of a `train` call holds.
"""
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import AFLConfig
from repro.core import scan_staleness
from repro.core.aggregators import make_aggregator
from repro.core.scan_staleness import (STAGES, build_fault_schedule,
                                       build_staleness_randomness,
                                       hlo_op_stages,
                                       make_chunked_staleness_runner)

N, C = 8, 4
PARAMS0 = {"a": jnp.zeros((64,), jnp.float32),
           "b": jnp.zeros((4, 8), jnp.float32)}
#: the stages every build runs, and those only some builds have
ALWAYS = tuple(s for s in STAGES if s not in ("afl.guards", "afl.resync"))
#: (layout, arrivals a tick): the flat K-batched tick with the fused commit,
#: and the tree per-arrival tick with the int8 history ring
BUILDS = [("flat", 4), ("tree", 1)]
#: an HLO instruction line's name, read apart from `hlo_op_stages`
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")


def _grad_fn(w, client, key):
    def loss(w):
        c = client.astype(jnp.float32)
        return sum(jnp.mean((x - c) ** 2) for x in jax.tree.leaves(w))
    return jax.value_and_grad(loss)(w)


def _runner(layout, k, guarded):
    aflc = AFLConfig(algorithm="ace", n_clients=N, cache_dtype="int8",
                     k_batch=k)
    return make_chunked_staleness_runner(
        grad_fn=_grad_fn, params0=PARAMS0, aggregator=make_aggregator(aflc),
        n_clients=N, T=1000, beta=5.0, speed_skew=3.0, layout=layout,
        history_dtype="int8" if layout == "tree" else "float32",
        guards=guarded, resync_every=2 if guarded else None, k_batch=k)


def _chunk_args(runner, guarded):
    """`chunk`'s arguments as shapes: nothing is run or allocated."""
    k = runner.k_batch
    lr = jnp.float32(0.0)
    carry = jax.eval_shape(runner.init, jax.random.PRNGKey(0), lr)
    rand = build_staleness_randomness(0, C, N, 5.0, speed_skew=3.0,
                                      k_batch=k)
    args = [carry, rand.gumbels, rand.tau_raw, rand.leave_at, rand.rejoin_at,
            lr]
    if guarded:
        faults = build_fault_schedule(0, C, k_batch=k, nan_rate=0.25)
        args += [faults.kind, faults.scale, jnp.float32(1.0)]
    return args


def _program(text):
    """A compiled module's computations and instructions, without metadata
    and the debug tables printed before them."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return [ln for ln in text.splitlines()
            if ln.startswith(("%", "ENTRY", " ", "}"))]


@pytest.mark.parametrize("guarded", [False, True])
@pytest.mark.parametrize("layout,k", BUILDS)
def test_op_stages_names_every_stage_and_instruction(layout, k, guarded):
    runner = _runner(layout, k, guarded)
    args = _chunk_args(runner, guarded)
    stages = runner.op_stages(*args)
    built = set(ALWAYS) | ({"afl.guards", "afl.resync"} if guarded else set())
    assert built <= set(stages.values())
    assert set(stages.values()) <= built | {""}
    text = runner.jit_chunk.lower(*args).compile().as_text()
    names = {m.group(1) for m in map(INSTR.match, text.splitlines()) if m}
    assert set(stages) == names


@pytest.mark.parametrize("guarded", [False, True])
@pytest.mark.parametrize("layout,k", BUILDS)
def test_scopes_leave_the_compiled_program_unchanged(layout, k, guarded,
                                                     monkeypatch):
    """Instruction for instruction, the chunk compiles to the same program
    with the stage scopes as with every scope taken out."""
    runner = _runner(layout, k, guarded)
    args = _chunk_args(runner, guarded)
    scoped = runner.jit_chunk.lower(*args).compile().as_text()
    monkeypatch.setattr(scan_staleness, "_stage",
                        lambda name: contextlib.nullcontext())
    plain = _runner(layout, k, guarded).jit_chunk.lower(
        *args).compile().as_text()
    assert "afl.commit" in scoped and "afl.commit" not in plain
    assert _program(scoped) == _program(plain)


def test_op_stages_reads_the_scopes_past_the_compile_cache(tmp_path,
                                                          monkeypatch):
    """The persistent compilation cache keys a program without its metadata,
    so it can hand back an executable compiled with other scopes or none:
    the stage map still names the stages of the program asked for."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        with monkeypatch.context() as m:
            m.setattr(scan_staleness, "_stage",
                      lambda name: contextlib.nullcontext())
            plain = _runner("tree", 1, False)
            args = _chunk_args(plain, False)
            plain.jit_chunk.lower(*args).compile()   # cached without scopes
        assert list(tmp_path.iterdir())
        stages = _runner("tree", 1, False).op_stages(*args)
        assert set(ALWAYS) <= set(stages.values())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_hlo_op_stages_takes_the_outermost_stage():
    text = "\n".join([
        "HloModule jit_chunk_fn",
        "%fused_computation (param_0: f32[8]) -> f32[8] {",
        '  %param_0 = f32[8]{0} parameter(0)',
        "}",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(chunk_fn)/while/body/afl.commit/jit(commit_batch)/mul"}',
        '  %copy.3 = f32[8]{0} copy(%fusion.7)',
        '  %add.1 = f32[8]{0} add(%copy.3, %copy.3), metadata={op_name='
        '"jit(chunk_fn)/while/body/afl.client/transpose(jvp(afl.sample))/add"}',
        '  %neg.2 = f32[8]{0} negate(%add.1), metadata={op_name='
        '"jit(chunk_fn)/while/body/transpose(jvp(afl.update))/neg"}',
        '  ROOT %tuple.9 = (f32[8]{0}) tuple(%neg.2), metadata={op_name='
        '"jit(chunk_fn)/afl.unknown/x"}',
        "}"])
    assert hlo_op_stages(text) == {
        "param_0": "", "fusion.7": "afl.commit", "copy.3": "",
        "add.1": "afl.client", "neg.2": "afl.update", "tuple.9": ""}


def test_stage_scope_refuses_an_unknown_name():
    with pytest.raises(ValueError):
        scan_staleness._stage("afl.nothing")


def test_trainer_writes_host_spans(tmp_path):
    """A profiled `train` call holds the loop's four host spans, each with
    its chunk's event offset; a checkpoint save is never inside a
    dispatch."""
    from jax.profiler import ProfileData
    from repro.launch.train import train
    with jax.profiler.trace(str(tmp_path / "trace")):
        train(reduced=True, steps=8, d_model=32, layers=1, seq=16, batch=2,
              vocab=64, n_clients=4, chunk_events=4, log_every=2,
              ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=4)
    (path,) = glob.glob(os.path.join(tmp_path, "trace", "**", "*.xplane.pb"),
                        recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("afl."):
                    assert "lo" in dict(e.stats)
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert set(spans) == {"afl.dispatch", "afl.readback", "afl.checkpoint",
                          "afl.log"}
    for s, e in spans["afl.checkpoint"]:
        assert not any(ds <= s and e <= de for ds, de in spans["afl.dispatch"])

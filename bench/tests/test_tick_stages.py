"""Device time by tick stage: the grouping of the trace's ops by the
program's stage map, the `tick_*_ms` readers, the map the benchmark builds
against the program that ran, and a recorded TPU trace of the tiny flat cell
with its stage map (`record_stage_trace.py`)."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
import tick_stages  # noqa: E402
import tiny  # noqa: E402

trace = run.load_module(BENCH / "trace.py", "test_stages_trace")
Event = trace.Event
DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "tiny_flat_k4_stages.xplane.pb"
RECORDED_MAP = DATA / "tiny_flat_k4_stages.json"
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
TICK_METRICS = [m["name"] for m in SPEC["per_layer"] if m["name"].startswith("tick_")]
STAGE_OF = {"tick_sample_ms": "afl.sample", "tick_stale_read_ms": "afl.stale_read",
            "tick_client_ms": "afl.client", "tick_commit_ms": "afl.commit",
            "tick_select_ms": "afl.select", "tick_update_ms": "afl.update",
            "tick_ring_ms": "afl.ring"}


def _reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py", f"test_reader_{name}")


def _hand_made():
    """Two chunks of a `while` op spanning its body's ops on device 0; one
    op ("copy.9") is missing from the map."""
    ops = [Event("while.1 s32[]", 10, 40), Event("fusion.2 f32[8]", 12, 10),
           Event("commit_batch.3 s8[4,8]", 25, 15), Event("copy.9 f32[8]", 45, 3),
           Event("while.1 s32[]", 60, 30), Event("fusion.2 f32[8]", 62, 20),
           Event("commit_batch.3 s8[4,8]", 82, 5)]
    spans = [Event("bench.dispatch", 0, 10), Event("bench.readback", 10, 40),
             Event("bench.dispatch", 50, 10), Event("bench.readback", 60, 40)]
    stage_map = {"while.1": "", "fusion.2": "afl.sample",
                 "commit_batch.3": "afl.commit"}
    return trace.reduce({0: ops}, spans), stage_map


def test_stage_times_and_unscoped_sum_to_busy():
    r, stage_map = _hand_made()
    times, missing = tick_stages.group(r.op_time_s, stage_map)
    assert missing == {"copy.9 f32[8]": pytest.approx(3e-9)}
    # the loop's own time (12 + 5 ns) and the missing copy count unscoped
    assert times == {"": pytest.approx(17e-9 + 3e-9), "afl.sample": pytest.approx(30e-9),
                     "afl.commit": pytest.approx(20e-9)}
    assert sum(times.values()) == pytest.approx(r.busy_s)


def test_tick_readers_split_the_busy_time(monkeypatch):
    r, stage_map = _hand_made()
    monkeypatch.setattr(tick_stages, "program_stage_map", lambda record: stage_map)
    record = {"trace": r, "ticks": 2}
    values = {m: _reader(m).read(record) for m in TICK_METRICS}
    assert set(values) == set(STAGE_OF) | {"tick_unscoped_ms"}
    assert values["tick_sample_ms"] == pytest.approx(1e3 * 30e-9 / 2)
    assert values["tick_commit_ms"] == pytest.approx(1e3 * 20e-9 / 2)
    assert values["tick_unscoped_ms"] == pytest.approx(1e3 * 20e-9 / 2)
    assert values["tick_client_ms"] == 0.0
    assert sum(values.values()) * 2 == pytest.approx(1e3 * r.busy_s)


def test_tick_readers_read_nothing_without_a_stage_map(monkeypatch):
    """A program that names no stages leaves every tick metric out."""
    r, _ = _hand_made()
    monkeypatch.setattr(tick_stages, "program_stage_map", lambda record: None)
    assert all(_reader(m).read({"trace": r, "ticks": 2}) is None
               for m in TICK_METRICS)


def test_tick_metrics_are_declared_as_the_readers_read_them():
    for m in SPEC["per_layer"]:
        if m["name"] in TICK_METRICS:
            assert (m["unit"], m["better"], m["source"], m["moves"]) == (
                "ms", "lower", "device_trace", "events_per_s")
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", ["flat-tiny-k4", "lm-tiny-k1"])
def test_program_stage_map_is_the_map_of_the_program_run(cell, tmp_path, monkeypatch):
    """The map built from shapes alone names exactly the instructions of the
    chunk compiled for the arrays a run feeds it."""
    import jax
    import numpy as np
    import traffic as traffic_mod
    from repro.core.scan_staleness import hlo_op_stages
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    root = tiny.make_root(tmp_path / "root")
    c = run.Cell(cell, root, require_tpu=False)
    stage_map = tick_stages.program_stage_map(dict(
        config=c.config, family=c.family, traffic=c.traffic, chips=c.chips))
    k_weights, k_stream, k_init = c.keys(5)
    runner = c.family.build_program(c.config, c.traffic, c.family.init_params(
        k_weights, c.config["model"]), None)
    stream = traffic_mod.make_stream(k_stream, c.traffic, c.n)
    lr0 = np.float32(0.0)
    carry = runner.init(k_init, lr0)
    g, tau = stream.chunk(0)
    text = runner.jit_chunk.lower(carry, g, tau, stream.leave_at, stream.rejoin_at,
                                  lr0).compile().as_text()
    assert stage_map == hlo_op_stages(text)
    assert set(STAGE_OF.values()) <= set(stage_map.values())
    jax.clear_caches()


def test_recorded_trace_by_stage():
    """The tiny flat cell (K = 4, d = 4096) traced on one TPU v5e chip with
    the stage scopes in place, and its chunk's stage map: nearly all busy
    time maps to a known instruction, the named stages hold most of it, the
    fused commit kernel is in the commit stage, and idle time falls in the
    loop's spans alone."""
    r = trace.reduce_file(str(RECORDED))
    stage_map = json.loads(RECORDED_MAP.read_text())
    times, missing = tick_stages.group(r.op_time_s, stage_map)
    assert sum(missing.values()) <= 0.01 * r.busy_s
    assert sum(times.values()) == pytest.approx(r.busy_s, rel=1e-6)
    pattern = run.load_module(BENCH / "metrics" / "commit_batch_roofline.py",
                              "test_stages_cb").NAME
    kernels = [op for op in r.op_time_s if pattern.match(op)]
    assert kernels
    assert {stage_map[op.split(" ")[0]] for op in kernels} == {"afl.commit"}
    # the model update fuses into the ring append's fusion on the chip, so
    # its time counts under `afl.ring`
    assert sum(times.get(s, 0.0) for s in STAGE_OF.values()) >= 0.5 * r.busy_s
    # every idle gap falls in one of the loop's two spans (in this recording
    # all of them in a readback), none outside the loop
    assert "bench.readback" in r.idle_by_span
    assert set(r.idle_by_span) <= {"bench.dispatch", "bench.readback"}
    assert sum(r.idle_by_span.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)

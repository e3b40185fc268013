"""The trace reduction, on hand-made events and on a recorded TPU trace."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

trace = run.load_module(BENCH / "trace.py", "test_bench_trace")
Event = trace.Event
RECORDED = Path(__file__).resolve().parent / "data" / "tiny_flat_k4.xplane.pb"


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_busy_idle_and_kernels():
    """Two chunks: dispatch spans 0-10 and 50-60 ns, readbacks 10-50 and
    60-100; ops on the device cover 12-40 and 62-90."""
    ops = [Event("fusion.1", 12, 13), Event("commit_batch", 25, 15),
           Event("fusion.1", 62, 23), Event("commit_batch", 85, 5)]
    spans = [Event("bench.dispatch", 0, 10), Event("bench.readback", 10, 40),
             Event("bench.dispatch", 50, 10), Event("bench.readback", 60, 40)]
    r = trace.reduce({0: ops, 1: [Event("x", 0, 100)]}, spans)
    assert r.window_s == pytest.approx(100e-9)
    # device 0 busy 28 + 28 = 56 ns, device 1 busy 100 ns: mean 78 ns
    assert r.busy_s == pytest.approx(78e-9)
    assert r.kernel_time("commit_batch") == (pytest.approx(20e-9), 2)
    # idle on device 0: 0-12 (dispatch 0-10, readback 10-12, midpoint 6 in
    # dispatch), 40-62 (midpoint 51: dispatch), 90-100 (readback)
    assert r.idle_by_span == {"bench.dispatch": pytest.approx(34e-9),
                              "bench.readback": pytest.approx(10e-9)}
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(36e-9)]
    assert b["idle_gaps"][0][0] == "bench.dispatch"


def test_self_time_of_nested_ops():
    """A loop op spanning its body's ops keeps only the time no body op
    covers; the sum of self times is the busy time."""
    ops = [Event("while.1 s32[]", 0, 100), Event("fusion.2 f32[8]", 10, 30),
           Event("fusion.3 f32[8]", 50, 40)]
    spans = [Event("bench.dispatch", 0, 100)]
    r = trace.reduce({0: ops}, spans)
    assert r.op_time_s == {"while.1 s32[]": pytest.approx(30e-9),
                           "fusion.2 f32[8]": pytest.approx(30e-9),
                           "fusion.3 f32[8]": pytest.approx(40e-9)}
    assert sum(r.op_time_s.values()) == pytest.approx(r.busy_s)


def test_op_name_keeps_name_and_shape():
    assert trace.op_name("%copy.261 = s8[512,64]{1,0:T(8,128)} copy(s8[512,64] %x)") \
        == "copy.261 s8[512,64]"
    assert trace.op_name("%commit_batch.7 = (s8[16,64]{1,0}, f32[1,64]{1,0}) "
                         "custom-call(%a)") == "commit_batch.7 s8[16,64]"


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trace.reduce({}, [Event("bench.dispatch", 0, 1)])


def test_recorded_tpu_trace():
    """A trace of the tiny flat cell (K = 4, d = 4096) recorded on one TPU
    v5e chip: every chunk's ops lie inside the loop's spans, the device is
    busy for part of the window, and the fused commit kernel is there."""
    r = trace.reduce_file(str(RECORDED))
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    secs, calls = r.kernel_time(run.load_module(
        BENCH / "metrics" / "commit_batch_roofline.py", "test_cb_reader").NAME.pattern)
    assert calls > 0 and secs > 0
    assert set(r.idle_by_span) == {"bench.dispatch", "bench.readback"}
    assert sum(r.idle_by_span.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert sum(r.op_time_s.values()) == pytest.approx(r.busy_s, rel=1e-6)

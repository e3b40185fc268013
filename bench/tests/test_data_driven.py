"""A cell, a configuration, a traffic mix and a per-layer metric are found by
name: adding them is adding files and entries, with no file edited."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
import tiny  # noqa: E402


def test_new_files_are_found(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    root = tiny.make_root(tmp_path / "root")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    cfg = dict(tiny.FLAT, name="flat-new", model=dict(tiny.FLAT["model"], d=2048))
    (root / "bench/configs/flat-new.json").write_text(json.dumps(cfg))
    mix = dict(tiny.TRAFFIC["tiny-k4"], k_batch=2, chunk_events=5)
    (root / "bench/traffic/new-k2.json").write_text(json.dumps(mix))
    (root / "bench/limits/flat-new-k2.json").write_text(
        (root / "bench/limits/flat-tiny-k4.json").read_text())
    (root / "bench/metrics/arrivals_per_tick.py").write_text(
        "def read(record):\n    return record['arrivals'] / record['ticks']\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "flat-new", "source": "test",
                            "file": "bench/configs/flat-new.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "flat-new-k2", "config": "flat-new",
                              "traffic": "new-k2", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "arrivals_per_tick", "unit": "events",
                              "better": "higher", "source": "host_clock",
                              "layer": "chunk loop", "moves": "events_per_s",
                              "workloads": ["flat-new-k2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(p.read_bytes() == b for p, b in before.items())

    c = run.load_cell("flat-new-k2", root)
    assert c["config"]["model"]["d"] == 2048
    assert (c["traffic"].k_batch, c["traffic"].chunk_events) == (2, 5)
    assert set(c["readers"]) == {"arrivals_per_tick"}
    assert c["readers"]["arrivals_per_tick"].read({"arrivals": 10, "ticks": 5}) == 2
    res = run.run(run.parse(["--workload", "flat-new-k2", "--seed", "4",
                             "--seconds", "0.2", "--trace", "0"]),
                  require_tpu=False, root=root)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] % 10 == 0


def test_unknown_traffic_key_is_refused(tmp_path):
    import traffic
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"algorithm": "ace"}))
    with pytest.raises(ValueError):
        traffic.load(p)

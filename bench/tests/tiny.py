"""A copy of the benchmark at sizes a CPU test run holds: `make_root(dir)`
lays out BENCHMARK.json, bench/ and a link to the program's src/ under `dir`,
with two tiny cells in place of the real ones."""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

FLAT = {"name": "flat-tiny", "family": "flat", "source": "test",
        "model": {"d": 4096, "table_rows": 4, "table_seed": 7},
        "clients": {"n_clients": 16, "cache_dtype": "int8", "history_dtype": "float32"}}
LM = {"name": "mamba2-tiny", "family": "mamba2", "source": "test",
      "model": {"num_hidden_layers": 2, "hidden_size": 64, "expand": 2,
                "state_size": 32, "head_dim": 32, "n_groups": 1, "conv_kernel": 4,
                "chunk_size": 32, "vocab_size": 256, "tie_word_embeddings": True,
                "rms_norm_eps": 1e-06},
      "program": {"argv": ["--arch", "mamba2-780m", "--reduced", "--layers", "2",
                           "--d-model", "64", "--vocab", "256"]},
      "clients": {"n_clients": 4, "batch": 2, "seq": 32, "cache_dtype": "int8",
                  "history_dtype": "int8", "corpus_seed": 0, "corpus_tokens": 4096}}
TRAFFIC = {
    "tiny-k4": {"algorithm": "ace", "k_batch": 4, "chunk_events": 8, "beta": 5.0,
                "speed_skew": 3.0, "lr_scale": 0.5, "T": 1000000,
                "stream_chunks": 4, "check_chunks": 3},
    "tiny-k1": {"algorithm": "ace", "k_batch": 1, "chunk_events": 4, "beta": 5.0,
                "speed_skew": 3.0, "lr_scale": 0.5, "T": 1000000,
                "stream_chunks": 4, "check_chunks": 3}}
CELLS = [("flat-tiny-k4", "flat-tiny", "tiny-k4"),
         ("flat-tiny-k1", "flat-tiny", "tiny-k1"),
         ("lm-tiny-k1", "mamba2-tiny", "tiny-k1")]


def make_root(dest: Path, limits=None) -> Path:
    """`limits` maps a cell to its limits; by default the committed limits of
    the real cell of the same kind."""
    dest = Path(dest)
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(REPO / "src", dest / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = []
    for cfg in (FLAT, LM):
        (dest / "bench" / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": cfg["name"], "source": "test",
                                "file": f"bench/configs/{cfg['name']}.json",
                                "reduced": [], "why": "test"})
    for name, mix in TRAFFIC.items():
        (dest / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    like = {"flat-tiny-k4": "flat-ace-k16", "flat-tiny-k1": "flat-ace-k16",
            "lm-tiny-k1": "lm-mamba2-2l-ace"}
    spec["workloads"] = []
    for cell, cfg, mix in CELLS:
        spec["workloads"].append({"name": cell, "config": cfg, "traffic": mix,
                                  "chips": 1, "why": "test"})
        lim = (limits or {}).get(cell) or json.loads(
            (REPO / "bench" / "limits" / f"{like[cell]}.json").read_text())
        (dest / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(lim))
    for m in spec["per_layer"]:
        m["workloads"] = [c for c, _, _ in CELLS]
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest

"""Launch/dry-run machinery unit tests (the 512-device runs live in
src/repro/launch/dryrun.py; here we test its components on 1 device),
the trainer's depth cut, and the compile-cache helper."""
import dataclasses
from pathlib import Path

import pytest

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import get_config
from repro.launch.analytic import analytic_costs, decode_flops, forward_flops
from repro.launch.dryrun import _with_reps, collective_bytes


def test_collective_bytes_parser():
    hlo = """
  %ag = bf16[16,4096] all-gather(bf16[1,4096] %x), replica_groups=[16,16]<=[256]
  %ar = f32[1024] all-reduce(f32[1024] %y), replica_groups={{0,1,2,3}}
  %rs.1 = (f32[64]) reduce-scatter(f32[1024] %z), replica_groups=[2,128]<=[256]
  %a2a = bf16[8,128] all-to-all(bf16[8,128] %w), replica_groups=[32,8]<=[256]
  %cp = u32[10] collective-permute(u32[10] %v)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 4096 * 2
    assert out["all-reduce"] == 2 * 1024 * 4
    assert out["all-to-all"] == 8 * 128 * 2
    assert out["collective-permute"] == 10 * 4
    assert out["total"] > 0


def test_with_reps_reduces_depth():
    cfg = get_config("zamba2-1.2b")
    red = _with_reps(cfg, [1, 1], 0)
    assert red.num_layers == 7   # one 6-unit + one mamba
    assert not red.scan_layers
    red2 = _with_reps(cfg, [2, 1], 0)
    assert red2.num_layers == 13


@pytest.mark.parametrize("arch", ["yi-9b", "qwen3-moe-235b-a22b",
                                  "mamba2-780m", "llama3-405b"])
def test_analytic_flops_sane(arch):
    cfg = get_config(arch, dtype="bfloat16")
    shape = INPUT_SHAPES["train_4k"]
    costs = analytic_costs(cfg, shape, remat="full")
    tokens = shape.global_batch * shape.seq_len
    model_flops = 6 * cfg.active_param_count() * tokens
    # analytic total (with remat + attention) must exceed the 6ND floor but
    # stay within ~3x of it for these shapes
    assert costs["flops"] > model_flops * 0.9
    assert costs["flops"] < model_flops * 3.5
    assert costs["bytes"] > cfg.param_count()  # at least one weight stream


def test_decode_flops_scale_with_cache_depth():
    cfg = get_config("yi-9b", dtype="bfloat16")
    f32k = decode_flops(cfg, 128, 32768)
    f16k = decode_flops(cfg, 128, 16384)
    assert f32k > f16k
    # params term dominates at small batch: 2*N*B
    assert f32k > 2 * cfg.param_count() * 128


def test_window_reduces_analytic_attention():
    full = get_config("gemma2-2b")
    swa = get_config("gemma2-2b", shape="long_500k")
    B, L = 1, 32768
    assert forward_flops(swa, B, L) < forward_flops(full, B, L)


def _published_widths(cfg):
    depth = {"name", "num_layers", "stages", "num_encoder_layers"}
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name not in depth}


@pytest.mark.parametrize("arch,layers,want_reps", [
    ("mamba2-780m", 2, (2,)),
    ("yi-9b", 3, (3,)),
    ("gemma2-2b", 5, (2,)),          # (local, global) period of 2
    ("zamba2-1.2b", 7, (1, 1)),      # one period of each of the two stages
    ("zamba2-1.2b", 20, (3, 2)),     # second stage capped at its 2 repeats
])
def test_layers_without_reduced_cuts_depth_only(arch, layers, want_reps):
    """--layers without --reduced keeps every published width and whole
    periods of every stage pattern; only the repeats shrink."""
    from repro.launch.train import _parser, model_config
    pub = get_config(arch)
    cfg = model_config(_parser().parse_args(
        ["--arch", arch, "--layers", str(layers)]))
    assert _published_widths(cfg) == _published_widths(pub)
    assert [p for p, _ in cfg.stages] == [p for p, _ in pub.stages]
    assert tuple(r for _, r in cfg.stages) == want_reps
    assert cfg.num_layers == sum(len(p) * r for p, r in cfg.stages) <= layers


def test_layers_below_one_period_is_refused():
    with pytest.raises(ValueError, match="one period of every stage"):
        get_config("zamba2-1.2b").cut_depth(6)


def test_no_layers_keeps_the_published_model():
    from repro.launch.train import _parser, model_config
    assert model_config(_parser().parse_args(["--arch", "zamba2-1.2b"])) \
        == get_config("zamba2-1.2b")


def test_compile_cache_dir_respects_env(monkeypatch):
    """`JAX_COMPILATION_CACHE_DIR` wins and nothing is set in code;
    without it the cache goes to the fixed in-checkout path."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.use_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]

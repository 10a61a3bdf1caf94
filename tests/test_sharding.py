"""Sharding-rule unit tests (AbstractMesh — no devices needed)."""
from jax.sharding import AbstractMesh

import repro.configs as C
from repro.runtime.sharding import param_spec


MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_expert_stack_ep_rule():
    """Stacked MoE expert weights shard E over model + d_ff over data —
    the maverick-wo regression (EXPERIMENTS.md §Perf #4)."""
    cfg = C.get_config("llama4-maverick-400b-a17b")
    spec = param_spec("groups/1/moe/wo", (24, 128, 8192, 5120), MESH, cfg)
    assert spec[1] == "model"          # experts
    assert "data" in str(spec)         # FSDP somewhere
    spec = param_spec("groups/1/moe/wi_gate", (24, 128, 5120, 8192),
                      MESH, cfg)
    assert spec[1] == "model"


def test_attention_heads_rule():
    cfg = C.get_config("granite-3-2b")
    spec = param_spec("groups/0/attn/wq", (40, 2048, 32, 64), MESH, cfg)
    assert spec[2] == "model"          # 32 heads / 16
    assert spec[1] == "data"           # FSDP on d_model


def test_indivisible_heads_fall_back():
    cfg = C.get_config("internvl2-1b")   # 14 heads, not divisible by 16
    spec = param_spec("groups/0/attn/wq", (24, 896, 14, 64), MESH, cfg)
    assert "model" not in tuple(spec)


def test_embedding_vocab_rule():
    cfg = C.get_config("qwen3-0.6b")
    spec = param_spec("embed/table", (151936, 1024), MESH, cfg)
    assert spec[0] == "model"
    assert spec[1] == "data"


def test_mlp_rules():
    cfg = C.get_config("gemma2-27b")
    up = param_spec("groups/0/mlp/wi_gate", (23, 4608, 36864), MESH, cfg)
    assert up[2] == "model"
    down = param_spec("groups/0/mlp/wo", (23, 36864, 4608), MESH, cfg)
    assert down[1] == "model"


def test_multipod_fsdp_uses_both_axes():
    cfg = C.get_config("qwen3-0.6b")
    spec = param_spec("embed/table", (151936, 1024), MESH3, cfg)
    assert spec[0] == "model"
    assert spec[1] == ("pod", "data")  # 1024 % 32 == 0


def test_norm_scales_replicated():
    cfg = C.get_config("qwen3-0.6b")
    spec = param_spec("groups/0/ln_attn/scale", (28, 1024,), MESH, cfg)
    # rank-2 stacked scale: at most FSDP, never model-TP
    assert "model" not in tuple(spec)

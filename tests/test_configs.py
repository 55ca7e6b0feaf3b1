"""Assigned-architecture configs match the assignment table exactly."""
import pytest

from repro.configs.all import ALL_ARCHS
from repro.configs.base import SHAPES, get_arch, shape_applicable

TABLE = {
    # name: (L, d_model, H, kv, d_ff, vocab)
    "moonlight-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
    "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
    "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
    "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
    "stablelm-1.6b": (24, 2048, 32, 32, 5632, 100352),
    "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
    "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
    "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
    "mamba2-780m": (48, 1536, 0, 0, 0, 50280),
    "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
}


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_dimensions_match_assignment(name):
    c = get_arch(name)
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == TABLE[name]


def test_moe_settings():
    m = get_arch("moonlight-16b-a3b")
    assert m.n_experts == 64 and m.top_k == 6
    # d_ff is the expert width; the one dense layer has its own
    assert m.d_ff == 1408 and m.dense_d_ff == 11264
    assert m.n_shared_experts == 2
    assert m.routed_scaling == 2.446 and m.experts_held == 64
    assert (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
            m.v_head_dim) == (512, 128, 64, 128)
    assert m.pattern_groups == ((("mla",), 1), (("mla_moe",), 26))
    assert not m.tie_embeddings and m.rope_theta == 50_000
    g = get_arch("grok-1-314b")
    assert g.n_experts == 8 and g.top_k == 2


def test_special_structure():
    g = get_arch("gemma3-27b")
    pats = [b for p, n in g.pattern_groups for _ in range(n) for b in p]
    assert pats.count("global") == 10 and pats.count("local") == 52
    r = get_arch("recurrentgemma-9b")
    pats = [b for p, n in r.pattern_groups for _ in range(n) for b in p]
    assert pats.count("rglru") == 26 and pats.count("local") == 12
    v = get_arch("llama-3.2-vision-90b")
    pats = [b for p, n in v.pattern_groups for _ in range(n) for b in p]
    assert pats.count("cross") == 20
    w = get_arch("whisper-medium")
    assert w.enc_layers == 24 and w.frontend_tokens == 1500
    m = get_arch("mamba2-780m")
    assert m.ssm_state == 128 and m.attention_free
    assert get_arch("qwen2.5-3b").qkv_bias


def test_shape_grid():
    s = SHAPES
    assert s["train_4k"].seq_len == 4096 and s["train_4k"].global_batch == 256
    assert s["prefill_32k"].seq_len == 32768
    assert s["decode_32k"].global_batch == 128
    assert s["long_500k"].seq_len == 524288 and s["long_500k"].global_batch == 1


def test_long_500k_applicability():
    runs = {n: shape_applicable(get_arch(n), SHAPES["long_500k"])[0]
            for n in ALL_ARCHS}
    assert runs["mamba2-780m"] and runs["recurrentgemma-9b"]
    assert runs["gemma3-27b"]                   # mostly-local hybrid
    assert not runs["phi4-mini-3.8b"]           # pure full attention
    assert not runs["whisper-medium"]           # bounded enc-dec
    assert not runs["grok-1-314b"]
    # 40-cell accounting: 10 archs x 4 shapes, with documented skips
    total = sum(1 for n in ALL_ARCHS for sh in SHAPES.values()
                if shape_applicable(get_arch(n), sh)[0])
    skips = 40 - total
    assert skips == 7                           # 7 documented long_500k skips

"""Each configuration file holds its published config.json values."""

import pytest

from bench import spec

PUBLISHED = {
    # huggingface.co/Qwen/Qwen2-1.5B/blob/main/config.json
    "qwen2-1.5b": dict(
        hidden_act="silu", hidden_size=1536, intermediate_size=8960,
        max_position_embeddings=131072, max_window_layers=28,
        num_attention_heads=12, num_hidden_layers=28, num_key_value_heads=2,
        rms_norm_eps=1e-06, rope_theta=1000000.0, sliding_window=131072,
        tie_word_embeddings=True, torch_dtype="bfloat16",
        use_sliding_window=False, vocab_size=151936),
    # huggingface.co/Qwen/Qwen1.5-1.8B/blob/main/config.json
    "qwen1.5-1.8b": dict(
        hidden_act="silu", hidden_size=2048, intermediate_size=5504,
        max_position_embeddings=32768, max_window_layers=21,
        num_attention_heads=16, num_hidden_layers=24, num_key_value_heads=16,
        rms_norm_eps=1e-06, rope_theta=1000000.0, sliding_window=32768,
        tie_word_embeddings=False, torch_dtype="bfloat16",
        use_sliding_window=False, vocab_size=151936),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_config_matches_published(name):
    cfg = spec.config(name)
    for key, value in PUBLISHED[name].items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == []
    entry = {c["name"]: c for c in spec.benchmark()["configs"]}[name]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_arch_config_keeps_the_widths(name):
    cfg = spec.config(name)
    a = spec.arch_config(cfg)
    assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads, a.d_ff,
            a.vocab, a.hd) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["intermediate_size"], cfg["vocab_size"], 128)
    assert a.qkv_bias and a.tie_embeddings == cfg["tie_word_embeddings"]
    assert a.param_dtype == "bfloat16"

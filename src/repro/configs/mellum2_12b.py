"""Mellum2-12B-A2.5B: 28 layers of 64 small experts (top-8, no shared
expert) beside GQA attention whose layers repeat three sliding-window
(1,024) layers and one full layer with YaRN rotary scaling
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]."""
from repro.configs.base import ModelConfig, SplitConfig

SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    arch_type="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,        # GQA kv=4
    head_dim=128,
    d_ff=896,            # moe_intermediate_size: every MLP is sparse
    vocab_size=98304,
    n_experts=64,
    experts_per_tok=8,
    sliding_window=1024,
    layer_types=("sliding_attention",) * 3 + ("full_attention",),
    rope_theta=500_000.0,
    yarn_factor=16.0,
    yarn_original_max_position=8192,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782,
    split=SplitConfig(split_at=14, d_bottleneck=512, quant_bits=8),
    source=SOURCE,
)


def reduced() -> ModelConfig:
    """Two periods at CPU-test widths: 16 routed experts, top-4, window 16,
    YaRN over a 64-position original length."""
    import dataclasses
    return dataclasses.replace(
        CONFIG, n_layers=8, d_model=256, n_heads=8, n_kv_heads=2,
        head_dim=32, d_ff=128, vocab_size=512, n_experts=16,
        experts_per_tok=4, sliding_window=16, yarn_factor=4.0,
        yarn_original_max_position=64, yarn_attention_factor=0.0,
        split=SplitConfig(split_at=4, d_bottleneck=64, quant_bits=8))

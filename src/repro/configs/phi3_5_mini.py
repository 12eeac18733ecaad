"""phi-3.5-mini (3.8B) — the paper's Table 1 model #2. [arXiv:2404.14219]

32L, d_model=3072, 32 heads with 32 kv heads (multi-head attention:
``num_key_value_heads: 32`` in microsoft/Phi-3.5-mini-instruct), d_ff=8192,
vocab 32064 — 3.82B parameters.
"""
from repro.configs.base import LayerSpec, ModelConfig, pattern_from_rule

CONFIG = ModelConfig(
    name="phi-3.5-mini",
    n_layers=32,
    d_model=3072,
    n_heads=32,
    n_kv_heads=32,
    d_ff=8192,
    vocab_size=32064,
    layer_pattern=pattern_from_rule(32, lambda i: LayerSpec("attn", "dense")),
    rope_theta=10000.0,
    act="silu",
    max_context=131072,
    sub_quadratic=False,
    source="arXiv:2404.14219 (Phi-3.5-mini) — WebLLM Table 1",
)

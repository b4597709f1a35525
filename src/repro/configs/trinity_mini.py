"""Trinity-Mini [afmoe] — experts over window and full layers.
32L d_model=2048 32H (GQA kv=4) head_dim=128 vocab=200192; layers 0-1 a
dense FFN of 6144, layers 2-31 128 experts of 1024 (top 8, sigmoid routing
with a selection bias, route_scale 2.826) plus one shared expert of 1024;
every 4th layer (3, 7, ...) full attention without rotary, the others a
2048-key sliding window; QK norm, a sigmoid output gate, sandwich norms,
embeddings times sqrt(d_model), untied head.
[hf:arcee-ai/Trinity-Mini, model_type afmoe]

The published window counts the query's own key (query i sees key j iff
i - j < sliding_window); the program's window does not (i - j <= window),
so a sliding layer's window here is sliding_window - 1.

The layer pattern lists all 32 layers as one super-block: the two leading
dense layers break any shorter period.
"""
from repro.core.types import AttentionSpec, ModelConfig, MoESpec

LAYERS, DENSE, FULL_EVERY, SLIDING_WINDOW = 32, 2, 4, 2048


def layer_pattern(n: int, dense: int):
    return tuple("attn" if i < dense else "attn_moe" for i in range(n))


def window_schedule(n: int, every: int, sliding_window: int):
    """Full layers (every `every`-th, from index every-1) None, the others
    the program's window for a published sliding_window."""
    return tuple(None if i % every == every - 1 else sliding_window - 1
                 for i in range(n))


CONFIG = ModelConfig(
    name="trinity-mini",
    num_layers=LAYERS,
    d_model=2048,
    num_heads=32, num_kv_heads=4,
    head_dim=128,
    d_ff=6144,                          # the leading dense layers
    vocab_size=200192,
    layer_pattern=layer_pattern(LAYERS, DENSE),
    attention=AttentionSpec(kind="dense", causal=True),
    window_schedule=window_schedule(LAYERS, FULL_EVERY, SLIDING_WINDOW),
    moe=MoESpec(num_experts=128, top_k=8,
                expert_width=1024, shared_width=1024, score="sigmoid",
                route_scale=2.826),
    tie_embeddings=False,
    embed_scale=True,                   # mup_enabled: x *= sqrt(d_model)
    rope_theta=10_000.0,
    norm_eps=1e-5,
    qk_norm=True,
    attn_gate=True,
    sandwich_norm=True,
    full_rope=False,
)

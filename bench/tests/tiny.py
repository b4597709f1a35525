"""Smoke-size copies of the benchmark's configurations and mixes for CPU
tests: the same architectures and code paths, tiny widths."""
import copy

BASE = {"rope_theta": 10000.0, "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
        "torch_dtype": "bfloat16", "swat": {"window": 16, "num_global": 4},
        "serving": {"batch_slots": 4, "max_len": 256, "pad_to": 16,
                    "max_prefill_tokens": 64}, "reduced": []}


def moe(**kw):
    c = dict(copy.deepcopy(BASE), name="tiny-moe-swat",
             repro_arch="granite-moe-1b-a400m", num_hidden_layers=2,
             hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=32, num_local_experts=4,
             num_experts_per_tok=2, vocab_size=256)
    c.update(kw)
    return c


def dense(**kw):
    c = dict(copy.deepcopy(BASE), name="tiny-8b-swat", repro_arch="granite-8b",
             num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=96,
             vocab_size=256, rope_theta=10000000.0, rms_norm_eps=1e-5)
    c.update(kw)
    return c


CLOSED = {"loop": "closed", "requests_per_client": 6, "first_wave": "residual",
          "prompt": {"dist": "loguniform", "min": 20, "max": 40},
          "output": {"dist": "loguniform", "min": 8, "max": 24}}
OPEN = {"loop": "open", "arrivals": "poisson", "rate_per_s": 6.0, "preroll_s": 0.5,
        "schedule_seed": 1,
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 40},
        "output": {"dist": "lognormal", "median": 10, "sigma": 0.5, "min": 4, "max": 24}}
CHECKS = {"sample_tokens": 64, "sample_requests": 4}

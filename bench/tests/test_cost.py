"""cost.py against hand counts: the dense 8B configuration, and the\ngranite-3.0-1b-a400m mixture of experts for the expert arithmetic."""
import json
from pathlib import Path

import pytest

import cost
from arch import granite_swat

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
DENSE = json.loads((CONFIGS / "granite-8b-swat.json").read_text())
# granite-3.0-1b-a400m at its published sizes, with the same window: the
# expert arithmetic (routed against dense dispatch) on a real MoE shape
MOE = dict(DENSE, num_hidden_layers=24, hidden_size=1024, num_attention_heads=16,
           num_key_value_heads=8, head_dim=64, intermediate_size=512,
           num_local_experts=32, num_experts_per_tok=8, vocab_size=49155)
LAYER = granite_swat.layers(MOE)[0]
ATTN, FFN = LAYER["mixer"], LAYER["ffn"]


def test_visible_keys_window_plus_sinks():
    assert [cost.visible_keys(ATTN, p) for p in (0, 2047, 2048, 2049, 2176, 5000)] \
        == [1, 2048, 2049, 2050, 2177, 2177]


@pytest.mark.parametrize("start,stop", [(0, 1), (0, 2049), (100, 2300), (0, 8192),
                                        (2176, 2177), (3000, 3001)])
def test_sum_visible_keys_closed_form(start, stop):
    assert cost.sum_visible_keys(ATTN, start, stop) == sum(
        cost.visible_keys(ATTN, p) for p in range(start, stop))


def test_moe_parameters_and_routed_against_dense_flops():
    p = cost.layer_params(LAYER)
    assert p["attn"] == 1024 * 32 * 64 + 16 * 64 * 1024 == 3_145_728
    assert p["ffn"] == 8 * 3 * 1024 * 512 == 12_582_912
    assert cost.layer_params(LAYER, routed=False)["ffn"] == 50_331_648
    total = (49155 * 1024 + 24 * (3_145_728 + 50_331_648 + 1024 * 32 + 2 * 1024)
             + 1024)
    assert total == 1_334_628_352
    routed = cost.token_flops(MOE, 0, unembed=False)
    assert routed == 24 * (2 * (3_145_728 + 12_582_912 + 32_768) + 4 * 16 * 64)
    dense = cost.token_flops(MOE, 0, unembed=False, routed=False)
    assert dense - routed == 24 * 2 * (50_331_648 - 12_582_912)


def test_prefill_flops_counts_band_and_one_unembed():
    n = 3000
    per_layer = 2 * (3_145_728 + 12_582_912 + 32_768)
    attn = 4 * 16 * 64 * cost.sum_visible_keys(ATTN, 0, n)
    assert cost.prefill_flops(MOE, n) == 24 * (per_layer * n + attn) + 2 * 1024 * 49155


def test_dense_decode_bytes_by_hand():
    layer = (4096 * 48 * 128 + 32 * 128 * 4096) * 2 + 3 * 4096 * 14336 * 2 + 2 * 4096 * 4
    weights = 18 * layer + 49152 * 4096 * 2 + 4096 * 4
    assert cost.weight_bytes(DENSE) == weights == 8_254_996_480
    kv = 2177 * 18 * 2 * 8 * 128 * 2
    assert cost.decode_step_bytes(DENSE, [5000]) == weights + kv
    assert cost.decode_step_bytes(DENSE, []) == 0


def test_moe_decode_bytes_count_selected_experts():
    assert cost.expected_experts(FFN, 1) == pytest.approx(8)
    assert cost.expected_experts(FFN, 48) == pytest.approx(32, abs=1e-4)
    one = cost.weight_bytes(MOE, tokens=1)
    full = cost.weight_bytes(MOE)
    assert full - one == 24 * 24 * 3 * 1024 * 512 * 2
    assert cost.decode_step_bytes(MOE, [10]) == one + 24 * 11 * 2 * 8 * 64 * 2

"""The afmoe architecture module (arch/afmoe.py) at smoke size on the CPU:
through `run.execute` a sound run is correct and a run with a token altered
where it is produced, or the fp8 control in the engine's place, is not; its
cost descriptors count the gate, the QK and sandwich norms, the expert bias,
the held experts and the shared expert as worked out by hand; the
configuration file holds the catalog's numbers and departs from the
program's registered model only where it says it is reduced."""
import copy
import json
from pathlib import Path

import pytest

import arch
import cost
import run
from repro.serving import sampling
from tiny import CHECKS, CLOSED

ROOT = Path(__file__).resolve().parents[2]
FILE = json.loads((ROOT / "bench" / "configs" / "trinity-mini-ep8.json").read_text())
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

# the configuration file's keys at smoke widths: four layers (one period,
# full at 3), two dense; 4 of the router's 16 experts held, top 4; a
# 12-key published window (program window 11), prompts of 20-40 tokens
TINY = dict(copy.deepcopy(FILE), name="tiny-afmoe", num_hidden_layers=4,
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=96, moe_intermediate_size=32,
            num_experts=4, num_experts_per_tok=4, vocab_size=256,
            sliding_window=12, published={"num_experts": 16},
            serving={"batch_slots": 4, "max_len": 256, "pad_to": 16,
                     "max_prefill_tokens": 64})
# sigmoid scores of the smoke router lie about 0.05 apart near the top 4,
# and bf16 moves a score by about 0.005: within 0.02 the choice is
# rounding's (every request compared: the engine reads at most 0.017 and
# the control at least 0.46 over 3 seeds, CPU)
MARGIN = 0.02
LIMIT = 0.3


def _execute(seed, control=False):
    checks = dict(CHECKS, widest_logit_gap=LIMIT, router_margin=MARGIN,
                  sample_tokens=10**9, sample_requests=10**9)
    cell = run.Cell("tiny-afmoe", 1, TINY, CLOSED, checks,
                    [{"name": "tokens_per_s", "unit": "tokens/s"}])
    return run.execute(cell, seed, 2.0, False, device=DEVICE, control=control)


def test_sound_run_is_correct():
    out = _execute(2**31 + 99)
    c = out["compared"]
    assert out["correct"], out["checks"]
    assert c["tokens_compared"] > 40 and c["tokens_left_out"] > 0


def test_altered_token_is_not_correct(monkeypatch):
    orig = sampling.sample

    def altered(key, logits, temperatures, top_k=0, any_sampling=None):
        return (orig(key, logits, temperatures, top_k, any_sampling) + 1) % logits.shape[-1]
    monkeypatch.setattr(sampling, "sample", altered)
    from repro.serving import engine as E
    E._get_compiled.cache_clear()
    out = _execute(5)
    E._get_compiled.cache_clear()
    gap = out["checks"]["widest_logit_gap"]
    assert not out["correct"] and gap["value"] > gap["limit"]


def test_control_is_not_correct():
    out = _execute(17, control=True)
    c, gap = out["compared"], out["checks"]["widest_logit_gap"]
    assert not out["correct"]
    assert gap["value"] == c["control_widest_logit_gap"] > LIMIT
    assert c["widest_logit_gap"] < LIMIT


def test_cost_by_hand():
    """TINY's four layers by hand: attention projections with the gate
    (d x hq*dh more), 2 x dh f32 qk gains, 4 d-wide f32 norm gains, the
    16-wide f32 expert bias on expert layers; dense width 96; experts: 4
    held of 16, top 4 (one held expert a token on average), 32 wide, a
    32-wide shared expert, a 64 x 16 f32 router. Window 11 on layers 0-2,
    full on layer 3."""
    d, v = 64, 256
    proj = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64 + 64 * 4 * 16    # wq wk wv wo wg
    dense, shared, expert = 3 * d * 96, 3 * d * 32, 3 * d * 32
    router = d * 16
    win = sum(min(p + 1, 12) for p in range(40))
    full = sum(p + 1 for p in range(40))
    attn_flops = 4 * 4 * 16 * (3 * win + full)
    ffn_flops = 2 * (2 * dense + 2 * (4 * 4 / 16 * expert + shared + router))
    assert cost.prefill_flops(TINY, 40) == (4 * 2 * proj * 40 + ffn_flops * 40
                                            + attn_flops + 2 * d * v)
    positions = [30, 50]
    tokens = len(positions)
    held = 4 * (1 - (1 - 4 / 16) ** tokens)         # held experts the step reads
    layer = proj * 2 + 2 * 16 * 4 + 4 * d * 4
    weights = (4 * layer + 2 * dense * 2 + 2 * 16 * 4
               + 2 * (held * expert * 2 + router * 4 + shared * 2)
               + v * d * 2 + d * 4)
    row = 2 * 2 * 16 * 2
    kv = row * (3 * (12 + 12) + (31 + 51))
    assert cost.decode_step_bytes(TINY, positions) == pytest.approx(weights + kv,
                                                                    rel=1e-12)


def test_file_holds_the_published_model():
    """The configuration file's departures from the registered program are
    the keys it lists as reduced: 16 of 32 layers, 16 of 128 experts held,
    the router as published."""
    mod = arch.load(FILE)
    assert mod.__name__ == "arch.afmoe"
    assert mod.departures(FILE) == []
    assert sorted(FILE["reduced"]) == ["num_experts", "num_hidden_layers"]
    mcfg = mod.model_config(FILE)
    assert mcfg.moe.num_experts == 128 and mcfg.moe.held_count == 16
    assert mcfg.moe.top_k == 8 and mcfg.moe.shared_width == 1024
    assert mcfg.window_schedule == (2047, 2047, 2047, None) * 4
    assert mcfg.layer_pattern == ("attn",) * 2 + ("attn_moe",) * 14
    full = [i for i, layer in enumerate(mod.layers(FILE))
            if layer["mixer"]["state_bytes"]([5000]) > 2048 * 2 * 4 * 128 * 2]
    assert full == [3, 7, 11, 15]

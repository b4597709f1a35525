"""A configuration brings its architecture as new files alone: a copy of the
benchmark gains an architecture module (two attention kinds in one layer
pattern, an untied head) and a configuration that names it, edits no file
it had, and runs through `run.execute` on the CPU -- correct when sound,
not correct with a token altered where it is produced. cost.py counts the
new layers as the descriptors say, and an expert layer that holds fewer
experts than its router scores by hand."""
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

import arch
import cost
from tiny import CHECKS, CLOSED

BENCH = Path(__file__).resolve().parents[1]
SRC = BENCH.parent / "src"
TWO_KINDS = {"name": "tiny-two-kinds", "arch": "two_kinds", "num_hidden_layers": 4,
             "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "intermediate_size": 96, "vocab_size": 256, "window": 16,
             "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16",
             "tie_word_embeddings": False, "reduced": [],
             "serving": {"batch_slots": 4, "max_len": 256, "pad_to": 16,
                         "max_prefill_tokens": 64}}

SCRIPT = """
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
import arch, cost, run
from repro.serving import engine as E, sampling
cfg = json.loads(open(sys.argv[2]).read())
cell = run.Cell("two-kinds", 1, cfg, *json.loads(sys.argv[3]))
device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
sound = run.execute(cell, 7, 2.0, False, device=device)
orig = sampling.sample
sampling.sample = lambda *a, **k: (orig(*a, **k) + 1) % cfg["vocab_size"]
E._get_compiled.cache_clear()
jax.clear_caches()
fault = run.execute(cell, 7, 2.0, False, device=device)
print(json.dumps({"module": arch.load(cfg).__file__, "run": run.__file__,
                  "sound": [sound["correct"], sound["checks"]],
                  "fault": [fault["correct"], fault["checks"]],
                  "prefill_40": cost.prefill_flops(cfg, 40),
                  "step_bytes": cost.decode_step_bytes(cfg, [30, 50])}))
"""


def test_new_architecture_runs_from_new_files_alone(tmp_path):
    copy = tmp_path / "bench"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}
    shutil.copy(BENCH / "tests" / "two_kinds_arch.py", copy / "arch" / "two_kinds.py")
    (copy / "configs" / "tiny-two-kinds.json").write_text(json.dumps(TWO_KINDS))
    assert all(p.read_bytes() == b for p, b in before.items())
    assert not (BENCH / "arch" / "two_kinds.py").exists()

    specs = [{"name": "tokens_per_s", "unit": "tokens/s"}, {"name": "setup_s", "unit": "s"}]
    checks = dict(CHECKS, widest_logit_gap=0.3)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(copy),
                        str(copy / "configs" / "tiny-two-kinds.json"),
                        json.dumps([CLOSED, checks, specs])],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert Path(out["module"]).parent == copy / "arch"
    assert Path(out["run"]).parent == copy
    assert out["sound"][0], out["sound"]
    assert out["sound"][1]["tokens_compared"]["value"] > 40   # past the window
    assert not out["fault"][0]
    gap = out["fault"][1]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]

    # four layers: 0 and 2 a window of 16 keys, 1 and 3 full
    attn, ffn = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64, 3 * 64 * 96
    window_keys = sum(min(p + 1, 17) for p in range(40))
    full_keys = sum(p + 1 for p in range(40))
    assert out["prefill_40"] == (4 * 2 * (attn + ffn) * 40
                                 + 4 * 4 * 16 * 2 * (window_keys + full_keys)
                                 + 2 * 64 * 256)
    weights = 4 * ((attn + ffn) * 2 + 2 * 64 * 4) + 256 * 64 * 2 + 64 * 4
    kv_row = 2 * 2 * 16 * 2
    assert out["step_bytes"] == weights + kv_row * 2 * ((17 + 17) + (31 + 51))


# an expert layer of which this chip holds 8 of the 64 experts its router
# scores, 6 per token, with a shared expert; one window layer, one full
D, WIDTH, SHARED = 1024, 768, 1536
EXPERTS = {"kind": "experts", "router_width": 64, "held": 8, "k": 6,
           "width": WIDTH, "shared": SHARED}
HELD = [{"d": D, "norms": 2, "ffn": EXPERTS,
         "mixer": {"kind": "attention", "hq": 16, "hkv": 4, "dh": 64,
                   "window": w, "sinks": 0}} for w in (4096, None)]
HELD_CFG = {"hidden_size": D, "vocab_size": 1000}


def _layers(monkeypatch, layers):
    """cost.py reads these layers for any configuration."""
    mod = types.SimpleNamespace(layers=lambda cfg: layers)
    monkeypatch.setattr(arch, "load", lambda cfg: mod)


def test_held_experts_by_hand(monkeypatch):
    _layers(monkeypatch, HELD)
    attn = D * (16 + 2 * 4) * 64 + 16 * 64 * D
    routed = 6 * 8 / 64 * 3 * D * WIDTH + 3 * D * SHARED     # 0.75 expert a token
    router = D * 64
    assert cost.layer_params(HELD[0]) == {"attn": attn, "ffn": routed, "router": router}
    assert cost.layer_params(HELD[0], routed=False)["ffn"] == 8 * 3 * D * WIDTH + 3 * D * SHARED
    pos = 5000                                    # 4097 keys in the window, all 5001 full
    assert cost.token_flops(HELD_CFG, pos, unembed=False) == (
        2 * 2 * (attn + routed + router) + 4 * 16 * 64 * (4097 + 5001))
    assert cost.token_flops(HELD_CFG, pos) - cost.token_flops(
        HELD_CFG, pos, unembed=False) == 2 * D * 1000

    tokens = 16
    distinct = 8 * (1 - (1 - 6 / 64) ** tokens)
    assert cost.expected_experts(EXPERTS, tokens) == pytest.approx(distinct)
    assert 8 * (1 - (1 - 6 / 64) ** 1) == pytest.approx(0.75)
    layer_w = attn * 2 + (distinct * 3 * D * WIDTH * 2 + D * 64 * 4 + 3 * D * SHARED * 2) \
        + 2 * D * 4
    assert cost.weight_bytes(HELD_CFG, tokens) == pytest.approx(
        2 * layer_w + 1000 * D * 2 + D * 4, rel=1e-12)
    positions = [100 * i + 4000 for i in range(tokens)]
    kv = 2 * 4 * 64 * 2 * (sum(min(p + 1, 4097) for p in positions)
                           + sum(p + 1 for p in positions))
    assert cost.decode_step_bytes(HELD_CFG, positions) == pytest.approx(
        2 * layer_w + 1000 * D * 2 + D * 4 + kv, rel=1e-12)


# a layer whose mixer cost.py does not know: its module gives the cost
RECURRENT = {"kind": "recurrent", "flops": lambda start, stop: 1000 * (stop - start),
             "weight_bytes": 5000, "state_bytes": lambda positions: 77 * len(positions)}
OTHER = [dict(HELD[0], mixer=RECURRENT, ffn={"kind": "dense", "width": WIDTH})]


def test_other_kind_carries_its_own_cost(monkeypatch):
    _layers(monkeypatch, OTHER)
    ffn = 3 * D * WIDTH
    assert cost.prefill_flops(HELD_CFG, 10) == 10 * (1000 + 2 * ffn) + 2 * D * 1000
    assert cost.decode_flops(HELD_CFG, [7, 9]) == 2 * (1000 + 2 * ffn + 2 * D * 1000)
    head = 1000 * D * 2 + D * 4
    assert cost.decode_step_bytes(HELD_CFG, [7, 9]) == (
        5000 + ffn * 2 + 2 * D * 4 + head + 77 * 2)

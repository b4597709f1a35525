"""The harness end to end on the CPU, at smoke size: the device check
refuses a machine with no chip; with the look for a chip skipped, a sound
run is correct and a run with the timed path broken underneath is not --
once for each fault a one-chip serving cell can have (a token altered
where it is produced, a decode step that returns its cache unchanged, half
of the batch left out), on smoke-size copies of the dense configuration the
cells run and of the MoE one; and the control, put in the engine's place,
comes out not correct: its widest gap lies above the cell's limit. With a
router margin the MoE's near-ties are left out of the comparison, faults
still show, and a run that leaves out too much is not correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import run
from repro.core import model as Mod
from repro.kernels import ref as R
from repro.serving import engine as E
from repro.serving import sampling
from tiny import CHECKS, CLOSED, OPEN, dense, moe

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "granite-8b-swat.decode-long"
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
# open loop busy enough that every slot serves requests
BUSY = dict(OPEN, rate_per_s=40.0, output={"dist": "fixed", "value": 40})
# each smoke mix stands for the cell of its loop, and takes that cell's limit
CELL_OF = {"closed": CELL, "open": "granite-8b-swat.chat"}


def _limit(mix):
    path = ROOT / "bench" / "checks" / f"{CELL_OF[mix['loop']]}.json"
    return json.loads(path.read_text())["widest_logit_gap"]


def _execute(cfg, mix, seed, control=False, trace=False, every_request=False,
             **more_checks):
    checks = dict(CHECKS, widest_logit_gap=_limit(mix), **more_checks)
    if every_request:
        checks.update(sample_tokens=10**9, sample_requests=10**9)
    specs = run.metric_specs(BENCH, CELL_OF[mix["loop"]], trace)
    cell = run.Cell("tiny", 1, cfg, mix, checks, specs)
    return run.execute(cell, seed, 2.0, trace, device=DEVICE, control=control)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("make", [dense, moe], ids=["dense", "moe"])
def test_sound_run_is_correct_and_reports_cell_metrics(make):
    out = _execute(make(), CLOSED, 2**31 + 99)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] >= 4


def _altered_token(monkeypatch):
    """Every token the engine samples is replaced by the next id."""
    orig = sampling.sample

    def altered(key, logits, temperatures, top_k=0, any_sampling=None):
        tok = orig(key, logits, temperatures, top_k, any_sampling)
        return (tok + 1) % logits.shape[-1]
    monkeypatch.setattr(sampling, "sample", altered)


def _state_unchanged(monkeypatch):
    """A decode step returns its ring caches unchanged: the new token's
    keys and values never enter the cache."""
    monkeypatch.setattr(R, "ring_insert_ref",
                        lambda cache, new, pos, num_new, **kw: cache)


def _half_batch(monkeypatch):
    """The second half of the slots is left out of each decode step: its
    rows' logits are never computed (zeros)."""
    orig = Mod.decode_step

    def half(params, cfg, batch, caches, **kw):
        logits, c = orig(params, cfg, batch, caches, **kw)
        b = logits.shape[0]
        keep = (jnp.arange(b) < (b + 1) // 2)[:, None, None]
        return jnp.where(keep, logits, 0.0), c
    monkeypatch.setattr(Mod, "decode_step", half)


@pytest.fixture
def fresh_programs():
    E._get_compiled.cache_clear()
    jax.clear_caches()
    yield
    E._get_compiled.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("make", [dense, moe], ids=["dense", "moe"])
@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged, _half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
@pytest.mark.parametrize("mix", [CLOSED, BUSY], ids=["closed", "open"])
def test_fault_is_not_correct(fresh_programs, monkeypatch, fault, mix, make):
    fault(monkeypatch)
    out = _execute(make(), mix, 5, every_request=True)
    assert not out["correct"]
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("mix", [CLOSED, BUSY], ids=["closed", "open"])
def test_control_is_not_correct(mix):
    """The dense configuration only: at the MoE's near-ties of the router
    the engine and the control read alike (PERF.md, Open questions), which
    is why no cell runs the MoE."""
    out = _execute(dense(), mix, 17, control=True, every_request=True)
    c, gap = out["compared"], out["checks"]["widest_logit_gap"]
    assert not out["correct"]
    assert gap["value"] == c["control_widest_logit_gap"] > gap["limit"]
    assert c["widest_logit_gap"] < gap["limit"]


# router logits of the smoke MoE are about unit-sized, and bf16 moves one by
# about 0.01: within 0.05 of the k-th, the choice of experts is rounding's
MARGIN = 0.05


def test_router_near_ties_are_left_out():
    out = _execute(moe(), CLOSED, 2**31 + 99, router_margin=MARGIN)
    c = out["compared"]
    assert out["correct"], out["checks"]
    assert c["tokens_left_out"] > 0 and c["tokens_compared"] > 0
    assert out["checks"]["tokens_compared"] == {"value": c["tokens_compared"],
                                                "limit": 1}


def test_too_few_tokens_compared_is_not_correct():
    """Every position left out (a margin no router gap reaches), or fewer
    tokens left than the checks ask for (4 requests of at most 24 tokens
    leave at most 96): not correct."""
    out = _execute(moe(), CLOSED, 2**31 + 99, router_margin=1e9)
    assert out["compared"]["tokens_compared"] == 0 and not out["correct"]
    out = _execute(moe(), CLOSED, 2**31 + 99, router_margin=MARGIN,
                   min_tokens_compared=97)
    tc = out["checks"]["tokens_compared"]
    assert tc["value"] < tc["limit"] == 97 and not out["correct"]


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged, _half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
@pytest.mark.parametrize("mix", [CLOSED, BUSY], ids=["closed", "open"])
def test_fault_is_not_correct_at_router_margin(fresh_programs, monkeypatch,
                                               fault, mix):
    fault(monkeypatch)
    out = _execute(moe(), mix, 5, every_request=True, router_margin=MARGIN)
    assert not out["correct"]
    assert out["compared"]["tokens_left_out"] > 0
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]

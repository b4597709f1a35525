"""driver.Driver, fed the same requests, returns the tokens that
ServingEngine.run returns, on smoke-size copies of both configurations."""
import time

import numpy as np
import pytest

import driver
import weights as W
from arch.granite_swat import model_config, to_params
from repro.serving.engine import Request, ServingEngine
from tiny import dense, moe


@pytest.mark.parametrize("make", [moe, dense], ids=["moe", "dense"])
def test_driver_matches_engine_run(make):
    cfg = make()
    w = W.make(cfg, 11)
    rng = np.random.default_rng(0)
    reqs = [(i, rng.integers(0, cfg["vocab_size"], int(n)).astype(np.int32), int(m))
            for i, (n, m) in enumerate(zip(rng.integers(5, 40, 7),
                                           rng.integers(3, 20, 7)))]
    drv = driver.Driver(cfg, w, 5)
    for rid, prompt, m in reqs:
        drv.submit(rid, prompt, m)
    while len(drv.results) < len(reqs):
        drv.step(time.perf_counter)
    mc = model_config(cfg)
    eng = ServingEngine(mc, to_params(w, mc), seed=5,
                        **{k: cfg["serving"][k] for k in driver.SERVING_KEYS})
    want = {r.rid: (r.status, r.tokens)
            for r in eng.run([Request(i, p, max_new_tokens=m) for i, p, m in reqs])}
    assert drv.results == want
    assert all(len(t) == m for (_, t), (_, _, m) in zip(
        [drv.results[i] for i in range(len(reqs))], reqs))
    # every token reached the harness's clock: first at admission, rest by block
    per = {}
    for rid, _, k in drv.deliveries:
        per[rid] = per.get(rid, 0) + k
    assert per == {rid: len(t) for rid, (_, t) in want.items()}


def test_warm_runs_every_reachable_shape():
    cfg = moe()
    drv = driver.Driver(cfg, W.make(cfg, 1), 1)
    shapes = drv.prefill_shapes(4, 40)
    # buckets of 16 up to 48; two rows fit in 64 tokens only at 16 and 32
    assert shapes == [(1, 16), (2, 16), (3, 16), (4, 16), (1, 32), (2, 32), (1, 48)]
    assert drv.warm(4, 40, np.random.default_rng(0)) == len(shapes) + drv.scan_steps
    assert drv.live() == 0 and not drv.spans and not drv.deliveries

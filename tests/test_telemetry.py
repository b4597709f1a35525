"""swatscope telemetry: device counters, lifecycle tracing, exports.

The observability contract (src/repro/telemetry/ + the engine hooks):

  * metrics-on decode is BITWISE identical to metrics-off — both decode
    impls, sequential and speculative, and under chaos (the counter
    pytree is one extra donated int32 carry; it never touches sampling,
    RNG, or control flow),
  * device counters match a hand-computed oracle: tokens/pos equal the
    in-scan emission count, ring_wraps equals the revolutions of the
    narrowest logical ring, speculative draft counters equal the host's
    per-attempt accounting, the chaos drill bumps `quarantined` exactly
    once,
  * per-attempt vs per-request accounting: `tokens_emitted` counts work
    (retries re-count), `tokens_delivered` counts exactly the tokens
    clients received — a kernel-fallback retry never double-counts,
  * the tracer holds O(capacity) memory under sustained load and its
    exports (Chrome trace JSON, Prometheus text) pass the telemetry
    schema validators,
  * degradation events flow over ONE bus: `faults.record_event` is a
    shim over `telemetry.events`, and engine tracers see the same stream
    `consume_events()` drains,
  * the engine's host spans land in a profiler trace, nested, with their
    counts; its programs carry their names and named scopes; its layer
    counters match a hand count.

The 4-device slot-parallel metrics-identity case lives in
test_serving_sharded.py with the other mesh suites.
"""
import pathlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config, with_swat
from repro.core import model as Mod
from repro.serving import faults as F
from repro.serving.engine import Request, ServingEngine
from repro.serving.faults import FaultPlan
from repro.telemetry import events as TEV
from repro.telemetry import kernelprof as KP
from repro.telemetry import metrics as MX
from repro.telemetry.tracer import Tracer, live_tracers, prometheus_text
from repro.telemetry.validate import (validate_chrome_trace,
                                      validate_prometheus)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llama3p2_1b")
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def swat_setup():
    cfg = with_swat(get_smoke_config("llama3p2_1b"), window=16, num_global=4)
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(autouse=True)
def _clean_event_bus():
    F.consume_events()
    yield
    F.consume_events()


def mkreqs(cfg, n=3, m=10, plen=12):
    return [Request(rid=i, prompt=np.random.RandomState(i).randint(
                0, cfg.vocab_size, (plen,)).astype(np.int32),
                max_new_tokens=m) for i in range(n)]


def by_rid(results):
    return {r.rid: r for r in results}


# ------------------------------------------------- bitwise identity ----


def _identity_case(cfg, params, *, n=3, m=10, **kw):
    off = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, metrics=False, **kw)
    ref = by_rid(off.run(mkreqs(cfg, n=n, m=m)))
    on = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                       scan_steps=4, metrics=True, **kw)
    out = by_rid(on.run(mkreqs(cfg, n=n, m=m)))
    for i in ref:
        assert out[i].status == ref[i].status
        assert out[i].tokens == ref[i].tokens, (i, out[i].tokens,
                                                ref[i].tokens)
    return on


def test_metrics_identity_ref(setup):
    eng = _identity_case(*setup)
    dev = eng.device_metrics()
    assert dev["tokens"] == eng.stats["tokens_emitted"]


def test_metrics_identity_pallas(swat_setup):
    eng = _identity_case(*swat_setup, decode_impl="pallas")
    assert eng.device_metrics()["tokens"] == eng.stats["tokens_emitted"]


def test_metrics_identity_speculative(setup):
    eng = _identity_case(*setup, speculative=2)
    dev = eng.device_metrics()
    assert dev["drafts_proposed"] == eng.stats["draft_proposed"]
    assert dev["drafts_accepted"] == eng.stats["draft_accepted"]


def test_metrics_identity_under_chaos(setup):
    """The quarantine path with counters compiled in: healthy slots
    bitwise identical, the poisoned request degrades identically."""
    cfg, params = setup
    plan = FaultPlan(poison_logits=((0, 3, "nan"),))
    off = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, faults=plan)
    ref = by_rid(off.run(mkreqs(cfg)))
    on = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                       scan_steps=4, faults=plan, metrics=True)
    out = by_rid(on.run(mkreqs(cfg)))
    for i in ref:
        assert (out[i].status, out[i].tokens) == (ref[i].status,
                                                  ref[i].tokens)
    dev = on.device_metrics()
    assert dev["quarantined"] == 1 == on.stats["quarantined"]


# ------------------------------------------------- counter oracles ----


def test_counter_oracle_sequential(setup):
    """Sequential decode, no faults: the device counters must equal the
    hand count — every request emits max_new_tokens - 1 tokens in-scan
    (the first token is sampled at prefill, host-side), pos mirrors
    tokens, nothing quarantines, and per-step emission bounds steps."""
    cfg, params = setup
    n, m = 3, 10
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, metrics=True)
    out = by_rid(eng.run(mkreqs(cfg, n=n, m=m)))
    assert all(r.status == "ok" and len(r.tokens) == m
               for r in out.values())
    dev = eng.device_metrics()
    want = n * (m - 1)
    assert dev["tokens"] == want == eng.stats["tokens_emitted"]
    assert dev["pos"] == want          # sequential: one write per emit
    assert dev["quarantined"] == 0
    assert dev["drafts_proposed"] == 0 == dev["drafts_accepted"]
    assert dev["steps"] > 0
    # each scan iteration emits at most one token per slot
    assert want <= dev["steps"] * eng.slots
    assert eng.stats["tokens_delivered"] == n * m


def test_counter_oracle_ring_wraps(swat_setup):
    """One slot, one long request: decode writes wrap the narrowest
    logical ring exactly floor(emitted / modulus) times."""
    cfg, params = swat_setup
    m = 40
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=64,
                        scan_steps=4, metrics=True)
    out = eng.run(mkreqs(cfg, n=1, m=m))
    assert out[0].status == "ok" and len(out[0].tokens) == m
    mod = MX.ring_modulus(cfg, 64)
    assert eng._c.ring_mod == mod
    dev = eng.device_metrics()
    assert dev["tokens"] == m - 1
    assert dev["ring_wraps"] == (m - 1) // mod, (dev, mod)


def test_counter_oracle_speculative(setup):
    """Speculative verify: device draft counters mirror the host's
    accounting identity — every verify step that ran proposed k drafts
    and kept emitted - 1 of them."""
    cfg, params = setup
    k = 2
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, speculative=k, metrics=True)
    out = by_rid(eng.run(mkreqs(cfg, n=3, m=10)))
    assert all(r.status == "ok" for r in out.values())
    dev = eng.device_metrics()
    assert dev["drafts_proposed"] == eng.stats["draft_proposed"] > 0
    assert dev["drafts_accepted"] == eng.stats["draft_accepted"]
    assert dev["tokens"] == eng.stats["tokens_emitted"]
    # identity: emitted = verify steps that ran + accepted drafts
    assert (dev["tokens"]
            == dev["drafts_proposed"] // k + dev["drafts_accepted"])


def test_counters_survive_admission_waves(setup):
    """More requests than slots: counters are engine-lifetime totals,
    accumulated across slot reuse — never reset by a restage."""
    cfg, params = setup
    n, m = 5, 8                  # 5 requests through 2 slots
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, metrics=True)
    out = by_rid(eng.run(mkreqs(cfg, n=n, m=m)))
    assert all(r.status == "ok" for r in out.values())
    assert eng.device_metrics()["tokens"] == n * (m - 1)


# ------------------------------------- per-attempt vs per-request ----


def test_retry_never_double_counts_delivered(swat_setup):
    """A pallas dispatch failure retries the block after recompiling with
    the ref impl. `tokens_emitted` counts per-attempt WORK; the new
    `tokens_delivered` must equal exactly the tokens clients received."""
    cfg, params = swat_setup
    F.consume_events()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                                scan_steps=2, decode_impl="pallas",
                                metrics=True,
                                faults=FaultPlan(fail_pallas_dispatch=True))
            out = eng.run(mkreqs(cfg, m=8))
    finally:
        F.clear_kernel_failure()
    assert eng.stats["kernel_fallbacks"] == 1
    assert all(r.status == "ok" for r in out)
    assert eng.stats["tokens_delivered"] == sum(len(r.tokens) for r in out)
    # a retried attempt's record counts once per admission in the tracer
    recs = {r.rid: r for r in eng.tracer.records}
    assert all(recs[r.rid].tokens == len(r.tokens) for r in out)


# ----------------------------------------------------- the tracer ----


def test_tracer_ring_bounds_memory():
    """Sustained load holds O(capacity) records — the deque drops the
    oldest, latency summaries keep working, nothing grows unbounded."""
    t = [0.0]

    def clock():
        t[0] += 0.5
        return t[0]

    tr = Tracer(capacity=8, clock=clock)
    for rid in range(100):
        tr.on_submit(rid)
        tr.on_admit([rid])
        tr.on_first_token([rid])
        tr.on_block("seq", 4, clock(), 4)
        tr.on_finish(rid, "ok", 5)
    assert len(tr.records) == 8
    assert len(tr.blocks) == 8
    assert {r.rid for r in tr.records} == set(range(92, 100))
    summ = tr.latency_summary()
    assert summ["ttft"]["count"] == 8
    assert summ["tpot"]["p50"] > 0
    assert tr.dropped_requests == 0


def test_tracer_deterministic_latency_math():
    """With an injected clock the derived latencies are exact: ttft =
    first_token - submit, tpot = (finish - first) / (tokens - 1),
    queue_delay = admit - submit."""
    t = {"now": 0.0}

    def clock():
        return t["now"]

    tr = Tracer(capacity=4, clock=clock)
    tr.on_submit(7)
    t["now"] = 1.0
    tr.on_admit([7])
    t["now"] = 3.0
    tr.on_first_token([7])
    t["now"] = 11.0
    tr.on_finish(7, "ok", 5)
    (rec,) = tr.records
    assert rec.queue_delay == 1.0
    assert rec.ttft == 3.0
    assert rec.tpot == (11.0 - 3.0) / 4


def test_tracer_retry_restarts_attempt_clock():
    """A second admission of the same rid is a retry: attempts bumps and
    the first-token clock resets, but submit (the client's clock) holds."""
    t = {"now": 0.0}
    tr = Tracer(capacity=4, clock=lambda: t["now"])
    tr.on_submit(0)
    t["now"] = 1.0
    tr.on_admit([0])
    t["now"] = 2.0
    tr.on_first_token([0])
    t["now"] = 3.0
    tr.on_admit([0])              # retry
    t["now"] = 5.0
    tr.on_first_token([0])
    t["now"] = 9.0
    tr.on_finish(0, "ok", 3)
    (rec,) = tr.records
    assert rec.attempts == 2
    assert rec.submit == 0.0 and rec.admit == 3.0
    assert rec.ttft == 5.0


def test_on_submit_takes_the_callers_due_time():
    """A caller that knows when a request was due passes it: queue delay
    and TTFT count from that time, not from the call."""
    t = {"now": 5.0}
    tr = Tracer(capacity=4, clock=lambda: t["now"])
    tr.on_submit(3, t=2.0)
    t["now"] = 6.0
    tr.on_admit([3])
    t["now"] = 7.5
    tr.on_first_token([3])
    tr.on_finish(3, "ok", 1)
    (rec,) = tr.records
    assert rec.submit == 2.0
    assert rec.queue_delay == 4.0
    assert rec.ttft == 5.5


def test_admit_without_submit_has_no_queue_delay():
    """A request admitted with no submit time has no queue delay or TTFT
    (not 0), and the latency summary leaves it out."""
    t = {"now": 0.0}
    tr = Tracer(capacity=4, clock=lambda: t["now"])
    tr.on_submit(1)
    t["now"] = 1.0
    tr.on_admit([1, 2])               # rid 2 was never submitted
    t["now"] = 2.0
    tr.on_first_token([1, 2])
    tr.on_finish(1, "ok", 1)
    tr.on_finish(2, "ok", 1)
    recs = {r.rid: r for r in tr.records}
    assert recs[1].queue_delay == 1.0 and recs[1].ttft == 2.0
    assert recs[2].submit is None
    assert recs[2].queue_delay is None and recs[2].ttft is None
    summ = tr.latency_summary()
    assert summ["queue_delay"]["count"] == 1
    assert summ["queue_delay"]["p50"] == 1.0
    assert summ["ttft"]["count"] == 1
    assert validate_chrome_trace(tr.chrome_trace()) == []


def test_span_records_on_the_tracers_clock():
    """A span records (name, t0, t1, counts) on the tracer's clock, nests,
    and is exported to the Chrome trace; tracers are found alive."""
    t = {"now": 0.0}
    tr = Tracer(capacity=8, clock=lambda: t["now"])
    with tr.span("swat.decode", n=4, slots=2, live=1) as outer:
        t["now"] = 1.0
        with tr.span("swat.decode.sync"):
            t["now"] = 3.0
        t["now"] = 4.0
    assert (outer.t0, outer.t1) == (0.0, 4.0)
    assert list(tr.spans) == [("swat.decode.sync", 1.0, 3.0, {}),
                              ("swat.decode", 0.0, 4.0,
                               {"n": 4, "slots": 2, "live": 1})]
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc) == []
    spans = [ev for ev in doc["traceEvents"] if ev.get("cat") == "span"]
    assert [ev["name"] for ev in spans] == ["swat.decode.sync", "swat.decode"]
    assert spans[1]["args"] == {"n": 4, "slots": 2, "live": 1}
    assert any(x is tr for x in live_tracers())


# ------------------------------------------- spans, scopes, counters ----


def _prompt(cfg, n, seed):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (n,)).astype(np.int32)


def _host_events(trace_dir):
    """swat.* events of the newest xplane under trace_dir, in start order:
    (name, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("swat."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def test_spans_land_in_the_profiler_trace(swat_setup, tmp_path):
    """A served batch under the profiler yields swat.plan, swat.admit and
    swat.decode with their children; every child lies inside its parent;
    padded_tokens is rows x padded length and emitted is what the block
    delivered."""
    cfg, params = swat_setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4)
    reqs = [Request(rid=i, prompt=_prompt(cfg, n, i), max_new_tokens=6)
            for i, n in enumerate((5, 20, 12))]
    eng.run([Request(rid=9, prompt=_prompt(cfg, 8, 9), max_new_tokens=3)])
    with jax.profiler.trace(str(tmp_path)):
        out = eng.run(reqs)
    assert all(r.ok for r in out)
    evs = _host_events(tmp_path)
    names = {ev[0] for ev in evs}
    assert {"swat.plan", "swat.admit", "swat.admit.prefill",
            "swat.admit.sample", "swat.admit.insert", "swat.decode",
            "swat.decode.stage", "swat.decode.dispatch", "swat.decode.sync",
            "swat.decode.retire"} <= names
    parents = [ev for ev in evs if ev[0] in ("swat.admit", "swat.decode")]
    for name, s0, e0, _ in evs:
        if name.count(".") == 2:
            parent = name.rsplit(".", 1)[0]
            assert any(p[0] == parent and p[1] <= s0 and e0 <= p[2]
                       for p in parents), name
    admits = [ev[3] for ev in evs if ev[0] == "swat.admit"]
    for a in admits:
        assert a["padded_tokens"] == a["rows"] * a["padded_len"]
    # rids 0 and 1 share a batch padded to 32; rid 2 comes alone, to 16
    assert sorted((a["rows"], a["padded_len"], a["prompt_tokens"])
                  for a in admits) == [(1, 16, 12), (2, 32, 25)]
    blocks = [ev for ev in evs if ev[0] == "swat.decode"]
    emitted = [ev[3]["emitted"] for ev in evs
               if ev[0] == "swat.decode.retire"]
    assert len(blocks) == len(emitted) == len(eng.tracer.blocks) - 1
    assert emitted == [b["tokens"] for b in list(eng.tracer.blocks)[1:]]
    assert sum(emitted) == sum(len(r.tokens) - 1 for r in out)
    for _, _, _, a in blocks:
        assert a["slots"] == 2 and 1 <= a["live"] <= 2 and a["n"] >= 1


def _scan_args(eng):
    dev = eng._dev
    return (eng.params, eng.caches, dev["tok"], dev["active"],
            dev["budget"], dev["temps"], dev["anyt"], eng.key,
            dev["poisoned"])


@pytest.mark.parametrize("program", ["decode_scan", "prefill",
                                     "prefill_chunk"])
def test_programs_carry_names_and_scopes(swat_setup, program):
    """Compiled serving programs are named jit_swat_<program> and their
    operations carry the model's named scopes."""
    cfg, params = swat_setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, prefill_chunk=16)
    eng.run(mkreqs(cfg, n=2, m=6))
    tok = jnp.zeros((2, 16), jnp.int32)
    lens = jnp.full((2,), 16, jnp.int32)
    if program == "decode_scan":
        # the staged slot vectors outlive the run's last block
        lowered = eng._c.scan(4, 2).lower(*_scan_args(eng))
    elif program == "prefill":
        lowered = eng._c.prefill(2).lower(eng.params, tok, lens)
    else:
        lowered = eng._c.chunk(2).lower(
            eng.params, eng._c.fresh_caches(2), tok, jnp.int32(0), lens,
            jnp.zeros((2, cfg.vocab_size), jnp.float32))
    text = lowered.compile().as_text()
    assert text.startswith(f"HloModule jit_swat_{program},")
    scopes = set(re.findall(r'op_name="jit\(swat_\w+\)/([^"]*)"', text))
    joined = "\n".join(scopes)
    for want in ("attn/qkv/", "attn/ring_write/", "attn/attend/",
                 "attn/out/", "mlp/", "embed/", "unembed/"):
        assert want in joined, (want, sorted(scopes)[:20])
    if program == "decode_scan":
        assert "sample/" in joined


def test_layer_counters_hand_checked(swat_setup):
    """engine.stats counts padded prefill tokens and decode blocks, steps
    and slot-steps. Two slots, blocks of at most 4 steps, three requests
    of 10 tokens (9 decoded each) with prompts of 5, 20 and 12 tokens
    padded to multiples of 16: rids 0-1 prefill as 2 x 32, rid 2 as
    1 x 16; each wave decodes in blocks of 4, 4 and 1 steps."""
    cfg, params = swat_setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, pad_to=16)
    reqs = [Request(rid=i, prompt=_prompt(cfg, n, i), max_new_tokens=10)
            for i, n in enumerate((5, 20, 12))]
    assert all(r.ok for r in eng.run(reqs))
    st = eng.stats
    assert st["prefill_tokens_computed"] == 5 + 20 + 12
    assert st["prefill_tokens_padded"] == 2 * 32 + 16
    assert st["decode_blocks"] == 6
    assert st["decode_steps"] == 2 * (4 + 4 + 1)
    assert st["decode_slot_steps"] == 2 * st["decode_steps"]
    assert st["tokens_emitted"] == 3 * 9
    text = eng.metrics_text()
    assert validate_prometheus(text) == []
    for k in ("prefill_tokens_padded", "decode_blocks", "decode_steps",
              "decode_slot_steps"):
        assert f"swat_{k} {st[k]}" in text


# ------------------------------------------------- exports + schema ----


def test_exports_validate(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, metrics=True)
    eng.run(mkreqs(cfg))
    doc = eng.chrome_trace()
    assert validate_chrome_trace(doc) == []
    assert any(ev.get("cat") == "request" for ev in doc["traceEvents"])
    text = eng.metrics_text()
    assert validate_prometheus(text) == []
    assert "swat_device_tokens" in text
    assert 'quantile="0.95"' in text


def test_validators_reject_garbage():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]}) != []
    assert validate_prometheus("") != []
    assert validate_prometheus("no type line 1\n") != []
    good = prometheus_text({"a_total": 3})
    assert validate_prometheus(good) == []


def test_snapshot_round_trips(setup):
    import json

    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4, metrics=True)
    eng.run(mkreqs(cfg, n=2, m=6))
    snap = eng.snapshot()
    assert snap["device"]["tokens"] == eng.stats["tokens_emitted"]
    assert snap["stats"]["tokens_delivered"] == 12
    json.dumps(snap)              # everything JSON-serializable


# -------------------------------------------------- unified events ----


def test_event_bus_single_stream(setup):
    """faults.record_event IS telemetry.events.record_event, subscribed
    engine tracers see the same event, and consume drains one queue."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64)
    F.record_event("synthetic_drill", rid=42)
    assert TEV.peek_events()[-1]["kind"] == "synthetic_drill"
    assert eng.tracer.events[-1]["kind"] == "synthetic_drill"
    drained = F.consume_events()
    assert [e["kind"] for e in drained] == ["synthetic_drill"]
    assert TEV.consume_events() == []          # one queue, now empty
    # the tracer keeps its bounded copy for the chrome trace
    assert eng.tracer.events[-1]["rid"] == 42


def test_quarantine_event_reaches_tracer(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4,
                        faults=FaultPlan(poison_logits=((0, 3, "nan"),)))
    eng.run(mkreqs(cfg))
    kinds = [e["kind"] for e in eng.tracer.events]
    assert "slot_quarantined" in kinds
    names = [ev["name"] for ev in eng.chrome_trace()["traceEvents"]]
    assert "slot_quarantined" in names


# ------------------------------------------------ kernel profiling ----


def test_dispatch_census_is_trace_time(setup):
    """The census records one entry per compiled shape regardless of how
    many steps execute, and is inert when disabled."""
    cfg, params = setup
    KP.consume_census()
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        scan_steps=4)
    eng.run(mkreqs(cfg, n=2, m=6))
    assert KP.consume_census() == []           # disabled: zero overhead
    KP.enable_census(True)
    try:
        # distinct scan_steps -> a fresh compile (the engine memoizes
        # compiled programs per shape; the census records at trace time)
        eng2 = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                             scan_steps=3)
        eng2.run(mkreqs(cfg, n=2, m=6))
    finally:
        KP.enable_census(False)
    census = KP.consume_census()
    assert census, "census saw no decode dispatches"
    ops = {rec["op"] for rec in census}
    assert "decode_attention" in ops
    assert all(rec["traces"] >= 1 for rec in census)


def test_banded_cost_is_window_linear():
    """The paper's O(window) claim in the analytic model: doubling the
    window ~doubles banded FLOPs while dense FLOPs track cap."""
    base = dict(b=1, h_q=4, h_kv=2, t=1, d=64, cap=4096, num_global=4)
    w64 = KP.banded_decode_cost(window=64, **base)
    w128 = KP.banded_decode_cost(window=128, **base)
    dense = KP.banded_decode_cost(window=0, **base)
    assert 1.5 < w128["flops"] / w64["flops"] < 2.1
    assert dense["flops"] > 20 * w64["flops"]
    assert w64["band_rows"] == 64 + 4 + 1

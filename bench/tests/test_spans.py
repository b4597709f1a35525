"""The program's spans and scopes in a trace, on synthetic traces: idle time
goes to the innermost span, scope and program self time add up to the
device time inside a span, HLO text gives each operation its scope; the
readers of the program's span counts, on hand-made records, on a program
that records none, and in a traced run on the CPU."""
import json
from pathlib import Path

import pytest

import run
import spans
from metrics import decode_slot_use, prefill_pad_share
from repro.telemetry import tracer as T
from tiny import CHECKS, CLOSED, OPEN, dense

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

HOST = [("swat.decode", 1.0, 5.0, {"n": 4, "slots": 2, "live": 2}),
        ("swat.decode.stage", 1.0, 1.5, {}),
        ("swat.decode.dispatch", 1.5, 2.0, {}),
        ("swat.decode.sync", 2.0, 4.5, {}),
        ("swat.decode.retire", 4.5, 5.0, {"emitted": 6}),
        ("swat.admit", 6.0, 8.0, {"rows": 2, "padded_len": 16,
                                  "prompt_tokens": 20, "padded_tokens": 32}),
        ("swat.admit.prefill", 6.0, 7.0, {})]
# (start, end, op, program, scope): a decode loop holding its body's
# operations, and a prefill whose last operation runs past its span
OPS = [(0.5, 1.2, "%fusion.1", "jit_swat_insert", "unscoped"),
       (1.8, 4.4, "%while.2", "jit_swat_decode_scan", "unscoped"),
       (1.9, 2.9, "%fusion.3", "jit_swat_decode_scan", "attn/ring_write"),
       (3.0, 4.0, "%fusion.4", "jit_swat_decode_scan", "mlp"),
       (6.5, 7.5, "%fusion.5", "jit_swat_prefill", "attn/attend"),
       (7.5, 8.5, "%fusion.6", "jit_swat_insert", "unscoped")]


def test_idle_goes_to_the_innermost_span():
    r = spans.reduce({"/device:TPU:0": OPS}, HOST, (0.0, 10.0))
    assert r["busy_s"] == pytest.approx(0.7 + 2.6 + 1.0 + 1.0)
    want = {"outside": 0.5 + 1.0 + 1.5, "swat.decode.stage": 0.3,
            "swat.decode.dispatch": 0.3, "swat.decode.sync": 0.1,
            "swat.decode.retire": 0.5, "swat.admit.prefill": 0.5,
            "swat.admit": 0.0}
    assert r["idle_s"] == pytest.approx(want)
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert spans.decode_host_ms(r) == pytest.approx(1e3 * 1.2)
    # longest first, each labelled by the span over its middle
    assert r["idle_gaps"] == [
        ["outside", pytest.approx(4.4), pytest.approx(2.1)],
        ["outside", pytest.approx(8.5), pytest.approx(1.5)],
        ["swat.decode.dispatch", pytest.approx(1.2), pytest.approx(0.6)],
        ["outside", pytest.approx(0.0), pytest.approx(0.5)]]


def test_scope_self_time_adds_up_to_device_time_inside_spans():
    r = spans.reduce({"/device:TPU:0": OPS}, HOST, (0.0, 10.0))
    dec, adm = r["inside"]["swat.decode"], r["inside"]["swat.admit"]
    assert dec["device_s"] == pytest.approx(0.2 + 2.6)
    assert dec["by_scope"] == pytest.approx(
        {"unscoped": 0.2 + 0.6, "attn/ring_write": 1.0, "mlp": 1.0})
    assert dec["by_top_scope"]["attn"] == pytest.approx(1.0)
    assert dec["by_program"] == pytest.approx(
        {"jit_swat_insert": 0.2, "jit_swat_decode_scan": 2.6})
    for d in (dec, adm):
        for f in ("by_scope", "by_top_scope", "by_program"):
            assert sum(d[f].values()) == pytest.approx(d["device_s"])
    assert adm["by_program"] == pytest.approx(
        {"jit_swat_prefill": 1.0, "jit_swat_insert": 0.5})
    assert spans.decode_attention_ms(r) == pytest.approx(1e3 * 1.0 / 4)
    assert r["spans"]["swat.admit"]["args"]["padded_tokens"] == 32
    assert r["spans"]["swat.decode"]["seconds"] == pytest.approx(4.0)
    assert ["%fusion.3", "jit_swat_decode_scan", "attn/ring_write",
            pytest.approx(1.0)] in r["top_ops"]


def test_self_pieces_tile_the_union():
    got = dict(spans.self_pieces([(s, e, op) for s, e, op, *_ in OPS]))
    assert got["%while.2"] == [(1.8, 1.9), (2.9, 3.0), (4.0, 4.4)]
    assert got["%fusion.3"] == [(1.9, 2.9)]
    assert sum(y - x for p in got.values() for x, y in p) == pytest.approx(
        0.7 + 2.6 + 1.0 + 1.0)


HLO = """HloModule jit_swat_decode_scan, is_scheduled=true

%fused_computation.7 (param_0: bf16[4,8]) -> bf16[4,8] {
  %param_0 = bf16[4,8]{1,0} parameter(0)
  %select.1 = bf16[4,8]{1,0} select(%p, %q, %param_0), metadata={op_name="jit(swat_decode_scan)/while/body/attn/ring_write/select_n"}
  ROOT %dynamic-update-slice.2 = bf16[4,8]{1,0} dynamic-update-slice(%select.1, %c), metadata={op_name="jit(swat_decode_scan)/while/body/dynamic_update_slice"}
}

%fused_computation.8 (param_0: f32[4]) -> f32[4] {
  ROOT %exp.3 = f32[4]{0} exponential(%param_0), metadata={op_name="attn/attend/reduce_max;mlp/add"}
}

ENTRY %main.9 (p: bf16[4,8]) -> (bf16[4,8], f32[4]) {
  %fusion.10 = bf16[4,8]{1,0:T(4,128)} fusion(%p), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(swat_decode_scan)/squeeze"}
  %fusion.11 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.8
  %copy.12 = bf16[4,8]{1,0} copy(%fusion.10)
  %dot.13 = f32[4,4]{1,0} dot(%a, %b), metadata={op_name="jit(swat_decode_scan)/while/body/mlp/dot_general"}
  ROOT %tuple.14 = (bf16[4,8]{1,0}, f32[4]{0}) tuple(%copy.12, %fusion.11)
}
"""


def test_hlo_text_gives_each_operation_its_scope():
    module, sc = spans.hlo_scopes(HLO)
    assert module == "jit_swat_decode_scan"
    assert sc[("fusion.10", "bf16[4,8]")] == "attn/ring_write"
    assert sc[("fusion.11", "f32[4]")] == "attn/attend"
    assert sc[("copy.12", "bf16[4,8]")] == "unscoped"
    assert sc[("dot.13", "f32[4,4]")] == "mlp"
    ops = [(1.0, 2.0, "%fusion.10 = bf16[4,8]{1,0:T(4,128)S(1)} fusion("
                      "bf16[4,8]{1,0} %p), kind=kLoop, calls=%fused_computation.7"),
           (2.0, 3.0, "%dot.13 = f32[4,4]{1,0} dot(f32[4,2]{1,0} %a)"),
           (5.0, 6.0, "%dot.13 = f32[4,4]{1,0} dot(f32[4,2]{1,0} %a)")]
    mods = [(0.5, 3.5, "jit_swat_decode_scan"), (4.5, 6.5, "jit_swat_insert")]
    got = spans.label_ops(ops, mods, {module: sc})
    assert [(p, s) for _, _, _, p, s in got] == [
        ("jit_swat_decode_scan", "attn/ring_write"),
        ("jit_swat_decode_scan", "mlp"), ("jit_swat_insert", "unscoped")]
    assert got[0][2] == "%fusion.10 = bf16[4,8]"
    tup = spans.parse_instr("%copy-start.1 = (f32[64]{0:T(128)S(1)}, u32[]{:S(2)})"
                            " copy-start(f32[64]{0} %p)")
    assert (tup["name"], tup["shape"], tup["opcode"]) == (
        "copy-start.1", "(f32[64], u32[])", "copy-start")


class _Run:
    """What a reader sees of a run: the harness spans of the traced part
    and the window's end, on the host clock."""

    def __init__(self, t0, t_end):
        self.traced_spans = [type("S", (), {"t0": t0, "t1": t0 + 1})()]
        self.t_end = t_end


def test_readers_read_the_programs_span_counts():
    t = {"now": -100.0}         # before any monotonic reading of an engine
    tr = T.Tracer(clock=lambda: t["now"])

    def block(n, slots, emitted):
        with tr.span("swat.decode", n=n, slots=slots, live=slots):
            t["now"] += 0.5
            with tr.span("swat.decode.retire", emitted=emitted):
                t["now"] += 0.5

    def admit(prompt, padded):
        with tr.span("swat.admit", rows=1, padded_len=padded,
                     prompt_tokens=prompt, padded_tokens=padded):
            t["now"] += 1.0
    block(8, 4, 32)                 # before the traced part: not counted
    admit(5, 16)
    t0 = t["now"]
    block(8, 4, 30)
    block(2, 4, 5)
    admit(10, 16)
    admit(20, 32)
    run = _Run(t0, t["now"] + 0.1)
    assert decode_slot_use.read(run) == pytest.approx(100.0 * 35 / 40)
    assert prefill_pad_share.read(run) == pytest.approx(100.0 * 18 / 48)


def test_readers_return_none_without_the_programs_spans(monkeypatch):
    run = _Run(1e12, 1e12 + 1)        # no record lies in this window
    assert decode_slot_use.read(run) is None
    assert prefill_pad_share.read(run) is None
    monkeypatch.delattr(T, "live_tracers")   # a program that keeps none
    run = _Run(0.0, 1e12)
    assert decode_slot_use.read(run) is None
    assert prefill_pad_share.read(run) is None


@pytest.mark.parametrize("mix,cell", [(CLOSED, "granite-8b-swat.decode-long"),
                                      (OPEN, "granite-8b-swat.chat")],
                         ids=["closed", "open"])
def test_traced_run_on_the_cpu_reports_the_span_counts(mix, cell):
    """A traced run at smoke size reports each new metric of its cell from
    the program's spans (the device's metrics find no TPU here)."""
    specs = run.metric_specs(BENCH, cell, True)
    mine = [m["name"] for m in specs if m["source"] == "program_counter"
            and m["name"].split(".")[0] in ("decode_slot_use",
                                            "prefill_pad_share")]
    assert mine
    limit = json.loads((ROOT / "bench" / "checks" / f"{cell}.json").read_text())
    checks = dict(CHECKS, widest_logit_gap=limit["widest_logit_gap"])
    out = run.execute(run.Cell("tiny", 1, dense(), mix, checks, specs), 7,
                      2.0, True, device=DEVICE)
    assert out["correct"], out["checks"]
    for name in mine:
        assert 0.0 <= out["metrics"][name]["value"] <= 100.0, name

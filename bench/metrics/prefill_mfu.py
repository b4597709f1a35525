"""Prefill: routed model FLOPs of the prompts admitted in the traced window
over the device time of operations inside `bench.admit` spans, as a share
of the chip's bf16 peak, in %. Routed counts the top-k experts a token
uses, not the every-expert work of dense dispatch."""
import cost


def read(run):
    tr, spans = run.trace, run.traced_spans
    if not tr or not spans:
        return None
    busy = tr["span_busy_s"].get("bench.admit", 0.0)
    flops = sum(cost.prefill_flops(run.cfg, n) for s in spans
                if s.name == "bench.admit" for n in s.info["lengths"])
    if busy <= 0 or flops <= 0:
        return None
    return 100.0 * flops / busy / run.peaks["bf16_flops_per_s"]

"""Whole model step: routed model FLOPs of every token processed in the
window (prompts admitted and output tokens decoded) over the window's
seconds, as a share of the chip's bf16 peak, in %."""
import cost


def read(run):
    flops = 0.0
    for s in run.spans:
        if not (run.t_open < s.t1 <= run.t_end):
            continue
        if s.name == "bench.admit":
            flops += sum(cost.prefill_flops(run.cfg, n) for n in s.info["lengths"])
        elif s.name == "bench.decode_block":
            for p, k in zip(s.info["positions"], s.info["steps"]):
                flops += cost.decode_flops(run.cfg, range(p, p + k))
    if flops <= 0:
        return None
    return 100.0 * flops / (run.t_end - run.t_open) / run.peaks["bf16_flops_per_s"]

"""Decode step: bytes the steps of the traced window need (weights once
per step, each live slot's visible key/value rows and its new row) over
the device time of operations inside `bench.decode_block` spans, as a share
of the chip's HBM bandwidth, in %."""
import cost


def block_bytes(cfg, info):
    total = 0.0
    for step in range(info["n"]):
        live = [p + step for p, k in zip(info["positions"], info["steps"])
                if step < k]
        total += cost.decode_step_bytes(cfg, live)
    return total


def read(run):
    tr, spans = run.trace, run.traced_spans
    if not tr or not spans:
        return None
    busy = tr["span_busy_s"].get("bench.decode_block", 0.0)
    need = sum(block_bytes(run.cfg, s.info) for s in spans
               if s.name == "bench.decode_block")
    if busy <= 0 or need <= 0:
        return None
    return 100.0 * need / busy / run.peaks["hbm_bytes_per_s"]

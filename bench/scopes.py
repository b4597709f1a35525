"""Where a cell's device time goes, by the program's own spans, programs
and named scopes.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> [--out FILE]

Serves the cell as a `run.py --trace 1` run does (weights, engine, every
program shape warmed, the set-up traffic, then `--seconds` of traffic with
the profiler on over the last `run.TRACE_SECONDS`), with the compile cache
off and XLA dumping every `jit_swat_*` program's optimized HLO text, so
that each operation in the trace finds its named scope. Reduces the trace
with `spans.reduce`; checks no tokens (run.py does).

Also measures what the engine's host spans cost with no profiler running
(a decode block's five spans and its block record, timed in a loop on this
host), and the decode blocks' time per step in the traced part of the
window against the part before it: the cost with the profiler on.

Prints tables to stderr and one JSON line to stdout (and to --out). Runs
only on a chip listed in peaks.py; exits 3 with no result otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))


def span_cost_us(iters: int = 20000) -> float:
    """Microseconds per decode block of the engine's span calls with no
    profiler running."""
    from repro.telemetry.tracer import Tracer
    tr = Tracer()
    t = time.perf_counter()
    for _ in range(iters):
        with tr.span("swat.decode", n=8, slots=16, live=16) as blk:
            with tr.span("swat.decode.stage"):
                pass
            with tr.span("swat.decode.dispatch"):
                pass
            with tr.span("swat.decode.sync"):
                pass
            with tr.span("swat.decode.retire", emitted=128):
                pass
        tr.on_block("seq", 8, blk.t0, 128, blk.t1)
    return (time.perf_counter() - t) / iters * 1e6


def step_ms(blocks, lo: float, hi: float, n: int):
    """Median ms per step of the n-step decode blocks starting in [lo, hi)."""
    per = [(s.t1 - s.t0) / n * 1e3 for s in blocks
           if s.info["n"] == n and lo <= s.t0 < hi]
    return (statistics.median(per) if per else None), len(per)


def table(title: str, rows, total: float, log):
    print(f"{title} (s, share of {total:.6f} s)", file=log)
    for k, v in sorted(rows.items(), key=lambda kv: -kv[1]):
        print(f"  {v:12.6f}  {100 * v / total if total else 0:6.2f} %  {k}",
              file=log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    log = sys.stderr
    dump = tempfile.mkdtemp(prefix="scopes_hlo_")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump}",
        "--xla_dump_hlo_as_text", "--xla_dump_hlo_module_re=jit_swat_.*"]))
    import run
    try:
        cell = run.load_cell(a.workload, trace=True)
    except KeyError:
        print(f"unknown workload {a.workload!r}", file=log)
        return 2
    try:
        device = run.device_info(cell.chips)
    except Exception as e:                      # no chip: no result
        print(f"device check failed: {e}", file=log)
        return 3
    import jax
    import numpy as np
    import driver
    import spans
    import traffic
    import weights as W
    jax.config.update("jax_enable_compilation_cache", False)
    off_us = span_cost_us()
    print(f"spans with no profiler: {off_us:.3f} us per decode block", file=log)

    cfg, clock = cell.cfg, time.perf_counter
    rng = np.random.default_rng(W.jax_seed(a.seed))
    w = W.make(cfg, a.seed)
    drv = driver.Driver(cfg, w, W.jax_seed(a.seed))
    plan = traffic.make(cell.mix, a.seed, vocab=cfg["vocab_size"],
                        slots=drv.slots, seconds=a.seconds)
    drv.warm(*traffic.length_bounds(cell.mix["prompt"]), rng)
    trace_dir = tempfile.mkdtemp(prefix="scopes_trace_")
    win = run.serve(drv, plan, a.seconds, trace_dir, clock)
    t_red = clock()
    hlo = spans.load_hlo(Path(dump))
    raw = spans.load(Path(trace_dir), hlo, prefix=("swat.", "bench.window"))
    window = next((s, e) for n, s, e, _ in raw["host"] if n == "bench.window")
    host = [h for h in raw["host"] if h[0].startswith("swat.")]
    red = spans.reduce(raw["devices"], host, window)
    print(f"reduced in {clock() - t_red:.1f} s: programs {sorted(hlo)}; "
          f"device ops {sum(len(v) for v in raw['devices'].values())}; "
          f"host spans {len(host)}", file=log)

    idle = red["window_s"] - red["busy_s"]
    table("device idle by innermost span", red["idle_s"], idle, log)
    print(f"  idle attributed {sum(red['idle_s'].values()):.6f} s, window "
          f"idle {idle:.6f} s", file=log)
    for kind, d in red["inside"].items():
        for f in ("by_top_scope", "by_scope", "by_program"):
            table(f"device self time inside {kind}, {f}", d[f],
                  d["device_s"], log)
        print(f"  {kind}: self time summed {sum(d['by_scope'].values()):.6f}"
              f" s, device time inside {d['device_s']:.6f} s", file=log)
    print("longest idle gaps (innermost span, start in window s, s): "
          f"{red['idle_gaps']}", file=log)
    print("top operations (self s, program, scope, op):", file=log)
    for op, program, scope, t in red["top_ops"]:
        print(f"  {t:10.6f}  {program}  {scope}  {op}", file=log)
    for name, st in sorted(red["spans"].items()):
        print(f"span {name}: {st['count']} spans, {st['seconds']:.6f} s, "
              f"args {st['args']}", file=log)

    blocks = [s for s in drv.spans if s.name == "bench.decode_block"]
    t_traced = min((s.t0 for s in win["traced_spans"]), default=win["t_end"])
    n = drv.scan_steps
    before, nb = step_ms(blocks, win["t_open"], t_traced, n)
    during, nd = step_ms(blocks, t_traced, win["t_end"], n)
    print(f"decode blocks of {n} steps, median ms per step: before the "
          f"trace {before} ({nb} blocks), traced {during} ({nd} blocks)",
          file=log)
    sums = {k: v["args"] for k, v in red["spans"].items()}
    dec, adm = sums.get("swat.decode", {}), sums.get("swat.admit", {})
    emitted = sums.get("swat.decode.retire", {}).get("emitted", 0)
    slot_steps = sum(c["n"] * c["slots"] for name, s, e, c in host
                     if name == "swat.decode"
                     and window[0] <= (s + e) / 2 <= window[1])
    out = {"workload": a.workload, "seed": a.seed, "device": device,
           "span_cost_us_per_block": off_us,
           "step_ms_before_trace": before, "step_ms_traced": during,
           "decode_host_ms": spans.decode_host_ms(red),
           "decode_attention_ms": spans.decode_attention_ms(red),
           "decode_slot_use": (100.0 * emitted / slot_steps
                               if slot_steps else None),
           "prefill_pad_share": (
               100.0 * (1 - adm["prompt_tokens"] / adm["padded_tokens"])
               if adm.get("padded_tokens") else None),
           "decode_steps": dec.get("n", 0),
           "reduction": red}
    line = json.dumps(out)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

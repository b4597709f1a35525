"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of `workloads` in BENCHMARK.json) names a configuration
(`bench/configs/<config>.json`, whose `arch` names its architecture module
`bench/arch/<arch>.py`) and a traffic mix (`bench/traffic/<mix>.json`); its
correctness limits are in `bench/checks/<cell>.json`, and each metric is
read by `bench/metrics/<metric>.py`. Nothing here knows a cell or a model's
layers by name.

Set-up (timed as setup_s, from process start to the window's opening):
weights from the seed, the engine, every program shape the cell's traffic
can reach, and the traffic served before the window (a closed loop's first
wave; an open loop's pre-roll). Then the window: `--seconds` of traffic.
With --trace 1 the profiler records the window's last seconds and the
result carries the per-layer metrics; with --trace 0, the end-to-end ones.
After the window: the device's peak memory, then the engine's state is
freed and the served tokens are checked against the float32 reference.

Runs only on a chip listed in peaks.py; exits 3 with no result otherwise.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse                      # noqa: E402
import dataclasses                   # noqa: E402
import importlib                     # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
import tempfile                      # noqa: E402
from pathlib import Path             # noqa: E402
from typing import Dict, List, Optional   # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

TRACE_SECONDS = 12.0       # the traced part of a --trace 1 window (its end)
DRAIN_SECONDS = 60.0       # open loop: wait for first tokens past the close
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
LAST: dict = {}            # the last execute()'s Run, for sweep.py


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read from one run."""
    cfg: dict
    peaks: dict
    setup_s: float
    t_open: float
    t_close: float
    t_end: float
    due: Dict[int, float]                 # window's requests: rid -> due time
    deliveries: list                      # (rid, host time, tokens)
    spans: list                           # driver.Span, whole run
    admitted_at: Dict[int, float]
    lowered_at: List[float]               # host times of program lowerings
    lateness: List[float]                 # generator: fed time - due time
    trace: Optional[dict] = None          # trace.reduce() of the traced part
    traced_spans: Optional[list] = None   # driver spans inside the trace


def enable_compile_cache():
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; raises unless they are `chips` or
    more TPUs whose kind has published peaks."""
    import jax
    import peaks
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {d0.platform} ({d0.device_kind})")
    if len(devs) < chips:
        raise RuntimeError(f"cell needs {chips} chips, JAX found {len(devs)}")
    peaks.peaks_for(d0.device_kind)
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def reader(name: str):
    """The reader of a metric: `metrics/<name>.py`. A quantity split by the
    end-to-end metric it moves (`device_idle_share.itl`) shares one reader."""
    return importlib.import_module(f"metrics.{name.split('.')[0]}").read


def metric_specs(bench: dict, cell: str, trace: bool) -> List[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Cell:
    """A cell as its files give it: its configuration, traffic mix, checks,
    and the metrics it reports (per-layer when traced, else end-to-end)."""
    name: str
    chips: int
    cfg: dict
    mix: dict
    checks: dict
    specs: List[dict]


def load_cell(name: str, trace: bool = False) -> Cell:
    """The cell `name` of BENCHMARK.json; KeyError if there is none."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return Cell(name, cell["chips"], load_json(ROOT / conf["file"]),
                load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
                load_json(HERE / "checks" / f"{name}.json"),
                metric_specs(bench, name, trace))


def open_device(cell: Cell) -> Optional[dict]:
    """The cell's devices (device_info), with the compile cache enabled;
    None, with the reason on stderr, when they are not there."""
    try:
        device = device_info(cell.chips)
    except Exception as e:                      # no chip: no result
        print(f"device check failed: {e}", file=sys.stderr)
        return None
    enable_compile_cache()
    return device


def serve(drv, plan, seconds: float, trace_dir: Optional[str], clock):
    """Set-up traffic, then the window, then (open loop) the drain. Returns
    the window's bounds and records."""
    import jax
    closed = plan.loop == "closed"
    lateness: List[float] = []
    due: Dict[int, float] = {}
    if closed:
        nxt = [1] * len(plan.clients)
        owner = {}
        for c, reqs in enumerate(plan.clients):
            r = reqs[0]
            drv.submit(r.rid, r.prompt, r.max_new)
            owner[r.rid] = c
            due[r.rid] = clock()
        while drv.pending:
            drv._admit(clock)
        t_open = clock()
    else:
        t_open = clock() + plan.preroll_s
        queue = list(plan.requests)
    t_close = t_open + seconds
    t_trace = t_close - min(seconds, TRACE_SECONDS)
    tracing, window_ann, spans_from = False, None, 0
    t_traced = None
    i = 0
    t_end = None
    while True:
        now = clock()
        if t_end is None and now >= t_close:
            t_end = now
            if window_ann is not None:
                window_ann.__exit__(None, None, None)
        if trace_dir and not tracing and now >= t_trace:
            jax.profiler.start_trace(trace_dir)
            window_ann = jax.profiler.TraceAnnotation("bench.window")
            window_ann.__enter__()
            tracing, spans_from, t_traced = True, len(drv.spans), clock()
        if t_end is not None:
            if closed:
                break
            waiting = [r for r in due if r not in drv.admitted_at]
            if not waiting or now > t_end + DRAIN_SECONDS:
                break
        if not closed:
            while i < len(queue) and t_open + queue[i].due <= now:
                r = queue[i]
                d = t_open + r.due
                drv.submit(r.rid, r.prompt, r.max_new)
                lateness.append(now - d)
                if d >= t_open:
                    due[r.rid] = d
                i += 1
        busy = drv.step(clock)
        if closed and t_end is None:
            for rid in [r for r in list(owner) if r in drv.results]:
                c = owner.pop(rid)
                if nxt[c] < len(plan.clients[c]):
                    r = plan.clients[c][nxt[c]]
                    nxt[c] += 1
                    drv.submit(r.rid, r.prompt, r.max_new)
                    owner[r.rid] = c
                    due[r.rid] = clock()
        if not busy and not drv.pending:
            until = now + 0.001
            if not closed and i < len(queue):
                until = t_open + queue[i].due
            if t_end is None:
                until = min(until, t_close)
            drv.idle(until, clock)
    if tracing:
        jax.profiler.stop_trace()
    traced = None
    if tracing:
        traced = [s for s in drv.spans[spans_from:]
                  if t_traced <= (s.t0 + s.t1) / 2 <= t_end]
    return {"t_open": t_open, "t_close": t_close, "t_end": t_end, "due": due,
            "lateness": lateness, "traced_spans": traced}


def execute(cell: Cell, seed: int, seconds: float, trace: bool, *,
            device: dict, control: bool = False, verify: bool = True,
            log=sys.stderr) -> dict:
    """One run of `cell`; the result line as a dict. With `control`, the
    control (check.py) is read too and stands in the engine's place in
    `correct`: a sound control run is not correct."""
    import jax
    import numpy as np
    import arch
    import check
    import driver
    import peaks
    import traffic
    import devtrace as tr
    import weights as W

    cfg, mix, checks = cell.cfg, cell.mix, cell.checks
    clock = time.perf_counter
    lowered: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _d, **_k: lowered.append(clock()) if name == LOWERED else None)
    rng = np.random.default_rng(W.jax_seed(seed))
    w = W.make(cfg, seed)
    jax.block_until_ready(w)
    t_w = clock()
    drv = driver.Driver(cfg, w, W.jax_seed(seed))
    for d in arch.load(cfg).departures(cfg):
        print(f"note: program config departs from file: {d}", file=log)
    plan = traffic.make(mix, seed, vocab=cfg["vocab_size"], slots=drv.slots,
                        seconds=seconds)
    lo, hi = traffic.length_bounds(mix["prompt"])
    t_e = clock()
    shapes = drv.warm(lo, hi, rng)
    t_warm = clock()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        win = serve(drv, plan, seconds, trace_dir, clock)
        kind = device["kind"]
        pk = peaks.PEAKS.get(kind, {})
        stats = [d.memory_stats() or {} for d in jax.devices()[:device["count"]]]
        mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        tsum = None
        if trace:
            t_tr = clock()
            raw = tr.load(Path(trace_dir))
            if raw is None:
                raise RuntimeError("the profiler wrote no trace")
            print(f"trace: read in {clock() - t_tr:.3f} s; device ops "
                  f"{ {k: len(v) for k, v in raw['devices'].items()} }; host "
                  f"spans {len(raw['host'])}; planes {json.dumps(raw['planes'])}",
                  file=log)
            wins = [(a, b) for n, a, b in raw["host"] if n == "bench.window"]
            host = [h for h in raw["host"] if h[0] != "bench.window"]
            if not wins:
                raise RuntimeError("no bench.window span in the trace")
            tsum = tr.reduce(raw["devices"], host, wins[0])
            print(f"trace: longest idle gaps (span, start in window s, s): "
                  f"{tsum['idle_gaps_at']}", file=log)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = win["t_open"] - T_PROCESS
    run = Run(cfg=cfg, peaks=pk, setup_s=setup_s,
              t_open=win["t_open"], t_close=win["t_close"], t_end=win["t_end"],
              due=win["due"], deliveries=list(drv.deliveries),
              spans=list(drv.spans), admitted_at=dict(drv.admitted_at),
              lowered_at=lowered, lateness=win["lateness"], trace=tsum,
              traced_spans=win["traced_spans"])
    LAST["run"] = run
    metrics, missing = {}, []
    for m in cell.specs:
        val = reader(m["name"])(run)
        if val is None:
            missing.append(m["name"])
        else:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    results, prompts = dict(drv.results), dict(drv.prompts)
    drv.close()
    del drv
    window = set(win["due"])
    failed = sum(1 for r in window
                 if (r in results and results[r][0] != "ok")
                 or (plan.loop == "open" and r not in run.admitted_at))
    t_ref = clock()
    rids = check.sample({r: v for r, v in results.items() if r >= 0}, rng,
                        checks["sample_tokens"], checks["sample_requests"],
                        checks.get("sample_min_requests", 1))
    if not verify:
        rids = []
    cmp = check.compare(w, cfg, prompts, results, rids, control=control,
                        router_margin=checks.get("router_margin"))
    ref_s = clock() - t_ref
    limit = checks["widest_logit_gap"]
    min_tokens = checks.get("min_tokens_compared", 1)
    gap = cmp["control_widest_logit_gap" if control else "widest_logit_gap"]
    lines = {"widest_logit_gap": {"value": gap, "limit": limit},
             "failed_requests": {"value": failed, "limit": 0},
             "requests_compared": {"value": cmp["requests_compared"], "limit": 1},
             "tokens_compared": {"value": cmp["tokens_compared"], "limit": min_tokens}}
    correct = (gap <= limit and failed == 0
               and cmp["requests_compared"] >= 1
               and cmp["tokens_compared"] >= min_tokens)
    import tails
    gaps = tails.itl_gaps(run.deliveries, run.t_open, run.t_end)
    tt = tails.ttfts(run.deliveries, run.due)
    print("window: itl ms p50/p90/p95/p99 " + "/".join(
        f"{(tails.percentile(gaps, q) or 0) * 1e3:.2f}" for q in (50, 90, 95, 99))
        + f" over {len(gaps)} gaps; ttft s p50/p90/p99 " + "/".join(
        f"{tails.percentile(tt, q) or 0:.4f}" for q in (50, 90, 99))
        + f" over {len(tt)} requests", file=log)
    late = sorted(run.lateness)
    print(f"setup: start {t_w - T_PROCESS:.3f} s to weights, engine "
          f"{t_e - t_w:.3f} s, {shapes} program shapes warmed in "
          f"{t_warm - t_e:.3f} s, traffic before the window "
          f"{win['t_open'] - t_warm:.3f} s; setup_s {setup_s:.3f}", file=log)
    print(f"generator lateness: n {len(late)} p50 "
          f"{late[len(late) // 2] if late else 0:.6f} s max "
          f"{late[-1] if late else 0:.6f} s", file=log)
    print(f"reference: {cmp['tokens_compared']} tokens of "
          f"{cmp['requests_compared']} requests in {ref_s:.3f} s "
          f"({cmp['tokens_left_out']} left out at router near-ties); logit "
          f"spread {cmp['logit_spread']:.4f}", file=log)
    if control:
        print(f"control: the fp8 reference in the engine's place; engine's "
              f"own widest gap {cmp['widest_logit_gap']}", file=log)
    if missing:
        print(f"metrics with nothing to read: {missing}", file=log)
    dev = dict(device, memory_peak_bytes=mem_peak)
    out = {"correct": bool(correct), "attempted": len(window), "failed": failed,
           "metrics": metrics, "device": dev}
    if tsum is not None:
        dev["busy_s"] = tsum["busy_s"]
        dev["window_s"] = tsum["window_s"]
        out["breakdown"] = {"device_ops": tsum["device_ops"],
                            "idle_gaps": tsum["idle_gaps"]}
    out["compared"] = cmp
    out["window"] = {"backlog_at_close": sum(
        1 for r, d in run.due.items()
        if d < run.t_close and run.admitted_at.get(r, run.t_end + 1) > run.t_end)}
    out["checks"] = lines
    for name, v in lines.items():
        print(f"check {name}: {v['value']} limit {v['limit']}", file=log)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        cell = load_cell(a.workload, bool(a.trace))
    except KeyError:
        print(f"unknown workload {a.workload!r}", file=sys.stderr)
        return 2
    device = open_device(cell)
    if device is None:
        return 3
    out = execute(cell, a.seed, a.seconds, bool(a.trace), device=device)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

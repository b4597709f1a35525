"""Reduction of a profiler trace to device busy time, idle gaps and the
device time spent inside the harness's own host spans.

The JAX profiler writes `<dir>/plugins/profile/<run>/<host>.xplane.pb`.
Device planes are named `/device:TPU:<n>`; their "XLA Ops" line holds one
event per executed operation. The harness's `TraceAnnotation`s (`bench.*`)
are events on the host plane's Python thread, on the same clock. All
arithmetic below works on plain lists of intervals, so it is tested on
synthetic traces.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: List[Interval], a: float, b: float) -> float:
    """Length of [a, b] covered by sorted disjoint intervals."""
    if b <= a or not merged:
        return 0.0
    i = max(0, bisect.bisect_right([x for x, _ in merged], a) - 1)
    total = 0.0
    for x, y in merged[i:]:
        if x >= b:
            break
        total += max(0.0, min(y, b) - max(x, a))
    return total


def gaps(merged: List[Interval], a: float, b: float) -> List[Interval]:
    """Sub-intervals of [a, b] covered by none of the merged intervals."""
    out, cur = [], a
    for x, y in merged:
        if y <= cur:
            continue
        if x >= b:
            break
        if x > cur:
            out.append((cur, min(x, b)))
        cur = max(cur, y)
    if cur < b:
        out.append((cur, b))
    return out


def short_name(op: str) -> str:
    """An HLO instruction's name and result type, without its layout and
    operands: '%copy.9 = bf16[24,48,8,2240,64]{...} copy(...)' ->
    '%copy.9 = bf16[24,48,8,2240,64]'."""
    return op.split("{", 1)[0].split(" fusion(", 1)[0].strip()[:120]


def self_times(ops: List[Tuple[float, float, str]]) -> List[Tuple[str, float]]:
    """Each operation's time less the time of the operations nested in it
    (a while loop holds its body's operations on the same line)."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []          # [end, name, self time]
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            end, n, t = stack.pop()
            out.append((n, t))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, short_name(name), e - s])
    out += [(n, t) for _, n, t in stack]
    return out


def reduce(devices: Dict[str, List[Tuple[float, float, str]]],
           host: List[Tuple[str, float, float]], window: Interval) -> dict:
    """devices: per device, (start, end, op name) in seconds; host: the
    harness's spans (name, start, end); window: the traced window.
    Returns busy and window seconds (busy averaged over devices), device
    busy seconds inside each kind of host span, the ten operations that took
    most device time and the ten longest idle gaps labelled by the host span
    that holds their midpoint."""
    a, b = window
    per_dev = {d: merge([(s, e) for s, e, _ in ops]) for d, ops in devices.items()}
    ndev = max(1, len(per_dev))
    busy = sum(covered(m, a, b) for m in per_dev.values()) / ndev
    span_busy: Dict[str, float] = defaultdict(float)
    for name, s, e in host:
        if e <= a or s >= b:
            continue
        span_busy[name] += sum(covered(m, max(s, a), min(e, b))
                               for m in per_dev.values()) / ndev
    op_time: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        for name, t in self_times([o for o in ops if a <= o[0] < b]):
            op_time[name] += t / ndev
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    first = next(iter(per_dev.values()), [])
    spans_sorted = sorted(host, key=lambda h: h[1])
    starts = [h[1] for h in spans_sorted]

    def label(t):
        # the harness's spans run one after another on one thread, so only
        # the last one to start before t can hold it
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and spans_sorted[i][2] >= t:
            return spans_sorted[i][0]
        return "none"

    idle = sorted(gaps(first, a, b), key=lambda g: g[0] - g[1])[:10]
    return {"busy_s": busy, "window_s": b - a,
            "span_busy_s": dict(span_busy),
            "device_ops": [[n, t] for n, t in top_ops],
            "idle_gaps": [[label((x + y) / 2), y - x] for x, y in idle],
            "idle_gaps_at": [[label((x + y) / 2), x - a, y - x] for x, y in idle],
            "devices": len(per_dev)}


def _ops_line(plane):
    lines = list(plane.lines)
    for ln in lines:
        if ln.name == "XLA Ops":
            return ln
    cands = [ln for ln in lines if "Ops" in ln.name and "Framework" not in ln.name]
    return cands[0] if cands else None


def load(trace_dir: Path, prefix: str = "bench.") -> Optional[dict]:
    """Read the newest xplane file under trace_dir: device ops per TPU plane
    and the harness's host spans, in seconds on the profiler's clock."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    pd = ProfileData.from_file(str(files[-1]))
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[str, float, float]] = []
    planes = []
    for plane in pd.planes:
        lines = list(plane.lines)
        planes.append([plane.name, [ln.name for ln in lines][:12]])
        if plane.name.startswith("/device:TPU:"):
            ln = _ops_line(plane)
            if ln is not None:
                devices[plane.name] = [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(prefix):
                        host.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    return {"devices": devices, "host": host, "planes": planes}

"""The program's own spans, programs and named scopes in a profiler trace.

The serving engine marks its layer boundaries with host spans (`swat.plan`,
`swat.admit`, `swat.decode` and their children, each a
`jax.profiler.TraceAnnotation` with integer arguments), names every compiled
program (`jit_swat_decode_scan`, `jit_swat_prefill`, ...) and tags the
model's operations with `jax.named_scope` (`attn/ring_write`, `mlp`, ...).

A TPU trace gives each operation only its HLO text and its times: the
program is the "XLA Modules" event that holds it on the same device line,
and the scope is the `op_name` metadata of the compiled program's HLO text,
which `load` reads from an XLA dump (`--xla_dump_to`, `--xla_dump_hlo_as_text`).
A fusion takes its root's scope, or else the scope most of its fused
instructions share; an operation with no scope (a copy XLA inserted, a loop)
is `unscoped`.

`reduce` attributes device idle time to the innermost host span, and device
self time to scopes and programs inside `swat.decode` and `swat.admit`.
`program_spans` reads the same spans from the engine's own records, for
readers of program counters. The arithmetic works on plain lists, so it is
tested on synthetic traces (tests/test_spans.py).
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from devtrace import merge, short_name

Interval = Tuple[float, float]
# the model's named scopes (core/model.py, core/layers.py, kernels/ops.py,
# serving/sampling.py); other components of an op_name are JAX's own
SCOPES = frozenset({"embed", "attn", "qkv", "ring_write", "attend", "out",
                    "mlp", "moe", "unembed", "sample", "mamba", "xattn"})
UNSCOPED = "unscoped"
LAYER_SPANS = ("swat.decode", "swat.admit")

_INSTR = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+) = ")
_HEADER = re.compile(r"^(ENTRY\s+)?%([\w.\-]+) .*\{\s*$")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def scope_path(op_name: str) -> str:
    """'jit(swat_decode_scan)/while/body/attn/ring_write/select_n' ->
    'attn/ring_write': the named scopes an op_name passes through. XLA joins
    the names of instructions it merged with ';': the first one counts."""
    parts = [p for p in op_name.split(";")[0].split("/") if p in SCOPES]
    return "/".join(parts) or UNSCOPED


def parse_instr(line: str) -> Optional[dict]:
    """One HLO instruction, as compiled text or a TPU trace event prints it:
    name, result type without layouts, opcode, the computation it calls,
    its op_name and whether it is its computation's root."""
    m = _INSTR.match(line)
    if not m:
        return None
    rest = line[m.end():]
    if rest.startswith("("):                  # tuple result type
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 2:]
    else:
        shape, _, rest = rest.partition(" ")
    calls = re.search(r"calls=%([\w.\-]+)", rest)
    op = re.search(r'op_name="([^"]*)"', rest)
    return {"name": m.group(2), "shape": _LAYOUT.sub("", shape),
            "opcode": rest.split("(", 1)[0],
            "calls": calls.group(1) if calls else None,
            "op_name": op.group(1) if op else "", "root": bool(m.group(1))}


def hlo_scopes(text: str) -> Tuple[str, Dict[Tuple[str, str], str]]:
    """A compiled module's name and the scope of each instruction, keyed by
    (name, result type)."""
    module = (text.split(None, 2)[1].rstrip(",")
              if text.startswith("HloModule") else "")
    comps: Dict[str, List[dict]] = defaultdict(list)
    cur = None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h and not line.startswith(" "):
            cur = h.group(2)
            continue
        ins = parse_instr(line)
        if ins is not None and cur is not None:
            comps[cur].append(ins)

    def fused_scope(comp: str) -> str:
        inner = comps.get(comp, [])
        for ins in inner:
            if ins["root"] and scope_path(ins["op_name"]) != UNSCOPED:
                return scope_path(ins["op_name"])
        votes = Counter(s for s in (scope_path(i["op_name"]) for i in inner)
                        if s != UNSCOPED)
        return votes.most_common(1)[0][0] if votes else UNSCOPED

    out = {}
    for inner in comps.values():
        for ins in inner:
            scope = (fused_scope(ins["calls"]) if ins["opcode"] == "fusion"
                     and ins["calls"] else scope_path(ins["op_name"]))
            out[(ins["name"], ins["shape"])] = scope
    return module, out


def load_hlo(dump_dir: Path) -> Dict[str, Dict[Tuple[str, str], str]]:
    """Scopes of every optimized module in an XLA text dump, by module name.
    Modules of one name (one program at several shapes) share a map; where
    they disagree on an instruction, the scope most of them give wins."""
    votes: Dict[str, Dict[Tuple[str, str], Counter]] = defaultdict(
        lambda: defaultdict(Counter))
    for path in sorted(Path(dump_dir).glob("*after_optimizations.txt")):
        module, scopes = hlo_scopes(path.read_text())
        for key, scope in scopes.items():
            votes[module][key][scope] += 1
    return {m: {k: c.most_common(1)[0][0] for k, c in keys.items()}
            for m, keys in votes.items()}


class Cover:
    """Sorted disjoint intervals, asked how much of [a, b] they cover."""

    def __init__(self, merged: List[Interval]):
        self.merged = merged
        self.starts = [x for x, _ in merged]

    def __call__(self, a: float, b: float) -> float:
        if b <= a:
            return 0.0
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        total = 0.0
        for x, y in self.merged[i:]:
            if x >= b:
                break
            total += max(0.0, min(y, b) - max(x, a))
        return total


def _module_of(modules: List[Tuple[float, float, str]]):
    """A lookup from a time to the program running on the device then."""
    starts = [m[0] for m in modules]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and modules[i][1] >= t:
            return modules[i][2]
        return "none"
    return find


def label_ops(ops: List[Tuple[float, float, str]],
              modules: List[Tuple[float, float, str]],
              hlo: Dict[str, Dict[Tuple[str, str], str]]
              ) -> List[Tuple[float, float, str, str, str]]:
    """Each operation (start, end, HLO text) with its program, from the
    module running when it started, and its scope, from that program's
    compiled text (UNSCOPED when the text or the instruction is missing)."""
    find = _module_of(sorted(modules))
    out = []
    for s, e, text in ops:
        program = find(s)
        ins = parse_instr(text)
        scope = UNSCOPED
        if ins is not None:
            scope = hlo.get(program, {}).get((ins["name"], ins["shape"]),
                                             UNSCOPED)
        out.append((s, e, short_name(text), program, scope))
    return out


def load(trace_dir: Path, hlo: Optional[dict] = None,
         prefix=("swat.",)) -> Optional[dict]:
    """The newest xplane under trace_dir: host spans whose names start with
    a prefix (name, start, end, arguments) and, per TPU, its operations
    (start, end, short name, program, scope), in seconds on the profiler's
    clock. `hlo`: `load_hlo` of the programs' dump."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    pd = ProfileData.from_file(str(files[-1]))
    host, devices = [], {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            modules = [(e.start_ns * 1e-9, e.end_ns * 1e-9,
                        e.name.split("(", 1)[0])
                       for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            ops = [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                   for e in lines["XLA Ops"].events]
            devices[plane.name] = label_ops(ops, modules, hlo or {})
        elif plane.name.startswith("/host:"):
            for ln in lines.values():
                for e in ln.events:
                    if e.name.startswith(tuple(prefix)):
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.end_ns * 1e-9, dict(e.stats)))
    return {"host": host, "devices": devices}


def self_pieces(ops: List[Tuple[float, float, object]]
                ) -> List[Tuple[object, List[Interval]]]:
    """Each operation's own intervals: its span less those of the
    operations nested in it (a loop holds its body's operations on the
    same line). The pieces of all operations tile their union."""
    out: List[Tuple[object, List[Interval]]] = []
    stack: List[list] = []          # [end, key, cursor, pieces]

    def close(top):
        end, key, cur, pieces = top
        if end > cur:
            pieces.append((cur, end))
        out.append((key, pieces))

    for s, e, key in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            par = stack[-1]
            if s > par[2]:
                par[3].append((par[2], s))
            par[2] = max(par[2], min(e, par[0]))
        stack.append([e, key, s, []])
    while stack:
        close(stack.pop())
    return out


def innermost(host: List[tuple], a: float, b: float
              ) -> List[Tuple[float, float, Optional[str]]]:
    """[a, b] cut where host spans open and close, each piece labelled by
    the innermost span open over it (None outside every span). Spans of one
    thread nest, so the innermost is the one opened last."""
    pts = []
    for i, (name, s, e, *_) in enumerate(host):
        s, e = max(s, a), min(e, b)
        if e > s:
            pts.append((s, 1, -e, i))
            pts.append((e, 0, 0, i))
    pts.sort()
    out, stack, cur = [], [], a
    for t, opening, _, i in pts:
        if t > cur:
            out.append((cur, t, host[stack[-1]][0] if stack else None))
            cur = t
        if opening:
            stack.append(i)
        else:
            stack.remove(i)
    if cur < b:
        out.append((cur, b, None))
    return out


def reduce(devices: Dict[str, list], host: List[tuple],
           window: Interval) -> dict:
    """devices: per device, (start, end, op, program, scope); host: the
    program's spans (name, start, end, arguments); window: the traced
    window. Returns, averaged over devices:

    idle_s        device idle seconds by the innermost span kind over them
                  ("outside" where no span is open); they sum to the
                  window's idle time
    inside        for swat.decode and swat.admit: device seconds inside
                  their spans, and the operations' self seconds in that
                  time by scope, by top scope and by program (each sums to
                  the device seconds)
    spans         per span kind with its middle in the window: count,
                  seconds, and each argument summed
    top_ops       the ten operations with most self time in the window,
                  with program and scope
    idle_gaps     the ten longest idle gaps of the first device: the
                  innermost span over each one's middle, its start in the
                  window and its length
    """
    a, b = window
    ndev = max(1, len(devices))
    busy, idle = 0.0, defaultdict(float)
    pieces = innermost(host, a, b)
    inside = {k: {"device_s": 0.0, "by_scope": defaultdict(float),
                  "by_top_scope": defaultdict(float),
                  "by_program": defaultdict(float)} for k in LAYER_SPANS}
    kinds = {k: Cover(merge([(max(s, a), min(e, b)) for n, s, e, *_ in host
                             if n == k])) for k in LAYER_SPANS}
    op_time = defaultdict(float)
    longest: List[list] = []
    for ops in devices.values():
        dev = Cover(merge([(s, e) for s, e, *_ in ops]))
        if not longest:
            longest = _longest_gaps(dev.merged, pieces, a, b)
        busy += dev(a, b) / ndev
        for s, e, name in pieces:
            idle[name or "outside"] += (e - s - dev(s, e)) / ndev
        for k, cov in kinds.items():
            inside[k]["device_s"] += sum(dev(s, e)
                                         for s, e in cov.merged) / ndev
        for (op, program, scope), own in self_pieces(
                [(s, e, (op, program, scope)) for s, e, op, program, scope
                 in ops]):
            op_time[(op, program, scope)] += sum(
                max(0.0, min(y, b) - max(x, a)) for x, y in own) / ndev
            for k, cov in kinds.items():
                t = sum(cov(x, y) for x, y in own) / ndev
                if t > 0:
                    d = inside[k]
                    d["by_scope"][scope] += t
                    d["by_top_scope"][scope.split("/")[0]] += t
                    d["by_program"][program] += t
    stats: Dict[str, dict] = {}
    for name, s, e, args in host:
        if not a <= (s + e) / 2 <= b:
            continue
        st = stats.setdefault(name, {"count": 0, "seconds": 0.0, "args": {}})
        st["count"] += 1
        st["seconds"] += e - s
        for k, v in args.items():
            if isinstance(v, int):
                st["args"][k] = st["args"].get(k, 0) + v
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": b - a, "busy_s": busy, "idle_s": dict(idle),
            "inside": {k: {"device_s": d["device_s"],
                           **{f: dict(d[f]) for f in
                              ("by_scope", "by_top_scope", "by_program")}}
                       for k, d in inside.items()},
            "spans": stats,
            "top_ops": [[op, program, scope, t]
                        for (op, program, scope), t in top],
            "idle_gaps": longest}


def _longest_gaps(merged: List[Interval], pieces, a: float, b: float,
                  k: int = 10) -> List[list]:
    """The k longest stretches of [a, b] outside the merged intervals, as
    [innermost span over the middle, start - a, length]."""
    out, cur = [], a
    for x, y in merged + [(b, b)]:
        x, y = max(x, a), min(y, b)
        if x > cur:
            out.append((cur, x))
        cur = max(cur, y)
    out = sorted(out, key=lambda g: g[0] - g[1])[:k]
    starts = [p[0] for p in pieces]

    def label(t):
        i = bisect.bisect_right(starts, t) - 1
        return (pieces[i][2] if i >= 0 and pieces[i][1] >= t else None) \
            or "outside"
    return [[label((x + y) / 2), x - a, y - x] for x, y in out]


def decode_host_ms(red: dict) -> Optional[float]:
    """Device-idle milliseconds inside `swat.decode` spans (any of its
    children innermost), per decode block."""
    n = red["spans"].get("swat.decode", {}).get("count", 0)
    if not n or not red["idle_s"]:
        return None
    idle = sum(t for k, t in red["idle_s"].items()
               if k == "swat.decode" or k.startswith("swat.decode."))
    return 1e3 * idle / n


def decode_attention_ms(red: dict) -> Optional[float]:
    """Device self milliseconds under scope `attn` inside `swat.decode`
    spans, per decode step."""
    steps = red["spans"].get("swat.decode", {}).get("args", {}).get("n", 0)
    if not steps or not red["inside"]["swat.decode"]["device_s"]:
        return None
    attn = red["inside"]["swat.decode"]["by_top_scope"].get("attn", 0.0)
    return 1e3 * attn / steps


def program_spans(run, name: str) -> Optional[List[tuple]]:
    """The engine's own records (name, t0, t1, counts) of spans `name`
    whose middle lies in the traced part of the window, on the host clock
    the harness reads too. None when the program keeps no such records."""
    try:
        from repro.telemetry.tracer import live_tracers
    except ImportError:
        return None
    if not run.traced_spans:
        return None
    lo, hi = min(s.t0 for s in run.traced_spans), run.t_end
    return [r for tr in live_tracers() for r in tr.spans
            if r[0] == name and lo <= (r[1] + r[2]) / 2 <= hi]

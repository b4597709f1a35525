"""Kernel profiling hooks (swatscope layer 3) — opt-in, never hot-path.

Two tools that feed the shape-adaptive-dispatch roadmap item with real
data instead of guesswork:

  dispatch census   `kernels/ops.py` / `swat_decode.py` call
                    `record_dispatch()` at TRACE time when the census is
                    enabled — jit traces once per shape, so the census is
                    a complete (shape -> dispatch count) map of what the
                    engine actually compiled, at zero runtime cost (the
                    compiled program is byte-identical; nothing executes
                    per step).
  analytic roofline `banded_decode_cost()` — FLOPs over the logical
                    banded geometry (window + globals + lookahead, the
                    paper's O(window) argument) and HBM bytes over the
                    physical ring rows a decode step actually touches;
                    intensity = flops/bytes locates each shape on the
                    roofline.

Times come from a device trace of the served path (the engine's
`swat.*` host spans and named scopes), not from here.

Census state is module-global and OFF by default — `enable_census()` in
a `try/finally` like `faults.install_kernel_failure`.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

_CENSUS_ON = False
_CENSUS: Dict[Tuple, Dict[str, Any]] = {}


def enable_census(on: bool = True) -> None:
    """Start (or stop) recording kernel dispatch traces. Trace-time only:
    enabling this never changes a compiled program or adds runtime work."""
    global _CENSUS_ON
    _CENSUS_ON = on


def census_enabled() -> bool:
    return _CENSUS_ON


def record_dispatch(**fields) -> None:
    """Record one kernel trace event (deduped by field tuple; `traces`
    counts how many times jit traced this exact shape)."""
    if not _CENSUS_ON:
        return
    key = tuple(sorted((k, repr(v)) for k, v in fields.items()))
    rec = _CENSUS.get(key)
    if rec is None:
        _CENSUS[key] = {**fields, "traces": 1}
    else:
        rec["traces"] += 1


def consume_census() -> List[Dict[str, Any]]:
    """Drain the census (insertion order)."""
    out = list(_CENSUS.values())
    _CENSUS.clear()
    return out


# ------------------------------------------------------------- roofline ---

def banded_decode_cost(*, b: int, h_q: int, h_kv: int, t: int, d: int,
                       window: int, num_global: int = 0,
                       cap: Optional[int] = None,
                       dtype_bytes: int = 2,
                       fused: bool = True) -> Dict[str, float]:
    """Analytic cost of one T-token banded decode step.

    FLOPs count the LOGICAL band each query row attends — min(cap,
    window + globals + T) rows for sparse specs, the whole cap for dense
    (window=0 means dense here) — with 2*d per QK and AV MAC plus ~4 ops
    per softmax cell. Bytes count the PHYSICAL traffic: both ring caches
    streamed once (cap rows), q read, out written, and the fused
    insert's T new K/V rows written back. intensity (flops/byte) tells
    you which side of the roofline ridge the shape sits on — decode is
    classically bandwidth-bound, which is why the fused kernel's single
    cache pass is the whole game."""
    assert cap is not None and cap >= 1
    band = min(cap, window + num_global + t) if window else cap
    q_rows = b * h_q * t
    flops = q_rows * band * (4 * d + 4)
    bytes_ = (2 * b * h_kv * cap * d * dtype_bytes      # K+V stream
              + q_rows * d * dtype_bytes                # q read
              + q_rows * d * dtype_bytes)               # out write
    if fused:
        bytes_ += 2 * b * h_kv * t * d * dtype_bytes    # ring insert
    return {"flops": float(flops), "hbm_bytes": float(bytes_),
            "intensity": float(flops) / float(bytes_), "band_rows": band}

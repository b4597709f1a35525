"""swatscope: hot-path-safe observability for the serving stack.

Three layers, one contract — telemetry must never change what the hot
path computes or how it runs:

  device   `metrics.py` — a tiny int32 counter pytree carried through the
           decode / spec-verify scan bodies (tokens emitted, drafts
           proposed/accepted, guard-quarantine trips, ring wraps). Pure
           additions to the carry, donated like the ring caches, never
           read inside a block: the steady-state transfer_guard and the
           collective-free slot-parallel proof hold with metrics on, and
           tokens are bitwise identical to a metrics-off engine
           (tests/test_telemetry.py). swatlint's `metrics_on` engine +
           `telemetry` rule family pin this statically.
  host     `tracer.py` + `events.py` — a ring-buffered Tracer recording
           per-Request lifecycle spans (submit -> queued -> admitted ->
           prefill -> decode blocks -> done/degraded) with TTFT / TPOT /
           queue-delay, the engine's `swat.*` host spans (profiler
           annotations on the device trace's clock, with their counts),
           fed by the unified degradation-event bus that
           `serving/faults.py` now delegates to (one event stream, not
           two). Exports Chrome-trace JSON and a Prometheus-style text
           exposition (`ServingEngine.metrics_text()`).
  kernel   `kernelprof.py` — opt-in dispatch census (trace-time, zero
           runtime cost) + analytic FLOP/byte roofline from the banded
           decode geometry; the data feed for the shape-adaptive-dispatch
           roadmap item. Device times come from a profiler trace of the
           served path, read by the engine's named scopes and programs.

`validate.py` schema-checks the exported artifacts (the CI metrics lane);
`repro.launch.scope` pretty-prints a live engine snapshot.
"""
from repro.telemetry import events, kernelprof, metrics, tracer, validate
from repro.telemetry.events import (consume_events, peek_events,
                                    record_event)
from repro.telemetry.metrics import (COUNTER_DOC, init_metrics,
                                     metrics_shardings, ring_modulus,
                                     seq_update, spec_update)
from repro.telemetry.tracer import Tracer

__all__ = [
    "events", "kernelprof", "metrics", "tracer", "validate",
    "record_event", "consume_events", "peek_events",
    "COUNTER_DOC", "init_metrics", "metrics_shardings", "ring_modulus",
    "seq_update", "spec_update", "Tracer",
]

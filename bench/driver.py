"""The engine adapter: with the architecture modules' `program`, the only
code of the benchmark that touches the program (`repro`, under the
checkout's `src/`).

It drives `repro.serving.engine.ServingEngine` with the body of its own
`run()` loop -- `_expire_deadlines`, the `_admit` loop (one scheduler plan
and one `_prefill_into` at a time), `_block_len`, `_decode_block` and
`take_completed` -- so that requests can be fed in when they are due (an
open loop) and every admission and decode block is timed on the host clock.
These are private methods: a public step-level entry point in the engine
would let this file stop reaching inside it.

Each admission and decode block, and each wait for the next arrival, is
wrapped in a `jax.profiler.TraceAnnotation` (`bench.admit`,
`bench.decode_block`, `bench.idle_wait`): the layer spans of a traced run.

The program's config and parameter tree come from the configuration's
architecture module (`program`, arch/__init__.py). A configuration passes
only what a deployment sizes (`serving`: slots, max_len, pad_to,
max_prefill_tokens); every implementation choice of the engine stays at its
default.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax                                   # noqa: E402
import numpy as np                           # noqa: E402

import arch                                 # noqa: E402
from repro.serving.engine import Request, ServingEngine           # noqa: E402

SERVING_KEYS = ("batch_slots", "max_len", "pad_to", "max_prefill_tokens")
ANNOTATE = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    info: dict


class Driver:
    def __init__(self, cfg: dict, weights: dict, seed: int):
        self.cfg = cfg
        self.mcfg, params = arch.load(cfg).program(cfg, weights)
        sv = {k: cfg["serving"][k] for k in SERVING_KEYS if k in cfg["serving"]}
        self.eng = ServingEngine(self.mcfg, params, seed=seed, **sv)
        self.pending: collections.deque = collections.deque()
        self.spans: List[Span] = []
        # (rid, host time, tokens delivered at that time)
        self.deliveries: List[Tuple[int, float, int]] = []
        self.admitted_at: Dict[int, float] = {}   # rid -> admit call start
        self.results: Dict[int, Tuple[str, List[int]]] = {}
        self.prompts: Dict[int, np.ndarray] = {}

    # ------------------------------------------------------------ shapes --
    @property
    def slots(self) -> int:
        return self.eng.slots

    @property
    def scan_steps(self) -> int:
        return self.eng.scan_steps

    def prefill_shapes(self, lo: int, hi: int) -> List[Tuple[int, int]]:
        """Every (rows, padded length) batch the scheduler can form from
        prompts of lo..hi tokens: lengths pad up to a multiple of pad_to,
        and more than one row only while rows * padded <= max_prefill_tokens."""
        sch = self.eng.scheduler
        out = []
        for l_pad in range(sch._bucket(lo), sch._bucket(hi) + 1, sch.pad_to):
            rows = max(1, min(self.slots, sch.max_prefill_tokens // l_pad))
            out += [(r, l_pad) for r in range(1, rows + 1)]
        return out

    def warm(self, lo: int, hi: int, rng) -> int:
        """Run every program shape a traffic of lo..hi-token prompts can
        reach, through the engine's own calls, on throwaway requests: each
        prefill batch shape (with its sampling and cache insert), and each
        decode block length 1..scan_steps. Returns the shapes run."""
        rid = -1
        shapes = self.prefill_shapes(lo, hi)
        for rows, l_pad in shapes:
            for _ in range(rows):
                self.pending.append(Request(
                    rid, rng.integers(0, self.mcfg.vocab_size, l_pad).astype(np.int32),
                    max_new_tokens=1))
                rid -= 1
            self.step(time.perf_counter)
        for n in range(1, self.scan_steps + 1):
            self.pending.append(Request(
                rid, rng.integers(0, self.mcfg.vocab_size, lo).astype(np.int32),
                max_new_tokens=n + 1))
            rid -= 1
            self.step(time.perf_counter)
        while self.live():
            self.step(time.perf_counter)
        self.eng.take_completed()
        self.spans.clear()
        self.deliveries.clear()
        self.admitted_at.clear()
        return len(shapes) + self.scan_steps

    # ------------------------------------------------------------- loop ---
    def submit(self, rid: int, prompt: np.ndarray, max_new: int):
        self.prompts[rid] = prompt
        self.pending.append(Request(rid, prompt, max_new_tokens=max_new))

    def live(self) -> int:
        return sum(not f for f in self.eng.slot_free)

    def _admit(self, clock):
        eng = self.eng
        while self.pending:
            free = [s for s in range(eng.slots) if eng.slot_free[s]]
            if not free:
                break
            width = len(free) if eng.batch_prefill else 1
            plan = eng.scheduler.plan(self.pending, width)
            if plan is None:
                break
            t0 = clock()
            with ANNOTATE("bench.admit"):
                eng._prefill_into(plan, free[:len(plan.requests)])
            t1 = clock()
            rids = [r.rid for r in plan.requests]
            self.spans.append(Span("bench.admit", t0, t1, {
                "rids": rids, "lengths": [int(x) for x in plan.lengths],
                "padded": int(plan.tokens.shape[1])}))
            for r in rids:
                self.admitted_at[r] = t0
                self.deliveries.append((r, t1, 1))
        eng._drain_rejections()

    def _decode(self, clock) -> bool:
        eng = self.eng
        n = eng._block_len()
        if not n:
            return False
        live = [s for s in range(eng.slots) if not eng.slot_free[s]]
        before = {s: (eng.slot_req[s].rid, len(eng.slot_out[s])) for s in live}
        positions = [len(eng.slot_req[s].prompt) + len(eng.slot_out[s]) - 1
                     for s in live]
        steps = [min(n, int(eng.slot_budget[s])) for s in live]
        t0 = clock()
        with ANNOTATE("bench.decode_block"):
            eng._decode_block(n)
        t1 = clock()
        self.spans.append(Span("bench.decode_block", t0, t1, {
            "n": n, "positions": positions, "steps": steps}))
        for s in live:
            rid, had = before[s]
            k = len(eng.slot_out[s]) - had
            if k > 0:
                self.deliveries.append((rid, t1, k))
        return True

    def step(self, clock) -> bool:
        """One turn of the engine's serving loop. False when there was
        nothing to admit or decode."""
        self.eng._expire_deadlines(self.pending)
        had = len(self.pending)
        self._admit(clock)
        admitted = len(self.pending) < had
        decoded = self._decode(clock)
        for res in self.eng.take_completed():
            self.results[res.rid] = (res.status, list(res.tokens))
        return admitted or decoded

    def idle(self, until: float, clock):
        """Wait for the next arrival, as its own span."""
        t0 = clock()
        if until > t0:
            with ANNOTATE("bench.idle_wait"):
                time.sleep(until - t0)
            self.spans.append(Span("bench.idle_wait", t0, clock(), {}))

    def close(self):
        """Free the program's device state (the ring caches and staged
        vectors); the weights belong to the caller."""
        eng = self.eng
        eng.caches = None
        eng._dev = None
        eng.params = None
        self.eng = None

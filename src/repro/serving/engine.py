"""Device-resident continuous-batching serving engine.

The paper's FIFO K/V buffer is the serving-side win of window attention:
decode memory is O(window), not O(context) — SWAT's Fig. 3 linear-memory
claim. The engine turns that into throughput:

  * static batch of slots (TPU-friendly: shapes never change) with PER-SLOT
    ring write positions — every row of every cache tracks its own step, so
    slots at different depths share one batched kernel call,
  * batched, padded prefill: the scheduler packs all pending prompts that
    fit into one call (per-row `lengths` mask the padding), optionally
    chunked along the sequence axis so prefill VMEM is bounded by the chunk
    size rather than the longest prompt,
  * scan decode: N tokens per dispatch under `jax.lax.scan` with per-slot
    done/budget flags — the host syncs once per block instead of once per
    token (the seed engine's per-token round-trip),
  * per-slot temperature / top-k sampling (jitted; greedy rows take argmax).

Mesh-sharded serving (`ServingEngine(mesh=, profile=)`): every jitted entry
point carries `in_shardings`/`out_shardings` from the distributed/sharding.py
rule tables — params by `param_sharding` (placed ONCE at engine
construction), ring caches by `cache_sharding` (slot dim over ('pod','data'),
kv heads over 'model', per-slot `step` riding the slot axis), and the
per-slot decode state (`slot_last`/`slot_budget`/`slot_temp`/active flags)
by `decode_batch_sharding`. XLA then PARTITIONS decode across the mesh
instead of replicating it — the scan-decode block is the sync quantum. The
scheduler is told the slot-axis size so admitted prefill batches stay
divisible (and therefore sharded) whenever enough prompts are pending.

Determinism: the RNG key splits once per executed decode step and once per
prefill batch, in the same order whatever `scan_steps` is (blocks stop at
the earliest slot completion), so scan decode is token-for-token identical
to stepwise decode — the property test_serving.py pins down. The sharded
engine runs the same program partitioned, so it is token-for-token identical
to the single-device engine (tests/test_serving_sharded.py).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
import warnings
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Partitionable threefry keeps sharded sampling collective-free: legacy
# threefry (the pre-0.4.36 default) lowers cross-device permutes and a
# u32 all-reduce whenever an RNG output is sharded, which would plant
# collectives inside the slot-parallel decode scan. Trace-time flag, so
# flipping it here covers every program the engine compiles; it changes
# sampled (temps>0) token streams but never greedy decoding.
jax.config.update("jax_threefry_partitionable", True)

from repro.core import layers as L
from repro.core import model as Mod
from repro.core.types import ModelConfig
from repro.kernels import ops as kops
from repro.serving import faults as F
from repro.serving import sampling
from repro.serving.drafter import NGramDrafter, get_drafter
from repro.serving.faults import FaultPlan
from repro.serving.scheduler import PrefillPlan, Scheduler, normalize_prompt
from repro.telemetry import events as TEV
from repro.telemetry import metrics as MX
from repro.telemetry.tracer import Tracer, prometheus_text

# the Result status taxonomy (see serving/README.md "Resilience"):
#   ok        full budget served (or prompt-only request)
#   rejected  never admitted: malformed/oversized prompt or queue overflow
#   poisoned  quarantined mid-decode: non-finite logits in the slot's row;
#             tokens holds everything emitted BEFORE the poison
#   deadline  per-request deadline expired (partial tokens kept)
#   failed    infrastructure failure after the slot's state was consumed
#             (e.g. kernel dispatch died after cache donation)
STATUSES = ("ok", "rejected", "poisoned", "deadline", "failed")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # any int spelling; normalized to (L,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    # seconds from run() submission; None = no deadline. Checked at block
    # boundaries (the host-sync quantum), so expiry resolution is one
    # decode block — an expired request finalizes with what it has.
    deadline: Optional[float] = None
    # infrastructure-failure retries: a request whose slot died with status
    # "failed" (cache donation consumed) is readmitted through the normal
    # queue up to this many times before it finalizes as failed. Retries
    # restart from the prompt — partial tokens from the dead slot are
    # discarded, never stitched.
    max_retries: int = 0

    def __post_init__(self):
        # normalize ONCE at the boundary: a (1, L) / list-of-lists prompt
        # used to len()-measure as 1 and crash (or mis-pad) at batch fill.
        # A ragged prompt that cannot normalize is kept as-is: the
        # scheduler rejects it per-request instead of raising here.
        try:
            self.prompt = normalize_prompt(self.prompt)
        except (ValueError, TypeError):
            pass


@dataclasses.dataclass
class Result:
    rid: int
    tokens: List[int]
    status: str = "ok"           # one of STATUSES
    reason: str = ""             # human-readable detail for status != ok
    retries: int = 0             # readmissions consumed (see max_retries)

    @property
    def ok(self) -> bool:
        return self.status == "ok"


def _jit(name: str, fn, **kw):
    """`jax.jit` under a stable program name: XLA names the module
    `jit_<name>`, and a device trace reports it with every operation."""
    fn.__name__ = name
    return jax.jit(fn, **kw)


class _Compiled:
    """Jitted functions shared by every engine over the same
    (cfg, max_len, decode_impl, top_k, mesh, profile, tokens_per_step):
    compiles are per-model, engines are cheap per-session objects
    (constructing a second engine must not pay XLA again — `_get_compiled`
    memoizes these).

    tokens_per_step sizes the ring lookahead (caches get T-1 extra ring
    rows so a T-token decode step never evicts an in-window token) and is
    part of the compile identity: every cache shape, prefill, and scan
    program depends on it — the speculative-decode hook.

    With a mesh, every function is keyed by its batch-row count so each
    shape gets exact `in_shardings`/`out_shardings` (the sharding rules are
    divisibility-aware, so specs depend on the concrete row count).

    donate=True (the default) donates the ring-cache carry of every entry
    point that consumes one — the scan/spec-scan decode blocks, the
    cache-insert, and the chunked-prefill carry — so XLA aliases the caches
    in place instead of copying the full multi-MB buffer per call. The
    analyzer (repro.analysis) proves the aliases hold in the compiled
    executables; donate=False keeps the pre-donation programs around as the
    analyzer's known-bad fixture and for A/B benchmarking."""

    def __init__(self, cfg: ModelConfig, max_len: int, decode_impl: str,
                 top_k: int, mesh=None, profile: str = "tp",
                 tokens_per_step: int = 1, speculative: int = 0,
                 draft: Optional[NGramDrafter] = None, donate: bool = True,
                 faults: FaultPlan = FaultPlan(),
                 kv_layout: str = "contiguous", metrics: bool = False):
        self.cfg, self.max_len = cfg, max_len
        self.decode_impl, self.top_k = decode_impl, top_k
        # "paged": slot caches hold block pools + tables instead of
        # per-slot contiguous rings; decode gathers a ring VIEW per layer
        # (bitwise the contiguous kernel — PAGE_SIZE divides every
        # allocation), and admission/COW address blocks through tables
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        self.tokens_per_step = tokens_per_step
        self.lookahead = tokens_per_step - 1
        self.speculative = speculative
        self.drafter = get_drafter(draft) if speculative else None
        self.donate = donate
        # frozen/hashable like the drafter spec: a plan with logit faults
        # compiles an extra countdown argument + one masked select into the
        # scan body; the default plan compiles the production program
        self.faults = faults
        # metrics=True compiles the device-counter carry (telemetry.metrics)
        # into the scan bodies — part of the compile identity like faults,
        # so metrics=False engines run today's exact programs. ring_mod is
        # the wrap modulus for the ring_wraps counter: static per engine.
        self.metrics = metrics
        self.ring_mod = MX.ring_modulus(cfg, max_len, tokens_per_step - 1)
        self.mesh, self.profile = mesh, profile
        if mesh is not None:
            from repro.distributed import sharding as Sh
            self._Sh = Sh
            pshapes = jax.eval_shape(
                lambda: Mod.init_model(jax.random.PRNGKey(0), cfg))
            self.param_sharding = Sh.param_sharding_serving(pshapes, mesh,
                                                            profile)
            self._rep = Sh.replicated(mesh)
        else:
            self._Sh = None
            self.param_sharding = None
            self._rep = None
        self._prefill_fns: Dict[int, Any] = {}
        self._chunk_fns: Dict[int, Any] = {}
        self._insert_fns: Dict[Tuple[int, int], Any] = {}
        self._sample_fns: Dict[int, Any] = {}
        self._scan_fns: Dict[Tuple[int, int], Any] = {}
        self._spec_fns: Dict[Tuple[int, int], Any] = {}
        self._init_fns: Dict[int, Any] = {}
        self._slot_init_fns: Dict[int, Any] = {}
        self._insert_paged_fns: Dict[Tuple[int, int], Any] = {}
        self._fixup_fns: Dict[Tuple[int, int], Any] = {}
        self._bcast_fns: Dict[int, Any] = {}

    # ------------------------------------------------------- sharding maps --
    def cache_sharding(self, n: int):
        shapes = jax.eval_shape(
            lambda: Mod.init_caches(self.cfg, n, self.max_len,
                                    lookahead=self.lookahead))
        return self._Sh.cache_sharding(shapes, self.mesh)

    def slot_cache_sharding(self, slots: int):
        """Sharding of the engine's SLOT caches — the decode-scan carry.
        Contiguous engines: same as cache_sharding. Paged engines: the pool
        leaves (pk/pv/table) carry their own rules — under a mesh the pool
        is per-slot (local block ids), slot dim over the batch axes, so
        slot-parallel paged decode stays collective-free."""
        if not self.paged:
            return self.cache_sharding(slots)
        shapes = jax.eval_shape(
            lambda: Mod.init_paged_caches(self.cfg, slots, self.max_len,
                                          lookahead=self.lookahead,
                                          shared_pool=self.mesh is None))
        return self._Sh.cache_sharding(shapes, self.mesh)

    @functools.cached_property
    def row_write(self) -> bool:
        """Whether the decode programs write each step's K/V rows into the
        slot caches in place: some layer attends over a K/V ring and
        `kops.ring_rows_in_place`, the rule the decode step follows."""
        return (any(not kind.startswith("mamba")
                    for kind in self.cfg.layer_pattern)
                and kops.ring_rows_in_place(self.decode_impl, self.paged))

    @functools.cached_property
    def full_caches(self) -> Tuple[int, int]:
        """(full-attention layers, rows of each one's cache a decode step
        reads per slot, `kops.decode_rows_read`): the layers whose decode
        attends a whole max_len cache, not a ring of a window's rows."""
        layers, rows = 0, 0
        for i, kind in enumerate(self.cfg.layer_pattern):
            if kind.startswith("mamba"):
                continue
            acfg = Mod.attn_cfg(self.cfg, kind, index=i)
            if not acfg.spec.is_sparse:
                layers += self.cfg.num_super_blocks
                rows = kops.decode_rows_read(
                    self.decode_impl,
                    L.cache_allocation(acfg, self.max_len, self.lookahead))
        return layers, rows

    def batch_sharding(self, shapes, n: int, slot_dim: int = 0):
        """decode_batch_sharding over a pytree of ShapeDtypeStructs."""
        return self._Sh.decode_batch_sharding(shapes, self.mesh, n,
                                              slot_dim=slot_dim)

    def _sds(self, shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def slot_vector_shardings(self, slots: int) -> Dict[str, Any]:
        """Placement of the staged per-slot decode vectors (engine._dev).
        Must match the scan/spec_scan in_shardings exactly: the block
        dispatch runs under transfer_guard("disallow"), so a host-staged
        vector left on the default device would need an implicit
        (disallowed) reshard onto the mesh."""
        veci = self.batch_sharding(self._sds((slots,)), slots)
        vecb = self.batch_sharding(self._sds((slots,), jnp.bool_), slots)
        sh = {"tok": veci, "budget": veci, "active": vecb,
              "poisoned": vecb,
              "temps": self.batch_sharding(
                  self._sds((slots,), jnp.float32), slots),
              "anyt": self._rep}
        if self.faults.has_logit_faults:
            sh["fin"] = veci
        if self.drafter is not None:
            sh["hist"] = self.batch_sharding(
                self._sds((slots, self.drafter.history)), slots)
            sh["hcnt"] = veci
        return sh

    def _donate(self, *argnums: int) -> Tuple[int, ...]:
        """Carry argnums to donate (empty when donation is disabled)."""
        return tuple(argnums) if self.donate else ()

    def slot_quantum(self, slots: int) -> int:
        """Slot-axis size when the engine's slot count shards over it —
        the scheduler keeps prefill batches divisible by this."""
        if self.mesh is None:
            return 1
        size = 1
        for a in ("pod", "data"):
            if a in self.mesh.axis_names:
                size *= self.mesh.shape[a]
        return size if size > 1 and slots % size == 0 else 1

    def _act_sharding(self, n: int, t: int = 1):
        if self.mesh is None:
            return None
        return self.batch_sharding(
            self._sds((n, t, self.cfg.d_model), jnp.float32), n)

    # ------------------------------------------------------------ prefill --
    def prefill(self, n: int):
        if n not in self._prefill_fns:
            act = self._act_sharding(n)

            def fn(p, tok, lens):
                return Mod.prefill(p, self.cfg, {"tokens": tok},
                                   max_len=self.max_len, lengths=lens,
                                   act_sharding=act,
                                   lookahead=self.lookahead)
            if self.mesh is None:
                self._prefill_fns[n] = _jit("swat_prefill", fn)
            else:
                vec = self.batch_sharding(self._sds((n,)), n)
                tok_sh = self.batch_sharding(self._sds((n, 1)), n)
                logit_sh = self.batch_sharding(
                    self._sds((n, 1, self.cfg.vocab_size), jnp.float32), n)
                self._prefill_fns[n] = _jit(
                    "swat_prefill", fn,
                    in_shardings=(self.param_sharding, tok_sh, vec),
                    out_shardings=(logit_sh, self.cache_sharding(n)))
        return self._prefill_fns[n]

    def chunk(self, n: int):
        if n not in self._chunk_fns:
            act = self._act_sharding(n)
            fn = functools.partial(self._chunk_impl, act_sharding=act)
            # the chunk loop carries (caches, last_logits): donate both so
            # walking a long prompt re-uses one cache allocation instead of
            # copying it per chunk
            don = self._donate(1, 5)
            if self.mesh is None:
                self._chunk_fns[n] = _jit("swat_prefill_chunk", fn,
                                          donate_argnums=don)
            else:
                vec = self.batch_sharding(self._sds((n,)), n)
                tok_sh = self.batch_sharding(self._sds((n, 1)), n)
                logit_sh = self.batch_sharding(
                    self._sds((n, self.cfg.vocab_size), jnp.float32), n)
                cache_sh = self.cache_sharding(n)
                self._chunk_fns[n] = _jit(
                    "swat_prefill_chunk", fn,
                    in_shardings=(self.param_sharding, cache_sh, tok_sh,
                                  self._rep, vec, logit_sh),
                    out_shardings=(logit_sh, cache_sh),
                    donate_argnums=don)
        return self._chunk_fns[n]

    def _chunk_impl(self, params, caches, tok, pos0, lengths, last_logits,
                    act_sharding=None):
        """One prefill chunk + carry of each row's last-real-token logits
        (pos0 is traced: one compile serves every chunk index). Only the
        gathered (B, 1, D) row is unembedded — never the whole chunk."""
        x, caches = Mod.prefill_chunk(
            params, self.cfg, {"tokens": tok}, caches, pos0, lengths,
            act_sharding=act_sharding, lookahead=self.lookahead)
        t = tok.shape[1]
        tpos = lengths - 1 - pos0
        hit = (tpos >= 0) & (tpos < t)
        xsel = jnp.take_along_axis(
            x, jnp.broadcast_to(
                jnp.clip(tpos, 0, t - 1)[:, None, None],
                (x.shape[0], 1, x.shape[2])), axis=1)
        sel = Mod._unembed(params, self.cfg, xsel)[:, 0]
        return jnp.where(hit[:, None], sel, last_logits), caches

    def insert(self, slots: int, n: int):
        key = (slots, n)
        if key not in self._insert_fns:
            def fn(full, one, idx):
                return jax.tree.map(
                    lambda f, o: f.at[:, idx].set(o.astype(f.dtype)),
                    full, one)
            # donate the full slot caches: admission scatters n fresh rows
            # into them, everything else is carried through unchanged — an
            # un-donated insert copies every cache at every admission
            don = self._donate(0)
            if self.mesh is None:
                self._insert_fns[key] = _jit("swat_insert", fn,
                                             donate_argnums=don)
            else:
                self._insert_fns[key] = _jit(
                    "swat_insert", fn,
                    in_shardings=(self.cache_sharding(slots),
                                  self.cache_sharding(n), self._rep),
                    out_shardings=self.cache_sharding(slots),
                    donate_argnums=don)
        return self._insert_fns[key]

    def sample(self, n: int):
        if n not in self._sample_fns:
            fn = functools.partial(sampling.sample, top_k=self.top_k)
            if self.mesh is None:
                self._sample_fns[n] = _jit("swat_sample", fn)
            else:
                veci = self.batch_sharding(self._sds((n,)), n)
                logit_sh = self.batch_sharding(
                    self._sds((n, self.cfg.vocab_size), jnp.float32), n)
                # temps rides REPLICATED (16 bytes): sampling's all-greedy
                # fast path does `jnp.any(temps > 0)`, which on a slot-
                # sharded vector lowers to a pred[] all-reduce — the only
                # collective left on the slot-parallel hot path. Replicated
                # it folds to a local reduce.
                self._sample_fns[n] = _jit(
                    "swat_sample", fn,
                    in_shardings=(self._rep, logit_sh, self._rep),
                    out_shardings=veci)
        return self._sample_fns[n]

    def fresh_caches(self, n: int):
        if n not in self._init_fns:
            out_sh = None if self.mesh is None else self.cache_sharding(n)
            self._init_fns[n] = _jit(
                "swat_init_caches",
                lambda: Mod.init_caches(self.cfg, n, self.max_len,
                                        lookahead=self.lookahead),
                out_shardings=out_sh)
        return self._init_fns[n]()

    # -------------------------------------------------------------- paged --
    def fresh_slot_caches(self, slots: int):
        """The engine's slot caches: paged pools+tables for kv_layout=
        'paged', plain contiguous rings otherwise. Prefill always runs
        contiguous — rows paginate at insert."""
        if not self.paged:
            return self.fresh_caches(slots)
        if slots not in self._slot_init_fns:
            out_sh = (None if self.mesh is None
                      else self.slot_cache_sharding(slots))
            self._slot_init_fns[slots] = _jit(
                "swat_init_slot_caches",
                lambda: Mod.init_paged_caches(
                    self.cfg, slots, self.max_len, lookahead=self.lookahead,
                    shared_pool=self.mesh is None),
                out_shardings=out_sh)
        return self._slot_init_fns[slots]()

    def insert_paged(self, slots: int, n: int):
        """Paged admission: reshape n freshly prefilled CONTIGUOUS rows
        into page blocks, scatter them to each row's table blocks, and push
        the full host table mirror atomically in the same dispatch (the
        staleness contract in serving/paged.py). Shared-prefix admissions
        point several rows at the same block ids — the duplicate scatters
        carry bitwise-identical content by the shareable-block invariant,
        so whichever lands is exact."""
        key = (slots, n)
        if key not in self._insert_paged_fns:
            def fn(full, one, idx, tables):
                out = {}
                for li, fc in full.items():
                    oc = one[li]
                    if not (isinstance(fc, dict) and "pk" in fc):
                        out[li] = jax.tree.map(
                            lambda f, o: f.at[:, idx].set(o.astype(f.dtype)),
                            fc, oc)
                        continue
                    nb = fc["table"].shape[-1]
                    page = fc["pk"].shape[-2]
                    tbl = tables[li]
                    nc = dict(fc)
                    sb, nn, hh, cap, dd = oc["k"].shape

                    def blocks(a):
                        return a.reshape(sb, nn, hh, nb, page, dd
                                         ).transpose(0, 1, 3, 2, 4, 5)
                    if fc["pk"].ndim == 5:       # shared global-id pool
                        dest = tbl[idx].reshape(-1)
                        for pkey, ckey in (("pk", "k"), ("pv", "v")):
                            blk = blocks(oc[ckey]).reshape(
                                sb, nn * nb, hh, page, dd)
                            nc[pkey] = fc[pkey].at[:, dest].set(
                                blk.astype(fc[pkey].dtype))
                    else:                         # per-slot local-id pool
                        for pkey, ckey in (("pk", "k"), ("pv", "v")):
                            nc[pkey] = fc[pkey].at[:, idx, :nb].set(
                                blocks(oc[ckey]).astype(fc[pkey].dtype))
                    nc["table"] = jnp.broadcast_to(
                        tbl[None].astype(fc["table"].dtype),
                        fc["table"].shape)
                    nc["step"] = fc["step"].at[:, idx].set(
                        oc["step"].astype(fc["step"].dtype))
                    for extra in ("xk", "xv"):
                        if extra in fc:
                            nc[extra] = fc[extra].at[:, idx].set(
                                oc[extra].astype(fc[extra].dtype))
                    out[li] = nc
                return out
            don = self._donate(0)
            if self.mesh is None:
                self._insert_paged_fns[key] = _jit(
                    "swat_insert_paged", fn, donate_argnums=don)
            else:
                self._insert_paged_fns[key] = _jit(
                    "swat_insert_paged", fn,
                    in_shardings=(self.slot_cache_sharding(slots),
                                  self.cache_sharding(n), self._rep,
                                  self._rep),
                    out_shardings=self.slot_cache_sharding(slots),
                    donate_argnums=don)
        return self._insert_paged_fns[key]

    def fixup(self, slots: int, m: int):
        """Pre-block paged maintenance: copy-on-write block moves (m (src,
        dst) pairs per layer, padded with scratch self-moves) plus a
        wholesale push of the host table mirror. dst ids are freshly
        allocated, so the gather of the OLD pool before the scatter is
        consistent — no move ever reads another move's destination."""
        key = (slots, m)
        if key not in self._fixup_fns:
            def fn(caches, tables, srcs, dsts):
                out = {}
                for li, c in caches.items():
                    if not (isinstance(c, dict) and "pk" in c):
                        out[li] = c
                        continue
                    nc = dict(c)
                    if m:
                        s_, d_ = srcs[li], dsts[li]
                        nc["pk"] = nc["pk"].at[:, d_].set(nc["pk"][:, s_])
                        nc["pv"] = nc["pv"].at[:, d_].set(nc["pv"][:, s_])
                    nc["table"] = jnp.broadcast_to(
                        tables[li][None].astype(c["table"].dtype),
                        c["table"].shape)
                    out[li] = nc
                return out
            don = self._donate(0)
            if self.mesh is None:
                self._fixup_fns[key] = _jit("swat_paged_fixup", fn,
                                            donate_argnums=don)
            else:
                sh = self.slot_cache_sharding(slots)
                self._fixup_fns[key] = _jit(
                    "swat_paged_fixup", fn,
                    in_shardings=(sh, self._rep, self._rep, self._rep),
                    out_shardings=sh, donate_argnums=don)
        return self._fixup_fns[key]

    def broadcast_prefix(self, n: int):
        """Prefix-sharing prefill: replicate a 1-row prefix cache (and its
        last-token logits) across n batch rows. jnp.repeat copies rows, so
        every row starts bitwise the single-row prefill."""
        if n not in self._bcast_fns:
            def fn(c1, lg):
                caches = jax.tree.map(lambda x: jnp.repeat(x, n, axis=1), c1)
                return jnp.broadcast_to(lg, (n,) + lg.shape[1:]), caches
            if self.mesh is None:
                self._bcast_fns[n] = _jit("swat_broadcast_prefix", fn)
            else:
                logit_sh = self.batch_sharding(
                    self._sds((n, self.cfg.vocab_size), jnp.float32), n)
                self._bcast_fns[n] = _jit(
                    "swat_broadcast_prefix", fn,
                    out_shardings=(logit_sh, self.cache_sharding(n)))
        return self._bcast_fns[n]

    # ------------------------------------------------------------- decode --
    def scan(self, n: int, slots: int):
        key = (n, slots)
        if key not in self._scan_fns:
            self._scan_fns[key] = self._make_scan(n, slots)
        return self._scan_fns[key]

    def _make_scan(self, n: int, slots: int):
        cfg, impl, top_k = self.cfg, self.decode_impl, self.top_k
        lookahead = self.lookahead
        act = self._act_sharding(slots)
        inject = self.faults.has_logit_faults
        metrics, ring_mod = self.metrics, self.ring_mod
        # poison value per slot: a NUMPY constant baked into the trace
        # (eager jnp here would dispatch under the engine's transfer guard)
        bad_val = (np.where(self.faults.inf_mask(slots),
                            np.inf, np.nan).astype(np.float32)
                   if inject else None)

        def run_scan(params, caches, tok, active, budget, temps, anyt, key,
                     poisoned, fin, mx):
            def body(carry, _):
                caches, tok, active, budget, key, poisoned, fin, mx = carry
                logits, caches = Mod.decode_step(
                    params, cfg, {"tokens": tok[:, None]}, caches, impl=impl,
                    act_sharding=act, lookahead=lookahead, mesh=self.mesh)
                lg = logits[:, 0]
                if inject:
                    # chaos countdown: when a slot's trigger step arrives,
                    # its whole logits row becomes nan/inf — one masked
                    # select, invisible to every other row
                    lg = jnp.where((active & (fin == 0))[:, None],
                                   bad_val[:, None], lg)
                key, sub = jax.random.split(key)
                nxt = sampling.sample(sub, lg, temps, top_k,
                                      any_sampling=anyt)
                # numerical guard: a non-finite row is QUARANTINED — not
                # emitted, budget untouched, slot deactivated for the host
                # to finalize as status "poisoned". Every op here is
                # row-wise and the RNG split count is unchanged, so on a
                # clean run (bad == False) the program's healthy-slot
                # tokens are bitwise the unguarded engine's.
                bad = active & ~sampling.finite_rows(lg)
                ok = active & ~bad
                nxt = jnp.where(ok, nxt, tok)
                emitted = ok
                budget = budget - ok.astype(jnp.int32)
                poisoned = poisoned | bad
                active = ok & (budget > 0)
                if inject:
                    fin = fin - ok.astype(jnp.int32)
                if metrics:
                    # device counters: pure per-slot int32 adds over values
                    # the body already holds — sampling/RNG/guard math are
                    # untouched, so tokens stay bitwise metrics-off
                    mx = MX.seq_update(mx, ok, bad, ring_mod)
                return ((caches, nxt, active, budget, key, poisoned, fin,
                         mx), (nxt, emitted))

            carry, (toks, emit) = jax.lax.scan(
                body, (caches, tok, active, budget, key, poisoned, fin, mx),
                None, length=n)
            caches, tok, active, budget, key, poisoned, fin, mx = carry
            return (caches, tok, active, budget, key, toks, emit, poisoned
                    ) + ((fin,) if inject else ()) + ((mx,) if metrics
                                                      else ())

        # fin / mx ride the carry as empty pytrees (None) when their
        # feature is off, so the plain program has no extra state at all
        if inject and metrics:
            fn = run_scan
        elif inject:
            def fn(params, caches, tok, active, budget, temps, anyt, key,
                   poisoned, fin):
                return run_scan(params, caches, tok, active, budget, temps,
                                anyt, key, poisoned, fin, None)
        elif metrics:
            def fn(params, caches, tok, active, budget, temps, anyt, key,
                   poisoned, mx):
                return run_scan(params, caches, tok, active, budget, temps,
                                anyt, key, poisoned, None, mx)
        else:
            def fn(params, caches, tok, active, budget, temps, anyt, key,
                   poisoned):
                return run_scan(params, caches, tok, active, budget, temps,
                                anyt, key, poisoned, None, None)

        # donate the ring caches: the decode block's only multi-MB carry.
        # Un-donated, XLA materializes a full copy of every K/V ring per
        # block (the analyzer's first real catch); donated, the compiled
        # executable aliases them input->output and the scan mutates the
        # same buffers the engine re-feeds next block.
        # the metrics carry is donated like the caches: tiny, but donation
        # keeps the counters a true in-place accumulator (no copy per block
        # and the telemetry lint can prove the alias)
        mx_arg = 9 + (1 if inject else 0)
        don = self._donate(1, mx_arg) if metrics else self._donate(1)
        if self.mesh is None:
            return _jit("swat_decode_scan", fn, donate_argnums=don)
        cache_sh = self.slot_cache_sharding(slots)
        veci = self.batch_sharding(self._sds((slots,)), slots)
        vecb = self.batch_sharding(self._sds((slots,), jnp.bool_), slots)
        vecf = self.batch_sharding(self._sds((slots,), jnp.float32), slots)
        blk = self.batch_sharding(self._sds((n, slots)), slots, slot_dim=1)
        fin_in = (veci,) if inject else ()
        mx_in = ((MX.metrics_shardings(veci, self._rep),) if metrics
                 else ())
        return _jit(
            "swat_decode_scan", fn,
            in_shardings=(self.param_sharding, cache_sh, veci, vecb, veci,
                          vecf, self._rep, self._rep, vecb) + fin_in + mx_in,
            out_shardings=(cache_sh, veci, vecb, veci, self._rep, blk, blk,
                           vecb) + fin_in + mx_in,
            donate_argnums=don)

    # ------------------------------------------------------- speculative --
    def spec_scan(self, n: int, slots: int):
        key = (n, slots)
        if key not in self._spec_fns:
            self._spec_fns[key] = self._make_spec_scan(n, slots)
        return self._spec_fns[key]

    def _make_spec_scan(self, n: int, slots: int):
        """n draft/verify/accept steps per dispatch. Each step feeds the
        model (B, T=k+1) tokens — the slot's pending token plus k drafts —
        in ONE `decode_step` (the PR-3 lookahead-ring primitive), then:

          accept   logits[:, j] is the model's next-token distribution
                   given x[:, :j+1], so draft x[:, j+1] is kept iff it
                   equals the model's own choice ver[:, j]; `acc` is the
                   longest all-match prefix and the step emits e = acc+1
                   tokens — acc verified drafts plus the model's bonus
                   token after them. Every emitted token is the model's
                   output for a fully verified prefix, hence greedy spec
                   decode is bitwise the sequential engine.
          rollback `decode_step` advanced every ring `step` by T and wrote
                   T rows; setting step -= T - e keeps exactly the rows a
                   sequential engine would hold after e tokens. The T-e
                   rejected rows are garbage but DEAD: the lookahead rows
                   mean no in-window token was evicted, the stale slots
                   reconstruct (ring_slot_positions) to positions the
                   window/validity mask drops, and the very next step's
                   T-row insert starts at step and overwrites all of them
                   before any attention read. Inactive slots take e=0, so
                   their step is restored exactly (no drift).
          budget   e is clamped per slot to the remaining budget, so a
                   slot never overshoots mid-block; done slots go
                   inactive and the loop exits early when none remain.

        A `lax.while_loop` (not scan) so the RNG key splits once per
        EXECUTED step — the same determinism contract as the sequential
        scan. Verify positions sample under fold_in(sub, j); greedy rows
        ignore the key entirely, which is why the identity guarantee is
        greedy-only (sampled rows are distributionally exact — each token
        is drawn conditioned on a verified prefix — but ride a different
        key stream than sequential decode)."""
        cfg, impl, top_k = self.cfg, self.decode_impl, self.top_k
        k = self.speculative
        t = k + 1
        assert self.lookahead >= k, (self.lookahead, k)
        drafter = self.drafter
        act = self._act_sharding(slots, t)
        inject = self.faults.has_logit_faults
        metrics, ring_mod = self.metrics, self.ring_mod
        bad_val = (np.where(self.faults.inf_mask(slots),
                            np.inf, np.nan).astype(np.float32)
                   if inject else None)
        corrupt = (self.faults.draft_mask(slots)
                   if self.faults.corrupt_draft_slots else None)

        def run_spec(params, caches, tok, active, budget, temps, anyt, key,
                     hist, hcnt, poisoned, fin, mx):
            toks0 = jnp.zeros((n, slots, t), jnp.int32)
            emit0 = jnp.zeros((n, slots, t), jnp.bool_)
            active0 = active

            def cond(carry):
                i, _, _, active, *_ = carry
                # exit as soon as ANY slot drains (not just all): a spec
                # slot's finish step is data-dependent (acceptance), so
                # running the block to n strands the freed slot idle
                # until the block boundary — exiting returns control to
                # the scheduler, which refills and redispatches. The
                # sequential scan never needs this: its block length
                # min(budgets) already ends exactly at first retirement.
                # Quarantined slots flip active too, so poison exits here.
                return (i < n) & jnp.all(active == active0)

            def body(carry):
                (i, caches, tok, active, budget, key, hist, hcnt, poisoned,
                 fin, toks_buf, emit_buf, mx) = carry
                drafts = drafter.propose(hist, hcnt, k)
                if corrupt is not None:
                    # chaos: replace the slot's proposals with out-of-vocab
                    # garbage — sanitize below must keep it harmless
                    drafts = jnp.where(corrupt[:, None],
                                       jnp.int32(cfg.vocab_size + 1337),
                                       drafts)
                # proposals are suggestions, never trusted: clip into the
                # vocab so a corrupt drafter can't exploit OOB-gather
                # clamping (garbage fails verification instead)
                drafts = drafter.sanitize(drafts, cfg.vocab_size)
                x = jnp.concatenate([tok[:, None], drafts], axis=1)
                logits, caches = Mod.decode_step(
                    params, cfg, {"tokens": x}, caches, impl=impl,
                    act_sharding=act, lookahead=k, mesh=self.mesh)
                if inject:
                    # a spec step verifies a window of T positions; poison
                    # exactly the position the countdown lands on (window
                    # position fin = token index tokens_done + fin), so a
                    # poisoned request keeps exactly target_idx tokens on
                    # every engine flavor — sequential and speculative agree
                    pos = jnp.arange(t, dtype=jnp.int32)[None, :]
                    hitpos = ((active & (fin >= 0) & (fin < t))[:, None]
                              & (pos == fin[:, None]))
                    logits = jnp.where(hitpos[:, :, None],
                                       bad_val[:, None, None], logits)
                key, sub = jax.random.split(key)
                # one batched sample over the T verify positions (vmap is
                # bitwise the per-j loop: same fold_in(sub, j) keys, same
                # row math) — unrolling T sample chains costs as much as
                # the whole forward on small models
                subs = jax.vmap(
                    lambda j: jax.random.fold_in(sub, j))(jnp.arange(t))
                ver = jax.vmap(
                    lambda kj, lj: sampling.sample(kj, lj, temps, top_k,
                                                   any_sampling=anyt),
                    in_axes=(0, 1), out_axes=1)(subs, logits)  # (B, T)
                # numerical guard over every verify position. A slot is
                # quarantined only when a non-finite position would actually
                # be CONSUMED (its index < the acceptance-gated emission
                # count): it emits the verified-clean prefix strictly before
                # the first bad position, then deactivates for host
                # quarantine. A bad position beyond acceptance was never
                # going to be emitted — the slot stays live and the guard
                # re-checks next step. Row/position-wise only, so healthy
                # slots are bitwise the unguarded program.
                finpos = jnp.all(jnp.isfinite(logits), axis=-1)   # (B, T)
                first_bad = jnp.where(
                    jnp.all(finpos, axis=1), jnp.int32(t),
                    jnp.argmin(finpos.astype(jnp.int32), axis=1)
                    .astype(jnp.int32))
                match = (drafts == ver[:, :k]).astype(jnp.int32)
                acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                e_clean = jnp.minimum(acc + 1, budget)
                bad = active & (first_bad < e_clean)
                ok = active & ~bad
                e = jnp.where(active,
                              jnp.where(bad, first_bad, e_clean),
                              0)
                caches = jax.tree.map(
                    lambda c: ({**c, "step": c["step"] - t
                                + e[None, :].astype(c["step"].dtype)}
                               if isinstance(c, dict) and "step" in c else c),
                    caches, is_leaf=lambda c: isinstance(c, dict)
                    and "step" in c)
                newlast = jnp.take_along_axis(
                    ver, jnp.maximum(e - 1, 0)[:, None], axis=1)[:, 0]
                tok = jnp.where(ok, newlast, tok)
                hist, hcnt = drafter.observe(hist, hcnt, ver, e)
                emitted = jnp.arange(t, dtype=jnp.int32)[None, :] < e[:, None]
                budget = budget - e
                poisoned = poisoned | bad
                active = ok & (budget > 0)
                if inject:
                    fin = fin - e
                if metrics:
                    # mirrors the host-side spec accounting exactly: a slot
                    # that ran (e >= 1) proposed k drafts, kept e - 1
                    mx = MX.spec_update(mx, e, bad, k, ring_mod)
                return (i + 1, caches, tok, active, budget, key, hist, hcnt,
                        poisoned, fin,
                        toks_buf.at[i].set(ver), emit_buf.at[i].set(emitted),
                        mx)

            (steps, caches, tok, active, budget, key, hist, hcnt, poisoned,
             fin, toks, emit, mx) = jax.lax.while_loop(
                cond, body, (jnp.int32(0), caches, tok, active, budget, key,
                             hist, hcnt, poisoned, fin, toks0, emit0, mx))
            return (caches, tok, active, budget, key, hist, hcnt, toks,
                    emit, steps, poisoned) + ((fin,) if inject else ()
                                              ) + ((mx,) if metrics else ())

        # fin / mx: empty (None) carries when their feature is off
        if inject and metrics:
            fn = run_spec
        elif inject:
            def fn(params, caches, tok, active, budget, temps, anyt, key,
                   hist, hcnt, poisoned, fin):
                return run_spec(params, caches, tok, active, budget, temps,
                                anyt, key, hist, hcnt, poisoned, fin, None)
        elif metrics:
            def fn(params, caches, tok, active, budget, temps, anyt, key,
                   hist, hcnt, poisoned, mx):
                return run_spec(params, caches, tok, active, budget, temps,
                                anyt, key, hist, hcnt, poisoned, None, mx)
        else:
            def fn(params, caches, tok, active, budget, temps, anyt, key,
                   hist, hcnt, poisoned):
                return run_spec(params, caches, tok, active, budget, temps,
                                anyt, key, hist, hcnt, poisoned, None, None)

        # caches + metrics carries donated: see _make_scan
        mx_arg = 11 + (1 if inject else 0)
        don = self._donate(1, mx_arg) if metrics else self._donate(1)
        if self.mesh is None:
            return _jit("swat_spec_scan", fn, donate_argnums=don)
        cache_sh = self.slot_cache_sharding(slots)
        veci = self.batch_sharding(self._sds((slots,)), slots)
        vecb = self.batch_sharding(self._sds((slots,), jnp.bool_), slots)
        vecf = self.batch_sharding(self._sds((slots,), jnp.float32), slots)
        hist_sh = self.batch_sharding(
            self._sds((slots, drafter.history)), slots)
        blk = self.batch_sharding(
            self._sds((n, slots, t)), slots, slot_dim=1)
        fin_in = (veci,) if inject else ()
        mx_in = ((MX.metrics_shardings(veci, self._rep),) if metrics
                 else ())
        return _jit(
            "swat_spec_scan", fn,
            in_shardings=(self.param_sharding, cache_sh, veci, vecb, veci,
                          vecf, self._rep, self._rep, hist_sh, veci,
                          vecb) + fin_in + mx_in,
            out_shardings=(cache_sh, veci, vecb, veci, self._rep, hist_sh,
                           veci, blk, blk, self._rep, vecb) + fin_in + mx_in,
            donate_argnums=don)


@functools.lru_cache(maxsize=16)
def _get_compiled(cfg: ModelConfig, max_len: int, decode_impl: str,
                  top_k: int, mesh=None, profile: str = "tp",
                  tokens_per_step: int = 1, speculative: int = 0,
                  draft: Optional[NGramDrafter] = None,
                  donate: bool = True,
                  faults: FaultPlan = FaultPlan(),
                  kv_layout: str = "contiguous",
                  metrics: bool = False) -> _Compiled:
    return _Compiled(cfg, max_len, decode_impl, top_k, mesh, profile,
                     tokens_per_step, speculative, draft, donate, faults,
                     kv_layout, metrics)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 8,
                 max_len: int = 4096, seed: int = 0, scan_steps: int = 8,
                 batch_prefill: bool = True, prefill_chunk: int = 0,
                 max_prefill_tokens: int = 8192, pad_to: int = 16,
                 top_k: int = 0, decode_impl: str = "ref",
                 mesh=None, profile: str = "tp", tokens_per_step: int = 1,
                 speculative: int = 0, draft: Optional[NGramDrafter] = None,
                 donate: bool = True, transfer_guard: bool = True,
                 faults: Optional[FaultPlan] = None,
                 max_prompt_len: Optional[int] = None,
                 max_pending: Optional[int] = None,
                 spec_min_acceptance: float = 0.0,
                 spec_acceptance_window: int = 4,
                 spec_retry_blocks: int = 8,
                 spec_resume_acceptance: Optional[float] = None,
                 kv_layout: str = "contiguous",
                 share_prefix: bool = False,
                 share_min_prefix: int = 16,
                 metrics: bool = False,
                 trace_capacity: int = 4096):
        """scan_steps=1 degenerates to the seed engine's per-token host
        sync; prefill_chunk=0 disables sequence-axis chunking (single-shot
        batched prefill); batch_prefill=False admits one prompt per prefill
        call (the seed behavior, kept for benchmarking).

        tokens_per_step: ring lookahead for multi-token decode steps — the
        caches carry T-1 extra ring rows and every compiled entry point is
        keyed by it, so a speculative-decode step can verify T draft
        tokens per dispatch on these caches. Generated tokens are unchanged
        (the positional window mask hides the extra ring depth).

        speculative: draft tokens per decode step (0 = sequential decode).
        Each step proposes `speculative` tokens with `draft` (default:
        NGramDrafter self-drafting), verifies them all in one decode_step
        dispatch, and emits the longest verified prefix plus the model's
        own next token — 1..speculative+1 tokens per step per slot. Greedy
        requests produce bitwise the sequential engine's tokens (the
        tests/test_speculative.py contract); acceptance telemetry
        accumulates in `self.stats` / `self.acceptance_rate`. Forces
        tokens_per_step up to speculative+1 so the ring carries the
        lookahead rows the rollback guarantee needs.

        mesh: optional jax.sharding.Mesh — params are placed once at
        construction (`param_sharding(profile)`), caches/decode state carry
        the serving sharding rules, and every jitted call runs partitioned.
        batch_slots should be a multiple of the slot-axis size
        (('pod',)'data') for the slot dim to actually shard; indivisible
        counts degrade gracefully to replication.

        donate: donate the ring-cache carries of the decode-scan,
        cache-insert, and chunked-prefill entry points so the compiled
        executables alias them in place (no full-cache copy per block —
        tokens are unchanged, only buffer reuse). False keeps the copying
        programs: the analyzer's known-bad fixture and the serve_bench
        donation A/B.

        transfer_guard: run the steady-state decode dispatch under
        jax.transfer_guard("disallow") so any implicit host<->device
        transfer that sneaks into the hot loop raises instead of silently
        syncing every block (the scheduled host syncs — staging admitted
        slots, draining block outputs — are explicit transfers and stay
        legal).

        faults: a `serving.faults.FaultPlan` — deterministic chaos layer.
        Part of the compile identity: logit faults compile a countdown
        vector + one masked select into the scan body; the default plan
        compiles the production program. The numerical GUARDS (quarantine
        of non-finite rows) are always compiled in — on a clean run they
        are bitwise-invisible.

        max_prompt_len: reject (status "rejected") prompts longer than
        this instead of admitting them; None (default) serves long prompts
        via the ring exactly as before.
        max_pending: bounded-queue backpressure — `run()` rejects requests
        beyond this queue depth (status "rejected", reason "queue
        overflow") instead of buffering unboundedly under overload.

        spec_min_acceptance: speculative-decode auto-disable — when the
        windowed draft acceptance rate (over `spec_acceptance_window`
        spec blocks) drops below this, the engine decodes sequentially
        (same tokens for greedy requests, no wasted verify lanes). After
        `spec_retry_blocks` sequential blocks it probes with one spec
        block and re-enables only if that block's acceptance reaches
        `spec_resume_acceptance` (default: same threshold) — the
        hysteresis that stops flapping. 0.0 (default) disables the
        ladder.

        kv_layout: "contiguous" (per-slot ring buffers, the historical
        layout) or "paged" — slot caches become fixed-size PAGE_SIZE-row
        blocks in a device pool addressed through per-slot block tables
        (serving/paged.py). Every allocation tiles exactly into pages, so
        the decode gather-view is bitwise the contiguous ring and tokens
        are IDENTICAL across layouts; what pages buy is block-granular
        bookkeeping — prefix sharing, copy-on-write, O(1) slot free.
        share_prefix: paged single-device engines only — when an admitted
        batch shares a token prefix of at least `share_min_prefix`
        (PrefillPlan.prefix_len, the scheduler's radix-trie LCP) and
        prefill chunking is on, the prefix prefills ONCE, broadcasts to
        every row, and untouched prefix blocks are refcount-shared until
        a ring write diverges them (copy-on-write).

        metrics: compile device-resident telemetry counters (swatscope
        layer 1) into the decode/verify scan bodies — one extra donated
        int32-pytree carry, read out ONLY at `device_metrics()` /
        `metrics_text()`, never inside a block. Part of the compile
        identity: metrics=False (default) engines run today's exact
        programs; metrics=True tokens are bitwise identical (the
        test_telemetry.py contract).
        trace_capacity: ring-buffer depth of the always-on host-side
        `self.tracer` (request lifecycle spans, decode-block spans, the
        `swat.*` host spans, and the unified degradation-event stream).
        Bounded memory forever — O(trace_capacity), however long the
        engine serves."""
        self.cfg = cfg
        self.slots = batch_slots
        self.max_len = max_len
        self.scan_steps = max(1, scan_steps)
        self.batch_prefill = batch_prefill
        self.prefill_chunk = (prefill_chunk
                              if Mod.prefill_chunkable(cfg) else 0)
        self.top_k = top_k
        self.decode_impl = decode_impl
        self.speculative = max(0, speculative)
        if self.speculative:
            assert Mod.speculative_supported(cfg), (
                "speculative decode needs rotary positions and "
                "attention-only layers (no mamba/encoder-decoder state to "
                "roll back); config %s does not qualify" % (cfg.name,))
        self.tokens_per_step = max(1, tokens_per_step, self.speculative + 1)
        self.mesh, self.profile = mesh, profile
        self.transfer_guard = transfer_guard
        self.faults = faults if faults is not None else FaultPlan()
        if self.faults.fail_pallas_dispatch:
            F.install_kernel_failure()
        assert kv_layout in ("contiguous", "paged"), kv_layout
        self.kv_layout = kv_layout
        # block sharing needs the shared global-id pool (single device);
        # sharded engines keep per-slot local ids, so sharing is a no-op
        self.share_prefix = (bool(share_prefix) and kv_layout == "paged"
                             and mesh is None)
        self.share_min_prefix = max(1, share_min_prefix)
        self.metrics = bool(metrics)
        self.key = jax.random.PRNGKey(seed)
        self._c = _get_compiled(cfg, max_len, decode_impl, top_k, mesh,
                                profile, self.tokens_per_step,
                                self.speculative,
                                get_drafter(draft) if self.speculative
                                else None, donate, self.faults, kv_layout,
                                self.metrics)
        self.drafter = self._c.drafter
        self.params = (params if mesh is None
                       else jax.device_put(params, self._c.param_sharding))
        # host-side lifecycle tracer (always on — O(1) Python per hook,
        # zero device work) + its subscription to the unified degradation
        # bus: the tracer's bounded `events` ring sees every record_event
        # without consuming the bus (tests/benches still drain it)
        self.tracer = Tracer(capacity=trace_capacity)
        TEV.BUS.subscribe(self.tracer.on_bus_event)
        self.scheduler = Scheduler(
            max_prefill_tokens=max_prefill_tokens, pad_to=pad_to,
            slot_quantum=self._c.slot_quantum(batch_slots),
            max_prompt_len=max_prompt_len, vocab_size=cfg.vocab_size,
            tracer=self.tracer)
        self.max_pending = max_pending
        self.spec_min_acceptance = float(spec_min_acceptance)
        self.spec_resume_acceptance = float(
            spec_min_acceptance if spec_resume_acceptance is None
            else spec_resume_acceptance)
        self.spec_retry_blocks = spec_retry_blocks
        self._acc_window: Deque[Tuple[int, int]] = collections.deque(
            maxlen=max(1, spec_acceptance_window))
        self._spec_off = False            # degradation-ladder state
        self._blocks_since_spec = 0
        self._hist_stale = False          # drafter history vs slot_out
        self._fallback_warned = False
        self._cache_poison_applied: set = set()
        self._faults_fired: set = set()   # slots whose logit fault fired
        self._run_t0: Optional[float] = None
        if kv_layout == "paged":
            from repro.serving.paged import PagedManager
            self._paged: Optional[PagedManager] = PagedManager(
                Mod.paged_layout(cfg, max_len, self._c.lookahead),
                batch_slots, mode="shared" if mesh is None else "local")
        else:
            self._paged = None
        # next ring-write token position per slot (paged COW planning)
        self._slot_pos = np.zeros((batch_slots,), np.int64)
        self._retry_counts: Dict[int, int] = {}
        self._readmit: List[Request] = []

        self.caches = self._c.fresh_slot_caches(batch_slots)
        self.slot_free = [True] * batch_slots
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_out: List[List[int]] = [[] for _ in range(batch_slots)]
        self.slot_last = np.zeros((batch_slots,), np.int32)
        self.slot_budget = np.zeros((batch_slots,), np.int32)
        self.slot_temp = np.zeros((batch_slots,), np.float32)
        if self.speculative:
            self.slot_hist, self.slot_hcnt = \
                self.drafter.init_state(batch_slots)
        # device-staged copies of the per-slot decode vectors; None means
        # "stale, rebuild from the host mirrors" (set by every admission)
        self._dev: Optional[Dict[str, Any]] = None
        self._completed: List[Result] = []
        # device metrics carry (metrics=True): persistent OUTSIDE _dev so
        # admission restages never reset counters; donated per block like
        # the caches, read only via device_metrics()'s explicit sync
        self._mx: Optional[Dict[str, Any]] = None
        if self.metrics:
            self._stage_metrics()
        # decode telemetry (accumulated across run()/step() calls):
        # spec_steps counts executed verify dispatches, draft_proposed /
        # draft_accepted count drafts offered vs kept (acceptance_rate).
        # PER-ATTEMPT vs PER-REQUEST accounting (the retry drift fix):
        # tokens_emitted counts every token produced by decode steps —
        # WORK, including tokens a failed attempt discarded before its
        # readmission; tokens_delivered counts tokens in finalized
        # Results — exactly once per request, whatever max_retries did.
        # The resilience counters mirror the degradation-event bus
        # (faults.consume_events) so a bench/test can assert "nothing
        # degraded" from either side. Work at the layer boundaries, the
        # same counts the swat.admit / swat.decode spans carry:
        # prefill_tokens_computed counts real prompt tokens prefilled,
        # prefill_tokens_padded the tokens the device computed for them
        # (rows x padded length); decode_steps counts the steps decode
        # blocks ran, decode_slot_steps those steps times the slot count
        # (every slot computes every step, live or not), and
        # decode_steps_row_write the steps whose program wrote their K/V
        # rows into the rings in place (`_Compiled.row_write`).
        self.stats = {"spec_steps": 0, "draft_proposed": 0,
                      "draft_accepted": 0, "tokens_emitted": 0,
                      "tokens_delivered": 0,
                      "quarantined": 0, "rejected": 0, "deadline": 0,
                      "failed": 0, "kernel_fallbacks": 0,
                      "spec_autodisable": 0, "spec_resume": 0,
                      "readmitted": 0, "prefill_tokens_computed": 0,
                      "prefill_tokens_padded": 0,
                      "prefill_prefix_shared": 0, "cow_moves": 0,
                      "decode_blocks": 0, "decode_steps": 0,
                      "decode_slot_steps": 0, "decode_steps_row_write": 0}

    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the verifier kept."""
        p = self.stats["draft_proposed"]
        return self.stats["draft_accepted"] / p if p else 0.0

    # -------------------------------------------------------- observability --
    def _stage_metrics(self):
        """(Re)create the device metrics carry, zeroed, placed to match the
        scan in_shardings (the guarded dispatch may not reshard)."""
        self._mx = MX.init_metrics(self.slots)
        if self.mesh is not None:
            veci = self._c.batch_sharding(
                self._c._sds((self.slots,)), self.slots)
            self._mx = jax.device_put(
                self._mx, MX.metrics_shardings(veci, self._c._rep))

    def device_metrics(self, per_slot: bool = False) -> Dict[str, Any]:
        """Read the device-resident counters — an EXPLICIT, scheduled host
        sync outside the decode transfer guard (the one place layer-1
        telemetry touches the host). Returns int totals, or the raw
        (slots,) vectors with per_slot=True. Empty when metrics=False."""
        if self._mx is None:
            return {}
        host = {k: np.asarray(v) for k, v in self._mx.items()}
        if per_slot:
            return host
        return {k: int(v.sum()) if v.ndim else int(v)
                for k, v in host.items()}

    def metrics_text(self) -> str:
        """Prometheus text exposition of host stats + device counters +
        tracer latency quantiles (one scrape endpoint's worth)."""
        counters = dict(self.stats)
        counters.update({f"device_{k}": v
                         for k, v in self.device_metrics().items()})
        counters.update(self.paged_stats())
        doc = {f"device_{k}": f"{v} (device-resident counter)"
               for k, v in MX.COUNTER_DOC.items()}
        return prometheus_text(counters, self.tracer.latency_summary(),
                               doc=doc)

    def chrome_trace(self) -> dict:
        """Chrome-trace JSON of the tracer's retained window (load in
        chrome://tracing / Perfetto)."""
        return self.tracer.chrome_trace(metadata={
            "model": self.cfg.name, "slots": self.slots,
            "decode_impl": self.decode_impl,
            "speculative": self.speculative, "kv_layout": self.kv_layout,
            "metrics": self.metrics})

    def snapshot(self) -> Dict[str, Any]:
        """One observability snapshot (the `swat-scope` CLI's payload):
        host stats, device counters, latency quantiles, pool occupancy."""
        return {"stats": dict(self.stats),
                "device": self.device_metrics(),
                "latency": self.tracer.latency_summary(),
                "paged": self.paged_stats(),
                "ring_modulus": self._c.ring_mod,
                "dropped_trace_records": self.tracer.dropped_requests}

    # --------------------------------------------------------- resilience --
    _STATUS_COUNTER = {"rejected": "rejected", "poisoned": "quarantined",
                       "deadline": "deadline", "failed": "failed"}
    _STATUS_EVENT = {"rejected": "request_rejected",
                     "poisoned": "slot_quarantined",
                     "deadline": "deadline_expired",
                     "failed": "request_failed"}

    def _finish(self, rid: int, tokens: List[int], status: str,
                reason: str = "") -> Result:
        """Finalize one request into self._completed (the ONLY result
        store — run() drains it, so a mid-loop exception never loses
        finished work) and mirror non-ok statuses to stats + event bus."""
        res = Result(rid, tokens, status=status, reason=reason,
                     retries=self._retry_counts.get(rid, 0))
        self._completed.append(res)
        # delivered = tokens in the FINAL Result, counted exactly once per
        # request (readmitted attempts never reach here); contrast
        # tokens_emitted, the per-attempt work counter
        self.stats["tokens_delivered"] += len(tokens)
        self.tracer.on_finish(rid, status, len(tokens))
        if status != "ok":
            self.stats[self._STATUS_COUNTER[status]] += 1
            F.record_event(self._STATUS_EVENT[status], rid=rid,
                           reason=reason)
        return res

    def take_completed(self) -> List[Result]:
        """Drain finished Results (rid order). After an exception escaped
        `run()`, this recovers everything that finished before it."""
        out, self._completed = self._completed, []
        return sorted(out, key=lambda r: r.rid)

    def _drain_rejections(self):
        for req, reason in self.scheduler.take_rejected():
            self._finish(req.rid, [], "rejected", reason)

    def _free_slot(self, s: int):
        self.slot_free[s] = True
        self.slot_req[s] = None
        self.slot_budget[s] = 0
        self._slot_pos[s] = 0
        if self._paged is not None:
            # release refcounts and park the table on the slot's scratch
            # block; the park is flushed to the device before the next
            # decode dispatch (manager.dirty) or by the next admission's
            # full-table push — nothing runs in between
            self._paged.free(s)

    def _expire_deadlines(self, pending: Deque[Request]):
        """Finalize requests whose deadline (seconds since run()
        submission) passed — queued ones with no tokens, live ones with
        their partial output. Block-boundary resolution."""
        if self._run_t0 is None:
            return
        elapsed = time.monotonic() - self._run_t0
        if pending and any(r.deadline is not None for r in pending):
            keep = []
            for r in pending:
                if r.deadline is not None and elapsed > r.deadline:
                    self._finish(r.rid, [], "deadline",
                                 f"deadline {r.deadline}s expired in queue")
                else:
                    keep.append(r)
            pending.clear()
            pending.extend(keep)
        freed = False
        for s in range(self.slots):
            req = self.slot_req[s]
            if (req is not None and req.deadline is not None
                    and elapsed > req.deadline):
                self._finish(req.rid, self.slot_out[s], "deadline",
                             f"deadline {req.deadline}s expired mid-decode")
                self._free_slot(s)
                freed = True
        if freed:
            self._dev = None      # host slot state changed: restage

    # ------------------------------------------------------------ prefill --
    def _prefill_into(self, plan: PrefillPlan, slots: List[int]):
        """Prefill one planned batch into free slots, as a `swat.admit`
        host span (rows, padded_len, prompt_tokens: real tokens computed,
        padded_tokens: tokens the device computes) with children
        `prefill`, `sample` (through the wait for the first tokens) and
        `insert` (the cache insert)."""
        n, l_pad = plan.tokens.shape
        prefix = 0
        if (self.share_prefix and n >= 2 and self.prefill_chunk
                and plan.prefix_len >= self.share_min_prefix):
            prefix = int(plan.prefix_len)
        real = prefix + sum(max(int(l) - prefix, 0) for l in plan.lengths)
        padded = prefix + n * (l_pad - prefix)
        self.stats["prefill_tokens_computed"] += real
        self.stats["prefill_tokens_padded"] += padded
        with self.tracer.span("swat.admit", rows=n, padded_len=l_pad,
                              prompt_tokens=real, padded_tokens=padded):
            self._admit_batch(plan, slots, prefix)

    def _admit_batch(self, plan: PrefillPlan, slots: List[int],
                     prefix: int):
        """The body of `_prefill_into`: prefill, first tokens, insert, and
        the slots' host state."""
        span = self.tracer.span
        self.tracer.on_admit([r.rid for r in plan.requests])
        n, l_pad = plan.tokens.shape
        with span("swat.admit.prefill"):
            tokens = jnp.asarray(plan.tokens)
            lengths = jnp.asarray(plan.lengths)
            if prefix:
                # prefill the shared prefix ONCE on one row, broadcast the
                # cache to every admitted row, then walk only the per-row
                # suffixes — prefill compute drops from sum(len_i) to
                # prefix + sum(len_i - prefix)
                out1, c1 = self._c.prefill(1)(
                    self.params, tokens[:1, :prefix],
                    jnp.full((1,), prefix, jnp.int32))
                last, caches = self._c.broadcast_prefix(n)(c1, out1[:, 0])
                for p in range(prefix, l_pad, self.prefill_chunk):
                    chunk = tokens[:, p:p + self.prefill_chunk]
                    last, caches = self._c.chunk(n)(
                        self.params, caches, chunk, jnp.int32(p), lengths,
                        last)
                logits = last
                self.stats["prefill_prefix_shared"] += 1
            elif self.prefill_chunk and l_pad > self.prefill_chunk:
                caches = self._c.fresh_caches(n)
                last = jnp.zeros((n, self.cfg.vocab_size), jnp.float32)
                for p in range(0, l_pad, self.prefill_chunk):
                    chunk = tokens[:, p:p + self.prefill_chunk]
                    last, caches = self._c.chunk(n)(
                        self.params, caches, chunk, jnp.int32(p), lengths,
                        last)
                logits = last
            else:
                out, caches = self._c.prefill(n)(self.params, tokens,
                                                  lengths)
                logits = out[:, 0]
        with span("swat.admit.sample"):
            temps = np.asarray([r.temperature for r in plan.requests],
                               np.float32)
            self.key, sub = jax.random.split(self.key)
            first = np.asarray(self._c.sample(n)(sub, logits,
                                                 jnp.asarray(temps)))
        self.tracer.on_first_token([r.rid for r in plan.requests])
        with span("swat.admit.insert"):
            if self._paged is not None:
                # shareability is judged against EVERY position prefill
                # wrote — padded rows carry (masked) garbage up to l_pad, so
                # the conservative write-span per row is [prefix, l_pad)
                self._paged.admit(slots, [l_pad] * n, prefix_len=prefix)
                ptables = {f"l{i}": jnp.asarray(t)
                           for i, t in self._paged.tables.items()}
                self.caches = self._c.insert_paged(self.slots, n)(
                    self.caches, caches, jnp.asarray(slots, jnp.int32),
                    ptables)
            else:
                self.caches = self._c.insert(self.slots, n)(
                    self.caches, caches, jnp.asarray(slots, jnp.int32))
        for s, l in zip(slots, plan.lengths):
            self._slot_pos[s] = int(l)
        for i, (req, s) in enumerate(zip(plan.requests, slots)):
            self.slot_out[s] = [int(first[i])]
            self.slot_last[s] = int(first[i])
            self.slot_temp[s] = req.temperature
            if self.speculative:
                # drafter context = the prompt plus the first sampled
                # token (hist must end at slot_last — propose() matches
                # the suffix that includes the pending token). slot_hist
                # may be device-resident after a decode block; pull it
                # back to numpy to write the seeded row.
                self.slot_hist = np.array(self.slot_hist, np.int32)
                self.slot_hcnt = np.array(self.slot_hcnt, np.int32)
                row, cnt = self.drafter.seed_row(
                    np.concatenate([req.prompt, [first[i]]]))
                self.slot_hist[s] = row
                self.slot_hcnt[s] = cnt
            budget = req.max_new_tokens - 1
            if budget <= 0:
                self._finish(req.rid, self.slot_out[s], "ok")
                self._free_slot(s)
            else:
                self.slot_free[s] = False
                self.slot_req[s] = req
                self.slot_budget[s] = budget
        self._dev = None          # host mirrors changed; restage on device

    def _admit(self, pending: Deque[Request]):
        while pending:
            free = [s for s in range(self.slots) if self.slot_free[s]]
            if not free:
                break
            width = len(free) if self.batch_prefill else 1
            plan = self.scheduler.plan(pending, width)
            if plan is None:
                break
            self._prefill_into(plan, free[:len(plan.requests)])
        # requests the scheduler refused (empty/oversized/out-of-vocab
        # prompts) finalize as status "rejected" — they never crash a batch
        self._drain_rejections()

    # ------------------------------------------------------------- decode --
    def _spec_mode(self) -> Tuple[bool, bool]:
        """(run speculatively this block?, is this a hysteresis probe?)
        under the acceptance ladder. Auto-disabled engines decode
        sequentially (same greedy tokens, no wasted verify lanes) and
        periodically probe one spec block to earn speculation back."""
        if not self.speculative:
            return False, False
        if not self._spec_off:
            return True, False
        self._blocks_since_spec += 1
        if self.spec_retry_blocks and \
                self._blocks_since_spec >= self.spec_retry_blocks:
            return True, True
        return False, False

    def _spec_ladder_update(self, prop: int, acc: int, probe: bool):
        """Feed one spec block's acceptance into the ladder."""
        if self.spec_min_acceptance <= 0:
            return
        if probe:
            rate = acc / prop if prop else 0.0
            if rate >= self.spec_resume_acceptance:
                self._spec_off = False
                self.stats["spec_resume"] += 1
                F.record_event("spec_resume", rate=rate)
                self._acc_window.clear()
            else:
                self._blocks_since_spec = 0    # stay off; probe again later
            return
        self._acc_window.append((prop, acc))
        wp = sum(p for p, _ in self._acc_window)
        wa = sum(a for _, a in self._acc_window)
        if wp >= 2 * self.speculative and wa / wp < self.spec_min_acceptance:
            self._spec_off = True
            self._blocks_since_spec = 0
            self._acc_window.clear()
            self.stats["spec_autodisable"] += 1
            F.record_event("spec_autodisable", rate=wa / wp)

    def _reseed_history(self, live: List[int]):
        """Sequential-fallback blocks emit tokens the drafter never
        observed; rebuild each live slot's history (prompt + full output)
        before the next speculative block."""
        hist = np.array(self.slot_hist, np.int32)
        hcnt = np.array(self.slot_hcnt, np.int32)
        for s in live:
            row, cnt = self.drafter.seed_row(
                np.concatenate([self.slot_req[s].prompt, self.slot_out[s]]))
            hist[s], hcnt[s] = row, cnt
        self.slot_hist, self.slot_hcnt = hist, hcnt
        self._hist_stale = False
        self._dev = None

    def _apply_cache_poisons(self, live: List[int]):
        """Chaos: smear NaN over a slot's ring K caches once it has
        emitted its trigger count — corruption the guard didn't see born.
        The next attention read propagates it into that slot's logits,
        where the in-scan guard quarantines it."""
        if not self.faults.poison_cache:
            return
        tokens_done = [len(self.slot_out[s]) if not self.slot_free[s] else -1
                       for s in range(self.slots)]
        for s in self.faults.cache_poisons_due(
                self.slots, tokens_done, self._cache_poison_applied):
            self._cache_poison_applied.add(s)
            if self._paged is not None:
                # the slot must own its blocks exclusively before NaN-ing:
                # poisoning a refcount-shared prefix block would quarantine
                # every sharer, not the targeted slot
                self._paged_flush(self._paged.force_private(s))
                self.caches = _poison_slot_k_paged(self.caches, s)
            else:
                self.caches = _poison_slot_k(self.caches, s)
            F.record_event("cache_poisoned", slot=s)

    # --------------------------------------------------------------- paged --
    def _paged_flush(self, moves: Dict[int, List[Tuple[int, int]]]):
        """Dispatch COW block copies + the host table mirror to the device
        (outside the decode transfer guard — tables are an explicit,
        scheduled host->device push). No-op when nothing changed."""
        pm = self._paged
        m = max((len(v) for v in moves.values()), default=0)
        if m == 0 and not pm.dirty:
            return
        self.stats["cow_moves"] += sum(len(v) for v in moves.values())
        tables = {f"l{i}": jnp.asarray(t) for i, t in pm.tables.items()}
        if m:
            # one bucketed move width per compile; layers with fewer moves
            # pad with scratch->scratch self-copies (scratch ids are never
            # real destinations, so padding can't collide with a move)
            mpad = 1 << (m - 1).bit_length()
            srcs, dsts = {}, {}
            for i, mv in moves.items():
                sc = pm.scratch_id(i, 0)
                pad = mpad - len(mv)
                srcs[f"l{i}"] = jnp.asarray(
                    [a for a, _ in mv] + [sc] * pad, jnp.int32)
                dsts[f"l{i}"] = jnp.asarray(
                    [b for _, b in mv] + [sc] * pad, jnp.int32)
            self.caches = self._c.fixup(self.slots, mpad)(
                self.caches, tables, srcs, dsts)
        else:
            self.caches = self._c.fixup(self.slots, 0)(
                self.caches, tables, {}, {})
        pm.dirty = False

    def _paged_sync(self, n: int):
        """Pre-block paged maintenance: plan copy-on-write for every ring
        row this block can write ([pos, pos+n*T) per live slot) and flush
        moves + any parked tables."""
        pm = self._paged
        positions = {s: int(self._slot_pos[s]) for s in range(self.slots)
                     if not self.slot_free[s]}
        self._paged_flush(
            pm.cow_moves(positions, n * self._c.tokens_per_step))

    def paged_stats(self) -> Dict[str, int]:
        """Block-pool occupancy (shared-prefix dedup shows up here)."""
        if self._paged is None:
            return {}
        return {"blocks_in_use": self._paged.blocks_in_use(),
                "blocks_total": self._paged.blocks_total()}

    def _kernel_fallback(self, err, n: int) -> List[Result]:
        """Rung one of the degradation ladder: the Pallas decode kernel
        failed to dispatch — swap this engine to the reference impl (for
        good: `_get_compiled` keys by impl, so this is a rebuild, not a
        recompile storm) and retry the block. The injected failure raises
        at trace time, BEFORE the donated ring caches are consumed, so the
        retry serves every in-flight slot untouched; if a mid-execution
        failure DID consume the donation, the slots cannot be resumed and
        finalize as status "failed" instead of silently garbage."""
        self.stats["kernel_fallbacks"] += 1
        F.record_event("pallas_fallback", error=str(err))
        if not self._fallback_warned:
            self._fallback_warned = True
            warnings.warn(
                "pallas decode dispatch failed; this engine now decodes "
                f"with the reference impl ({err})",
                RuntimeWarning, stacklevel=3)
        self.decode_impl = "ref"
        self._c = _get_compiled(self.cfg, self.max_len, "ref", self.top_k,
                                self.mesh, self.profile,
                                self.tokens_per_step, self.speculative,
                                self.drafter, self._c.donate, self.faults,
                                self.kv_layout, self.metrics)
        deleted = any(getattr(l, "is_deleted", lambda: False)()
                      for l in jax.tree.leaves(self.caches))
        if self.metrics and any(getattr(l, "is_deleted", lambda: False)()
                                for l in jax.tree.leaves(self._mx)):
            # the failed dispatch consumed the donated metrics carry:
            # counters restart from zero (documented loss — rare, and the
            # kernel_fallbacks stat records that it happened)
            self._stage_metrics()
        if not deleted:
            return self._decode_block(n)
        done = []
        for s in range(self.slots):
            if not self.slot_free[s]:
                req = self.slot_req[s]
                used = self._retry_counts.get(req.rid, 0)
                if used < req.max_retries:
                    # bounded retry: requeue through the normal admission
                    # path — the retry re-prefills from the prompt (the
                    # slot's partial output died with the donated caches)
                    self._retry_counts[req.rid] = used + 1
                    self.stats["readmitted"] += 1
                    F.record_event("request_readmitted", rid=req.rid,
                                   retry=used + 1)
                    self._readmit.append(req)
                    self._free_slot(s)
                else:
                    done.append(self._finish(
                        req.rid, self.slot_out[s], "failed",
                        "kernel dispatch failed after cache donation"))
                    self._free_slot(s)
        if self._paged is not None:
            self._paged.reset()
        self.caches = self._c.fresh_slot_caches(self.slots)
        self._dev = None
        return done

    def _decode_block(self, n: int) -> List[Result]:
        """Run n decode steps on-device (one host sync), then retire
        finished slots. Speculative engines run n draft/verify/accept
        steps instead, each emitting 1..speculative+1 tokens per slot.

        Host spans: `swat.decode` (n, slots, live, row_write: 1 if the
        program writes K/V rows in place; full_rows_needed: over the
        block's steps, live slots and full-attention layers, the cache
        rows a query may see, position + 1; full_rows_read: the rows
        those layers' attention reads for the live slots over the block's
        steps, by `kops.decode_rows_read`) around the block, with
        children `stage` (host work before dispatch: restaging the slot
        vectors and placing them), `dispatch` (the scan call until it
        returns), `sync` (the wait for its tokens) and `retire`
        (everything after; `emitted`, the tokens the block emitted).

        Resilience: the compiled scan body carries a per-slot `poisoned`
        flag — a slot whose logits go non-finite stops emitting on device
        and is QUARANTINED here (finalized as status "poisoned", slot
        freed, every other slot untouched); a Pallas dispatch failure
        falls back to the ref impl (`_kernel_fallback`)."""
        live = [s for s in range(self.slots) if not self.slot_free[s]]
        if not live:
            return []
        span = self.tracer.span
        full, rows = self._c.full_caches
        needed = 0
        if full:
            for s in live:
                p = len(self.slot_req[s].prompt) + len(self.slot_out[s]) - 1
                k = min(n, int(self.slot_budget[s]))
                needed += k * (p + 1) + k * (k - 1) // 2
        with span("swat.decode", n=n, slots=self.slots, live=len(live),
                  row_write=int(self._c.row_write),
                  full_rows_needed=full * needed,
                  full_rows_read=full * n * len(live) * rows) as blk:
            with span("swat.decode.stage"):
                use_spec, probe, inject = self._stage_block(n, live)
            dev = self._dev
            # steady-state guard: every operand is device-resident by now,
            # so the block dispatch must not transfer ANYTHING implicitly —
            # a host-synced scalar or np-array operand inside this loop
            # taxes every block and is exactly what the analyzer's
            # host-sync rule lints for. Explicit syncs (np.asarray on the
            # outputs below) stay legal under "disallow".
            guard = (jax.transfer_guard("disallow") if self.transfer_guard
                     else contextlib.nullcontext())
            extra = (dev["fin"],) if inject else ()
            # metrics carry rides LAST (donated); its updated pytree comes
            # back last and is simply re-fed next block — no host sync here
            mextra = (self._mx,) if self.metrics else ()
            try:
                if use_spec:
                    with span("swat.decode.dispatch"), guard:
                        outs = self._c.spec_scan(n, self.slots)(
                            self.params, self.caches, dev["tok"],
                            dev["active"], dev["budget"], dev["temps"],
                            dev["anyt"], self.key, dev["hist"], dev["hcnt"],
                            dev["poisoned"], *extra, *mextra)
                    (self.caches, tok, active_out, budget, self.key, hist,
                     hcnt, toks, emit, steps, poisoned) = outs[:11]
                    if inject:
                        dev["fin"] = outs[11]
                    if self.metrics:
                        self._mx = outs[11 + (1 if inject else 0)]
                    # drafter state stays device-resident too;
                    # _prefill_into materializes to numpy only when it
                    # needs to seed a row
                    self.slot_hist = hist
                    self.slot_hcnt = hcnt
                    dev.update(tok=tok, active=active_out, budget=budget,
                               hist=hist, hcnt=hcnt, poisoned=poisoned)
                else:
                    with span("swat.decode.dispatch"), guard:
                        outs = self._c.scan(n, self.slots)(
                            self.params, self.caches, dev["tok"],
                            dev["active"], dev["budget"], dev["temps"],
                            dev["anyt"], self.key, dev["poisoned"], *extra,
                            *mextra)
                    (self.caches, tok, active_out, budget, self.key, toks,
                     emit, poisoned) = outs[:8]
                    if inject:
                        dev["fin"] = outs[8]
                    if self.metrics:
                        self._mx = outs[8 + (1 if inject else 0)]
                    dev.update(tok=tok, active=active_out, budget=budget,
                               poisoned=poisoned)
                # the block's host sync: the span from dispatch to here
                # covers dispatch + device execution + drain
                with span("swat.decode.sync"):
                    toks, emit = np.asarray(toks), np.asarray(emit)
            except F.KernelDispatchError as e:
                return self._kernel_fallback(e, n)
            emitted = int(emit.sum())
            with span("swat.decode.retire", emitted=emitted):
                done = self._retire_block(n, live, use_spec, probe, toks,
                                          emit, tok, budget, poisoned,
                                          steps if use_spec else n)
        self.tracer.on_block("spec" if use_spec else "seq", n, blk.t0,
                             emitted, blk.t1)
        return done

    def _stage_block(self, n: int, live: List[int]):
        """Host work before a block's dispatch: chaos cache poisons, paged
        copy-on-write, drafter history, and (re)staging the per-slot
        vectors on device. Returns (speculative?, ladder probe?, logit
        faults compiled in?)."""
        self._apply_cache_poisons(live)
        if self._paged is not None:
            self._paged_sync(n)
        use_spec, probe = self._spec_mode()
        if use_spec and self._hist_stale:
            self._reseed_history(live)
        inject = self._c.faults.has_logit_faults
        if self._dev is None:
            # (re)stage the per-slot vectors on device. Admission is the
            # only writer outside a decode block, so between consecutive
            # blocks the scan's own outputs are reused verbatim and a
            # block dispatch uploads NOTHING — host->device staging of
            # half a dozen tiny arrays costs as much as a decode step on
            # small models.
            active = np.asarray([not f for f in self.slot_free], bool)
            self._dev = dict(
                tok=jnp.asarray(self.slot_last),
                active=jnp.asarray(active),
                budget=jnp.asarray(self.slot_budget),
                temps=jnp.asarray(self.slot_temp),
                # the all-greedy fast-path predicate, precomputed on host:
                # reducing the slot-sharded temps on device would cost a
                # pred[] all-reduce in every scan step (sampling.sample)
                anyt=jnp.asarray(bool(np.any((self.slot_temp > 0)
                                             & active))),
                # freed slots never re-enter `live` without a restage, so
                # zeros here cover every slot the scan may still touch
                poisoned=jnp.zeros((self.slots,), jnp.bool_))
            if inject:
                self._dev["fin"] = jnp.asarray(self.faults.logit_countdown(
                    self.slots,
                    [len(self.slot_out[s]) if not self.slot_free[s] else 0
                     for s in range(self.slots)],
                    fired=self._faults_fired))
            if self.speculative:
                self._dev["hist"] = jnp.asarray(self.slot_hist)
                self._dev["hcnt"] = jnp.asarray(self.slot_hcnt)
            if self.mesh is not None:
                # explicit mesh placement, matching the scan in_shardings —
                # the guarded dispatch below may not reshard implicitly
                sh = self._c.slot_vector_shardings(self.slots)
                self._dev = {k: jax.device_put(v, sh[k])
                             for k, v in self._dev.items()}
        if self.mesh is not None:
            # admission splits self.key on host (default placement); the
            # scan consumes it replicated — re-place explicitly (no-op
            # between consecutive blocks: scan outputs carry _rep already)
            self.key = jax.device_put(self.key, self._c._rep)
        return use_spec, probe, inject

    def _retire_block(self, n, live, use_spec, probe, toks, emit, tok,
                      budget, poisoned, steps) -> List[Result]:
        """Host work after a block's sync: counters, the speculative
        ladder, the paged position mirror, and finalizing finished or
        quarantined slots. `steps`: the steps the device ran."""
        steps = int(steps)
        if use_spec:
            counts = emit.sum(axis=-1)                    # (n, slots)
            ran = counts >= 1
            self.stats["spec_steps"] += steps
            prop = self.speculative * int(ran.sum())
            acc = int((counts[ran] - 1).sum())
            self.stats["draft_proposed"] += prop
            self.stats["draft_accepted"] += acc
            self._spec_ladder_update(prop, acc, probe)
        elif self.speculative:
            self._hist_stale = True   # drafter history lags output
        self.stats["tokens_emitted"] += int(emit.sum())
        self.stats["decode_blocks"] += 1
        self.stats["decode_steps"] += steps
        self.stats["decode_slot_steps"] += steps * self.slots
        if self._c.row_write:
            self.stats["decode_steps_row_write"] += steps
        if self._paged is not None:
            # advance the per-slot ring-write position mirror: sequential
            # steps write one row per executed step unconditionally (+n);
            # spec steps net +e after rollback, and emit rows equal e
            if use_spec:
                adv = emit.sum(axis=(0, 2))
                for s in live:
                    self._slot_pos[s] += int(adv[s])
            else:
                for s in live:
                    self._slot_pos[s] += n
        self.slot_last = np.array(tok, np.int32)      # writable host mirrors
        self.slot_budget = np.array(budget, np.int32)
        poisoned_np = np.asarray(poisoned)
        done: List[Result] = []
        for s in live:
            # row-major over (step[, position]) => chronological order
            self.slot_out[s].extend(
                int(t) for t in toks[:, s][emit[:, s]])
            if poisoned_np[s]:
                # disarm the slot's pending injections: a fault entry
                # targets one occupant, not every future tenant of the slot
                self._faults_fired.add(s)
                done.append(self._finish(
                    self.slot_req[s].rid, self.slot_out[s], "poisoned",
                    "non-finite logits; slot quarantined"))
                self._free_slot(s)
            elif self.slot_budget[s] <= 0:
                done.append(self._finish(
                    self.slot_req[s].rid, self.slot_out[s], "ok"))
                self._free_slot(s)
        return done

    def step(self) -> List[Result]:
        """One decode step for every live slot (the per-token-sync path)."""
        return self._decode_block(1)

    def _block_len(self) -> int:
        """Largest block that can't overshoot any live slot: stop at the
        earliest completion so slots free (and refill) at block boundaries
        and the RNG stream is identical for every scan_steps setting.

        Deliberate tradeoff: a short-budget request drags the whole batch
        to short blocks until it retires, and each distinct n compiles its
        own scan (bounded by scan_steps programs per model). Bucketing n
        would cut compiles but break the scan==stepwise token-for-token
        guarantee test_serving pins down; revisit if serving mixes budgets
        at scale."""
        live_budgets = [int(self.slot_budget[s]) for s in range(self.slots)
                        if not self.slot_free[s]]
        if not live_budgets:
            return 0
        floor = min(live_budgets)
        # Speculative blocks use the SAME floor: a spec step emits 1..T
        # tokens, so b steps always suffice and the per-slot budget clamp
        # plus the all-done early exit make any block length safe. Sizing
        # by ceil(b/T) instead (the fastest possible finish) assumes full
        # acceptance and collapses near-drain blocks to n=1 — a host
        # round trip per step, which is exactly the seed-engine overhead
        # batching exists to kill. A slot that finishes mid-block idles
        # until the block ends (refill latency <= scan_steps, the same
        # bound the sequential engine has).
        return max(1, min(self.scan_steps, floor))

    # --------------------------------------------------------------- run ---
    def run(self, requests: List[Request]) -> List[Result]:
        """Serve a batch to completion; one Result per request, rid order,
        each carrying a `status` (see STATUSES). Every finished request
        lands in `self._completed` the moment it finalizes — never a
        mid-loop local — so if an exception escapes this loop the caller
        recovers everything already served via `take_completed()` (the
        old code lost them: completed Results sat in a local `results`
        list the raise threw away).

        Overload: beyond `max_pending` queued requests, the tail is
        REJECTED up front (bounded-queue backpressure — an overloaded
        engine sheds load instead of buffering toward OOM). Per-request
        `deadline`s are measured from this submission and enforced at
        block boundaries."""
        self._run_t0 = time.monotonic()
        pending: Deque[Request] = collections.deque()
        for r in requests:
            self.tracer.on_submit(r.rid)
            if self.max_pending is not None and \
                    len(pending) >= self.max_pending:
                self._finish(r.rid, [], "rejected",
                             f"queue overflow (max_pending="
                             f"{self.max_pending})")
            else:
                pending.append(r)
        try:
            while pending or self._readmit or not all(self.slot_free):
                if self._readmit:
                    # failed-slot retries rejoin the queue tail: FCFS among
                    # themselves, no preemption of already-queued work
                    readd, self._readmit = self._readmit, []
                    pending.extend(readd)
                self._expire_deadlines(pending)
                self._admit(pending)
                n = self._block_len()
                if n:
                    self._decode_block(n)
        finally:
            # surface scheduler rejections even if the loop died between
            # plan() and the next _admit drain
            self._drain_rejections()
            self._run_t0 = None
        return self.take_completed()


def _poison_slot_k(caches, slot: int):
    """Overwrite one slot's ring K caches with NaN (every layer, every
    super-block) — the fault harness's cache-corruption primitive."""
    def visit(c):
        if isinstance(c, dict) and "k" in c:
            c = dict(c)
            c["k"] = c["k"].at[:, slot].set(jnp.nan)
        return c
    return jax.tree.map(visit, caches,
                        is_leaf=lambda c: isinstance(c, dict) and "k" in c)


def _poison_slot_k_paged(caches, slot: int):
    """Paged twin of `_poison_slot_k`: NaN the K pool blocks the slot's
    table references. Caller must have forced the slot's blocks private
    first (PagedManager.force_private) and flushed the moves."""
    def visit(c):
        if isinstance(c, dict) and "pk" in c:
            c = dict(c)
            if c["pk"].ndim == 6:       # local per-slot pool (S,B,nb+1,...)
                c["pk"] = c["pk"].at[:, slot].set(jnp.nan)
            else:                        # shared pool: poison the table row
                ids = c["table"][0, slot]
                c["pk"] = c["pk"].at[:, ids].set(jnp.nan)
        return c
    return jax.tree.map(visit, caches,
                        is_leaf=lambda c: isinstance(c, dict) and "pk" in c)


def ring_cache_bytes(cfg: ModelConfig, batch: int, context: int) -> int:
    """Decode-cache bytes — the paper's Fig. 3 memory comparison. Window
    attention: O(window); dense: O(context). Counts PHYSICAL rows
    (`cache_allocation`: logical capacity + the tile-rounding tail)."""
    from repro.core.layers import cache_allocation
    from repro.core.model import attn_cfg
    total = 0
    for i, kind in enumerate(cfg.layer_pattern):
        if kind.startswith("mamba"):
            spec = cfg.ssm
            h = spec.num_heads(cfg.d_model)
            total += batch * (h * spec.head_dim * spec.state_dim * 4
                              + (spec.conv_width - 1)
                              * (spec.d_inner(cfg.d_model)
                                 + 2 * spec.num_groups * spec.state_dim) * 2)
            continue
        acfg = attn_cfg(cfg, kind, index=i)
        cap = cache_allocation(acfg, context)
        total += 2 * batch * acfg.num_kv_heads * cap * acfg.head_dim * 2
    return total * cfg.num_super_blocks

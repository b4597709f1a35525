"""Serving engine benchmark: scan-based batched decode vs the seed engine's
per-token host sync, the mesh-sharded engine vs single-device, plus the
ring-cache memory claim.

    PYTHONPATH=src python benchmarks/serve_bench.py [--arch llama3.2-1b]
        [--requests 8 --slots 4 --new-tokens 64 --scan-steps 8]
        [--mesh 4x1 --force-devices 4]

Modes compared (same model, same requests, greedy, fixed seed):
  seed-style : scan_steps=1, one-prompt-at-a-time prefill — one host round
               trip per generated token (the seed ServingEngine behavior)
  batched    : batched padded prefill + lax.scan decode blocks — one host
               sync per scan_steps tokens
  sharded    : the batched engine under a --mesh device mesh (slot axis
               over 'data') — decode partitioned by XLA. On the default
               4x1 slot-parallel mesh every slot's math is device-local,
               so tokens must be IDENTICAL to the batched mode. Forced
               host CPU devices share the same silicon, so tok/s here
               measures partitioning overhead, not speedup — the sharded
               win is a real-multi-chip property.
  speculative: draft/verify/accept decode (k n-gram drafts verified per
               dispatch) vs the same engine at speculative=0, on a model
               briefly fitted to a repetitive corpus so greedy output has
               the self-similarity real workloads carry (random-init
               weights emit undraftable noise — recorded separately as
               the speculative_random diagnostic). Tokens must be
               IDENTICAL to sequential decode; the run fails below 1.3x.

Also prints ring-cache bytes (SWAT window spec) vs dense at the serving
context — the paper's Fig. 3 linear-memory claim applied to decode — and
writes the whole run to BENCH_serve.json (shapes, tok/s per mode, parity
flags) so future PRs have a machine-readable perf trajectory to diff.

A `prefix_sharing` section records the paged-KV shared-prefix workload
(>= 8 requests behind one system prompt: prefill work must drop below
0.5x, prefix pool blocks must dedup, tokens must stay identical), and a
`resilience` section records the fault-injection probes (clean-run
degradation events must be ZERO; the quarantine and pallas-fallback
drills must fire) — `kernel_bench --smoke` refuses on a bad section.
`--resilience-only` reruns just those probes and merges the section into
the existing artifact.

Each mode also carries a `latency` block (p50/p95/p99 TTFT / TPOT /
queue-delay in ms, from the engine's request tracer), and a `telemetry`
section records the device-counter A/B: the batched engine with the
metrics pytree compiled into the scan carry must emit bitwise-identical
tokens at under 3% throughput overhead (`kernel_bench --smoke` gates on
both), with the device counters matching the host-side stats.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))  # `python benchmarks/serve_bench.py` from anywhere

import numpy as np


def run_mode(cfg, params, reqs, *, scan_steps, batch_prefill, max_len,
             label, mesh=None, warm=True, speculative=0, draft=None,
             reps=1, donate=True, metrics=False):
    from repro.serving.engine import ServingEngine

    kw = {"donate": donate, "metrics": metrics}
    if speculative:
        kw.update(speculative=speculative, draft=draft)

    def once():
        eng = ServingEngine(cfg, params, batch_slots=ARGS.slots,
                            max_len=max_len, scan_steps=scan_steps,
                            batch_prefill=batch_prefill, mesh=mesh, **kw)
        t0 = time.perf_counter()
        results = eng.run(list(reqs))
        dt = time.perf_counter() - t0
        return results, dt, eng

    if warm:           # first run pays jit compiles for this mode's shapes
        once()
    # median over reps: 64-token runs finish in tens of ms, where scheduler
    # jitter swamps a single sample (speculative-vs-sequential especially)
    samples = sorted((once() for _ in range(reps)), key=lambda s: s[1])
    results, dt, eng = samples[len(samples) // 2]
    n = sum(len(r.tokens) for r in results)
    print(f"[serve_bench] {label:<16} {n:4d} tokens in {dt:6.2f}s "
          f"-> {n / dt:8.1f} tok/s")
    return results, n / dt, eng


def latency_of(eng):
    """p50/p95/p99 TTFT / TPOT / queue delay of one mode's median run, in
    milliseconds — the BENCH_serve.json `latency` rows (counts dropped:
    they equal the request count)."""
    summ = eng.tracer.latency_summary()
    return {field: {p: round(summ[field][p] * 1e3, 3)
                    for p in ("p50", "p95", "p99")}
            for field in ("ttft", "tpot", "queue_delay")}


def fit_selfsim(cfg, params, steps, Mod):
    """Fit the smoke model to a tiny repetitive corpus (seeded motifs,
    tiled) so greedy continuations carry the self-similarity real serving
    workloads have. Random-INIT weights emit near-chaotic trajectories no
    drafter can predict (acceptance ~0.1, recorded below as the
    speculative_random diagnostic), which says nothing about the engine —
    speculation is always benched on models whose output is predictable
    enough to draft. ~tens of seconds on CPU, fully deterministic."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    motifs = [rng.randint(0, cfg.vocab_size, (int(l),)).astype(np.int32)
              for l in rng.randint(4, 13, size=16)]

    def batch_of(bs, seq=96):
        idx = rng.randint(0, len(motifs), size=bs)
        rows = [np.tile(motifs[i], seq // len(motifs[i]) + 1)[:seq]
                for i in idx]
        x = jnp.asarray(np.stack(rows).astype(np.int32))
        return {"tokens": x, "labels": x}

    try:
        import optax
        opt = optax.adam(3e-3)
    except ImportError:                      # plain momentum SGD fallback
        class _SGD:
            def init(self, p):
                return jax.tree.map(jnp.zeros_like, p)

            def update(self, g, m):
                m = jax.tree.map(lambda m, g: 0.9 * m + g, m, g)
                return jax.tree.map(lambda m: -0.05 * m, m), m
        import types
        optax = types.SimpleNamespace(apply_updates=lambda p, u: jax.tree.map(
            lambda p, u: p + u, p, u))
        opt = _SGD()
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch):
        (_, aux), g = jax.value_and_grad(Mod.loss_fn, has_aux=True)(
            params, cfg, batch)
        upd, state = opt.update(g, state)
        return optax.apply_updates(params, upd), state, aux["loss"]

    t0 = time.perf_counter()
    for _ in range(steps):
        params, state, loss = step(params, state, batch_of(16))
    print(f"[serve_bench] fit {steps} steps on motif corpus in "
          f"{time.perf_counter() - t0:.1f}s (loss {float(loss):.3f})")
    prompts = [np.tile(motifs[i % len(motifs)],
                       ARGS.prompt_len // len(motifs[i % len(motifs)]) + 1)
               [:ARGS.prompt_len].astype(np.int32)
               for i in range(ARGS.requests)]
    return params, prompts


def resilience_section(cfg, params, reqs):
    """Resilience probes -> the BENCH_serve.json `resilience` section that
    `kernel_bench --smoke` gates on: a CLEAN run must record zero
    degradation events (guards are bitwise-invisible bystanders), and the
    two drills — logit poison, Pallas dispatch failure — must actually
    fire (quarantine exactly one slot / fall back to the ref impl) while
    every healthy request stays token-identical. Returns (section, ok)."""
    from repro.serving import faults as F
    from repro.serving.engine import ServingEngine
    from repro.serving.faults import FaultPlan

    def once(faults=None, **kw):
        eng = ServingEngine(cfg, params, batch_slots=ARGS.slots,
                            max_len=ARGS.max_len,
                            scan_steps=ARGS.scan_steps,
                            faults=faults if faults is not None
                            else FaultPlan(), **kw)
        return eng, {r.rid: r for r in eng.run(list(reqs))}

    F.consume_events()
    _, clean = once()
    clean_events = [e["kind"] for e in F.consume_events()]
    clean_ok = all(r.status == "ok" for r in clean.values())
    print(f"[serve_bench] resilience/clean: all_ok={clean_ok}, "
          f"degradation_events={len(clean_events)} (must be 0)")

    qeng, chaos = once(FaultPlan(poison_logits=((0, 3, "nan"),)))
    qevents = [e["kind"] for e in F.consume_events()]
    healthy_identical = all(chaos[i].tokens == clean[i].tokens
                            for i in clean if chaos[i].status == "ok")
    print(f"[serve_bench] resilience/quarantine drill: "
          f"quarantined={qeng.stats['quarantined']}, healthy bitwise "
          f"identical={healthy_identical}")

    try:
        feng, fb = once(FaultPlan(fail_pallas_dispatch=True),
                        decode_impl="pallas")
    finally:
        F.clear_kernel_failure()
    fevents = [e["kind"] for e in F.consume_events()]
    fb_ok = all(r.status == "ok" for r in fb.values())
    fb_identical = all(fb[i].tokens == clean[i].tokens for i in clean)
    print(f"[serve_bench] resilience/pallas-failure drill: "
          f"kernel_fallbacks={feng.stats['kernel_fallbacks']}, impl now "
          f"{feng.decode_impl!r}, all ok={fb_ok}, tokens identical to ref "
          f"engine={fb_identical}")

    section = {
        "clean": {"events": len(clean_events), "all_ok": bool(clean_ok)},
        "quarantine_drill": {
            "quarantined": int(qeng.stats["quarantined"]),
            "healthy_bitwise_identical": bool(healthy_identical),
            "events": qevents},
        "pallas_fallback_drill": {
            "kernel_fallbacks": int(feng.stats["kernel_fallbacks"]),
            "all_ok": bool(fb_ok),
            "identical_to_ref": bool(fb_identical),
            "events": fevents},
    }
    ok = (clean_ok and not clean_events
          and qeng.stats["quarantined"] == 1 and healthy_identical
          and feng.stats["kernel_fallbacks"] == 1 and fb_ok
          and fb_identical)
    return section, ok


def prefix_sharing_section(cfg, params):
    """Paged-KV prefix sharing -> the BENCH_serve.json `prefix_sharing`
    section: >= 8 requests behind one shared system prompt, served by the
    paged engine with sharing off vs on. Sharing must cut prefill work
    below 0.5x (the prefix prefills ONCE), dedup the prefix blocks in the
    pool (cache bytes), and stay token-identical — copy-on-write covers
    the divergence. Returns (section, ok)."""
    import collections

    from repro.serving.engine import Request, ServingEngine

    n = max(8, ARGS.requests)
    rng = np.random.RandomState(13)
    sys_prompt = rng.randint(0, cfg.vocab_size, (96,)).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rng.randint(0, cfg.vocab_size, (16,)
                                           ).astype(np.int32)])
               for _ in range(n)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=16)
                for i, p in enumerate(prompts)]

    def engine(share):
        return ServingEngine(cfg, params, batch_slots=n,
                             max_len=ARGS.max_len, kv_layout="paged",
                             prefill_chunk=32, share_prefix=share)

    def blocks_at_admission(eng):
        pend = collections.deque(reqs())
        eng._run_t0 = 0.0
        eng._admit(pend)
        used = eng.paged_stats()["blocks_in_use"]
        eng._run_t0 = None
        res = {r.rid: r for r in eng.run(list(pend)) + eng.take_completed()}
        return used, res

    off = engine(False)
    off_blocks, off_res = blocks_at_admission(off)
    on = engine(True)
    on_blocks, on_res = blocks_at_admission(on)

    identical = all(off_res[i].tokens == on_res[i].tokens for i in off_res)
    ratio = (on.stats["prefill_tokens_computed"]
             / max(off.stats["prefill_tokens_computed"], 1))
    # bytes per pool block: K+V rows for one block across every super-block
    # (shared pools are (S, NB, H, page, D); a block is one NB row)
    page_bytes = 0
    for c in on.caches.values():
        if isinstance(c, dict) and "pk" in c:
            pk = c["pk"]
            page_bytes = 2 * pk.dtype.itemsize * int(
                np.prod(pk.shape)) // pk.shape[1]
            break
    print(f"[serve_bench] prefix sharing ({n} reqs, 96-token system "
          f"prompt): prefill tokens {on.stats['prefill_tokens_computed']} "
          f"vs {off.stats['prefill_tokens_computed']} ({ratio:.2f}x, gate "
          f"< 0.5); pool blocks at admission {on_blocks} vs {off_blocks} "
          f"(~{(off_blocks - on_blocks) * page_bytes / 1e3:.1f}KB saved); "
          f"identical {identical}")
    section = {
        "requests": n, "system_prompt_len": 96, "suffix_len": 16,
        "prefill_tokens": {
            "sharing": int(on.stats["prefill_tokens_computed"]),
            "baseline": int(off.stats["prefill_tokens_computed"]),
            "ratio": round(ratio, 4)},
        "pool_blocks_at_admission": {
            "sharing": int(on_blocks), "baseline": int(off_blocks),
            "page_bytes": int(page_bytes)},
        "prefix_prefills_shared": int(on.stats["prefill_prefix_shared"]),
        "identical_to_unshared": bool(identical),
    }
    ok = (identical and ratio < 0.5
          and on.stats["prefill_prefix_shared"] >= 1
          and on_blocks < off_blocks)
    return section, ok


def main():
    global ARGS
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--scan-steps", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--window", type=int, default=64)
    ap.add_argument("--mesh", default="4x1",
                    help="sharded-mode mesh 'DxM' ('' disables the sharded "
                         "comparison)")
    ap.add_argument("--force-devices", type=int, default=0,
                    help="force this many host CPU devices (0 = the mesh "
                         "size; must be set before jax initializes, which "
                         "is why this script imports jax late)")
    ap.add_argument("--speculative", type=int, default=4,
                    help="draft depth k for the speculative section "
                         "(0 disables it)")
    ap.add_argument("--fit-steps", type=int, default=200,
                    help="optimizer steps fitting the smoke model to the "
                         "self-similar corpus the speculative section "
                         "decodes")
    ap.add_argument("--spec-reps", type=int, default=5,
                    help="timing repetitions (median) for the "
                         "speculative/sequential comparison")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--resilience-only", action="store_true",
                    help="run just the resilience probes and MERGE the "
                         "`resilience` section into an existing --out "
                         "artifact (the section kernel_bench --smoke "
                         "gates on)")
    ARGS = ap.parse_args()

    mesh_dims = (tuple(int(x) for x in ARGS.mesh.split("x"))
                 if ARGS.mesh else ())
    need = ARGS.force_devices or (int(np.prod(mesh_dims)) if mesh_dims else 0)
    if need > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={need} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if mesh_dims and jax.device_count() < int(np.prod(mesh_dims)):
        # e.g. a non-CPU default backend: the forced-host-device flag only
        # adds CPU-platform devices. This bench is the sharded-correctness
        # gate, so a mesh it cannot build is a failure, not a skip.
        print(f"[serve_bench] FAIL: mesh {ARGS.mesh} needs "
              f"{int(np.prod(mesh_dims))} devices, have "
              f"{jax.device_count()} ({jax.default_backend()}); pass "
              f"--mesh '' to run without the sharded comparison",
              file=sys.stderr)
        sys.exit(1)

    from repro.configs import get_smoke_config, with_swat
    from repro.core import model as Mod
    from repro.launch.mesh import parse_mesh
    from repro.serving.engine import Request, ring_cache_bytes

    cfg = with_swat(get_smoke_config(ARGS.arch), window=ARGS.window,
                    num_global=4)
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, prompt=rng.randint(
        0, cfg.vocab_size, (ARGS.prompt_len,)).astype(np.int32),
        max_new_tokens=ARGS.new_tokens) for i in range(ARGS.requests)]

    if ARGS.resilience_only:
        import json

        section, res_ok = resilience_section(cfg, params, reqs)
        existing = {}
        if os.path.exists(ARGS.out):
            with open(ARGS.out) as f:
                existing = json.load(f)
        existing["resilience"] = section
        from benchmarks.common import write_json
        write_json(ARGS.out, existing)
        if not res_ok:
            print("[serve_bench] FAIL: resilience probes (clean-run "
                  "events or a drill that did not fire)", file=sys.stderr)
            sys.exit(1)
        return

    base, base_tps, base_eng = run_mode(cfg, params, reqs, scan_steps=1,
                                        batch_prefill=False,
                                        max_len=ARGS.max_len,
                                        label="seed-style")
    fast, fast_tps, fast_eng = run_mode(cfg, params, reqs,
                                        scan_steps=ARGS.scan_steps,
                                        batch_prefill=True,
                                        max_len=ARGS.max_len,
                                        label="batched")

    same = all(a.tokens == b.tokens for a, b in zip(base, fast))
    print(f"[serve_bench] outputs identical: {same}; "
          f"speedup {fast_tps / base_tps:.2f}x "
          f"(scan_steps={ARGS.scan_steps} + batched prefill)")

    # donation A/B: the same batched engine with buffer donation disabled —
    # XLA materializes a fresh ring cache on every decode dispatch instead
    # of aliasing it in place. Token identity is the correctness contract
    # (donation must never change results); the per-block latency delta is
    # what swatlint's donation rule guards. Block latency is derived from
    # steady-state throughput at the tokens-per-dispatch granularity.
    don, don_tps, _ = run_mode(cfg, params, reqs,
                               scan_steps=ARGS.scan_steps,
                               batch_prefill=True, max_len=ARGS.max_len,
                               label="batched/donate", reps=ARGS.spec_reps)
    undon, undon_tps, _ = run_mode(cfg, params, reqs,
                                   scan_steps=ARGS.scan_steps,
                                   batch_prefill=True, max_len=ARGS.max_len,
                                   label="batched/no-donate", donate=False,
                                   reps=ARGS.spec_reps)
    don_same = all(a.tokens == b.tokens for a, b in zip(don, undon))
    blk = 1000.0 * ARGS.slots * ARGS.scan_steps   # tokens per scan dispatch
    print(f"[serve_bench] donation A/B: identical {don_same}; block "
          f"{blk / don_tps:.2f}ms donated vs {blk / undon_tps:.2f}ms "
          f"copied ({don_tps / undon_tps:.2f}x; smoke-scale caches — the "
          f"copy removed is ~ring bytes per block, see ring_cache)")

    # telemetry A/B: the same batched engine with the device counter pytree
    # compiled into the scan carry (swatscope layer 1). Tokens must stay
    # bitwise identical — counters are donated int32 carries read only at
    # block boundaries — and the throughput tax must stay under the 3%
    # gate kernel_bench --smoke enforces. The metrics-OFF side reuses the
    # donated median above (same engine parameters, same reps).
    met, met_tps, met_eng = run_mode(cfg, params, reqs,
                                     scan_steps=ARGS.scan_steps,
                                     batch_prefill=True,
                                     max_len=ARGS.max_len,
                                     label="batched/metrics", metrics=True,
                                     reps=ARGS.spec_reps)
    met_same = all(a.tokens == b.tokens for a, b in zip(don, met))
    overhead_pct = 100.0 * (1.0 - met_tps / don_tps)
    dev = met_eng.device_metrics()
    counters_match = dev["tokens"] == met_eng.stats["tokens_emitted"]
    print(f"[serve_bench] telemetry A/B: identical {met_same}; "
          f"{met_tps:.1f} vs {don_tps:.1f} tok/s "
          f"({overhead_pct:+.2f}% overhead, gate < 3); device "
          f"tokens={dev['tokens']} (host {met_eng.stats['tokens_emitted']}, "
          f"match={counters_match})")

    payload = {
        "bench": "serve", "arch": ARGS.arch,
        "requests": ARGS.requests, "slots": ARGS.slots,
        "prompt_len": ARGS.prompt_len, "new_tokens": ARGS.new_tokens,
        "scan_steps": ARGS.scan_steps, "window": ARGS.window,
        "modes": {"seed_style": {"tok_s": round(base_tps, 2),
                                 "latency": latency_of(base_eng)},
                  "batched": {"tok_s": round(fast_tps, 2),
                              "speedup_vs_seed":
                                  round(fast_tps / base_tps, 3),
                              "latency": latency_of(fast_eng)}},
        "outputs_identical": bool(same),
        "donation_ab": {
            "donated": {"tok_s": round(don_tps, 2),
                        "block_ms": round(blk / don_tps, 3)},
            "copied": {"tok_s": round(undon_tps, 2),
                       "block_ms": round(blk / undon_tps, 3)},
            "speedup": round(don_tps / undon_tps, 3),
            "identical": bool(don_same),
            "note": ("smoke-scale model on CPU: the removed per-block "
                     "copy is ~the ring-cache bytes, so the delta grows "
                     "with window*layers*slots; identity is the gate"),
        },
        "telemetry": {
            "metrics_on": {"tok_s": round(met_tps, 2),
                           "latency": latency_of(met_eng)},
            "metrics_off": {"tok_s": round(don_tps, 2)},
            "overhead_pct": round(overhead_pct, 3),
            "identical": bool(met_same),
            "device_counters": {k: int(v) for k, v in sorted(dev.items())},
            "device_matches_host": bool(counters_match),
        },
    }
    shard_same = True
    if mesh_dims:
        mesh = parse_mesh(ARGS.mesh)
        shard, shard_tps, shard_eng = run_mode(
            cfg, params, reqs, scan_steps=ARGS.scan_steps,
            batch_prefill=True, max_len=ARGS.max_len,
            label=f"sharded/{ARGS.mesh}", mesh=mesh)
        identical = all(a.tokens == b.tokens
                        for a, b in zip(fast, shard))
        # token-exactness is only guaranteed for slot-parallel meshes
        # (model dim 1): TP psums a bf16 contraction in a different order,
        # so near-tied draws may legitimately flip (see serving README)
        slot_parallel = len(mesh_dims) < 2 or mesh_dims[-1] == 1
        shard_same = identical or not slot_parallel
        note = ("" if slot_parallel
                else " (TP mesh: exactness not required, see README)")
        print(f"[serve_bench] sharded vs batched: identical {identical}"
              f"{note}; {shard_tps:.1f} vs {fast_tps:.1f} tok/s "
              f"({shard_tps / fast_tps:.2f}x on forced-{need}-device CPU — "
              f"partitioning overhead, not silicon)")
        payload["modes"]["sharded"] = {
            "mesh": ARGS.mesh, "tok_s": round(shard_tps, 2),
            "identical_to_batched": bool(identical),
            "slot_parallel": bool(slot_parallel),
            "latency": latency_of(shard_eng)}

    # ------------------------------------------------- speculative decode --
    spec_ok = True
    if ARGS.speculative:
        from repro.serving.drafter import NGramDrafter

        draft = NGramDrafter(max_ngram=3, history=64)
        # diagnostic first: speculation on the incompressible random-token
        # workload above. Acceptance collapses and the k-wide verify is a
        # pure compute tax — the expected, recorded loss that motivates the
        # self-similar workload below.
        _, rand_tps, rand_eng = run_mode(
            cfg, params, reqs, scan_steps=ARGS.scan_steps,
            batch_prefill=True, max_len=ARGS.max_len,
            label="spec/random", speculative=ARGS.speculative, draft=draft)
        print(f"[serve_bench] spec on random tokens: acceptance "
              f"{rand_eng.acceptance_rate:.3f} -> "
              f"{rand_tps / fast_tps:.2f}x vs batched (expected loss)")

        fit_params, fit_prompts = fit_selfsim(cfg, params, ARGS.fit_steps,
                                              Mod)
        fit_reqs = [Request(rid=i, prompt=p,
                            max_new_tokens=ARGS.new_tokens)
                    for i, p in enumerate(fit_prompts)]
        seqr, seq_tps, seq_eng = run_mode(
            cfg, fit_params, fit_reqs, scan_steps=ARGS.scan_steps,
            batch_prefill=True, max_len=ARGS.max_len,
            label="sequential/fit", reps=ARGS.spec_reps)
        specr, spec_tps, spec_eng = run_mode(
            cfg, fit_params, fit_reqs, scan_steps=ARGS.scan_steps,
            batch_prefill=True, max_len=ARGS.max_len,
            label=f"speculative/k={ARGS.speculative}",
            speculative=ARGS.speculative, draft=draft, reps=ARGS.spec_reps)
        spec_same = all(a.tokens == b.tokens for a, b in zip(seqr, specr))
        spec_speedup = spec_tps / seq_tps
        print(f"[serve_bench] speculative vs sequential: identical "
              f"{spec_same}; {spec_speedup:.2f}x at acceptance "
              f"{spec_eng.acceptance_rate:.3f} "
              f"({spec_eng.stats['spec_steps']} verify steps for "
              f"{spec_eng.stats['tokens_emitted']} tokens)")
        payload["modes"]["sequential_selfsim"] = {
            "tok_s": round(seq_tps, 2), "fit_steps": ARGS.fit_steps,
            "latency": latency_of(seq_eng)}
        payload["modes"]["speculative"] = {
            "tok_s": round(spec_tps, 2),
            "latency": latency_of(spec_eng),
            "speedup_vs_sequential": round(spec_speedup, 3),
            "acceptance_rate": round(spec_eng.acceptance_rate, 4),
            "k": ARGS.speculative,
            "draft": {"kind": "ngram", "max_ngram": draft.max_ngram,
                      "history": draft.history},
            "identical_to_sequential": bool(spec_same),
            "verify_steps": spec_eng.stats["spec_steps"],
        }
        payload["modes"]["speculative_random"] = {
            "tok_s": round(rand_tps, 2),
            "acceptance_rate": round(rand_eng.acceptance_rate, 4),
            "expected_loss": True,
        }
        spec_ok = spec_same and spec_speedup >= 1.3

    dense = get_smoke_config(ARGS.arch)
    ctx = 65536
    ring = ring_cache_bytes(cfg, ARGS.slots, ctx)
    dn = ring_cache_bytes(dense, ARGS.slots, ctx)
    print(f"[serve_bench] decode cache @ {ctx} ctx, {ARGS.slots} slots: "
          f"ring {ring / 1e6:.2f}MB vs dense {dn / 1e6:.2f}MB "
          f"({dn / max(ring, 1):.0f}x)")
    payload["ring_cache"] = {"context": ctx, "ring_bytes": ring,
                             "dense_bytes": dn,
                             "ratio": round(dn / max(ring, 1), 1)}
    payload["prefix_sharing"], share_ok = prefix_sharing_section(cfg, params)
    payload["resilience"], res_ok = resilience_section(cfg, params, reqs)
    from benchmarks.common import write_json
    write_json(ARGS.out, payload)
    if not same:
        print("[serve_bench] FAIL: modes disagree", file=sys.stderr)
        sys.exit(1)
    if not don_same:
        print("[serve_bench] FAIL: donation changed tokens", file=sys.stderr)
        sys.exit(1)
    if not met_same or not counters_match:
        print("[serve_bench] FAIL: device metrics changed tokens or "
              "disagree with host stats", file=sys.stderr)
        sys.exit(1)
    if not shard_same:
        print("[serve_bench] FAIL: sharded mode disagrees", file=sys.stderr)
        sys.exit(1)
    if fast_tps <= base_tps:
        print("[serve_bench] FAIL: batched mode not faster", file=sys.stderr)
        sys.exit(1)
    if not spec_ok:
        print("[serve_bench] FAIL: speculative decode below the 1.3x bar "
              "or not token-identical", file=sys.stderr)
        sys.exit(1)
    if not share_ok:
        print("[serve_bench] FAIL: prefix sharing above the 0.5x prefill "
              "bar, no block dedup, or not token-identical",
              file=sys.stderr)
        sys.exit(1)
    if not res_ok:
        print("[serve_bench] FAIL: resilience probes (clean-run events "
              "or a drill that did not fire)", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

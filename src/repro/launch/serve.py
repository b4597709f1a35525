"""Serving launcher: continuous-batching window-attention serving with ring
KV caches.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --requests 6 --slots 2 --scan-steps 8 --batch-prefill

--scan-steps 1 --no-batch-prefill reproduces the seed engine's per-token
host-sync behavior (the serve_bench.py baseline).

Mesh-sharded serving: `--mesh 2x2` runs the engine under a data×model
device mesh (`--mesh 4x1` = pure slot-parallel; 2x2x2 = pod×data×model)
with `--profile` picking the param sharding rules. On CPU, force a debug
device count FIRST, e.g.:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --smoke --mesh 2x2

Resilience drills (see serving/README.md "Resilience & fault injection"):
`--chaos-poison-slot 0` NaN-poisons a slot mid-decode and prints the
quarantine event; `--chaos-fail-pallas --decode-impl pallas` forces a
kernel dispatch failure and prints the ref-impl fallback. Per-request
`--deadline`, `--max-pending` backpressure and `--max-prompt-len`
rejection surface as per-status counts in the summary line.
"""
import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--swat", action="store_true",
                    help="swap dense attention for SWAT window attention")
    ap.add_argument("--window", type=int, default=256)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--scan-steps", type=int, default=8,
                    help="decode steps per host sync (1 = per-token sync)")
    ap.add_argument("--batch-prefill", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="pack pending prompts into one padded prefill "
                         "(on by default; --no-batch-prefill reverts to "
                         "one-prompt-at-a-time seed behavior)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="sequence-axis prefill chunk (0 = single-shot)")
    ap.add_argument("--max-prefill-tokens", type=int, default=8192)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--decode-impl", choices=("ref", "pallas"),
                    default="ref")
    ap.add_argument("--tokens-per-step", type=int, default=1,
                    help="ring lookahead for multi-token decode steps "
                         "(speculative-decode hook; tokens unchanged)")
    ap.add_argument("--speculative", type=int, default=0,
                    help="draft tokens per decode step (0 = sequential); "
                         "greedy output is token-identical either way")
    ap.add_argument("--draft-ngram", type=int, default=3,
                    help="n-gram drafter: longest context suffix to match")
    ap.add_argument("--draft-history", type=int, default=64,
                    help="n-gram drafter: per-slot token history length")
    ap.add_argument("--mesh", default=None,
                    help="device mesh 'DxM' (e.g. 2x2) — sharded serving; "
                         "default: single-device")
    ap.add_argument("--profile", choices=("tp", "cp", "fsdp"), default="tp",
                    help="param sharding profile for --mesh")
    # ----------------------------------------------- resilience knobs ----
    ap.add_argument("--max-prompt-len", type=int, default=0,
                    help="reject (status 'rejected') prompts longer than "
                         "this instead of serving them (0 = no limit)")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="bounded-queue backpressure: reject requests "
                         "beyond this many queued (0 = unbounded)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline in seconds from submission "
                         "(0 = none); expired requests finalize as "
                         "status 'deadline' with whatever they emitted")
    ap.add_argument("--spec-min-acceptance", type=float, default=0.0,
                    help="auto-disable speculative decode when windowed "
                         "acceptance drops below this rate (0 = never)")
    # ------------------------------------------------- chaos drills -----
    ap.add_argument("--chaos-poison-slot", type=int, default=None,
                    metavar="SLOT",
                    help="fault drill: poison SLOT's logits with NaN at "
                         "--chaos-poison-step and watch it quarantine")
    ap.add_argument("--chaos-poison-step", type=int, default=3)
    ap.add_argument("--chaos-fail-pallas", action="store_true",
                    help="fault drill: make the pallas decode kernel "
                         "fail dispatch; the engine must fall back to "
                         "the reference impl and finish the batch")
    ap.add_argument("--kv-layout", choices=("contiguous", "paged"),
                    default="contiguous",
                    help="cache residency: per-slot rings or the paged "
                         "block pool + tables (see serving README)")
    # ------------------------------------------------- observability ----
    ap.add_argument("--metrics", action="store_true",
                    help="compile device-resident counters into the decode "
                         "scan (tokens identical; read at block boundaries) "
                         "and print a Prometheus summary after the run")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of request lifecycle "
                         "spans to PATH (open in chrome://tracing / "
                         "Perfetto)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the Prometheus text exposition to PATH "
                         "(implies --metrics)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="prefill each admission batch's common prompt "
                         "prefix once and block-share it (paged, "
                         "single-device); demoed with a shared system "
                         "prompt across all requests")
    args = ap.parse_args()

    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import get_config, get_smoke_config, with_swat
    from repro.core import model as Mod
    from repro.launch.mesh import parse_mesh
    from repro.serving import faults as F
    from repro.serving.drafter import NGramDrafter
    from repro.serving.engine import Request, ServingEngine, ring_cache_bytes
    from repro.serving.faults import FaultPlan

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.swat:
        cfg = with_swat(cfg, window=args.window, num_global=4)
    mesh = parse_mesh(args.mesh) if args.mesh else None
    # jitted: full-size configs draw their weights on the device in one
    # program instead of op by op
    params = jax.jit(Mod.init_model, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    plan = FaultPlan(
        poison_logits=(((args.chaos_poison_slot, args.chaos_poison_step,
                         "nan"),)
                       if args.chaos_poison_slot is not None else ()),
        fail_pallas_dispatch=args.chaos_fail_pallas)
    engine = ServingEngine(
        cfg, params, batch_slots=args.slots, max_len=args.max_len,
        scan_steps=args.scan_steps, batch_prefill=args.batch_prefill,
        prefill_chunk=args.prefill_chunk,
        max_prefill_tokens=args.max_prefill_tokens,
        top_k=args.top_k, decode_impl=args.decode_impl,
        tokens_per_step=args.tokens_per_step,
        speculative=args.speculative,
        draft=NGramDrafter(max_ngram=args.draft_ngram,
                           history=args.draft_history),
        mesh=mesh, profile=args.profile,
        faults=plan,
        max_prompt_len=args.max_prompt_len or None,
        max_pending=args.max_pending or None,
        spec_min_acceptance=args.spec_min_acceptance,
        kv_layout=args.kv_layout, share_prefix=args.share_prefix,
        metrics=args.metrics or bool(args.metrics_out))
    rng = np.random.RandomState(0)
    if args.share_prefix:
        # shared-prefix demo workload: one system prompt, short suffixes
        sys_prompt = rng.randint(0, cfg.vocab_size,
                                 (max(args.prompt_len - 16, 16),)
                                 ).astype(np.int32)
        prompts = [np.concatenate(
            [sys_prompt, rng.randint(0, cfg.vocab_size, (16,)
                                     ).astype(np.int32)])
            for _ in range(args.requests)]
    else:
        prompts = [rng.randint(0, cfg.vocab_size, (args.prompt_len,)
                               ).astype(np.int32)
                   for _ in range(args.requests)]
    reqs = [Request(rid=i, prompt=prompts[i],
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature,
                    deadline=args.deadline or None)
            for i in range(args.requests)]
    t0 = time.time()
    try:
        results = engine.run(reqs)
    finally:
        F.clear_kernel_failure()
    dt = time.time() - t0
    n = sum(len(r.tokens) for r in results)
    mdesc = "single-device" if mesh is None else (
        "x".join(str(s) for s in mesh.devices.shape)
        + f" mesh ({args.profile})")
    spec = (f", speculative={args.speculative} "
            f"(acceptance {engine.acceptance_rate:.2f})"
            if args.speculative else "")
    print(f"[serve] {len(results)} requests / {n} tokens in {dt:.1f}s "
          f"({n / dt:.1f} tok/s; scan_steps={args.scan_steps}, "
          f"batch_prefill={args.batch_prefill}, "
          f"prefill_chunk={args.prefill_chunk}, {mdesc}{spec})")
    print(f"[serve] cache bytes @max_len: "
          f"{ring_cache_bytes(cfg, args.slots, args.max_len) / 1e6:.1f}MB")
    if args.kv_layout == "paged":
        ps = engine.paged_stats()
        line = (f"[serve] paged pool: {ps['blocks_in_use']}/"
                f"{ps['blocks_total']} blocks in use")
        if args.share_prefix:
            line += (f"; prefixes shared="
                     f"{engine.stats['prefill_prefix_shared']}, prefill "
                     f"tokens computed="
                     f"{engine.stats['prefill_tokens_computed']}")
        print(line)
    by_status = {}
    for r in results:
        by_status[r.status] = by_status.get(r.status, 0) + 1
    print("[serve] statuses: "
          + ", ".join(f"{k}={v}" for k, v in sorted(by_status.items())))
    for ev in F.consume_events():
        kind = ev.pop("kind")
        print(f"[serve] degradation event: {kind} "
              + " ".join(f"{k}={v}" for k, v in sorted(ev.items())))
    for r in results:
        if r.status != "ok":
            print(f"[serve]   rid {r.rid}: {r.status}"
                  + (f" — {r.reason}" if r.reason else ""))

    lat = engine.tracer.latency_summary()
    if lat["ttft"]["count"]:
        ttft, tpot = lat["ttft"], lat["tpot"]
        print(f"[serve] latency: ttft p50={ttft['p50'] * 1e3:.1f}ms "
              f"p95={ttft['p95'] * 1e3:.1f}ms; "
              f"tpot p50={tpot['p50'] * 1e3:.2f}ms "
              f"p95={tpot['p95'] * 1e3:.2f}ms "
              f"({ttft['count']} finished)")
    if engine.metrics:
        dev = engine.device_metrics()
        print("[serve] device counters: "
              + " ".join(f"{k}={v}" for k, v in sorted(dev.items())))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(engine.metrics_text())
        print(f"[serve] wrote metrics: {args.metrics_out}")
    if args.trace_out:
        import json
        with open(args.trace_out, "w") as f:
            json.dump(engine.chrome_trace(), f)
        print(f"[serve] wrote trace: {args.trace_out}")


if __name__ == "__main__":
    main()

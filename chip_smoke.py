#!/usr/bin/env python3
"""Serve gemma2-2b end to end on a TPU and check what comes out.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # the mesh phase, four chips

One chip (the default) runs, in one process:
  1. the device check: JAX must report a TPU, and the Pallas kernels must
     compile for it (never the interpreter);
  2. decode-kernel parity on the chip: `swat_decode` against the jnp
     oracle (`kernels/ref.decode_ref`, `ring_insert_ref`), plain and fused,
     at gemma2-2b's local-layer geometry and at the `--swat` geometry with
     4 pinned global tokens;
  3. gemma2-2b at its published widths (bf16, all 26 layers, random
     weights from --seed) served through `ServingEngine(decode_impl=
     "pallas")`: 7 prompts of 256-1024 tokens with 32 new tokens each,
     and one 4600-token prompt with 64 new tokens whose local-layer ring
     (4097 rows) wraps while it decodes. Every request must finish "ok",
     with no kernel fallback, no degradation event, and the Mosaic kernel
     in the compiled decode scan;
  4. logits parity: one decode step of the same prefilled cache through
     the pallas and the reference decode impls.

--four-chips runs only the mesh phase on a four-chip host: the same
requests on a one-chip engine, on a slot-parallel 4x1 mesh (tokens must be
identical) and on a 2x2 tensor-parallel mesh (every request must finish
"ok"; TP sums in another order, so token agreement is printed, not
required).

Any failed check exits non-zero with a FAIL line on stderr. The last line
of standard output is one JSON object naming the device. Wall-clock
figures printed on the way are informational, not metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SLOTS, MAX_LEN, SCAN_STEPS = 4, 8192, 8

# Tolerances, both absolute, for bf16 operands with f32 accumulation.
# A decode output is a convex combination of V rows, so |out| <= max|v|,
# under 8 for the unit normals used; bf16 (8 significant bits) spaces
# values below 8 at most 2^-5 apart. The kernel accumulates P@V in f32
# while the oracle rounds P to bf16 first (2^-9 relative per term), and
# both round the output once, so they may differ by an ulp or two: 2^-4.
# A mask or ring-slot error moves an output by a whole attended row's
# share, which for the short rows of these geometries is O(1).
KERNEL_ATOL = 2.0 ** -4
# Logits are f32 dots of the bf16 final hidden state (2304 features) with
# the bf16 tied embedding, softcapped at 30; with this init (embedding std
# 0.02, RMS-normed hidden state) their standard deviation is about 1. The
# impls differ only in how each of the 26 layers rounds its bf16 attention
# output (an ulp or two per element, as the kernel check shows), and the
# residual stream carries every layer's difference forward, so the logits
# drift by a few hundredths (0.064 on a TPU v5e at seed 0). 0.25 is four
# times that, and a quarter of the logits' spread: a wrong window, ring
# phase, scale, softcap or head grouping moves them by O(1).
LOGITS_ATOL = 0.25


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAIL: {what}")


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit is
    counted at its retrieval time)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, secs, **_):
        if event == self.EVENT:
            self.seconds += secs


def make_requests(seed: int, vocab: int):
    import numpy as np

    from repro.serving.engine import Request
    rng = np.random.RandomState(seed)
    reqs = [Request(rid=0, prompt=rng.randint(0, vocab, (4600,)),
                    max_new_tokens=64)]
    for rid in range(1, 8):
        n = int(rng.randint(256, 1025))
        reqs.append(Request(rid=rid, prompt=rng.randint(0, vocab, (n,)),
                            max_new_tokens=32))
    return reqs


def serve(engine, reqs, label: str):
    """Run the requests; fail unless every one finished ok with no kernel
    fallback and no degradation event. Returns {rid: tokens}."""
    from repro.kernels.swat_decode import consume_pad_events
    from repro.serving import faults as F

    t0 = time.perf_counter()
    results = engine.run(reqs)
    secs = time.perf_counter() - t0
    bad = [(r.rid, r.status, r.reason) for r in results if r.status != "ok"]
    check(len(results) == len(reqs) and not bad,
          f"{label}: requests not ok: {bad}")
    check(engine.stats["kernel_fallbacks"] == 0,
          f"{label}: {engine.stats['kernel_fallbacks']} kernel fallbacks")
    events = F.consume_events() + consume_pad_events()
    check(not events, f"{label}: degradation events {events}")
    for r, q in zip(results, reqs):
        check(len(r.tokens) == q.max_new_tokens,
              f"{label}: rid {r.rid} got {len(r.tokens)} tokens")
    n_tok = sum(len(r.tokens) for r in results)
    say(f"{label}: {len(results)} requests ok, {n_tok} tokens, "
        f"0 kernel fallbacks, no degradation events "
        f"({secs:.1f}s wall incl. compile, informational)")
    return {r.rid: r.tokens for r in results}


def kernel_parity(seed: int):
    """swat_decode vs the jnp oracle on the chip, plain and fused."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, with_swat
    from repro.core import layers as L
    from repro.core.model import attn_cfg
    from repro.kernels.ref import decode_ref, ring_insert_ref
    from repro.kernels.swat_decode import swat_decode

    geoms = [
        ("gemma2-2b local", attn_cfg(get_config("gemma2-2b"), "local_attn",
                                     index=0), (5, 4097, 4600, 9000)),
        ("--swat g=4", attn_cfg(with_swat(get_config("llama3.2-1b"),
                                          window=512, num_global=4),
                                "attn", index=0), (3, 100, 700, 1500)),
    ]
    for name, acfg, positions in geoms:
        spec = acfg.spec
        cap = L.cache_capacity(acfg, MAX_LEN)
        w = L.cache_allocation(acfg, MAX_LEN)
        b, hq, hkv, d = (len(positions), acfg.num_heads, acfg.num_kv_heads,
                         acfg.head_dim)
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        normal = lambda k, s: jax.random.normal(k, s, jnp.bfloat16)
        q = normal(ks[0], (b, hq, 1, d))
        kc, vc = normal(ks[1], (b, hkv, w, d)), normal(ks[2], (b, hkv, w, d))
        kn, vn = normal(ks[3], (b, hkv, 1, d)), normal(ks[4], (b, hkv, 1, d))
        pos = jnp.asarray(positions, jnp.int32)
        kw = dict(ring_cap=cap, num_global=spec.num_global,
                  window=spec.window, softcap=spec.softcap, interpret=False)

        got = jax.jit(lambda *a: swat_decode(*a, **kw))(q, kc, vc, pos)
        want = jax.jit(lambda q, k, v, p: decode_ref(
            q, k, v, None, spec, total=p, q0=p - 1, ring_cap=cap))(
                q, kc, vc, pos)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))

        got, k2, v2 = jax.jit(lambda q, k, v, p, nk, nv: swat_decode(
            q, k, v, p, new_k=nk, new_v=nv, **kw))(q, kc, vc, pos, kn, vn)

        def oracle(q, k, v, p, nk, nv):
            one = jnp.ones_like(p)
            g = spec.num_global
            k = ring_insert_ref(k, nk, p, one, ring_cap=cap, num_global=g)
            v = ring_insert_ref(v, nv, p, one, ring_cap=cap, num_global=g)
            return decode_ref(q, k, v, None, spec, total=p + 1, q0=p,
                              ring_cap=cap), k, v
        want, kr, vr = jax.jit(oracle)(q, kc, vc, pos, kn, vn)
        ferr = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - want.astype(jnp.float32))))
        same = (bool(np.array_equal(np.asarray(k2), np.asarray(kr)))
                and bool(np.array_equal(np.asarray(v2), np.asarray(vr))))
        say(f"kernel parity {name} (B {b}, Hq {hq}, Hkv {hkv}, D {d}, "
            f"W {w}, ring {cap}, window {spec.window}, globals "
            f"{spec.num_global}): plain max|diff| {err}, fused {ferr} "
            f"(atol {KERNEL_ATOL}), fused cache bitwise equal: {same}")
        check(err <= KERNEL_ATOL, f"{name} plain: max |diff| {err}")
        check(ferr <= KERNEL_ATOL, f"{name} fused: max |diff| {ferr}")
        check(same, f"{name} fused: ring insert differs from ring_insert_ref")


def logits_parity(cfg, params, prefill, prompt):
    """Decode steps from one prefilled cache through both decode impls.
    The first step's logits must agree within LOGITS_ATOL; greedy-token
    agreement over the rollout is printed, not required."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import model as Mod

    steps = 8
    l_pad = -(-len(prompt) // 16) * 16
    tok = np.zeros((1, l_pad), np.int32)
    tok[0, :len(prompt)] = prompt
    logits, caches = prefill(params, jnp.asarray(tok),
                             jnp.asarray([len(prompt)], jnp.int32))
    first = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)

    def rollout(params, caches, tok, impl):
        def body(carry, _):
            caches, tok = carry
            lg, caches = Mod.decode_step(params, cfg, {"tokens": tok[:, None]},
                                         caches, impl=impl)
            nxt = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
            return (caches, nxt), (lg[:, 0], nxt)
        _, out = jax.lax.scan(body, (caches, tok), None, length=steps)
        return out

    run = jax.jit(rollout, static_argnames="impl")
    lg_p, tok_p = run(params, caches, first, impl="pallas")
    lg_r, tok_r = run(params, caches, first, impl="ref")
    err = float(jnp.max(jnp.abs(lg_p[0] - lg_r[0])))
    spread = float(jnp.std(lg_r[0]))
    agree = float(np.mean(np.asarray(tok_p) == np.asarray(tok_r)))
    say(f"logits parity (prompt {len(prompt)} tokens, ring wrapped): first "
        f"step max|diff| {err} (atol {LOGITS_ATOL}, logit std {spread}); "
        f"greedy tokens agree on {agree:.3f} of {steps} steps "
        f"(informational)")
    check(bool(jnp.all(jnp.isfinite(lg_p))), "pallas logits not finite")
    check(err <= LOGITS_ATOL,
          f"decode-step logits pallas vs ref: max |diff| {err}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the weights, prompts and kernel inputs")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh phase on a four-chip host")
    args = ap.parse_args()

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke FAIL: no program at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    t_start = time.perf_counter()
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX reports {devs[0].platform}")
    want = 4 if args.four_chips else 1
    check(len(devs) >= want, f"{want} chips needed, JAX reports {len(devs)}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    from repro.configs import get_config
    from repro.core import model as Mod
    from repro.core.layers import cache_capacity
    from repro.kernels import ops
    from repro.launch.mesh import parse_mesh
    from repro.serving.engine import ServingEngine

    check(ops.default_interpret() is False,
          "Pallas kernels would run in interpret mode")
    say(f"device {device['kind']} x{device['count']}; Pallas interpret "
        f"mode off; compile cache {cache_dir}")

    if not args.four_chips:
        kernel_parity(args.seed)

    cfg = get_config("gemma2-2b")
    t0 = time.perf_counter()
    params = jax.jit(Mod.init_model, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    say(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads of {cfg.head_dim}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; "
        f"{n_params / 1e9:.3f}B parameters built in "
        f"{time.perf_counter() - t0:.1f}s (informational)")
    reqs = make_requests(args.seed, cfg.vocab_size)

    def engine(**kw):
        return ServingEngine(cfg, params, decode_impl="pallas",
                             batch_slots=SLOTS, max_len=MAX_LEN,
                             scan_steps=SCAN_STEPS, **kw)

    if args.four_chips:
        one = engine()
        base = serve(one, reqs, "one chip")
        del one
        for spec in ("4x1", "2x2"):
            eng = engine(mesh=parse_mesh(spec), profile="tp")
            got = serve(eng, reqs, f"{spec} mesh")
            del eng
            same = sum(a == b for rid in base
                       for a, b in zip(base[rid], got[rid]))
            total = sum(len(t) for t in base.values())
            say(f"{spec} mesh vs one chip: {same}/{total} greedy tokens "
                f"agree")
            if spec == "4x1":
                check(got == base, "4x1 slot-parallel tokens differ from "
                      "the one-chip engine")
    else:
        eng = engine()
        serve(eng, reqs, "gemma2-2b serving")
        n = SCAN_STEPS
        sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        vec = lambda dt: jax.ShapeDtypeStruct((SLOTS,), dt)
        hlo = eng._c.scan(n, SLOTS).lower(
            jax.tree.map(sds, eng.params), jax.tree.map(sds, eng.caches),
            vec(jnp.int32), vec(jnp.bool_), vec(jnp.int32),
            vec(jnp.float32), jax.ShapeDtypeStruct((), jnp.bool_),
            sds(eng.key), vec(jnp.bool_)).compile().as_text()
        check("tpu_custom_call" in hlo,
              "compiled decode scan has no Mosaic kernel")
        ring = cache_capacity(Mod.attn_cfg(cfg, "local_attn", index=0),
                              MAX_LEN)
        say(f"compiled {n}-step decode scan holds the Mosaic kernel "
            f"(tpu_custom_call); local ring {ring} rows, request 0 decoded "
            f"positions 4600-4663 (ring wrapped)")
        prefill = eng._c.prefill(1)
        del eng
        logits_parity(cfg, params, prefill, reqs[0].prompt)

    stats = devs[0].memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}"
        f"; backend compile {clock.seconds:.1f}s; total "
        f"{time.perf_counter() - t_start:.1f}s wall (informational)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

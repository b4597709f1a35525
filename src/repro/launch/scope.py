"""swatscope CLI: inspect and validate serving telemetry.

Two subcommands, both off the hot path:

    # validate exported artifacts (the CI metrics lane)
    PYTHONPATH=src python -m repro.launch.scope validate \
        --trace /tmp/trace.json --metrics /tmp/metrics.prom

    # trace-time dispatch census of a smoke serve (which kernel shapes
    # did the engine actually compile?)
    PYTHONPATH=src python -m repro.launch.scope census --arch llama3.2-1b

`validate` exits nonzero listing every schema problem; `census` runs a
tiny instrumented serve and prints the deduped (shape -> traces) map
plus the engine snapshot.
"""
import argparse
import json
import sys


def _cmd_validate(args):
    from repro.telemetry import validate as V

    problems = []
    if args.trace:
        with open(args.trace) as f:
            doc = json.load(f)
        for p in V.validate_chrome_trace(doc):
            problems.append(f"{args.trace}: {p}")
        if not problems:
            n = len(doc.get("traceEvents", []))
            print(f"[scope] {args.trace}: valid chrome trace ({n} events)")
    if args.metrics:
        with open(args.metrics) as f:
            text = f.read()
        ms = V.validate_prometheus(text)
        for p in ms:
            problems.append(f"{args.metrics}: {p}")
        if not ms:
            n = sum(1 for ln in text.splitlines()
                    if ln.strip() and not ln.startswith("#"))
            print(f"[scope] {args.metrics}: valid prometheus exposition "
                  f"({n} samples)")
    if not args.trace and not args.metrics:
        print("[scope] nothing to validate (pass --trace and/or --metrics)")
        return 2
    for p in problems:
        print(f"[scope] INVALID: {p}")
    return 1 if problems else 0


def _cmd_census(args):
    import jax
    import numpy as np

    from repro.configs import get_smoke_config, with_swat
    from repro.core import model as Mod
    from repro.serving.engine import Request, ServingEngine
    from repro.telemetry import kernelprof as KP

    cfg = with_swat(get_smoke_config(args.arch), window=args.window,
                    num_global=4)
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size, (16,)
                                       ).astype(np.int32),
                    max_new_tokens=8)
            for i in range(args.requests)]
    KP.enable_census(True)
    try:
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                            scan_steps=4, decode_impl=args.impl,
                            metrics=True)
        eng.run(reqs)
    finally:
        KP.enable_census(False)
    census = KP.consume_census()
    print(f"[scope] dispatch census: {len(census)} distinct kernel shapes")
    for rec in census:
        traces = rec.pop("traces")
        print("[scope]   " + " ".join(f"{k}={v}"
                                      for k, v in sorted(rec.items()))
              + f"  (traced {traces}x)")
    snap = eng.snapshot()
    print("[scope] engine snapshot: "
          + json.dumps(snap, sort_keys=True, default=str))
    return 0


def main():
    ap = argparse.ArgumentParser(prog="scope")
    sub = ap.add_subparsers(dest="cmd", required=True)

    v = sub.add_parser("validate", help="schema-check exported artifacts")
    v.add_argument("--trace", default=None,
                   help="chrome-trace JSON (from serve --trace-out)")
    v.add_argument("--metrics", default=None,
                   help="prometheus text (from serve --metrics-out)")
    v.set_defaults(fn=_cmd_validate)

    c = sub.add_parser("census", help="trace-time dispatch census of a "
                                      "smoke serve")
    c.add_argument("--arch", default="llama3.2-1b")
    c.add_argument("--impl", choices=("ref", "pallas"), default="ref")
    c.add_argument("--window", type=int, default=16)
    c.add_argument("--requests", type=int, default=4)
    c.set_defaults(fn=_cmd_census)

    args = ap.parse_args()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())

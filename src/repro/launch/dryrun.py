import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")  # mute absl/XLA warnings
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import — jax locks the device
count at first init. 512 host devices back both meshes:
  single pod : (16, 16)    axes (data, model)        — 256 chips
  multi-pod  : (2, 16, 16) axes (pod, data, model)   — 512 chips

For each cell this builds the real step function (train_step = fwd+bwd+AdamW;
serve_step = 1-token decode vs caches; prefill for the prefill cells),
shards params/optimizer/caches/batch with the rule tables in
distributed/sharding.py, lowers with ShapeDtypeStructs (no allocation),
compiles, and records memory_analysis / cost_analysis / collective traffic
to experiments/dryrun/<arch>__<shape>__<mesh>.json (incremental: existing
files are skipped unless --force).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
  python -m repro.launch.dryrun --all --swat-variant    # beyond-paper cells
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import ARCH_IDS, canonical, get_config, with_swat
from repro.core.types import ALL_SHAPES, ModelConfig, ShapeConfig
from repro.distributed import hlo_analysis as H
from repro.distributed import sharding as Sh
from repro.launch import analytic
from repro.launch import mesh as mesh_lib
from repro.launch import specs as Sp
from repro.launch import steps as St
from repro.optim import adamw

SHAPES = {s.name: s for s in ALL_SHAPES}

# dry-run only lowers+compiles (never executes): lower with TPU-native
# mixed-precision dots so the roofline sees no artificial fp32 copies
from repro.kernels import dots as _dots  # noqa: E402
_dots.native_mixed_dot(True)

# long_500k skip policy (DESIGN.md §4): pure full-attention archs skip in
# their faithful config; SSM/hybrid/local-attn archs run. whisper's decoder
# is structurally capped at 448 tokens.
LONG_CTX_OK = {"mamba2_1p3b", "jamba_1p5_large", "gemma2_2b"}
SKIP = {(a, "long_500k") for a in ARCH_IDS if a not in LONG_CTX_OK}


def out_dir() -> Path:
    d = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
    d.mkdir(parents=True, exist_ok=True)
    return d


def model_flops_per_device(cfg: ModelConfig, shape: ShapeConfig,
                           n_devices: int) -> float:
    n_active = Sp.active_param_count(cfg)
    if shape.mode == "train":
        f = 6.0 * n_active * shape.global_batch * shape.seq_len
    elif shape.mode == "prefill":
        f = 2.0 * n_active * shape.global_batch * shape.seq_len
    else:
        f = 2.0 * n_active * shape.global_batch
    return f / n_devices


def _memory_dict(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            if hasattr(ma, attr):
                out[attr] = int(getattr(ma, attr))
        if not out:
            out["repr"] = str(ma)
    except Exception as e:  # CPU backend may not implement it
        out["error"] = repr(e)
    return out


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
               impl: str = "xla", sequence_parallel: bool = True,
               unroll: bool = True, profile: str = "tp",
               remat_policy: str = "nothing"):
    """Build + lower + compile one cell. Returns (compiled, lowered).

    unroll=True unrolls the layer scan so XLA's cost analysis (which counts
    a while body exactly once) sees every layer's FLOPs/bytes/collectives —
    required for honest roofline terms. Training itself keeps the rolled
    scan.

    profile='cp' switches to 2D-FSDP sharding + halo-exchange context
    parallelism for the window-attention layers; profile='fsdp' is the same
    parameter placement with batch-parallel compute (§Perf beyond-paper
    modes)."""
    from repro.core import moe as moe_lib
    from repro.kernels import ops as kops
    kops.set_context_parallel(mesh if profile == "cp" else None, "model")
    moe_lib.set_expert_parallel(mesh)
    batch_specs = Sp.input_specs(cfg, shape)
    p_specs = Sp.param_specs(cfg)
    p_shard = Sh.param_sharding(p_specs, mesh, profile=profile)
    b_shard = Sh.batch_sharding(batch_specs, mesh, profile=profile)

    if shape.mode == "train":
        opt_cfg = adamw.AdamWConfig()
        o_specs = jax.eval_shape(adamw.init_opt_state, p_specs)
        o_shard = adamw.OptState(step=Sh.replicated(mesh), mu=p_shard,
                                 nu=p_shard)
        act = jax.sharding.NamedSharding(
            mesh, Sh.activation_spec(mesh, sequence_parallel, profile))
        step = St.make_train_step(cfg, opt_cfg, impl=impl, act_sharding=act,
                                  unroll=unroll,
                                  remat_policy=("nothing"
                                                if remat_policy == "off"
                                                else remat_policy),
                                  remat=remat_policy != "off")
        fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None),
                     donate_argnums=(0, 1))
        lowered = fn.lower(p_specs, o_specs, batch_specs)
    elif shape.mode == "prefill":
        step = St.make_prefill_step(cfg, max_len=shape.seq_len, impl=impl,
                                    unroll=unroll)
        fn = jax.jit(step, in_shardings=(p_shard, b_shard))
        lowered = fn.lower(p_specs, batch_specs)
    else:  # decode
        c_specs = Sp.cache_specs(cfg, shape)
        c_shard = Sh.cache_sharding(c_specs, mesh)
        step = St.make_serve_step(cfg, impl=impl, unroll=unroll, mesh=mesh)
        fn = jax.jit(step, in_shardings=(p_shard, c_shard, b_shard),
                     out_shardings=(None, c_shard), donate_argnums=(1,))
        lowered = fn.lower(p_specs, c_specs, batch_specs)
    compiled = lowered.compile()
    return compiled, lowered


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             swat_variant: bool = False, impl: str = "xla",
             sequence_parallel: bool = True, tag: str = "",
             profile: str = "tp", moe_dispatch: str = "sort",
             remat_policy: str = "nothing", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    if swat_variant:
        cfg = with_swat(cfg)
    if cfg.moe.enabled and moe_dispatch != cfg.moe.dispatch:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=moe_dispatch))
    shape = SHAPES[shape_name]
    multi = mesh_kind == "multi"
    mesh = mesh_lib.make_production_mesh(multi_pod=multi)
    n_dev = int(np.prod(list(mesh.shape.values())))

    # single-pod cells compile the UNROLLED stack so cost_analysis sees every
    # layer (the roofline table). multi-pod cells compile the production
    # rolled scan: the pass proves the pod-axis sharding is coherent, ~10x
    # faster, and is exactly what the trainer runs.
    unroll = not multi
    t0 = time.time()
    with mesh:
        compiled, lowered = lower_cell(cfg, shape, mesh, impl=impl,
                                       sequence_parallel=sequence_parallel,
                                       unroll=unroll, profile=profile,
                                       remat_policy=remat_policy)
    compile_s = time.time() - t0

    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    coll = H.parse_collectives(compiled.as_text())
    mf = model_flops_per_device(cfg, shape, n_dev)
    roof = H.roofline_terms(cost, coll, mf)
    mem = _memory_dict(compiled)

    rec = {
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_kind,
        "mode": shape.mode, "devices": n_dev, "impl": impl,
        "params": Sp.param_count(get_config(arch)),
        "active_params": Sp.active_param_count(get_config(arch)),
        "compile_s": round(compile_s, 2),
        "unrolled": unroll,
        "profile": profile,
        "memory": mem,
        "roofline": roof.to_dict(),
        "analytic_flops_per_device": analytic.step_flops(cfg, shape) / n_dev,
        "tag": tag,
    }
    if verbose:
        print(f"[dryrun] {cfg.name} x {shape.name} x {mesh_kind} "
              f"({n_dev} dev): compile={compile_s:.1f}s "
              f"flops/dev={roof.flops:.3e} bytes/dev={roof.bytes_accessed:.3e} "
              f"coll/dev={roof.collective_bytes:.3e} "
              f"dominant={roof.dominant} "
              f"roofline_frac={roof.roofline_fraction:.3f}")
        print(f"  memory_analysis: {mem}")
        print(f"  collectives: {roof.counts}")
    return rec


def cell_path(arch, shape_name, mesh_kind, swat_variant, tag="") -> Path:
    suffix = "+swat" if swat_variant else ""
    t = f"__{tag}" if tag else ""
    return out_dir() / f"{canonical(arch)}{suffix}__{shape_name}__{mesh_kind}{t}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--swat-variant", action="store_true",
                    help="beyond-paper: dense archs with SWAT window attn")
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--profile", default="tp", choices=["tp", "cp", "fsdp"],
                    help="cp: 2D-FSDP + halo-exchange context parallelism; "
                         "fsdp: 2D-FSDP, batch-parallel compute, no TP")
    ap.add_argument("--moe-dispatch", default="sort",
                    choices=["sort", "dense", "ep"])
    ap.add_argument("--remat", default="nothing",
                    choices=["nothing", "dots", "off"])
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence-parallel activation sharding")
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            skip_set = SKIP if not args.swat_variant else {
                (canonical(a), "long_500k") for a in ("whisper_tiny",
                                                      "mamba2_1p3b")}
            if (canonical(arch), shape_name) in skip_set:
                print(f"[dryrun] SKIP {arch} x {shape_name} "
                      f"(policy: DESIGN.md §4)")
                continue
            if args.swat_variant and get_config(arch).is_attention_free:
                continue
            for mesh_kind in meshes:
                path = cell_path(arch, shape_name, mesh_kind,
                                 args.swat_variant, args.tag)
                if path.exists() and not args.force:
                    print(f"[dryrun] cached {path.name}")
                    continue
                try:
                    rec = run_cell(arch, shape_name, mesh_kind,
                                   swat_variant=args.swat_variant,
                                   impl=args.impl,
                                   sequence_parallel=not args.no_sp,
                                   profile=args.profile,
                                   moe_dispatch=args.moe_dispatch,
                                   remat_policy=args.remat,
                                   tag=args.tag)
                    path.write_text(json.dumps(rec, indent=2))
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape_name, mesh_kind, repr(e)))
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nAll requested dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()

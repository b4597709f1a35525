"""Production training launcher: mesh-sharded pjit training with the full
fault-tolerance stack. On a real TPU fleet this is the per-host entry point
(jax.distributed.initialize + the same mesh); on CPU it runs the identical
code path on a debug mesh (--debug-mesh, subprocess-safe with
--device-count).

    # real pod (per host):
    python -m repro.launch.train --arch llama3.2-1b --steps 1000

    # CPU rehearsal on a 2x2 fake mesh:
    python -m repro.launch.train --arch llama3.2-1b --smoke \
        --device-count 4 --debug-mesh 2,2 --steps 4
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU rehearsal)")
    ap.add_argument("--device-count", type=int, default=0,
                    help="force host platform device count (set BEFORE jax)")
    ap.add_argument("--debug-mesh", default="",
                    help="e.g. 2,2 -> (data, model) debug mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--fail-at", type=int, default=-1)
    args = ap.parse_args()

    if args.device_count:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.device_count} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from repro.configs import get_config, get_smoke_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.distributed import sharding as Sh
    from repro.launch import mesh as mesh_lib
    from repro.launch import steps as St
    from repro.core import model as Mod
    from repro.optim import adamw
    from repro.checkpoint.manager import CheckpointManager

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.debug_mesh:
        dims = tuple(int(x) for x in args.debug_mesh.split(","))
        mesh = mesh_lib.make_debug_mesh(*dims)
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch))
    opt_cfg = adamw.AdamWConfig(total_steps=args.steps, warmup_steps=10)
    ckpt = CheckpointManager(args.ckpt_dir, keep=3)

    with mesh:
        p_specs = jax.eval_shape(
            lambda: Mod.init_model(jax.random.PRNGKey(0), cfg))
        p_shard = Sh.param_sharding(p_specs, mesh)
        o_shard = adamw.OptState(step=Sh.replicated(mesh), mu=p_shard,
                                 nu=p_shard)
        act = jax.sharding.NamedSharding(mesh, Sh.activation_spec(
            mesh, sequence_parallel=args.seq % mesh.shape["model"] == 0))
        step_fn = jax.jit(
            St.make_train_step(cfg, opt_cfg, act_sharding=act,
                               grad_compression=args.grad_compression),
            in_shardings=(p_shard, o_shard, None),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1))

        params = jax.jit(lambda: Mod.init_model(jax.random.PRNGKey(0), cfg),
                         out_shardings=p_shard)()
        opt_state = jax.jit(adamw.init_opt_state,
                            out_shardings=o_shard)(params)

        start = ckpt.latest_step() or 0
        if start:
            state = ckpt.restore(start, like={"params": params,
                                              "opt": opt_state},
                                 sharding={"params": p_shard,
                                           "opt": o_shard})
            params, opt_state = state["params"], state["opt"]
            print(f"[train] resumed at step {start}")

        for step in range(start, args.steps):
            if step == args.fail_at:
                raise RuntimeError(f"injected failure at step {step}")
            batch = {k: jnp.asarray(v)
                     for k, v in data.global_batch(step).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % 10 == 0 or step == args.steps - 1:
                print(f"[train] step {step} "
                      f"loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f}")
            if (step + 1) % 50 == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state})
        ckpt.save(args.steps, {"params": params, "opt": opt_state},
                  blocking=True)
        ckpt.wait()
        print("[train] done")


if __name__ == "__main__":
    main()

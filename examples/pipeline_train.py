"""Pipeline-parallel training: GPipe microbatches over the super-block seam.

A 2-stage pipeline on a (pipe=2, data=2) CPU mesh trains a small window-
attention LM; the script verifies the pipelined loss matches the single-pass
loss before training, then runs real PP steps.

    PYTHONPATH=src python examples/pipeline_train.py
"""
import os
import sys

if "device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))
    os.environ["TF_CPP_MIN_LOG_LEVEL"] = "2"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import time                                                   # noqa: E402

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.core.types import AttentionSpec, ModelConfig      # noqa: E402
from repro.core import model as Mod                           # noqa: E402
from repro.distributed import pipeline as PP                  # noqa: E402
from repro.launch import mesh as mesh_lib                     # noqa: E402
from repro.optim import adamw                                 # noqa: E402


def main():
    cfg = ModelConfig(
        name="pp-demo", num_layers=4, d_model=128, num_heads=4,
        num_kv_heads=4, d_ff=512, vocab_size=1000,
        attention=AttentionSpec(kind="swat", window=64, causal=True),
        dtype="float32")
    mesh = mesh_lib.make_debug_pp_mesh(n_pipe=2, n_data=2)
    pcfg = PP.PipelineConfig(num_stages=2, num_microbatches=4)
    print(f"stages=2 microbatches=4 "
          f"bubble={PP.bubble_fraction(pcfg):.2f}")

    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, 1000, (8, 128)),
                                   jnp.int32)}
    batch["labels"] = batch["tokens"]

    with jax.set_mesh(mesh):
        loss_fn = PP.make_pipeline_loss(cfg, pcfg, mesh)
        l_pp, _ = jax.jit(loss_fn)(params, batch)
    l_ref, _ = Mod.loss_fn(params, cfg, batch, remat=False)
    print(f"PP loss {float(l_pp):.4f} == single-pass {float(l_ref):.4f}")
    assert abs(float(l_pp) - float(l_ref)) < 1e-2

    opt_cfg = adamw.AdamWConfig(lr=3e-4, warmup_steps=5)
    opt = adamw.init_opt_state(params)
    with jax.set_mesh(mesh):
        step = jax.jit(PP.make_pp_train_step(cfg, opt_cfg, pcfg, mesh))
        for i in range(20):
            t0 = time.time()
            params, opt, m = step(params, opt, batch)
            if i % 5 == 0:
                print(f"step {i:>3} loss={float(m['loss']):.4f} "
                      f"({(time.time() - t0) * 1e3:.0f} ms)")
    print("pipeline training ran; loss decreased:",
          float(m["loss"]) < float(l_pp))


if __name__ == "__main__":
    main()

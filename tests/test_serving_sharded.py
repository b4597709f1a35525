"""Mesh-sharded serving: the sharded engine is the SAME function.

Subprocess with --xla_force_host_platform_device_count=4 (the
test_sharding_profiles.py pattern). The load-bearing claims:

  * a pure slot-parallel mesh (4x1, slot axis over 'data') is token-for-
    token IDENTICAL to the single-device engine for mixed greedy/sampled
    requests — every slot's math is device-local, so even the sampled rows
    must match bitwise,
  * the ring caches actually shard (slot dim over 'data', per-slot `step`
    riding the same axis — the rule this PR adds; silent replication is the
    failure mode these rules exist to prevent),
  * a 2x2 TP mesh (row-parallel psum splits a bf16 contraction => logits
    can move ~1 ulp) still reproduces every GREEDY row token-for-token and
    serves sampled rows to completion,
  * divisibility-aware admission: with 4 slots on a 4-way slot axis the
    scheduler trims prefill batches to quantum multiples.

Marked slow like every other subprocess suite, but still IN the CI fast
lane: ci.yml runs this file as its own step (no marker filter — CPU-only),
so a sharding regression is visible at a glance without double-running it
inside the `-m "not slow"` sweep.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

ROOT = Path(__file__).resolve().parents[1]


def run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = f"{ROOT}/src"
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


COMMON = """
    import jax
    import numpy as np
    from repro.configs import get_smoke_config, with_swat
    from repro.core import model as Mod
    from repro.launch import mesh as mesh_lib
    from repro.serving.engine import Request, ServingEngine

    assert jax.device_count() == 4, jax.devices()
    cfg = with_swat(get_smoke_config("llama3p2_1b"), window=16, num_global=4)
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
               for l in (12, 30, 7, 18, 25, 10)]
    temps = [0.0, 1.5, 0.0, 2.5, 1.0, 0.0]   # mixed greedy / sampled
    budgets = [6, 9, 4, 7, 5, 8]

    def reqs():
        return [Request(rid=i, prompt=prompts[i], max_new_tokens=budgets[i],
                        temperature=temps[i]) for i in range(6)]

    def run(mesh, **kw):
        eng = ServingEngine(cfg, params, batch_slots=4, max_len=128,
                            scan_steps=4, seed=11, mesh=mesh, **kw)
        return eng, {r.rid: r.tokens for r in eng.run(reqs())}

    def axes_of(spec):
        flat = []
        for entry in tuple(spec):
            if entry is None:
                continue
            flat.extend((entry,) if isinstance(entry, str) else entry)
        return flat
"""


def test_slot_parallel_mesh_token_identical():
    """4x1 slot-parallel mesh == single-device engine, bitwise, including
    temperature>0 rows (all math is slot-local under pure data sharding)."""
    run_sub(COMMON + """
    _, base = run(None)
    eng, shard = run(mesh_lib.make_debug_mesh(4, 1))
    k = eng.caches["l0"]["k"]
    assert "data" in axes_of(k.sharding.spec), k.sharding.spec
    step_spec = tuple(eng.caches["l0"]["step"].sharding.spec)
    assert step_spec[1] == "data", step_spec   # per-slot step rides the slot axis
    assert eng.scheduler.slot_quantum == 4
    assert base == shard, (base, shard)
    print("ok slot-parallel identical")
    """)


def test_tp_mesh_sharded_and_deterministic():
    """2x2 data x model mesh: caches shard on BOTH axes, every request is
    served to its exact budget, and the run is bit-reproducible (two
    identical engines agree). Token-for-token parity with the single-device
    engine is NOT asserted here: row-parallel TP psums a bf16 contraction
    in a different order, so logits move ~1 ulp and near-tied argmax /
    categorical draws may legitimately flip — the exact-parity bar lives on
    the slot-parallel mesh above, where all math is slot-local."""
    run_sub(COMMON + """
    eng, shard = run(mesh_lib.make_debug_mesh(2, 2))
    k = eng.caches["l0"]["k"]
    spec = tuple(k.sharding.spec)
    assert spec[1] == "data", spec             # slot axis sharded
    assert "model" in axes_of(k.sharding.spec), spec
    for i in range(6):
        assert len(shard[i]) == budgets[i]
    _, again = run(mesh_lib.make_debug_mesh(2, 2))
    assert shard == again, (shard, again)
    print("ok tp sharded + deterministic")
    """)


def test_sharded_chunked_prefill_matches():
    """Chunked prefill under the slot-parallel mesh is still exact."""
    run_sub(COMMON + """
    mesh = mesh_lib.make_debug_mesh(4, 1)
    _, single = run(mesh)
    _, chunked = run(mesh, prefill_chunk=8)
    assert single == chunked, (single, chunked)
    print("ok sharded chunked prefill")
    """)


def test_speculative_slot_parallel_identical():
    """Speculative decode on a 4-device slot-parallel mesh: bitwise the
    unsharded speculative engine on EVERY row (draft/verify/accept and
    the n-gram history are all slot-local math), and the greedy rows are
    bitwise the unsharded SEQUENTIAL engine — the ISSUE-6 acceptance bar.
    The drafter history state must actually ride the slot axis, not
    silently replicate."""
    run_sub(COMMON + """
    _, seq = run(None)
    _, spec = run(None, speculative=3)
    eng, shard = run(mesh_lib.make_debug_mesh(4, 1), speculative=3)
    assert spec == shard, (spec, shard)
    for i in (0, 2, 5):                      # the greedy rows
        assert shard[i] == seq[i], (i, shard[i], seq[i])
    assert eng.stats["draft_proposed"] > 0
    print("ok sharded speculative identical; acceptance",
          round(eng.acceptance_rate, 3))
    """)


def test_metrics_slot_parallel_identical():
    """Device counters on the 4-device slot-parallel mesh — the ISSUE-10
    acceptance bar: the metrics-on engine is BITWISE identical to the
    metrics-off sharded run (sequential AND speculative), the counter
    vectors actually ride the slot axis (no silent replication), and the
    device totals agree with the host-side stats."""
    run_sub(COMMON + """
    _, base = run(mesh_lib.make_debug_mesh(4, 1))
    eng, out = run(mesh_lib.make_debug_mesh(4, 1), metrics=True)
    assert base == out, (base, out)
    mx = eng._mx
    assert "data" in axes_of(mx["tokens"].sharding.spec), \\
        mx["tokens"].sharding.spec
    dev = eng.device_metrics()
    assert dev["tokens"] == eng.stats["tokens_emitted"]
    assert dev["quarantined"] == 0

    _, sbase = run(mesh_lib.make_debug_mesh(4, 1), speculative=3)
    seng, sout = run(mesh_lib.make_debug_mesh(4, 1), speculative=3,
                     metrics=True)
    assert sbase == sout, (sbase, sout)
    sdev = seng.device_metrics()
    assert sdev["drafts_proposed"] == seng.stats["draft_proposed"] > 0
    assert sdev["drafts_accepted"] == seng.stats["draft_accepted"]
    print("ok sharded metrics identical; device tokens", dev["tokens"])
    """)


def test_chaos_quarantine_slot_parallel():
    """Fault injection on the 4-device slot-parallel mesh — the ISSUE-8
    acceptance bar: NaN-poisoning one slot's logits quarantines exactly
    that request (status 'poisoned', clean-prefix tokens) while every
    healthy slot stays BITWISE identical to the fault-free sharded run.
    The injected scan is still slot-local math, so the guarded program is
    held to the same zero-collective budget (launch/analyze chaos_4x1)."""
    run_sub(COMMON + """
    from repro.serving import faults as Flt
    from repro.serving.faults import FaultPlan

    _, base = run(mesh_lib.make_debug_mesh(4, 1))
    Flt.consume_events()
    eng = ServingEngine(cfg, params, batch_slots=4, max_len=128,
                        scan_steps=4, seed=11,
                        mesh=mesh_lib.make_debug_mesh(4, 1),
                        faults=FaultPlan(poison_logits=((2, 3, "nan"),)))
    out = {r.rid: r for r in eng.run(reqs())}
    assert out[2].status == "poisoned", out[2]
    assert len(out[2].tokens) == 3 and out[2].tokens == base[2][:3]
    for i in (0, 1, 3, 4, 5):
        assert out[i].status == "ok" and out[i].tokens == base[i], i
    assert eng.stats["quarantined"] == 1
    kinds = [e["kind"] for e in Flt.consume_events()]
    assert "slot_quarantined" in kinds, kinds
    print("ok sharded chaos quarantine bitwise")
    """)


@pytest.mark.parametrize("dims", [(4, 1), (2, 2)], ids=["4x1", "2x2"])
def test_pallas_decode_runs_per_shard(dims):
    """The pallas decode kernel under a mesh runs inside shard_map (XLA
    cannot partition a Mosaic kernel). Slot-parallel: bitwise the
    single-device pallas engine. TP: every request served to budget."""
    run_sub(COMMON + f"""
    _, base = run(None, decode_impl="pallas")
    eng, shard = run(mesh_lib.make_debug_mesh(*{dims!r}),
                     decode_impl="pallas")
    assert eng.stats["kernel_fallbacks"] == 0
    if {dims!r}[1] == 1:
        assert base == shard, (base, shard)
    for i in range(6):
        assert len(shard[i]) == budgets[i]
    print("ok pallas per shard")
    """)

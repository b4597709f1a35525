"""Decode writes each step's K/V rows into the stacked rings in place.

`model.decode_step`'s layer scan carries the stacked caches, and the ref
decode writes a step's new rows straight into them (`ref.ring_insert_ref`,
a scatter; under a mesh per shard of the ring). It must give bitwise the
rings and greedy tokens of the iota==slot select over the whole ring
(`_select_insert` below), which decode ran before.

Meshes run in a subprocess on four forced CPU devices.
"""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config, with_swat
from repro.core import model as Mod
from repro.kernels import ops as kops
from repro.kernels import ref as R
from repro.kernels.ref import put_layer, ring_insert_ref, take_layer
from repro.serving import engine as E
from repro.serving.engine import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]


def _swat_cfg():
    # ring: window 16 + 1 + 4 sinks = 21 logical rows, 32 allocated
    cfg = with_swat(get_smoke_config("llama3p2_1b"), window=16, num_global=4)
    return cfg, Mod.init_model(jax.random.PRNGKey(0), cfg)


def _select_insert(cache, new, pos, num_new, *, ring_cap, num_global,
                   layer=None, row0=0):
    """`ring_insert_ref` as iota==slot selects over every row of the ring
    (ascending j: last writer wins); of a stack, the layer's ring is taken
    out and put back."""
    if cache.ndim == 5:
        return put_layer(cache, layer, _select_insert(
            take_layer(cache, layer), new, pos, num_new, ring_cap=ring_cap,
            num_global=num_global, row0=row0))
    b, _, wcap, _ = cache.shape
    g, ring = num_global, ring_cap - num_global
    pos = jnp.asarray(pos, jnp.int32).reshape(b)
    num_new = jnp.asarray(num_new, jnp.int32).reshape(b)
    rows = jnp.arange(wcap, dtype=jnp.int32)[None, :] + row0
    for j in range(new.shape[2]):
        pj = pos + j
        slot = jnp.where(pj < g, pj, g + jnp.mod(pj - g, ring))
        hit = ((rows == slot[:, None])
               & (j < num_new)[:, None])[:, None, :, None]
        cache = jnp.where(hit, new[:, :, j:j + 1].astype(cache.dtype), cache)
    return cache


def _row_write_and_select(run):
    """run() with the decode programs as built (the row write), then with
    the select compiled in; fresh compiles both ways."""
    E._get_compiled.cache_clear()
    try:
        rows = run()
        with mock.patch.object(R, "ring_insert_ref", _select_insert):
            E._get_compiled.cache_clear()
            select = run()
    finally:
        E._get_compiled.cache_clear()
    return rows, select


def _engine_run(speculative: int):
    cfg, params = _swat_cfg()
    rng = np.random.RandomState(5)
    if speculative:
        # repeating prompts: the n-gram drafter proposes, and the verifier
        # accepts a ragged number of them per slot and step
        base = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        prompts = [np.tile(base, 5)[:n] for n in (9, 26, 17)]
    else:
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 40, 23)]
    # 3 requests on 4 slots (one never active, the rest retiring at
    # different steps); positions reach 70, past two turns of the 17-row
    # ring and its 4 sinks
    budgets = (30, 31, 12)

    def run():
        eng = ServingEngine(cfg, params, batch_slots=4, max_len=128,
                            scan_steps=4, speculative=speculative)
        out = {r.rid: r.tokens for r in eng.run(
            [Request(rid=i, prompt=p, max_new_tokens=b)
             for i, (p, b) in enumerate(zip(prompts, budgets))])}
        if speculative:
            assert eng.stats["draft_accepted"] > 0
        return out, jax.device_get(eng.caches)
    return run


def _model_run(arch: str):
    cfg = get_smoke_config(arch)
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(7)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 12)),
                                   jnp.int32)}
    if cfg.encoder_decoder:
        batch["enc_embeddings"] = jnp.asarray(rng.randn(2, 16, cfg.d_model),
                                              jnp.float32)

    def run():
        logits, caches = Mod.prefill(params, cfg, batch, max_len=32)
        step = jax.jit(lambda c, t: Mod.decode_step(params, cfg,
                                                    {"tokens": t}, c))
        toks = []
        for _ in range(24):          # past max_len: the dense ring wraps
            t = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            toks.append(np.asarray(t))
            logits, caches = step(caches, t)
        return np.concatenate(toks, 1), jax.device_get(caches)
    return run


def _ops_run():
    """decode_attention on stacked caches: T=3 with ragged num_new (0 to
    3 rows real), sinks, a tile-rounded width, slots past two turns."""
    from repro.core.types import AttentionSpec
    spec = AttentionSpec(kind="swat", window=12, num_global=2)
    rng = np.random.RandomState(11)
    n, b, hkv, w, d, t = 3, 4, 2, 32, 8, 3
    cap = 12 + 1 + 2 + (t - 1)

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)
    q, kn, vn = arr(b, 2 * hkv, t, d), arr(b, hkv, t, d), arr(b, hkv, t, d)
    kc, vc = arr(n, b, hkv, w, d), arr(n, b, hkv, w, d)
    pos = jnp.asarray([0, 7, 33, 61], jnp.int32)
    num_new = jnp.asarray([3, 0, 2, 1], jnp.int32)

    def run():
        f = jax.jit(lambda kc, vc, layer: kops.decode_attention(
            q, kc, vc, None, spec, impl="ref", new_kv=(kn, vn),
            num_new=num_new, pos=pos, ring_cap=cap, layer=layer))
        return jax.device_get(f(kc, vc, 1))
    return run


CASES = {
    "t1_wrap_sinks_inactive": lambda: _engine_run(0),
    "spec_scan_ragged": lambda: _engine_run(3),
    "ops_stacked_ragged_num_new": _ops_run,
    "mamba_attn": lambda: _model_run("jamba_1p5_large"),
    "xattn": lambda: _model_run("whisper_tiny"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_row_write_matches_select(case):
    """Bitwise-equal rings, outputs and greedy tokens, row write against
    select."""
    rows, select = _row_write_and_select(CASES[case]())
    la, lb = jax.tree.leaves(rows), jax.tree.leaves(select)
    assert jax.tree.structure(rows) == jax.tree.structure(select)
    for a, b in zip(map(np.asarray, la), map(np.asarray, lb)):
        assert a.dtype == b.dtype and np.array_equal(a, b), case


def test_ring_insert_row_form_is_select_form():
    """The row write against the select on a bare (B, H, W, D) ring, on
    layer 2 of a stack of three, whose other layers it leaves alone, and
    on a ring split along W into three shards, each written with its
    `row0`."""
    rng = np.random.RandomState(2)
    b, h, w, d, t = 5, 3, 48, 4, 2
    cache = jnp.asarray(rng.randn(b, h, w, d), jnp.bfloat16)
    new = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    pos = jnp.asarray([0, 3, 40, 41, 200], jnp.int32)
    num_new = jnp.asarray([2, 1, 0, 2, 2], jnp.int32)
    kw = dict(ring_cap=43, num_global=3)
    want = _select_insert(cache, new, pos, num_new, **kw)
    assert not np.array_equal(want, cache)
    assert np.array_equal(ring_insert_ref(cache, new, pos, num_new, **kw),
                          want)
    stack = jnp.stack([cache + 1, cache + 2, cache])
    got = jax.jit(lambda s: ring_insert_ref(s, new, pos, num_new, layer=2,
                                            **kw))(stack)
    assert np.array_equal(got[2], want)
    assert np.array_equal(got[:2], stack[:2])
    shards = [ring_insert_ref(cache[:, :, r:r + 16], new, pos, num_new,
                              row0=r, **kw) for r in (0, 16, 32)]
    assert np.array_equal(jnp.concatenate(shards, 2), want)


def test_one_device_writes_rows():
    """With no mesh every decode step writes rows in place, and the
    `swat.decode` span says so."""
    cfg, params = _swat_cfg()
    rng = np.random.RandomState(1)
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=128,
                        scan_steps=4)
    eng.run([Request(rid=i, prompt=rng.randint(0, cfg.vocab_size, (9,)),
                     max_new_tokens=10) for i in range(3)])
    assert eng._c.row_write
    assert eng.stats["decode_steps_row_write"] == eng.stats["decode_steps"]
    assert eng.stats["decode_steps"] > 0
    counts = [c for name, _, _, c in eng.tracer.spans
              if name == "swat.decode"]
    assert counts and all(c["row_write"] == 1 for c in counts)


def _ring_shaped_ops(engine, slots: int):
    """Opcodes of the compiled decode scan's operations whose result has
    a stacked ring's shape."""
    c = engine._c
    vec = lambda dt: jnp.zeros((slots,), dt)
    compiled = c.scan(4, slots).lower(
        engine.params, engine.caches, vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.int32), vec(jnp.float32), jnp.asarray(False), engine.key,
        vec(jnp.bool_)).compile()
    k = engine.caches["l0"]["k"]
    shape = re.escape({"float32": "f32", "bfloat16": "bf16"}[k.dtype.name]
                      + f"[{','.join(map(str, k.shape))}]")
    return re.findall(rf"= {shape}\{{[^}}]*\}} ([a-z-]+)\(",
                      compiled.as_text())


def test_decode_scan_neither_copies_nor_selects_a_ring():
    """The compiled decode scan (CPU) neither copies nor selects a whole
    stacked ring: its updates are the row scatters."""
    cfg, params = _swat_cfg()
    eng = ServingEngine(cfg, params, batch_slots=4, max_len=128,
                        scan_steps=4)
    ops = _ring_shaped_ops(eng, 4)
    assert ops.count("scatter") == 2, ops
    assert not {"copy", "select", "transpose"} & set(ops), ops


def test_meshes_write_rows_in_place():
    """On four forced CPU devices every mesh writes rows in place, the
    counter says so, and the rings and tokens are the select's: a 4x1
    slot-parallel mesh (also token-identical to one device), and a 2x2
    mesh whose model axis splits the one kv head's ring rows. At 2x2 no
    collective of the compiled decode scan moves a ring's worth of data,
    and no ring is copied: the row write gathers nothing."""
    code = textwrap.dedent("""
        import re
        from unittest import mock
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.configs import get_smoke_config, with_swat
        from repro.core import model as Mod
        from repro.kernels import ref as R
        from repro.launch import mesh as mesh_lib
        from repro.serving import engine as E
        from repro.serving.engine import Request, ServingEngine
        from test_ring_row_write import _select_insert

        assert jax.device_count() == 4
        cfg = with_swat(get_smoke_config("llama3p2_1b"), window=16,
                        num_global=4)
        params = Mod.init_model(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (12, 30, 7, 25)]

        def run(mesh):
            E._get_compiled.cache_clear()
            eng = ServingEngine(cfg, params, batch_slots=4, max_len=128,
                                scan_steps=4, mesh=mesh)
            out = {r.rid: r.tokens for r in eng.run(
                [Request(rid=i, prompt=p, max_new_tokens=9)
                 for i, p in enumerate(prompts)])}
            st = eng.stats
            assert st["decode_steps"] > 0
            assert st["decode_steps_row_write"] == st["decode_steps"], st
            return eng, out, jax.device_get(eng.caches)

        def same(a, b):
            return all(np.array_equal(x, y) for x, y in
                       zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        def check_hlo(eng):
            k = eng.caches["l0"]["k"]
            assert k.sharding.spec[3] is not None, k.sharding   # rows split
            local = k.sharding.shard_shape(k.shape)
            vec = lambda dt: jnp.zeros((4,), dt)
            hlo = eng._c.scan(4, 4).lower(
                eng.params, eng.caches, vec(jnp.int32), vec(jnp.bool_),
                vec(jnp.int32), vec(jnp.float32), jnp.asarray(False),
                eng.key, vec(jnp.bool_)).compile().as_text()
            ring_elems = int(np.prod(local[1:]))    # one layer's shard
            for m in re.finditer(
                    r"= \\w+\\[([\\d,]*)\\][^ ]* (all-gather|all-reduce|"
                    r"all-to-all|collective-permute|reduce-scatter)"
                    r"(-start)?\\(", hlo):
                n = int(np.prod([int(x) for x in m.group(1).split(",") if x]))
                assert n < ring_elems, m.group(0)
            dims = ",".join(map(str, local))
            assert not re.search(rf"\\[{dims}\\]\\{{[^}}]*\\}} copy\\(", hlo)

        _, base, _ = run(None)
        for spec in ("4x1", "2x2"):
            mesh = mesh_lib.parse_mesh(spec)
            eng, out, caches = run(mesh)
            with mock.patch.object(R, "ring_insert_ref", _select_insert):
                _, sel_out, sel_caches = run(mesh)
            assert out == sel_out and same(caches, sel_caches), spec
            if spec == "4x1":
                assert out == base, spec
            else:
                check_hlo(eng)
            print("MESH_OK", spec)
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"),
                                         str(ROOT / "tests")])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.count("MESH_OK") == 2

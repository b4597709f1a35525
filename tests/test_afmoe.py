"""Trinity-Mini's block (afmoe) through the serving path, against a plain
float32 reference of its published equations.

The reference (`reference_logits`) is straightforward jax.numpy at HIGHEST
matmul precision, with no cache, kernels or batching; it reads the program's
parameter tree (norm gains stored as offsets from 1) and nothing else of
the program. Per layer, for hidden states x (T, d):

  h = rms(x) g_in;  q = h Wq, k = h Wk, v = h Wv;  q, k RMS-normed over
  head_dim; rotary on window layers only (full layers NoPE); query i sees
  key j iff j <= i and, on a window layer, i - j <= window
  o = attn * sigmoid(h Wg);  x = x + rms(o Wo) g_post1
  h = rms(x) g_pre_mlp
  dense: y = (silu(h W1) * h W3) W2
  experts: s = sigmoid(h Wr) over all experts; chosen = top-k(s + b);
           w_e = route_scale s_e / sum_chosen s;
           y = shared(h) + sum over chosen held e of w_e FFN_e(h)
  x = x + rms(y) g_post2
logits = rms(x) g_final @ W_head (untied), x0 = embed[tokens] sqrt(d).

The tiny model: four layers, one whole period (windows at 0-2, full at 3),
two leading dense layers, sigmoid-routed experts of which this device holds
8 of the router's 16 with a shared expert, an untied head, float32.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.trinity_mini import layer_pattern, window_schedule
from repro.core import layers as L
from repro.core import model as Mod
from repro.core import moe as M
from repro.core.types import AttentionSpec, ModelConfig, MoESpec
from repro.kernels import ops as kops
from repro.serving.engine import Request, ServingEngine

HI = jax.lax.Precision.HIGHEST
SLIDING = 12            # the published convention: i - j < SLIDING
CFG = ModelConfig(
    name="tiny-afmoe", num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=96, vocab_size=256, layer_pattern=layer_pattern(4, 2),
    attention=AttentionSpec(kind="dense", causal=True),
    window_schedule=window_schedule(4, 4, SLIDING),
    moe=MoESpec(num_experts=16, top_k=4, held=8,
                expert_width=32, shared_width=24, score="sigmoid",
                route_scale=2.826),
    tie_embeddings=False, embed_scale=True, rope_theta=10000.0,
    norm_eps=1e-5, qk_norm=True, attn_gate=True, sandwich_norm=True,
    full_rope=False, dtype="float32")
# float32 program against the float32 reference: only summation order
# differs (6e-7 of a logit of unit spread, measured on this model); 2e-5
# leaves room for that and nothing for a wrong term (0.47 and more)
TOL = 2e-5


def _params(cfg, seed=0):
    """init_model's tree with every norm gain and the selection bias drawn
    too (init leaves them 0), so each one changes the output."""
    p = Mod.init_model(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, x), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            x = 0.1 * jax.random.normal(k, x.shape, x.dtype)
        elif name.endswith("['bias']"):
            x = 0.02 * jax.random.normal(k, x.shape, x.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


# ------------------------------------------------------------ reference ---

def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + gain)


def _rope(x, theta):
    """x (T, heads, d): rotary on split halves at positions 0..T-1."""
    t, _, d = x.shape
    inv = theta ** (-np.arange(0, d, 2) / d)
    ang = np.arange(t)[:, None] * inv[None, :]
    sin, cos = np.sin(ang)[:, None], np.cos(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ffn(w1, w3, w2, h):
    return jnp.dot(jax.nn.silu(jnp.dot(h, w1, precision=HI))
                   * jnp.dot(h, w3, precision=HI), w2, precision=HI)


def _experts(p, spec, h):
    s = jax.nn.sigmoid(jnp.dot(h, p["router"], precision=HI))       # (T, E)
    _, chosen = jax.lax.top_k(s + p["bias"], spec.top_k)
    mask = jax.nn.one_hot(chosen, spec.num_experts).sum(1)          # (T, E)
    weight = spec.route_scale * s * mask / jnp.sum(s * mask, -1, keepdims=True)
    y = _ffn(p["shared"]["w1"], p["shared"]["w3"], p["shared"]["w2"], h)
    for e in range(spec.held_count):
        y = y + weight[:, e:e + 1] * _ffn(p["w1"][e], p["w3"][e], p["w2"][e], h)
    return y


def reference_logits(params, cfg, tokens):
    """(T, vocab) logits of one sequence, every position."""
    eps = cfg.norm_eps
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    t = len(tokens)
    x = params["embed"][jnp.asarray(tokens)] * np.sqrt(cfg.d_model)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    for n in range(cfg.num_layers):
        p = jax.tree.map(lambda a: a[0], params["blocks"][f"l{n}"])
        a = p["mixer"]
        h = _rms(x, p["norm1"]["scale"], eps)
        q = jnp.dot(h, a["wq"], precision=HI).reshape(t, hq, dh)
        k = jnp.dot(h, a["wk"], precision=HI).reshape(t, hkv, dh)
        v = jnp.dot(h, a["wv"], precision=HI).reshape(t, hkv, dh)
        q, k = _rms(q, a["q_norm"]["scale"], eps), _rms(k, a["k_norm"]["scale"], eps)
        window = cfg.window_schedule[n]
        if window is not None:
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        see = (j <= i) & ((i - j <= window) if window is not None else True)
        k, v = jnp.repeat(k, hq // hkv, 1), jnp.repeat(v, hq // hkv, 1)
        s = jnp.einsum("ihd,jhd->hij", q, k, precision=HI) / np.sqrt(dh)
        att = jax.nn.softmax(jnp.where(see, s, -jnp.inf), -1)
        o = jnp.einsum("hij,jhd->ihd", att, v, precision=HI).reshape(t, hq * dh)
        o = o * jax.nn.sigmoid(jnp.dot(h, a["wg"], precision=HI))
        x = x + _rms(jnp.dot(o, a["wo"], precision=HI), p["post_norm1"]["scale"], eps)
        h = _rms(x, p["norm2"]["scale"], eps)
        y = (_ffn(p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"], h)
             if "mlp" in p else _experts(p["moe"], cfg.moe, h))
        x = x + _rms(y, p["post_norm2"]["scale"], eps)
    return jnp.dot(_rms(x, params["final_norm"]["scale"], eps),
                   params["lm_head"], precision=HI)


# ------------------------------------------------------------- program ---

PROMPTS = (29, 17)      # both past the 11-key window; batch padded to 32
DECODE = 20             # to position 48


def _program_logits(params, cfg, seqs):
    """Per sequence, the logits the serving programs give at every position
    from the last prompt token on: one padded batched prefill, then
    decode steps through the caches, each fed the sequence's next token."""
    lens = np.asarray(PROMPTS, np.int32)
    pad = np.zeros((len(seqs), 32), np.int32)
    for r, s in enumerate(seqs):
        pad[r, :lens[r]] = s[:lens[r]]
    prefill = jax.jit(functools.partial(Mod.prefill, cfg=cfg, max_len=64))
    step = jax.jit(functools.partial(Mod.decode_step, cfg=cfg))
    lg, caches = prefill(params, batch={"tokens": jnp.asarray(pad)},
                         lengths=jnp.asarray(lens))
    out = [[lg[r, 0]] for r in range(len(seqs))]
    for n in range(DECODE):
        tok = np.asarray([[s[lens[r] + n]] for r, s in enumerate(seqs)], np.int32)
        lg, caches = step(params, batch={"tokens": jnp.asarray(tok)}, caches=caches)
        for r in range(len(seqs)):
            out[r].append(lg[r, 0])
    return [np.asarray(jnp.stack(o)) for o in out]


def _sequences(seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab_size, n + DECODE) for n in PROMPTS]


def _widest_gap(params, cfg=CFG):
    seqs = _sequences()
    got = _program_logits(params, cfg, seqs)
    widest = 0.0
    with jax.default_matmul_precision("highest"):
        for s, g, n in zip(seqs, got, PROMPTS):
            want = np.asarray(reference_logits(params, CFG, s))[n - 1:n + DECODE]
            widest = max(widest, float(np.abs(want - g).max()))
    return widest


def test_prefill_then_decode_matches_reference():
    """Padded prefill, then decode through the window rings and the full
    caches past the window, gives the reference's logits at every
    position."""
    assert _widest_gap(_params(CFG)) < TOL


def test_forward_matches_reference():
    """The full-sequence forward (`forward_logits`, `_apply_layer`) gives
    the reference's logits too."""
    cfg = dataclasses.replace(CFG, moe=dataclasses.replace(CFG.moe, dispatch="dense"))
    params = _params(cfg)
    s = _sequences()[0]
    got, _ = Mod.forward_logits(params, cfg, {"tokens": jnp.asarray(s)[None]},
                                remat=False)
    with jax.default_matmul_precision("highest"):
        want = reference_logits(params, CFG, s)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=TOL)


def _drop_gate(params, cfg, x, out):
    return out @ params["wo"]


def _swap_nope(orig):
    def attn_cfg(*a, **k):
        acfg = orig(*a, **k)
        return dataclasses.replace(acfg, use_rope=not acfg.use_rope)
    return attn_cfg


@pytest.mark.parametrize("fault", ["nope_swapped", "gate_dropped",
                                   "post_norm_dropped"])
def test_planted_fault_fails_the_comparison(monkeypatch, fault):
    """Each part of the block is load-bearing: the program with rotary on
    the full layers and none on the windows, with the output gate left
    out, or with the sandwich norms left out, lies far outside TOL."""
    if fault == "nope_swapped":
        monkeypatch.setattr(Mod, "attn_cfg", _swap_nope(Mod.attn_cfg))
    elif fault == "gate_dropped":
        monkeypatch.setattr(L, "_output", _drop_gate)
    else:
        monkeypatch.setattr(Mod, "_residual",
                            lambda p, cfg, norm, x, y: x + y)
    assert _widest_gap(_params(CFG)) > 100 * TOL


# -------------------------------------------------------------- engine ---

ENGINES = {
    "default": {},
    "paged": {"kv_layout": "paged"},
    "chunked_prefill": {"prefill_chunk": 16},
    "speculative": {"speculative": 2},
    "pallas_decode": {"decode_impl": "pallas"},
}


@pytest.mark.parametrize("opts", sorted(ENGINES))
def test_engine_tokens_are_the_reference_argmax(opts):
    """Greedy serving through ServingEngine, in its default form and in
    each form the benchmark does not run: at every position where the
    engine produced a token, that token's reference logit lies within TOL
    of the reference's best (positions past the window and decoded through
    the full caches included)."""
    params = _params(CFG)
    eng = ServingEngine(CFG, params, batch_slots=2, max_len=64, scan_steps=4,
                        pad_to=16, **ENGINES[opts])
    seqs = _sequences()
    reqs = [Request(rid=r, prompt=s[:n], max_new_tokens=DECODE)
            for r, (s, n) in enumerate(zip(seqs, PROMPTS))]
    res = {r.rid: r for r in eng.run(reqs)}
    with jax.default_matmul_precision("highest"):
        for req in reqs:
            served = res[req.rid].tokens
            assert res[req.rid].ok and len(served) == DECODE
            seq = np.concatenate([req.prompt, served[:-1]])
            lg = np.asarray(reference_logits(params, CFG, seq))[len(req.prompt) - 1:]
            gap = lg.max(-1) - lg[np.arange(len(served)), served]
            assert gap.max() < TOL, (opts, req.rid, gap.max())


def test_full_cache_rows_counted_by_hand():
    """The `swat.decode` span counts the full layers' cache rows: one
    request of a 5-token prompt and 4 new tokens decodes 3 steps at
    positions 5, 6, 7 in one block; one full layer of 64 rows per slot,
    1 of 2 slots live: needed 6 + 7 + 8, read 3 x 1 x 64."""
    eng = ServingEngine(CFG, _params(CFG), batch_slots=2, max_len=64,
                        scan_steps=4)
    assert eng._c.full_caches == (1, 64)
    eng.run([Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                     max_new_tokens=4)])
    blocks = [c for name, _, _, c in eng.tracer.spans if name == "swat.decode"]
    assert [(c["n"], c["full_rows_needed"], c["full_rows_read"])
            for c in blocks] == [(3, 21, 3 * 1 * 64)]


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_rows_read_rule_is_the_decode_attentions(impl):
    """`kops.decode_rows_read` is what `decode_attention` reads of a full
    layer's 200-row cache: the ref impl's scores span every row; the
    pallas kernel runs over the cache padded to whole 128-row blocks."""
    b, h, d, rows = 2, 2, 16, 200
    q = jnp.ones((b, h, 1, d))
    cache = jnp.zeros((b, h, rows, d))

    def step(q, kc, vc):
        return kops.decode_attention(
            q, kc, vc, None, AttentionSpec(kind="dense", causal=True),
            impl=impl, new_kv=(q, q), pos=jnp.full((b,), 5, jnp.int32))

    eqns = list(_eqns(jax.make_jaxpr(step)(q, cache, cache).jaxpr))
    if impl == "ref":
        read = [e.outvars[0].aval.shape[-1] for e in eqns
                if e.primitive.name == "dot_general"][0]
    else:
        read = [e.invars[3].aval.shape[2] for e in eqns
                if e.primitive.name == "pallas_call"][0]
    assert read == kops.decode_rows_read(impl, rows) == (
        rows if impl == "ref" else 256)


def test_window_only_model_counts_no_full_rows():
    """A model of window layers alone reads no full cache."""
    cfg = dataclasses.replace(CFG, window_schedule=(SLIDING - 1,) * 4)
    eng = ServingEngine(cfg, _params(cfg), batch_slots=2, max_len=64)
    assert eng._c.full_caches == (0, 0)


# ------------------------------------------------------- experts, window --

def test_held_shares_add_up_to_the_whole_layer():
    """Eight devices, each holding 2 of the router's 16 experts, together
    give the uncut layer: the sum of their outputs, with the shared expert
    (which every device computes alike) counted once, is the output of the
    layer that holds all 16. A device holds the router's first experts,
    so device i sees the router's columns (and selection bias) permuted to
    put its experts 2i, 2i + 1 first."""
    whole = dataclasses.replace(CFG.moe, held=0, num_experts=16)
    p = M.init_moe(jax.random.PRNGKey(5), 64, 96, whole, dtype=jnp.float32)
    p["bias"] = 0.02 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, 64))
    with jax.default_matmul_precision("highest"):
        full, _ = M.moe_ffn(p, x, whole, dispatch="dense")
        shared = L.mlp(p["shared"], x)
        total = shared
        spec = dataclasses.replace(whole, held=2)
        for i in range(8):
            mine = [2 * i, 2 * i + 1]
            perm = np.asarray(mine + [e for e in range(16) if e not in mine])
            part = dict(p, router=p["router"][:, perm], bias=p["bias"][perm],
                        **{w: p[w][2 * i:2 * i + 2] for w in ("w1", "w2", "w3")})
            y, _ = M.moe_ffn(part, x, spec, dispatch="dense")
            total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(full), atol=1e-5)
    assert float(jnp.abs(full - shared).max()) > 0.1


def test_selection_uses_bias_weights_do_not():
    """Sigmoid routing picks the top k of s + b but weights by s alone,
    renormalised over the chosen and scaled by route_scale."""
    spec = MoESpec(num_experts=4, top_k=2, score="sigmoid", route_scale=2.5)
    logits = np.asarray([2.0, 1.0, 0.5, 0.0], np.float32)
    p = {"router": jnp.asarray(np.diag(logits)),
         "bias": jnp.asarray([0.0, 0.0, 0.6, 0.0])}
    x = jnp.ones((1, 4), jnp.float32)
    _, gates, ids = M.route(p, x, spec)
    s = 1 / (1 + np.exp(-logits))
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 2]    # 1 beats 2 on s
    want = {0: s[0], 2: s[2]}
    for g, e in zip(np.asarray(gates[0]), np.asarray(ids[0])):
        assert g == pytest.approx(2.5 * want[int(e)] / (s[0] + s[2]), rel=1e-6)


def test_held_share_needs_dense_dispatch():
    """The sort dispatch cannot compute a held share: it refuses."""
    p = jax.tree.map(lambda a: a[0], _params(CFG)["blocks"]["l2"]["moe"])
    with pytest.raises(ValueError, match="dense dispatch"):
        M.moe_ffn(p, jnp.ones((1, 4, 64), jnp.float32), CFG.moe,
                  dispatch="sort")


@pytest.mark.parametrize("path", ["prefill_band", "decode"])
def test_window_off_by_one(path):
    """Trinity-Mini's published window: query i sees key j iff
    i - j < 2048. Its sliding layers get the program's window 2047 (the
    program's query sees key j iff i - j <= window): key i - 2047 is seen,
    key i - 2048 is not, in the prefill band and in the decode ring. Its
    full layers have no window and no rotary."""
    cfg = get_config("trinity-mini")
    acfg = Mod.attn_cfg(cfg, "attn", index=0)
    assert acfg.spec.window == 2047 and acfg.use_rope
    full = Mod.attn_cfg(cfg, "attn_moe", index=3)
    assert not full.spec.is_sparse and not full.use_rope
    spec, d = acfg.spec, 8

    def seen(i):
        """Share of query i's attention on key i - 2047 (value 1, all
        other values 0, all scores equal): 1/2048 when seen, 0 if not."""
        if path == "prefill_band":
            t = 2176
            v = np.zeros((1, 1, t, d), np.float32)
            v[0, 0, i - 2047] = 1.0
            z = jnp.zeros((1, 1, t, d), jnp.float32)
            out = kops.swat_attention(z, z, jnp.asarray(v), spec, impl="xla")
            return float(out[0, 0, i, 0]), float(out[0, 0, i + 1, 0])
        # a ring one row deeper than the window (lookahead 1), holding
        # keys 0..i-1 at their slots; the query's own key comes with it
        cap = 2049
        v = np.zeros((1, 1, cap, d), np.float32)
        v[0, 0, i - 2047] = 1.0
        z = jnp.zeros((1, 1, 1, d), jnp.float32)
        kc = jnp.zeros((1, 1, cap, d), jnp.float32)
        out, _, vc = kops.decode_attention(
            z, kc, jnp.asarray(v), None, spec, impl="ref", new_kv=(z, z),
            pos=jnp.asarray([i]), ring_cap=cap)
        nxt, _, _ = kops.decode_attention(
            z, kc, vc, None, spec, impl="ref", new_kv=(z, z),
            pos=jnp.asarray([i + 1]), ring_cap=cap)
        return float(out[0, 0, 0, 0]), float(nxt[0, 0, 0, 0])

    at, past = seen(2047)
    assert at == pytest.approx(1 / 2048, rel=1e-5)
    assert past == 0.0


MESH = """
import sys
sys.path.insert(0, {tests!r})
import jax
import numpy as np
import test_afmoe as T
from repro.distributed import sharding as Sh
from repro.launch import mesh as mesh_lib
from repro.serving.engine import Request, ServingEngine

assert jax.device_count() == 4
params = T._params(T.CFG)
seqs = T._sequences()

def run(mesh):
    eng = ServingEngine(T.CFG, params, batch_slots=4, max_len=64,
                        scan_steps=4, pad_to=16, mesh=mesh)
    reqs = [Request(rid=r, prompt=s[:n], max_new_tokens=T.DECODE)
            for r, (s, n) in enumerate(zip(seqs, T.PROMPTS))]
    return eng, {{r.rid: r.tokens for r in eng.run(reqs)}}

_, base = run(None)
eng, slots = run(mesh_lib.make_debug_mesh(4, 1))
assert slots == base, (slots, base)
eng, tp = run(mesh_lib.make_debug_mesh(2, 2))
assert tp == base, (tp, base)
spec = {{jax.tree_util.keystr(p): tuple(s.spec) for p, s in
        jax.tree_util.tree_flatten_with_path(eng._c.param_sharding)[0]}}
blk = "['blocks']['l2']"
want = {{
    blk + "['mixer']['wg']": (None, None, "model"),          # column-parallel
    blk + "['moe']['shared']['w1']": (None, None, "model"),  # a dense MLP
    blk + "['moe']['shared']['w2']": (None, "model"),
    blk + "['moe']['w1']": (None, "model"),                  # experts over model
    blk + "['moe']['bias']": (),                             # replicated
    blk + "['mixer']['q_norm']['scale']": (),
    blk + "['post_norm1']['scale']": (),
}}
for k, v in want.items():
    assert spec[k] == v, (k, spec[k], v)
print("ok")
"""


def test_meshes_serve_the_same_tokens():
    """On 4 forced CPU devices the sharding rules place the new leaves (the
    gate column-parallel, the shared expert as a dense MLP, the held
    experts over 'model', norms and the selection bias replicated), and a
    4x1 slot-parallel and a 2x2 tensor-parallel mesh serve the
    single-device engine's greedy tokens."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    tests = str(Path(__file__).resolve().parent)
    env = dict(os.environ, PYTHONPATH=str(Path(tests).parent / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
               + os.environ.get("XLA_FLAGS", ""))
    out = subprocess.run([sys.executable, "-c", MESH.format(tests=tests)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("ok")

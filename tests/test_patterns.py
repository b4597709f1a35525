"""Block-pattern properties: the static sparsity schedule is the paper's
'synthesis-time parameter' analogue — these invariants are what make the
kernels correct by construction."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import patterns
from repro.core.types import AttentionSpec


def spec_strategy():
    return st.builds(
        AttentionSpec,
        kind=st.just("swat"),
        window=st.sampled_from([16, 33, 64, 100]),
        num_global=st.sampled_from([0, 7, 32]),
        num_random=st.sampled_from([0, 1, 2]),
        random_seed=st.integers(0, 5),
        causal=st.booleans(),
    )


@settings(max_examples=30, deadline=None)
@given(spec=spec_strategy(),
       seq=st.sampled_from([128, 200, 512]),
       blk=st.sampled_from([32, 64, 128]))
def test_pattern_covers_mask(spec, seq, blk):
    """Every (i, j) allowed by the token-level mask must lie in some visited
    block — else the kernel would silently drop attention edges."""
    pat = patterns.build_block_pattern(spec, seq, seq, blk, blk)
    mask = patterns.random_blocks_mask(pat)
    covered = np.zeros((pat.num_q_blocks, pat.num_kv_blocks), bool)
    for i in range(pat.num_q_blocks):
        for s in range(pat.num_slots):
            if pat.slot_kinds[i, s] != patterns.PAD:
                covered[i, pat.kv_block_map[i, s]] = True
    need = np.zeros_like(covered)
    # rows i < num_global are the dense global-rows pass's responsibility
    # (ops.swat_attention replaces them wholesale), not the band pattern's
    for i in range(spec.num_global, seq):
        for j in np.where(mask[i])[0]:
            need[i // blk, j // blk] = True
    assert not (need & ~covered).any()


@settings(max_examples=30, deadline=None)
@given(spec=spec_strategy(), seq=st.sampled_from([128, 512]),
       blk=st.sampled_from([64, 128]))
def test_no_duplicate_slots(spec, seq, blk):
    """A kv block must appear at most once per q block (double counting
    would double softmax mass)."""
    pat = patterns.build_block_pattern(spec, seq, seq, blk, blk)
    for i in range(pat.num_q_blocks):
        live = [pat.kv_block_map[i, s] for s in range(pat.num_slots)
                if pat.slot_kinds[i, s] != patterns.PAD]
        assert len(live) == len(set(live)), (i, live)


@settings(max_examples=20, deadline=None)
@given(spec=spec_strategy(), seq=st.sampled_from([256, 512]))
def test_inverse_pattern_is_transpose(spec, seq):
    pat = patterns.build_block_pattern(spec, seq, seq, 64, 64)
    inv = pat.inverse()
    fwd_edges = set()
    for i in range(pat.num_q_blocks):
        for s in range(pat.num_slots):
            if pat.slot_kinds[i, s] != patterns.PAD:
                fwd_edges.add((i, int(pat.kv_block_map[i, s])))
    inv_edges = set()
    for j in range(inv.q_block_map.shape[0]):
        for s in range(inv.num_slots):
            if inv.slot_kinds[j, s] != patterns.PAD:
                inv_edges.add((int(inv.q_block_map[j, s]), j))
    assert fwd_edges == inv_edges


def test_active_fraction_linear_scaling():
    """Paper Fig. 3: window attention block count grows linearly with
    sequence length => active fraction ~ 1/N."""
    spec = AttentionSpec(kind="swat", window=128, causal=False)
    fracs = []
    for seq in (1024, 2048, 4096):
        pat = patterns.build_block_pattern(spec, seq, seq, 128, 128)
        fracs.append(pat.active_fraction() * seq)
    # N * active_fraction ~ constant band width in blocks
    assert max(fracs) / min(fracs) < 1.4, fracs


def test_sliding_chunks_redundancy_formula():
    """Paper §1: redundancy -> 1/2 as chunks grow."""
    r1 = patterns.sliding_chunks_flops_ratio(1024, 64)
    r2 = patterns.sliding_chunks_flops_ratio(65536, 64)
    assert r1 < r2 < 0.5
    assert abs(r2 - 0.5) < 1e-3


def test_causal_mask_has_no_future_leak():
    spec = AttentionSpec(kind="swat", window=32, num_global=8, num_random=1,
                         causal=True, random_seed=1)
    pat = patterns.build_block_pattern(spec, 256, 256, 64, 64)
    mask = patterns.random_blocks_mask(pat)
    i, j = np.triu_indices(256, k=1)
    assert not mask[i, j].any()

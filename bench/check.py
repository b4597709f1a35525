"""The comparison that decides `correct`.

Once the window has closed, a sample of the requests the engine finished,
drawn from the seed and always holding the one with the most served tokens,
is run through the float32 reference once each: prompt plus served tokens.
At every position where the engine produced a token, the gap by which that
token's reference logit lies below the reference's best logit is read; the
number compared is the widest such gap over the sample. Every request is
greedy, so an exact engine reads 0 and rounding reads small gaps.

The control (`control=True`) is the same reference in fp8 put in the
engine's place (reference.py): at each of the same positions, the token
the control ranks first is read against the float32 reference the same
way, and its widest gap is reported beside the engine's.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import reference


def sample(results: Dict[int, Tuple[str, List[int]]], rng,
           min_tokens: int, max_requests: int, min_requests: int = 1) -> List[int]:
    """rids of finished requests: the one with most served tokens, then
    others in seeded random order until min_tokens served tokens and
    min_requests requests are in (at most max_requests)."""
    done = sorted(r for r, (st, toks) in results.items() if st == "ok" and toks)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(results[r][1]), r))
    rest = [r for r in rng.permutation(done) if r != longest]
    out, total = [longest], len(results[longest][1])
    for r in rest:
        if (total >= min_tokens and len(out) >= min_requests) \
                or len(out) >= max_requests:
            break
        out.append(int(r))
        total += len(results[r][1])
    return out


def compare(weights, cfg: dict, prompts: Dict[int, np.ndarray],
            results: Dict[int, Tuple[str, List[int]]], rids: List[int],
            control: bool = False) -> dict:
    widest, ctrl, n, spread = 0.0, 0.0, 0, []
    for rid in rids:
        prompt, served = prompts[rid], np.asarray(results[rid][1], np.int32)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        pos = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
        gap, _, sd = reference.score(weights, cfg, seq, pos, served)
        widest = max(widest, float(gap.max()))
        spread.append(float(np.median(sd)))
        n += len(served)
        if control:
            _, first, _ = reference.score(weights, cfg, seq, pos, served,
                                          fp8=True)
            cgap, _, _ = reference.score(weights, cfg, seq, pos, first)
            ctrl = max(ctrl, float(cgap.max()))
    out = {"widest_logit_gap": widest, "tokens_compared": n,
           "requests_compared": len(rids),
           "logit_spread": float(np.median(spread)) if spread else 0.0}
    if control:
        out["control_widest_logit_gap"] = ctrl
    return out

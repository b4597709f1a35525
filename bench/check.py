"""The comparison that decides `correct`.

Once the window has closed, a sample of the requests the engine finished,
drawn from the seed and always holding the one with the most served tokens,
is run through the float32 reference once each: prompt plus served tokens.
At every position where the engine produced a token, the gap by which that
token's reference logit lies below the reference's best logit is read; the
number compared is the widest such gap over the sample. Every request is
greedy, so an exact engine reads 0 and rounding reads small gaps.

The reference is the configuration's architecture module's `score`
(arch/__init__.py). Positions where the reference itself is undecided are
left out: where, at some expert layer, its k-th and (k+1)-th router logits
lie within the checks file's `router_margin`, a rounding flips the experts
chosen and the token with them (every position counts where the file sets
no margin or the model has no experts).

The control (`control=True`) is the same reference in fp8 put in the
engine's place: at each of the same decisive positions, the token the
control ranks first is read against the float32 reference the same way,
and its widest gap is reported beside the engine's.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import arch


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
            control: bool = False, router_margin: Optional[float] = None) -> dict:
    score = arch.load(cfg).score
    widest, ctrl, n, left, spread = 0.0, 0.0, 0, 0, []
    for rid in rids:
        prompt, served = prompts[rid], np.asarray(results[rid][1], np.int32)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        pos = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
        gap, _, sd, dec = score(weights, cfg, seq, pos, served,
                                router_margin=router_margin)
        if dec.any():
            widest = max(widest, float(gap[dec].max()))
        spread.append(float(np.median(sd)))
        n += int(dec.sum())
        left += int((~dec).sum())
        if control and dec.any():
            _, first, _, _ = score(weights, cfg, seq, pos, served, fp8=True)
            cgap = score(weights, cfg, seq, pos, first)[0]
            ctrl = max(ctrl, float(cgap[dec].max()))
    out = {"widest_logit_gap": widest, "tokens_compared": n,
           "tokens_left_out": left, "requests_compared": len(rids),
           "logit_spread": float(np.median(spread)) if spread else 0.0}
    if control:
        out["control_widest_logit_gap"] = ctrl
    return out

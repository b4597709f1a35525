"""One general traffic generator. A mix is a data file,
`traffic/<mix>.json`, of parameters; nothing here knows a mix by name.

The seed never changes the amount of work. An open loop's schedule (due
times and sizes) is one draw of a Poisson process and of the length
distributions, taken from the mix's own `schedule_seed`: the same in every
run, bursts and all, so that the tails measure the system and not the draw;
the run's seed gives the token ids. A closed loop's sizes are a fixed
stratified set of quantiles of the distributions, put in an order drawn
from the seed, with token ids from the seed.

Keys of a mix file:
  loop         "open" (arrivals on a schedule) or "closed" (one client per
               slot, each sending its next request when the last finishes)
  prompt       length distribution (see `_ppf`)
  output       max-new-tokens distribution
  rate_per_s   open loop: mean arrival rate
  arrivals     open loop: "poisson" (see `_open`)
  preroll_s    open loop: seconds of arrivals served before the window
  schedule_seed  open loop: the seed of the schedule's draw
  requests_per_client  closed loop: requests queued per client
  first_wave   closed loop: "residual" -- the requests in flight when the
               window opens have a uniform share of their output left, so
               completions spread over the window from its start
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent


def load(mix: str) -> dict:
    return json.loads((HERE / "traffic" / f"{mix}.json").read_text())


def _ppf(spec: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a length distribution at probabilities u."""
    kind = spec["dist"]
    if kind == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        x = np.exp(lo + u * (hi - lo))
    elif kind == "lognormal":
        from statistics import NormalDist
        z = np.asarray([NormalDist().inv_cdf(float(v)) for v in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "fixed":
        x = np.full(u.shape, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = spec.get("min", -np.inf), spec.get("max", np.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws: the distribution at (i + 1/2)/n, i < n."""
    return _ppf(spec, (np.arange(n) + 0.5) / n)


def length_bounds(spec: dict) -> tuple:
    """Smallest and largest length a distribution can give."""
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    lo, hi = spec.get("min"), spec.get("max")
    if lo is None or hi is None:
        raise ValueError("length distributions need min and max")
    return int(lo), int(hi)


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0          # seconds from the window's opening (open loop)
    client: int = -1          # closed loop: the client that sends it


@dataclasses.dataclass
class Plan:
    loop: str
    requests: List[Req]                 # open loop: by due time
    clients: Optional[List[List[Req]]] = None   # closed loop: per client
    preroll_s: float = 0.0


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)


def _open(mix: dict, rng, vocab: int, seconds: float) -> Plan:
    """Poisson arrivals over [-preroll_s, seconds) conditioned on their
    count, round(rate x span): that many independent uniform times, sorted.
    Times and sizes come from the schedule seed, token ids from `rng`."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    rate = float(mix["rate_per_s"])
    pre = float(mix.get("preroll_s", 0.0))
    n = max(1, int(round(rate * (pre + seconds))))
    sched = np.random.default_rng(int(mix["schedule_seed"]))
    due = np.sort(sched.uniform(-pre, seconds, n))
    u = np.clip(sched.random((2, n)), 1e-12, 1 - 1e-12)  # 0 < u < 1
    plen, olen = _ppf(mix["prompt"], u[0]), _ppf(mix["output"], u[1])
    reqs = [Req(i, _tokens(rng, int(plen[i]), vocab), int(olen[i]),
                due=float(due[i])) for i in range(n)]
    return Plan("open", reqs, preroll_s=pre)


def make(mix: dict, seed: int, *, vocab: int, slots: int,
         seconds: float) -> Plan:
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        return _open(mix, rng, vocab, seconds)
    if mix["loop"] == "closed":
        per = int(mix.get("requests_per_client", 6))
        n = slots * per
        plen = rng.permutation(quantiles(mix["prompt"], n))
        olen = rng.permutation(quantiles(mix["output"], n))
        resid = rng.permutation((np.arange(slots) + 0.5) / slots)
        clients: List[List[Req]] = [[] for _ in range(slots)]
        for i in range(n):
            c = i % slots
            first = i < slots
            max_new = int(olen[i])
            if first and mix.get("first_wave") == "residual":
                max_new = max(1, int(math.ceil(resid[c] * max_new)))
            clients[c].append(Req(i, _tokens(rng, int(plen[i]), vocab),
                                  max_new, client=c))
        return Plan("closed", [r for cl in clients for r in cl],
                    clients=clients)
    raise ValueError(f"unknown loop {mix['loop']!r}")

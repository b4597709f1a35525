"""Find the highest arrival rate an open-loop cell sustains, once, on the
chip: one process serves the cell's traffic at each given rate and prints,
per rate, the latency tails, the queue wait in each half of the window and
the requests still queued at the close. A rate is sustained when the
queue wait does not grow from the first half of the window to the second
and nothing is left queued.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4,5 --seconds <s>
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import run
import tails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    cell = run.load_cell(a.workload)
    device = run.open_device(cell)
    if device is None:
        return 3
    cell.specs.append({"name": "queue_wait_p90_s", "unit": "s"})
    sweep(cell, [float(r) for r in a.rates.split(",")], a.seconds, a.seed,
          device)
    return 0


def sweep(cell, rates, seconds, seed, device):
    for rate in rates:
        at = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate))
        out = run.execute(at, seed, seconds, False, device=device,
                          verify=False)
        r = run.LAST["run"]
        mid = (r.t_open + r.t_close) / 2
        halves = []
        for lo, hi in ((r.t_open, mid), (mid, r.t_close)):
            w = [r.admitted_at[q] - d for q, d in r.due.items()
                 if lo <= d < hi and q in r.admitted_at]
            halves.append(tails.percentile(w, 90))
        print(json.dumps({"rate": rate, "attempted": out["attempted"],
                          "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                          "queue_wait_p90_halves": halves,
                          "backlog_at_close": out["window"]["backlog_at_close"]}),
              flush=True)


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set a cell's correctness limit, from one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds <s>

For each seed, runs the cell as `run.py` does (weights, engine, traffic,
window) and reads, on the served requests a run compares, the engine's
widest logit gap against the float32 reference; on the control seeds the
run puts the control (the reference in fp8, check.py) in the engine's
place, so its `correct` has to come out false. Prints one JSON line per
seed and a last line with the largest engine reading and the smallest
control reading. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    cell = run.load_cell(a.workload)
    device = run.open_device(cell)
    if device is None:
        return 3
    engine, control, control_correct = [], [], []
    with_control = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in [int(s) for s in a.seeds.split(",")]:
        ctl = seed in with_control
        out = run.execute(cell, seed, a.seconds, False, device=device,
                          control=ctl)
        c = out["compared"]
        engine.append(c["widest_logit_gap"])
        if ctl:
            control.append(c["control_widest_logit_gap"])
            control_correct.append(out["correct"])
        print(json.dumps({"seed": seed, "control": ctl, "correct": out["correct"],
                          "failed": out["failed"], **c,
                          "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
              flush=True)
    print(json.dumps({"workload": a.workload, "engine_max": max(engine),
                      "control_min": min(control, default=None),
                      "control_correct": control_correct,
                      "engine": engine, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

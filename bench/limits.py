#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers that a
cell's limits are set from: what sound runs of the program give over many
seeds (the lower reading is their largest), and what the control gives --
the plain reference computed in the configuration's ``control_precision``
and put in the program's place (the upper reading is its smallest).

    python3 bench/limits.py --workload <cell> --seeds 12 --controls 3 \
        --seconds 8 [--first-seed 7000000001]

One process reads every seed, so set-up's compilation is paid once.  One
JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.run import check_devices, load_cell, log, run_cell  # noqa: E402


def control_numbers(cell, seed: int) -> dict:
    """The widest reading of each number over the cycle, as a run takes
    it, with the control's answers in the program's place."""
    ref = cell.reference
    data = ref.make_data(cell.cfg, cell.chips, seed)
    worst = {}
    for query in ref.queries(cell.cfg, seed):
        exp = ref.answer(data, query)
        got = ref.answer(data, query, precision=cell.cfg["control_precision"])
        for name, value in ref.compare(got, exp).items():
            worst[name] = max(worst.get(name, 0), value)
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--first-seed", type=int, default=7000000001)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = check_devices(cell.chips)
    limits = cell.cfg["limits"]
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        res = run_cell(args.workload, seed, args.seconds, False, devices,
                       time.perf_counter())
        line = {"workload": args.workload, "seed": seed,
                "correct": res["correct"], "attempted": res["attempted"],
                "program": {n: v for n, (v, _) in res["compared"].items()}}
        if i < args.controls:
            line["control"] = control_numbers(cell, seed)
            line["control_fails"] = sorted(
                n for n, v in line["control"].items() if v > limits[n])
        log("limits", json.dumps(line))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

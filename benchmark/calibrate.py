"""Read, on the chip and at the cell's own size, the two numbers every
limit of `correct` is set from: the largest that sound runs of the program
give over many seeds, and the smallest that the lower-precision control
gives (the reference at float8 in the program's place).

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--seconds 6]

One process reads every seed, through the ``calibrate`` of the traffic
kind's driver (a train cell needs no measured window; a serve cell plays a
short window at the cell's own load on one engine whose weights are
swapped a seed). The benchmark's own runs do not run this.
Records go to ``benchmark/out/calibrate-<workload>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness, program  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    harness.find_chips(cell.chips)
    program.setup_compile_cache()
    recs = harness.driver(cell.traffic["kind"]).calibrate(
        cell, seeds, control, args.seconds)
    summary = {}
    for side, pick in (("program", max), ("control", min)):
        for r in recs:
            for k, v in r.get(side, {}).items():
                key = f"{side}.{k}"
                summary[key] = v if key not in summary else pick(summary[key], v)
    harness.say("largest of the program's, smallest of the control's: "
                + json.dumps(summary))
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(os.path.join(harness.OUT_DIR,
                           f"calibrate-{args.workload}.json"), "w") as f:
        json.dump({"records": recs, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

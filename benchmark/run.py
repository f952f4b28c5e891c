"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in BENCHMARK.json, its configuration in
``benchmark/configs/``, its traffic in ``benchmark/traffic/`` and its
per-layer readers in ``benchmark/layer_metrics/``, drives the cell for
``--seconds`` seconds on the chips it asks for, checks what the timed path
produced against the plain reference, and prints one JSON object as its
last line. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness  # noqa: E402

T_START = harness.process_start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    env = harness.find_chips(cell.chips)
    line = harness.driver(cell.traffic["kind"]).run(cell, env, args, T_START)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

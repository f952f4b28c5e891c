"""``calibrate.py`` for a serve cell whose weights do not fit the chip
twice: the two numbers `correct`'s serving limit is set from, read on the
chip at the cell's own size, one engine a seed.

    python3 benchmark/calibrate_lowmem.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 6]

``lib/open_loop_cell.py:calibrate`` keeps ONE engine and swaps its weights
a seed, so for a moment it holds two sets of weights beside the slot pool
(12 + 3 GB of a 16 GB chip for a 3 G-parameter model in bfloat16), and it
runs the reference while the engine is alive. Here every seed builds its
engine, plays a short window at the cell's own load, FREES the engine and
its weights, and only then runs the reference (and, for
``--control-seeds``, the float8 control) on the sample, as a run of the
cell does. Same records, same file:
``benchmark/out/calibrate-<workload>.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness, open_loop_cell, program  # noqa: E402
from lib import traffic as traffic_lib  # noqa: E402


def read_seed(cell: harness.Cell, reference, seed: int, control: bool,
              seconds: float) -> dict:
    import jax.numpy as jnp
    import numpy as np

    t, model = cell.traffic, cell.config["model"]
    served = open_loop_cell.Served(cell.config, t["engine"],
                                   reference.make_params(seed, model))
    served.warm_up(model["vocab_size"], t["sampling"])
    plan = traffic_lib.open_loop_plan(t, seed, seconds, model["vocab_size"])
    played = served.play(plan, 2.0, seconds, t["drain_s"], t["sampling"])
    time.sleep(1.0)  # let the tail of the window's traffic retire
    sample = open_loop_cell.check_sample(played, seed,
                                         t["check"]["sample_requests"])
    red = open_loop_cell.reduce_window(played, t["drain_s"])
    served.close()
    served.engine = served.runner = None
    del served
    gc.collect()
    seqs, tok, mask = open_loop_cell.token_gap_inputs(sample,
                                                      model["block_size"])
    rec = {"seed": seed, "requests": red["attempted"],
           "failed": red["failed"], "tokens_judged": int(mask.sum())}
    params = reference.make_params(seed, model)
    for name, quant in (("program", None), ("control", "fp8")):
        if quant and not control:
            continue
        gaps = np.asarray(reference.make_token_gaps(model, quant)(
            params, jnp.asarray(seqs), jnp.asarray(tok)))[mask]
        rec[name] = {"served_token_gap": float(gaps.max()),
                     "tokens_off_best": int((gaps > 0).sum())}
    harness.say(json.dumps(rec))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    if cell.traffic["kind"] != "open_loop":
        raise SystemExit("benchmark/calibrate_lowmem.py reads serve cells; "
                         "a train cell fits benchmark/calibrate.py")
    harness.find_chips(cell.chips)
    program.setup_compile_cache()
    reference = harness.load_reference(cell.config)
    recs = [read_seed(cell, reference, s, s in control, args.seconds)
            for s in seeds]
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

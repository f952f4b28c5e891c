"""Find, once, on the chip, what a serving mix's fixed numbers rest on:
the knee (the highest rate at which the backlog does not grow over the
window) and the faster of the engine's decode paths at this slot count.

    python3 benchmark/sweep.py --workload <name> --rates 50,100,200 [--seconds 10]
    python3 benchmark/sweep.py --workload <name> --shape-seeds 1,2,3
    python3 benchmark/sweep.py --workload <name> --impls xla,pallas --burst 2000

``--rates`` plays the cell's mix at each rate on one engine, one window a
rate, and prints the backlog at quarters of the window. ``--shape-seeds``
plays it at its own rate with other draws of the arrival trace and the
lengths than the mix's one ``shape_seed`` (how much of a reading belongs
to that draw). ``--impls`` builds
one engine an implementation, sends the same ``--burst`` requests all at
once (more than there are slots, so the engine runs full: a closed loop of
num_slots clients) and prints generated tokens a second. The cell's rate
and implementation are then written into its traffic file by hand, with
these records in PERF.md. Records go to ``benchmark/out/sweep-*.jsonl``.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness, program  # noqa: E402
from lib import open_loop_cell  # noqa: E402
from lib import traffic as traffic_lib  # noqa: E402
from lib.stats import percentile  # noqa: E402


def record(path: str, rec: dict) -> None:
    harness.say(json.dumps(rec))
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(os.path.join(harness.OUT_DIR, path), "a") as f:
        f.write(json.dumps(rec) + "\n")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--shape-seeds", default="")
    ap.add_argument("--impls", default="")
    ap.add_argument("--burst", type=int, default=2000)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--ramp", type=float, default=None,
                    help="ramp before each window (default: the mix's)")
    ap.add_argument("--slots", type=int, default=None,
                    help="num_slots of the engine (default: the mix's)")
    ap.add_argument("--seed", type=int, default=20260927)
    args = ap.parse_args()
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    env = harness.find_chips(cell.chips)
    program.setup_compile_cache()
    reference = harness.load_reference(cell.config)
    model, base = cell.config["model"], cell.traffic
    vocab = model["vocab_size"]

    def build(engine: dict) -> open_loop_cell.Served:
        served = open_loop_cell.Served(
            cell.config, engine, reference.make_params(args.seed, model))
        served.warm_up(vocab, base["sampling"])
        return served

    if args.slots is not None:
        base["engine"]["num_slots"] = args.slots
    variants = [{"rate_per_s": float(r)} for r in args.rates.split(",") if r]
    variants += [{"shape_seed": int(s)}
                 for s in args.shape_seeds.split(",") if s]
    if variants:
        served = build(base["engine"])
        for i, variant in enumerate(variants):
            mix = copy.deepcopy(base)
            mix["shape_seed"] = variant.get("shape_seed", base["shape_seed"])
            mix["arrival"]["rate_per_s"] = rate = variant.get(
                "rate_per_s", base["arrival"]["rate_per_s"])
            if args.ramp is not None:
                mix["arrival"]["ramp_s"] = args.ramp
            plan = traffic_lib.open_loop_plan(mix, args.seed + i,
                                              args.seconds, vocab)
            played = served.play(plan, mix["arrival"]["ramp_s"], args.seconds,
                                 mix["drain_s"], mix["sampling"])
            # every request's record is needed for the backlog: wait them out
            for r in played.requests:
                if r.pending is not None:
                    r.pending.done.wait(120)
            red = open_loop_cell.reduce_window(played, mix["drain_s"])
            record(f"sweep-rates-{args.workload}.jsonl", {
                "rate_per_s": rate, "shape_seed": mix["shape_seed"],
                "seconds": args.seconds, "ramp_s": mix["arrival"]["ramp_s"],
                "requests": red["attempted"], "failed": red["failed"],
                "tokens_per_s": red["tokens_per_s"],
                "ttft_p50_ms": percentile(red["ttft_ms"], 50),
                "ttft_p95_ms": percentile(red["ttft_ms"], 95),
                "itl_p50_ms": percentile(red["itl_ms"], 50),
                "itl_p95_ms": percentile(red["itl_ms"], 95),
                # a gap of seconds is the machine freezing, not the knee
                "itl_max_ms": max(red["itl_ms"]),
                "num_slots": base["engine"]["num_slots"],
                "memory_peak_bytes": harness.memory_peak_bytes(
                    env.devices),
                "gen_lag_p95_ms": percentile(red["gen_lag_ms"], 95),
                "backlog": [open_loop_cell.backlog(played, s)
                            for s in (0.0, 0.25, 0.5, 0.75, 1.0)]})
        served.close()
    for impl in (i for i in args.impls.split(",") if i):
        served = build(dict(base["engine"], decode_attention_impl=impl))
        mix = copy.deepcopy(base)
        mix["arrival"] = {"process": "burst", "count": args.burst, "ramp_s": 0.0}
        plan = traffic_lib.open_loop_plan(mix, args.seed, 1.0, vocab)
        played = served.play(plan, 0.0, 1.0, 600.0, mix["sampling"])
        outs = [r.output for r in played.requests if r.output is not None]
        end = max(o.finish_time for o in outs)
        tokens = sum(len(o.tokens) for o in outs)
        record(f"sweep-impls-{args.workload}.jsonl", {
            "decode_attention_impl": impl, "requests": len(played.requests),
            "finished": len(outs), "tokens": tokens,
            "seconds": end - played.t0,
            "tokens_per_s": tokens / (end - played.t0),
            "memory_peak_bytes": harness.memory_peak_bytes(env.devices)})
        served.close()
        served.engine = served.runner = None
        del served
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

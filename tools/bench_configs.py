"""Benchmark the BASELINE.json target configs (full train step, 1 chip).

The five configs in BASELINE.json name the capability points the
framework must cover (control parity scale, diff parity scale, mid-scale
diff, GPT-2-small-scale ndiff, long-context diff). This tool times each
one's END-TO-END optimizer step — forward + backward + clip + AdamW in
one jitted program — with bench.py's exact methodology: windows closed
by ``jax.block_until_ready`` and best + median
over BENCH_WINDOWS measurement windows (the shared chip shows ±30%
contention noise; the fastest window is the least-contended estimate).

The mesh aspects of configs 3/5 (v4-8 DP, v4-32) cannot be timed on one
chip; their sharded compile+execution is validated by
__graft_entry__.dryrun_multichip and tests/test_parallel.py every round.

    python tools/bench_configs.py --out results/bench_configs_r5.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

CONFIGS = [
    # (name, model kind, overrides, micro_batch)
    ("control 2L/128d T=256", "control",
     dict(n_embd=128, n_head=4, n_layer=2, block_size=256), 64),
    ("diff 2L/128d T=256", "diff",
     dict(n_embd=128, n_head=4, n_layer=2, block_size=256), 64),
    ("diff 6L/512d T=512", "diff",
     dict(n_embd=512, n_head=4, n_layer=6, block_size=512), 32),
    ("ndiff(n=4) 12L/768d T=512", "ndiff",
     dict(n_embd=768, n_head=4, n_layer=12, block_size=512, n_terms=4), 32),
    ("diff 20L/1024d T=4096 remat", "diff",
     dict(n_embd=1024, n_head=8, n_layer=20, block_size=4096, remat=True,
          loss_chunk=512), 2),
]


def bench_one(kind: str, overrides: dict, micro_batch: int, *,
              steps: int, warmup: int, windows: int, attn: str) -> dict:
    import jax
    import jax.numpy as jnp

    from differential_transformer_replication_tpu.config import (
        ModelConfig,
        TrainConfig,
    )
    from differential_transformer_replication_tpu.models import param_count
    from differential_transformer_replication_tpu.train import (
        create_train_state,
        make_train_step,
    )

    model = ModelConfig(
        model=kind, vocab_size=12000, dropout=0.0,
        compute_dtype="bfloat16", attention_impl=attn, **overrides,
    )
    cfg = TrainConfig(model=model, micro_batch_size=micro_batch,
                      grad_acc_steps=1)
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg)
    T = model.block_size
    x = jax.random.randint(
        jax.random.PRNGKey(1), (1, micro_batch, T), 0, model.vocab_size
    )
    batch = {"x": x, "y": jnp.roll(x, -1, axis=-1)}

    for _ in range(max(warmup, 1)):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics)

    window_secs = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        window_secs.append(time.perf_counter() - t0)
    best = min(window_secs)
    med = statistics.median(window_secs)
    toks = steps * micro_batch * T
    return {
        "params": param_count(state["params"]),
        "micro_batch": micro_batch,
        "ms_per_step_best": round(best / steps * 1e3, 1),
        "tokens_per_sec_best": round(toks / best, 1),
        "tokens_per_sec_median": round(toks / med, 1),
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--windows", type=int,
               default=int(os.environ.get("BENCH_WINDOWS", "3")))
    p.add_argument("--attention-impl", default="pallas",
                   choices=["xla", "pallas"])
    p.add_argument("--only", type=int, default=None,
                   help="run just config N (1-based)")
    p.add_argument("--out", default=None)
    args = p.parse_args()
    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    start_measurement("bench_configs")
    results = {}
    for i, (name, kind, overrides, mb) in enumerate(CONFIGS, 1):
        if args.only is not None and i != args.only:
            continue
        r = bench_one(kind, overrides, mb, steps=args.steps,
                      warmup=args.warmup, windows=args.windows,
                      attn=args.attention_impl)
        results[name] = r
        print(f"{i}. {name}: {r['params']/1e6:.1f}M params, "
              f"{r['ms_per_step_best']} ms/step, "
              f"{r['tokens_per_sec_best']/1e3:.1f}k tok/s best "
              f"({r['tokens_per_sec_median']/1e3:.1f}k median)",
              flush=True)
    if args.out:
        payload = {
            "config": vars(args),
            "results": results,
        }
        Path(args.out).write_text(json.dumps(payload, indent=1))
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()

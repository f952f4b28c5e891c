"""Per-op profile of the flagship train step — the one-shot CLI.

Captures a few steps under ``jax.profiler.trace`` and prints the
device-side XLA op breakdown (grouped + top ops). This is the workflow
that produced the step decompositions in BASELINE.md; the xplane
parsing itself lives in ``obs/xprof.py`` (a stdlib wire-format reader,
shared with the CONTINUOUS sampler ``obs/device_profile.py`` — this
tool is now a thin capture+report shell over that library).

    python tools/profile_step.py [--steps 5] [--attn pallas] [--top 25]
    python tools/profile_step.py --json          # one machine-readable line

``--json`` emits the grouped breakdown as ONE JSON line (grouped op
families, the custom-kernel buckets, device-busy ms/step, compile count)
so before/after MFU deltas are diffable in CI instead of eyeballed from
text. The fused Pallas kernels get their own buckets
(obs/xprof.py:KERNEL_BUCKETS): ``flash_attention`` (ops/flash.py),
``fused_ffn`` (ops/fused_ffn.py + ops/fused_norm_residual.py),
``decode_attention`` (ops/decode_attention.py), by the names of
kernel_names.py, and
``collectives`` (HLO communication ops). Without a TPU the command
exits: a breakdown of the host plane is not a device profile (the
capture/report functions still run anywhere, for the tests).

The capture window runs inside ``RecompileSentinel(budget=0)`` exactly
like bench.py's measured window: a profile of a RETRACING step would
produce a misleading breakdown (compile time and duplicate programs in
the trace), so it fails loudly instead. ``--allow-recompiles N`` loosens
the pin (-1 disables), mirroring BENCH_ALLOW_RECOMPILES.

The reference has no profiling at all (SURVEY.md section 5.1 — its only
instrument is GPU-memory prints); this plus utils/profiling.py
(ProfilerWindow, Throughput) is the TPU-native observability stack.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def capture(args):
    import jax
    import jax.numpy as jnp

    from differential_transformer_replication_tpu.analysis.sanitizers import (
        RecompileSentinel,
    )
    from differential_transformer_replication_tpu.config import (
        ModelConfig,
        TrainConfig,
    )
    from differential_transformer_replication_tpu.train.step import (
        create_train_state,
        make_train_step,
    )

    model = ModelConfig(
        model=args.model, vocab_size=args.vocab_size, n_embd=args.n_embd,
        n_head=args.n_head, n_layer=args.n_layer,
        block_size=args.block_size, dropout=0.0, compute_dtype=args.dtype,
        attention_impl=args.attn, ffn_impl=args.ffn,
    )
    cfg = TrainConfig(
        model=model, micro_batch_size=args.micro_batch, grad_acc_steps=1
    )
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg)
    x = jax.random.randint(
        jax.random.PRNGKey(1), (1, args.micro_batch, model.block_size), 0,
        model.vocab_size,
    )
    batch = {"x": x, "y": jnp.roll(x, -1, -1)}
    for _ in range(3):  # compile + warm
        state, m = step(state, batch)
    jax.block_until_ready(m)

    out_dir = args.out or tempfile.mkdtemp(prefix="profile_step_")
    # a retracing step inside the capture window = a misleading profile;
    # fail loudly like bench.py's measured window (budget configurable)
    budget = None if args.allow_recompiles < 0 else args.allow_recompiles
    sentinel = RecompileSentinel(budget=budget, name="profile-capture-window")
    with sentinel:
        with jax.profiler.trace(out_dir):
            for _ in range(args.steps):
                state, m = step(state, batch)
            jax.block_until_ready(m)
    return out_dir, sentinel.count


def report(out_dir: str, steps: int, top: int, compiles: int,
           as_json: bool) -> None:
    from differential_transformer_replication_tpu.obs.xprof import (
        summarize_trace,
    )

    parsed = summarize_trace(out_dir, steps=steps)
    if as_json:
        doc = {
            "metric": "profile_step_breakdown",
            "steps": steps,
            "compiles_in_window": compiles,
            "trace_dir": out_dir,
        }
        if isinstance(parsed, str):
            doc["error"] = parsed
        else:
            # which plane the numbers came from: plane_kind == "host"
            # means the plumbing-grade fallback (no device plane in
            # the capture — nested host events overcount), never to be
            # diffed against real device telemetry
            doc["plane"] = parsed["plane"]
            doc["plane_kind"] = parsed["plane_kind"]
            doc["device_busy_ms_per_step"] = round(
                parsed["busy_ms_per_step"], 3
            )
            doc["groups_ms_per_step"] = {
                k: round(v, 4) for k, v in sorted(
                    parsed["groups"].items(), key=lambda kv: -kv[1]
                )
            }
            doc["kernel_buckets_ms_per_step"] = {
                k: round(v, 4) for k, v in parsed["kernel_buckets"].items()
            }
        print(json.dumps(doc))
        return
    if isinstance(parsed, str):
        print(f"trace written to {out_dir} — {parsed}; open it in "
              "TensorBoard instead")
        return
    print(
        f"device busy: {parsed['busy_ms_per_step']:.2f} ms/step over "
        f"{steps} steps ({compiles} compiles in window; "
        f"{parsed['plane']} plane"
        + (" — HOST fallback, plumbing-grade numbers"
           if parsed["plane_kind"] == "host" else "")
        + ")\n"
    )
    print("grouped by op family (ms/step):")
    for k, ms in sorted(parsed["groups"].items(), key=lambda kv: -kv[1])[:15]:
        print(f"  {ms:8.3f}  {k}")
    if parsed["kernel_buckets"]:
        print("\ncustom-kernel buckets (ms/step):")
        for k, ms in sorted(
            parsed["kernel_buckets"].items(), key=lambda kv: -kv[1]
        ):
            print(f"  {ms:8.3f}  {k}")
    print(f"\ntop {top} ops (ms/step):")
    for name, ms in sorted(
        parsed["totals"].items(), key=lambda kv: -kv[1]
    )[:top]:
        print(
            f"  {ms / steps:7.3f} x{parsed['counts'][name] // steps:3d}  "
            f"{name[:110]}"
        )


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--micro-batch", type=int, default=32)
    p.add_argument("--block-size", type=int, default=512)
    p.add_argument("--model", default="diff", choices=["control", "diff", "ndiff"])
    p.add_argument("--attn", default="pallas", choices=["xla", "pallas"])
    p.add_argument("--ffn", default="pallas", choices=["xla", "pallas"])
    p.add_argument("--dtype", default="bfloat16")
    # recipe-shape overrides so CI can profile a tiny model quickly
    p.add_argument("--n-embd", type=int, default=768)
    p.add_argument("--n-head", type=int, default=4)
    p.add_argument("--n-layer", type=int, default=8)
    p.add_argument("--vocab-size", type=int, default=12000)
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--out", default=None, help="trace dir (default: temp)")
    p.add_argument("--json", action="store_true",
                   help="one machine-readable JSON line instead of text")
    p.add_argument("--allow-recompiles", type=int, default=0,
                   help="compile budget for the capture window "
                        "(default 0 = any retrace fails; -1 disables)")
    return p


def main() -> None:
    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    args = build_parser().parse_args()
    start_measurement("profile_step")
    out_dir, compiles = capture(args)
    report(out_dir, args.steps, args.top, compiles, args.json)


if __name__ == "__main__":
    main()

"""Benchmark harness: tokens/sec/chip on the flagship model's train step.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The measured quantity is end-to-end optimizer-step throughput (forward +
backward + clip + AdamW + cosine schedule, all inside one jitted XLA
program) for the 2-term DiffTransformer at the reference recipe scale
(train.py:60-69: 8L/768d/4-head/block-512, micro-batch 32, vocab 12000),
bf16 compute / fp32 params, on one TPU chip. Without a TPU it exits: a
number from the CPU backend is not a device measurement.

``vs_baseline`` is the ratio against the reference implementation's
measured tokens/sec. The reference publishes no numbers (BASELINE.md), so
the baseline was measured by importing the reference's own DiffTransformer
from /root/reference and timing identical synthetic-data train steps on
this image's torch device (CPU-only torch; see tools/measure_reference.py
and BASELINE.md for the number's provenance and hardware caveat).

Env overrides: BENCH_STEPS, BENCH_WARMUP, BENCH_MICRO_BATCH, BENCH_MODEL,
BENCH_ATTN ("xla" | "pallas"), BENCH_FFN ("xla" | "pallas"),
BENCH_REMAT/BENCH_REMAT_POLICY, BENCH_LOSS_CHUNK.

BENCH_OUT=path appends the JSON line to a history file (one line per
run) — the trajectory ``tools/perf_gate.py`` gates and
``tools/bench_trend.py`` renders.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp


# Baseline denominator. The only number measurable in this environment is the
# reference's torch implementation on host CPU (torch here has no CUDA):
# 125.6 tokens/sec (tools/measure_reference.py, micro-batch 8, recipe shapes,
# 94.4M params). Dividing a TPU number by a CPU number would be meaningless,
# so vs_baseline instead uses a deliberately GENEROUS estimate of the
# reference on a modern single GPU (A100 fp16 AMP) — 2e5 tokens/sec — i.e.
# we assume the reference's eager per-head-Python-loop implementation
# (diff_transformer.py:89) still reaches 200k tok/s. Both numbers and the
# reasoning are recorded in BASELINE.md. The north-star target (BASELINE.json)
# is vs_baseline >= 4.
REFERENCE_TOKENS_PER_SEC = 2.0e5  # estimated reference-on-A100; see BASELINE.md
REFERENCE_TOKENS_PER_SEC_MEASURED_CPU = 125.6  # measured, this host


def main() -> None:
    from differential_transformer_replication_tpu.config import (
        ModelConfig,
        TrainConfig,
    )
    from differential_transformer_replication_tpu.train import (
        create_train_state,
        make_multi_train_step,
    )
    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    device = start_measurement("bench")
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    # optimizer steps per jitted call (train/step.py:make_multi_train_step,
    # a lax.scan). Default 1 — exactly the launch pattern the trainer
    # (train/trainer.py) produces. K>1 amortizes per-launch PJRT argument
    # marshaling of the ~470-leaf state; measured WITHIN RUN-TO-RUN NOISE
    # on this platform (<=0.5% at K=10 vs K=1 — serial-launch marshaling
    # overlaps device compute in the pipelined loop), kept as an
    # experimentation knob only.
    spc = max(1, int(os.environ.get("BENCH_STEPS_PER_CALL", "1")))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    micro_batch = int(os.environ.get("BENCH_MICRO_BATCH", "32"))
    model_kind = os.environ.get("BENCH_MODEL", "diff")
    # pallas (the fused flash kernel) measured fastest at recipe scale
    # (186.0k vs XLA's ~175k tok/s with bf16 MXU operands + the custom
    # cross-entropy backward) and dominates at every longer context;
    # BENCH_ATTN=xla to compare.
    attn = os.environ.get("BENCH_ATTN", "pallas")
    # the fused FFN/norm path (ops/fused_ffn.py + fused_norm_residual.py:
    # block-boundary add+LN and the SwiGLU chain as Pallas kernels) is
    # the round-6 default; BENCH_FFN=xla reproduces the round-5 path.
    ffn = os.environ.get("BENCH_FFN", "pallas")
    # remat policy knob (only meaningful with BENCH_REMAT=1; sweep with
    # tools/ffn_sweep.py --remat-policies)
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    remat_policy = os.environ.get("BENCH_REMAT_POLICY", "none")
    loss_chunk = int(os.environ.get("BENCH_LOSS_CHUNK", "0")) or None

    model = ModelConfig(
        model=model_kind,
        vocab_size=12000,
        n_embd=768,
        n_head=4,
        n_layer=8,
        block_size=512,
        dropout=0.0,
        compute_dtype="bfloat16",
        attention_impl=attn,
        ffn_impl=ffn,
        remat=remat,
        remat_policy=remat_policy,
        loss_chunk=loss_chunk,
    )
    cfg = TrainConfig(model=model, micro_batch_size=micro_batch, grad_acc_steps=1)

    key = jax.random.PRNGKey(0)
    state = create_train_state(key, cfg)
    step = make_multi_train_step(cfg, spc)

    T = model.block_size
    x = jax.random.randint(
        jax.random.PRNGKey(1), (spc, 1, micro_batch, T), 0, model.vocab_size
    )
    batch = {"x": x, "y": jnp.roll(x, -1, axis=-1)}

    # Windows close with jax.block_until_ready: successive steps are
    # serialized by the state->state data dependence, so the last step's
    # metrics being ready means the whole window ran. On a v5e, windows
    # of ten steps closed this way and by a scalar read-back took the
    # same time to 0.1% (chip_smoke.py's sync phase, PR 21).
    for _ in range(max(warmup, 1)):  # >=1 so `metrics` exists for the sync
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics)

    windows = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))
    calls = max(1, steps // spc)
    steps = calls * spc  # what actually runs (and what the stderr reports)
    window_secs = []
    # Zero-recompile sentinel (analysis/sanitizers.py): warmup compiled
    # everything this loop runs, so ANY compilation inside the measured
    # windows means the bench is silently timing retraces — fail loudly
    # (RecompileBudgetError) instead of reporting degraded tok/s.
    # BENCH_ALLOW_RECOMPILES=N loosens the pin for experiments (-1
    # disables it, like serve_bench's --allow-recompiles); the sentinel
    # adds no device ops, so the loss trajectory is unchanged.
    from differential_transformer_replication_tpu.analysis.sanitizers import (
        RecompileSentinel,
    )

    allow = int(os.environ.get("BENCH_ALLOW_RECOMPILES", "0"))
    budget = None if allow < 0 else allow
    with RecompileSentinel(budget=budget, name="bench-measured-window"):
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(calls):
                state, metrics = step(state, batch)
            jax.block_until_ready(metrics)
            window_secs.append(time.perf_counter() - t0)
    dt = min(window_secs)
    dt_median = statistics.median(window_secs)

    tok_per_window = calls * spc * micro_batch * T
    tps = tok_per_window / dt
    tps_median = tok_per_window / dt_median

    # MFU accounting. 6*N*D is the standard train-FLOPs estimate over
    # non-embedding params; the attention-inclusive number adds the
    # O(T^2) attention matmul FLOPs under the same 1-fwd + 2x-bwd
    # convention: per token per layer, each of the S softmax streams does
    # a QK and a PV contraction over ~(T+1)/2 visible keys.
    from differential_transformer_replication_tpu.models import param_count
    from differential_transformer_replication_tpu.obs.xprof import (
        device_peaks,
        embedding_param_count,
    )

    rm = cfg.resolved_model()
    n_params = param_count(state["params"])
    # one shared definition of "non-embedding params" (obs/xprof.py) so
    # this mfu_6nd and the continuous device_mfu gauge subtract the
    # same N
    n_embed = embedding_param_count(
        model_kind, model.vocab_size, model.n_embd, model.block_size
    )
    flops_per_tok = 6 * (n_params - n_embed)
    n_streams = {"control": 1, "diff": 2, "ndiff": rm.n_terms}[model_kind]
    d_qk = rm.head_size
    d_v = d_qk if model_kind == "control" else 2 * d_qk
    attn_fwd = (
        rm.n_layer * rm.n_head * n_streams * 2 * (d_qk + d_v) * (T + 1) / 2
    )
    flops_per_tok_attn = flops_per_tok + 3 * attn_fwd
    # the one peak table, keyed by device kind; unknown kind = error
    peak = device_peaks(device["kind"])["bf16_flops_per_s"]

    line = json.dumps(
        {
            "metric": "train_tokens_per_sec_per_chip",
            "value": round(tps, 1),
            "unit": "tokens/sec",
            # vs the deliberately GENEROUS estimate of the reference on
            # a modern GPU (see header) — the conservative ratio
            "vs_baseline": round(tps / REFERENCE_TOKENS_PER_SEC, 2),
            # vs the only MEASURED reference number (torch on this
            # host's CPU; tools/measure_reference.py)
            "vs_reference_measured_cpu": round(
                tps / REFERENCE_TOKENS_PER_SEC_MEASURED_CPU, 1
            ),
            "mfu_6nd": round(tps * flops_per_tok / peak, 3),
            "mfu_attn_incl": round(tps * flops_per_tok_attn / peak, 3),
            # dispersion across the timing windows, machine-readable:
            # `value` is min-of-N; median + raw windows let readers
            # compare like-for-like estimators across rounds (ADVICE r2)
            "tokens_per_sec_median": round(tps_median, 1),
            "window_secs": [round(w, 4) for w in window_secs],
            "device": device,
        }
    )
    print(line)
    # append to the trajectory file perf_gate/bench_trend consume
    out_path = os.environ.get("BENCH_OUT")
    if out_path:
        with open(out_path, "a") as f:
            f.write(line + "\n")
    # diagnostics on stderr so stdout stays one JSON line
    print(
        f"[bench] model={model_kind} attn={attn} ffn={ffn} "
        f"device={device['kind']} "
        f"micro_batch={micro_batch} block={T} steps={steps} "
        f"tok/s best..median={tps:.0f}..{tps_median:.0f} "
        f"sec/step={dt / (calls * spc):.4f} steps_per_call={spc} "
        f"loss={float(metrics['loss'][-1]):.4f} "
        f"mfu~{tps * flops_per_tok / peak:.1%} "
        f"(attn-incl {tps * flops_per_tok_attn / peak:.1%})",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()

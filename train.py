#!/usr/bin/env python
"""CLI entry point.

The reference is configured by editing source and selects models by
commenting blocks in and out (train.py:57-93, 205-230). Here every recipe
field is a flag and the model switch is ``--model {control,diff,ndiff}``.

Defaults reproduce the reference recipe exactly (8L/768d, block 512,
micro-batch 32, 40k iters, AdamW 3.2e-4 -> 6e-5 cosine, warmup 1000,
TinyStories 1M docs, BPE-12k).
"""

from __future__ import annotations

import argparse
import dataclasses

from differential_transformer_replication_tpu.config import (
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from differential_transformer_replication_tpu.train.trainer import train


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    m = ModelConfig()
    t = TrainConfig()
    p.add_argument("--model", choices=("control", "diff", "ndiff", "jamba"),
                   default=m.model)
    p.add_argument("--n-embd", type=int, default=m.n_embd)
    p.add_argument("--n-head", type=int, default=m.n_head)
    p.add_argument("--n-layer", type=int, default=m.n_layer)
    p.add_argument("--block-size", type=int, default=m.block_size)
    p.add_argument("--dropout", type=float, default=m.dropout)
    p.add_argument("--n-terms", type=int, default=m.n_terms)
    p.add_argument("--compute-dtype", default=m.compute_dtype)
    p.add_argument("--attention-impl", choices=("xla", "pallas"), default=m.attention_impl)
    p.add_argument("--ffn-impl", choices=("xla", "pallas"), default=m.ffn_impl,
                   help="FFN/norm backend: reference XLA ops, or the fused "
                        "add+LayerNorm and SwiGLU Pallas kernels")
    p.add_argument("--sequence-impl", choices=("ring", "ulysses"),
                   default=m.sequence_impl,
                   help="sequence-parallel strategy when --sequence-parallel "
                        "> 1: K/V ring rotation or all-to-all re-sharding")
    p.add_argument("--loss-chunk", type=int, default=None,
                   help="fused chunked lm-head loss: positions per chunk "
                        "(never materializes full logits; for long context)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize blocks on backward (less activation memory)")
    p.add_argument("--remat-policy", default=m.remat_policy,
                   choices=("none", "dots", "dots_no_batch", "nothing",
                            "everything"),
                   help="what jax.checkpoint may save per block under "
                        "--remat (sweep with tools/ffn_sweep.py)")
    # the jamba family's fields (config.py:JAMBA_FIELDS); --model jamba sets
    # RMSNorm and no position information, which is all the family runs
    p.add_argument("--ffn-hidden", type=int, default=m.ffn_hidden,
                   help="jamba: hidden width of the gated MLP (0 = 4 * n_embd)")
    p.add_argument("--kv-heads", type=int, default=m.kv_heads,
                   help="jamba: K/V heads shared by groups of query heads "
                        "(0 = one a query head, 1 = multi-query)")
    p.add_argument("--tie-embeddings", action="store_true",
                   help="jamba: the head reuses the token table")
    p.add_argument("--attn-layer-period", type=int, default=m.attn_layer_period)
    p.add_argument("--attn-layer-offset", type=int, default=m.attn_layer_offset,
                   help="jamba: layer i attends iff i %% period == offset, "
                        "else it is a Mamba block")
    p.add_argument("--mamba-d-state", type=int, default=m.mamba_d_state)
    p.add_argument("--mamba-d-conv", type=int, default=m.mamba_d_conv)
    p.add_argument("--mamba-expand", type=int, default=m.mamba_expand)
    p.add_argument("--mamba-dt-rank", type=int, default=m.mamba_dt_rank)
    p.add_argument("--ssm-impl", choices=("xla", "pallas"), default=m.ssm_impl,
                   help="jamba: selective-scan backend; training needs xla "
                        "(the Pallas scan is forward only)")
    p.add_argument("--no-dp-overlap", action="store_true",
                   help="disable the bucketed backward-overlapped DP "
                        "gradient all-reduce (parallel/dp_step.py)")
    p.add_argument("--dp-bucket-layers", type=int, default=t.dp_bucket_layers,
                   help="transformer blocks per overlapped gradient "
                        "all-reduce bucket (parallel/dp_step.py)")

    p.add_argument("--dataset", default=t.dataset,
                   help="tinystories | synthetic | path to a text file")
    p.add_argument("--num-train-samples", type=int, default=t.num_train_samples)
    p.add_argument("--tokenizer-dir", default=t.tokenizer_dir,
                   help="tokenizer artifacts + token-stream cache dir")
    p.add_argument("--vocab-size", type=int, default=t.vocab_size)
    p.add_argument("--micro-batch-size", type=int, default=t.micro_batch_size)
    p.add_argument("--grad-acc-steps", type=int, default=t.grad_acc_steps)
    p.add_argument("--max-iters", type=int, default=t.max_iters)
    p.add_argument("--eval-interval", type=int, default=t.eval_interval)
    p.add_argument("--eval-iters", type=int, default=t.eval_iters)
    p.add_argument("--learning-rate", type=float, default=t.learning_rate)
    p.add_argument("--min-lr", type=float, default=t.min_lr)
    p.add_argument("--weight-decay", type=float, default=t.weight_decay)
    p.add_argument("--warmup-iters", type=int, default=t.warmup_iters)
    p.add_argument("--seed", type=int, default=t.seed)
    p.add_argument("--checkpoint-path", default=t.checkpoint_path)
    p.add_argument("--last-checkpoint-path", default=t.last_checkpoint_path,
                   help="resumable last-state checkpoint written on any "
                        "exit (SIGTERM/Ctrl-C/crash/completion); '' disables")
    p.add_argument("--resume-from", default=None,
                   help="checkpoint dir to resume from, or 'auto' to "
                        "pick the newest checkpoint that passes "
                        "integrity verification (step tree, then "
                        "last/best), falling back to older ones; with "
                        "no verified checkpoint, starts fresh")
    p.add_argument("--ckpt-interval", type=int, default=t.ckpt_interval,
                   help="iterations between rotating step-NNNNNNNN "
                        "checkpoints, each certified by a SHA-256 "
                        "manifest (train/ckpt_writer.py); 0 = off")
    p.add_argument("--ckpt-dir", default=t.ckpt_dir,
                   help="root of the step-checkpoint tree ('auto' = "
                        "<checkpoint-path stem>.steps)")
    p.add_argument("--ckpt-async", action=argparse.BooleanOptionalAction,
                   default=t.ckpt_async,
                   help="write step checkpoints from a background "
                        "thread (the loop blocks only for the "
                        "device->host snapshot); --no-ckpt-async "
                        "writes inline")
    p.add_argument("--ckpt-keep-last", type=int, default=t.ckpt_keep_last,
                   help="retention: newest N verified step checkpoints "
                        "to keep")
    p.add_argument("--ckpt-keep-every", type=int, default=t.ckpt_keep_every,
                   help="retention: additionally keep every Nth-step "
                        "checkpoint forever (0 = none)")
    p.add_argument("--checkpoint-min-interval-s", type=float,
                   default=t.checkpoint_min_interval_s,
                   help="throttle best-checkpoint disk writes to at most "
                        "one per this many seconds (0 = the reference's "
                        "write-every-improvement; the best state is still "
                        "snapshotted on-device each improvement and "
                        "flushed at exit)")
    p.add_argument("--anomaly-guard", action=argparse.BooleanOptionalAction,
                   default=t.anomaly_guard,
                   help="in-loop anomaly guard: skip non-finite/spiking "
                        "updates under lax.cond, roll back to an in-HBM "
                        "snapshot on persistent badness, abort cleanly "
                        "when rollbacks stop helping (train/anomaly.py)")
    p.add_argument("--anomaly-spike-factor", type=float,
                   default=t.anomaly_spike_factor,
                   help="skip when grad norm exceeds this multiple of the "
                        "good-step EMA")
    p.add_argument("--anomaly-warmup-steps", type=int,
                   default=t.anomaly_warmup_steps,
                   help="good steps before spike detection arms (the "
                        "non-finite check is always on)")
    p.add_argument("--anomaly-rollback-after", type=int,
                   default=t.anomaly_rollback_after,
                   help="consecutive bad steps before rolling back to the "
                        "good-state snapshot")
    p.add_argument("--anomaly-max-rollbacks", type=int,
                   default=t.anomaly_max_rollbacks,
                   help="rollbacks before the run aborts")
    p.add_argument("--anomaly-snapshot-interval", type=int,
                   default=t.anomaly_snapshot_interval,
                   help="iterations between good-state snapshots (pins one "
                        "extra train state in HBM)")
    p.add_argument("--anomaly-check-interval", type=int,
                   default=t.anomaly_check_interval,
                   help="iterations between host polls of the guard streak "
                        "(each poll syncs on the step result)")
    p.add_argument("--step-deadline-s", type=float, default=t.step_deadline_s,
                   help="step-deadline watchdog (train/watchdog.py): a "
                        "training iteration hung past this many seconds "
                        "dumps hang_report.json and exits with the "
                        "distinct hang code the supervisor restarts "
                        "under its own budget; 0 = off")
    p.add_argument("--hang-report-path", default=t.hang_report_path,
                   help="watchdog post-mortem destination ('auto' = "
                        "<checkpoint-path stem>.hang_report.json)")
    p.add_argument("--heartbeat-dir", default=t.heartbeat_dir,
                   help="multi-host liveness mesh (parallel/heartbeat"
                        ".py): shared-filesystem directory for per-"
                        "process heartbeat files; a peer silent past "
                        "--heartbeat-timeout-s trips the watchdog "
                        "immediately (coordinated abort) instead of "
                        "wedging in a collective; unset = off")
    p.add_argument("--heartbeat-interval-s", type=float,
                   default=t.heartbeat_interval_s,
                   help="seconds between heartbeat publications")
    p.add_argument("--heartbeat-timeout-s", type=float,
                   default=t.heartbeat_timeout_s,
                   help="peer silence past this = dead (coordinated "
                        "abort); must exceed the interval")
    p.add_argument("--allow-inexact-resume", action="store_true",
                   help="accept an elastic resume whose epoch-sampler "
                        "position cannot be reproduced exactly under "
                        "the new batch math (mid-accumulation boundary "
                        "or legacy checkpoint) instead of raising "
                        "ElasticResumeError")
    p.add_argument("--faults", default=None,
                   help="fault-injection spec for chaos testing, e.g. "
                        "'sigkill@120,nan@50-52' (utils/faults.py; also "
                        "via the DTX_FAULTS env var)")
    p.add_argument("--metrics-path", default=t.metrics_path)
    p.add_argument("--metrics-port", type=int, default=t.metrics_port,
                   help="serve the trainer's Prometheus registry at "
                        "http://0.0.0.0:PORT/metrics from a sidecar "
                        "thread (obs/http.py); 0 = off")
    p.add_argument("--trace-path", default=t.trace_path,
                   help="write a Chrome-trace-event JSON of the train "
                        "loop's host spans (data_wait/dispatch/block; "
                        "open in Perfetto) to this path")
    p.add_argument("--wandb", action="store_true", help="enable the wandb sink")
    p.add_argument(
        "--profile-dir", default=None,
        help="capture a 5-step steady-state jax.profiler trace (starting "
             "~10 iters after this run begins/resumes) into this dir",
    )
    p.add_argument("--profile-every", type=int, default=t.profile_every,
                   help="continuous on-device profiling: every N "
                        "iterations capture ONE step's device profile, "
                        "parse it off-loop, and publish device_* "
                        "gauges, device_profile metrics.jsonl rows and "
                        "a stitchable device-lane trace "
                        "(obs/device_profile.py); 0 = off")
    p.add_argument("--profile-spool-dir", default=t.profile_spool_dir,
                   help="rotating spool for --profile-every captures "
                        "('auto' = <checkpoint stem>.profiles)")
    p.add_argument("--data-parallel", type=int, default=1,
                   help="devices on the data mesh axis")
    p.add_argument("--tensor-parallel", type=int, default=1,
                   help="devices on the tensor mesh axis")
    p.add_argument("--fsdp", type=int, default=1,
                   help="devices on the fsdp (param-sharding) mesh axis")
    p.add_argument("--sequence-parallel", type=int, default=1,
                   help="devices on the sequence mesh axis (ring attention)")
    p.add_argument("--pipeline-parallel", type=int, default=1,
                   help="devices on the pipeline mesh axis (GPipe stages; "
                        "grad-acc microbatches stream through the stages — "
                        "use --grad-acc-steps >= stages)")
    return p


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    jamba = {}
    if args.model == "jamba":
        jamba = dict(
            ffn_hidden=args.ffn_hidden,
            kv_heads=args.kv_heads, tie_embeddings=args.tie_embeddings,
            attn_layer_period=args.attn_layer_period,
            attn_layer_offset=args.attn_layer_offset,
            mamba_d_state=args.mamba_d_state, mamba_d_conv=args.mamba_d_conv,
            mamba_expand=args.mamba_expand, mamba_dt_rank=args.mamba_dt_rank,
            ssm_impl=args.ssm_impl,
        )
    model = ModelConfig(
        **jamba,
        model=args.model,
        vocab_size=args.vocab_size,
        n_embd=args.n_embd,
        n_head=args.n_head,
        n_layer=args.n_layer,
        block_size=args.block_size,
        dropout=args.dropout,
        n_terms=args.n_terms,
        compute_dtype=args.compute_dtype,
        attention_impl=args.attention_impl,
        ffn_impl=args.ffn_impl,
        sequence_impl=args.sequence_impl,
        remat=args.remat,
        remat_policy=args.remat_policy,
        loss_chunk=args.loss_chunk,
    )
    return TrainConfig(
        model=model,
        mesh=MeshConfig(pipeline=args.pipeline_parallel,
                        data=args.data_parallel, fsdp=args.fsdp,
                        tensor=args.tensor_parallel,
                        sequence=args.sequence_parallel),
        dataset=args.dataset,
        num_train_samples=args.num_train_samples,
        tokenizer_dir=args.tokenizer_dir,
        vocab_size=args.vocab_size,
        micro_batch_size=args.micro_batch_size,
        grad_acc_steps=args.grad_acc_steps,
        max_iters=args.max_iters,
        eval_interval=args.eval_interval,
        eval_iters=args.eval_iters,
        learning_rate=args.learning_rate,
        min_lr=args.min_lr,
        weight_decay=args.weight_decay,
        warmup_iters=args.warmup_iters,
        seed=args.seed,
        checkpoint_path=args.checkpoint_path,
        last_checkpoint_path=args.last_checkpoint_path or None,
        resume_from=args.resume_from,
        checkpoint_min_interval_s=args.checkpoint_min_interval_s,
        ckpt_interval=args.ckpt_interval,
        ckpt_dir=args.ckpt_dir,
        ckpt_async=args.ckpt_async,
        ckpt_keep_last=args.ckpt_keep_last,
        ckpt_keep_every=args.ckpt_keep_every,
        dp_overlap=not args.no_dp_overlap,
        dp_bucket_layers=args.dp_bucket_layers,
        anomaly_guard=args.anomaly_guard,
        anomaly_spike_factor=args.anomaly_spike_factor,
        anomaly_warmup_steps=args.anomaly_warmup_steps,
        anomaly_rollback_after=args.anomaly_rollback_after,
        anomaly_max_rollbacks=args.anomaly_max_rollbacks,
        anomaly_snapshot_interval=args.anomaly_snapshot_interval,
        anomaly_check_interval=args.anomaly_check_interval,
        step_deadline_s=args.step_deadline_s,
        hang_report_path=args.hang_report_path,
        heartbeat_dir=args.heartbeat_dir,
        heartbeat_interval_s=args.heartbeat_interval_s,
        heartbeat_timeout_s=args.heartbeat_timeout_s,
        allow_inexact_resume=args.allow_inexact_resume,
        faults=args.faults,
        metrics_path=args.metrics_path,
        metrics_port=args.metrics_port,
        trace_path=args.trace_path,
        use_wandb=args.wandb,
        profile_dir=args.profile_dir,
        profile_every=args.profile_every,
        profile_spool_dir=args.profile_spool_dir,
    )


if __name__ == "__main__":
    train(config_from_args(build_parser().parse_args()))

"""The training runtime: data build, train loop, eval, checkpointing.

This is the TPU-native counterpart of ``train()`` (train.py:141-325):
same recipe, same eval protocol, same logging cadence, plus resume —
with the eager per-batch Python loop replaced by a jitted step over
device-resident data.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from differential_transformer_replication_tpu.config import TrainConfig
from differential_transformer_replication_tpu.data import (
    TokenWindows,
    encode_corpus,
    split_tokens,
    train_bpe_tokenizer,
)
from differential_transformer_replication_tpu.train.anomaly import (
    TrainingDivergedError,
    snapshot_state,
)
from differential_transformer_replication_tpu.train.checkpoint import (
    AsyncCheckpointWriter,
    elastic_resume_info,
    load_checkpoint,
    resolve_resume_auto,
    save_checkpoint,
    save_step_checkpoint,
)
from differential_transformer_replication_tpu.train.watchdog import (
    StepWatchdog,
)
from differential_transformer_replication_tpu.obs import (
    NOOP_TRACER,
    Registry,
    SpanTracer,
    set_build_info,
    start_metrics_server,
)
from differential_transformer_replication_tpu.obs.introspect import (
    lambda_record,
    make_param_summary,
)
from differential_transformer_replication_tpu.train.metrics import (
    MetricLogger,
    config_hash,
    device_memory_mb,
)
from differential_transformer_replication_tpu.utils import ProfilerWindow, Throughput
from differential_transformer_replication_tpu.utils import faults
from differential_transformer_replication_tpu.utils.device import (
    setup_compile_cache,
)
from differential_transformer_replication_tpu.train.step import (
    create_train_state,
    make_eval_many,
    make_train_step,
)


def estimate_loss(
    eval_many,
    params: dict,
    train_ds: TokenWindows,
    val_ds: TokenWindows,
    cfg: TrainConfig,
    rng: np.random.Generator,
    materialize=None,
) -> dict:
    """Mean loss over eval_iters batches from each split (train.py:125-139):
    train batches shuffled, val batches sequential from the start — the
    same draws the reference's two loaders produce.

    ``eval_many(params, xs, ys)`` evaluates ALL eval_iters stacked batches
    in one device call (a jitted scan, train/step.py:make_eval_many, or
    the pipeline microbatch stream, parallel/pipeline.py) and returns
    per-batch losses (or their scalar mean) — one host sync per split
    instead of one per batch. The rng draw sequence is identical to the
    old per-batch loop (one ``integers(size=B)`` call per train batch).

    ``materialize(ds, offs)`` turns (eval_iters, B) window offsets into a
    device batch dict. The trainer passes its ``_materialize`` so eval
    batches ride the SAME per-process-slice + global-assembly path as
    training batches on multi-process pods (every process computes
    identical offsets from the identically-seeded rng, so the slices are
    consistent); the default is the single-host device-side gather."""
    mat = materialize if materialize is not None else (lambda ds, offs: ds.batches(offs))
    out = {}
    for split, ds in (("train", train_ds), ("val", val_ds)):
        if split == "train":
            offs = np.stack(
                [
                    rng.integers(0, len(ds), size=cfg.micro_batch_size, dtype=np.int64)
                    for _ in range(cfg.eval_iters)
                ]
            )
        else:
            offs = np.stack(
                [
                    ds.sequential_offsets(k, cfg.micro_batch_size)
                    for k in range(cfg.eval_iters)
                ]
            )
        batch = mat(ds, offs)
        losses = np.asarray(
            jax.device_get(eval_many(params, batch["x"], batch["y"])), np.float64
        )
        out[split] = float(losses.mean())
    return out


def _cache_key(cfg: TrainConfig, source: str) -> str:
    """Key for the (token stream, tokenizer) cache pair: everything that
    determines them, over the corpus source ACTUALLY used (the
    tinystories->synthetic fallback must not poison the tinystories key).
    File-path datasets additionally key on mtime+size so edits invalidate.
    """
    import hashlib
    import os

    key_parts = [
        source, str(cfg.num_train_samples), str(cfg.vocab_size),
        str(cfg.min_frequency), str(cfg.seed), "v1",
    ]
    if os.path.exists(source):
        st = os.stat(source)
        key_parts += [str(st.st_mtime_ns), str(st.st_size)]
    return hashlib.sha1("|".join(key_parts).encode()).hexdigest()[:16]


def build_data(cfg: TrainConfig):
    """Corpus -> tokenizer -> token stream -> train/val window datasets
    (train.py:153-200).

    The encoded stream and its tokenizer are cached TOGETHER under a
    per-key directory (``tokenizer_dir/cache-<key>/``): corpus generation
    + BPE training + encoding cost minutes at the reference's 1M-document
    scale and are fully determined by the key. Pairing them in one
    directory means a cache hit can never load a mismatched tokenizer
    left in the shared dir by a different config. The freshly trained
    tokenizer is also saved to ``tokenizer_dir`` itself, matching the
    reference's artifact layout (train.py:49-50)."""
    import os

    from differential_transformer_replication_tpu.data.corpus import (
        load_corpus_resolved,
    )
    from differential_transformer_replication_tpu.data.tokenizer import (
        load_tokenizer,
    )

    # Resolve which corpus source the dataset name maps to. Only
    # "tinystories" is ambiguous (its network fallback depends on
    # HF-cache/egress state, corpus.py) — probe it with a 1-document load
    # (HF caches the dataset, so a later full load reuses the download).
    # "synthetic" and file paths resolve to themselves with no I/O.
    if cfg.dataset == "tinystories":
        _, source = load_corpus_resolved(cfg.dataset, 1, cfg.seed)
    else:
        source = cfg.dataset

    def cache_paths(src):
        d = os.path.join(cfg.tokenizer_dir, f"cache-{_cache_key(cfg, src)}")
        return d, os.path.join(d, "tokens.npy")

    cache_dir, tokens_path = cache_paths(source)
    if os.path.exists(tokens_path):
        tokenizer = load_tokenizer(cache_dir)
        tokens = np.load(tokens_path)
        print(f"Loaded {len(tokens)} cached tokens from {tokens_path}")
        vocab_size = tokenizer.get_vocab_size()
        print(f"Vocabulary size: {vocab_size}")  # train.py:161
    else:
        texts, source = load_corpus_resolved(
            cfg.dataset, cfg.num_train_samples, cfg.seed
        )
        # the full load may resolve differently than the probe (network
        # state can change between the two) — key on what was USED
        cache_dir, tokens_path = cache_paths(source)
        tokenizer = train_bpe_tokenizer(
            texts, cfg.vocab_size, cfg.min_frequency, cfg.tokenizer_dir
        )
        vocab_size = tokenizer.get_vocab_size()
        print(f"Vocabulary size: {vocab_size}")  # train.py:161
        tokens = encode_corpus(tokenizer, texts)
        # Build the WHOLE cache entry (tokenizer files + tokens) in a
        # scratch dir, then rename it into place: a crash or a concurrent
        # builder can never leave a half-written entry that matches the
        # key. If another process won the rename race, adopt its entry.
        tmp_dir = f"{cache_dir}.tmp.{os.getpid()}"
        os.makedirs(tmp_dir, exist_ok=True)
        tokenizer.save_model(tmp_dir)
        with open(os.path.join(tmp_dir, "tokens.npy"), "wb") as f:
            np.save(f, tokens)
        try:
            os.rename(tmp_dir, cache_dir)
        except OSError:
            import shutil

            shutil.rmtree(tmp_dir, ignore_errors=True)
    print(f"Total tokens: {len(tokens)}")  # train.py:174
    train_tokens, val_tokens = split_tokens(tokens, cfg.val_fraction)
    block = cfg.model.block_size
    return (
        tokenizer,
        vocab_size,
        TokenWindows(train_tokens, block),
        TokenWindows(val_tokens, block),
    )


def train(cfg: TrainConfig) -> dict:
    """Run the full recipe; returns the final train state."""
    from differential_transformer_replication_tpu.parallel.multihost import (
        gather_to_host,
        initialize as distributed_initialize,
        is_primary,
    )

    distributed_initialize()  # no-op single-process (multihost.py)
    setup_compile_cache()
    print(f"Using devices: {jax.devices()}")
    # chaos-test fault injection (utils/faults.py); inert unless armed
    # via cfg.faults or the DTX_FAULTS env var
    faults.arm(cfg.faults)

    tokenizer, vocab_size, train_ds, val_ds = build_data(cfg)
    cfg = cfg.replace(vocab_size=vocab_size)
    from differential_transformer_replication_tpu.data.tokenizer import (
        check_tokenizer_matches,
        tokenizer_fingerprint,
    )

    tok_fp = tokenizer_fingerprint(tokenizer)
    ckpt_auto_skipped = 0
    # auto-resolution digest-verifies its winner moments before the
    # load; skip the redundant second full-file hash there (explicit
    # --resume-from paths still verify at load)
    resume_verify = True
    if cfg.resume_from == "auto":
        # Verified resume: newest checkpoint that passes manifest
        # verification, falling back to older ones — a crash mid-save
        # (uncertified dir) or a bit-rotted file can never wedge the
        # restart loop (train/checkpoint.py:resolve_resume_auto).
        resolved, skipped = resolve_resume_auto(cfg)
        ckpt_auto_skipped = len(skipped)
        if is_primary():
            for p, why in skipped:
                print(f"[ckpt] skipping unverified checkpoint {p}: {why}")
            if resolved is None:
                print("[ckpt] --resume-from auto: no verified checkpoint "
                      "found; starting fresh")
            else:
                print(f"[ckpt] --resume-from auto: resuming from {resolved}")
        cfg = cfg.replace(resume_from=resolved)
        resume_verify = resolved is None
    resume_info = None  # elastic-resume facts (mesh/batch/consumed)
    if cfg.resume_from:
        # Resume must continue on the SAME token stream: if the cache
        # entry was lost and the corpus re-resolved to different content,
        # every id is still valid and training silently continues on a
        # differently-tokenized stream — then overwrites the checkpoint,
        # destroying the evidence. Compare content fingerprints up front
        # (older checkpoints without one degrade to the size check).
        import os as _os

        from differential_transformer_replication_tpu.train.checkpoint import (
            read_meta,
        )

        # a meta-less dir leaves resume_info None, which is safe: the
        # later load_checkpoint -> read_meta raises CheckpointError for
        # it, so no resume can proceed without passing through
        # elastic_resume_info here first
        meta_path = _os.path.join(cfg.resume_from, "meta.json")
        if _os.path.exists(meta_path):
            meta = read_meta(cfg.resume_from)
            # Elastic resume (train/checkpoint.py): assert param-shape
            # compatibility up front (a typed error, not a deep flax
            # shape mismatch) and recover the sampler's exact position
            # in consumed windows — a preemption that returns a
            # DIFFERENT device count (or a retuned global batch) still
            # resumes onto the new mesh, bit-exact where the batch
            # math allows. Raises ElasticResumeError when exactness is
            # impossible and --allow-inexact-resume was not given.
            resume_info = elastic_resume_info(meta, cfg)
            if is_primary() and resume_info["elastic"]:
                print(
                    f"[elastic] resuming a checkpoint trained on mesh "
                    f"{resume_info['saved_mesh']} onto "
                    f"{dataclasses.asdict(cfg.mesh)} "
                    f"({'exact' if resume_info['exact'] else 'INEXACT'} "
                    f"sampler fast-forward from "
                    f"{resume_info['consumed_windows']} consumed windows)"
                )
            # compare against the CHECKPOINT's recorded vocab size, not
            # cfg.vocab_size — the latter was just overwritten from this
            # very tokenizer (cfg.replace above), which made the size leg
            # vacuous: a wrong-size tokenizer then only failed later on
            # an unhelpful flax shape mismatch (ADVICE r5 finding 1)
            saved_cfg = meta.get("config", {})
            # the TOP-LEVEL vocab_size is the one save_checkpoint records
            # from the live run (trainer resolves the tokenizer's vocab
            # into it; the nested model.vocab_size keeps its un-resolved
            # construction-time default)
            recorded_vocab = (
                saved_cfg.get("vocab_size")
                or (saved_cfg.get("model") or {}).get("vocab_size")
                or cfg.vocab_size  # very old meta: degrade to vacuous
            )
            check_tokenizer_matches(
                tokenizer, recorded_vocab,
                meta.get("tokenizer_fingerprint"), context=cfg.resume_from,
            )

    logger = MetricLogger(cfg)

    # -- observability (obs/): registry + sidecar + host span tracer --
    # The registry always exists (instrumentation is unconditional and
    # cheap — a few lock-guarded float updates per iteration); the
    # sidecar exporter and the Chrome span trace are opt-in knobs.
    registry = Registry()
    # process identity on the sidecar's /metrics (same build_info gauge
    # roles as router/replica, so an aggregated fleet scrape that
    # includes a training sidecar stays attributable)
    set_build_info(registry, role="trainer",
                   config_hash=config_hash(cfg),
                   version=jax.__version__)
    obs_step_hist = registry.histogram(
        "train_step_seconds",
        "Wall time of one train-loop iteration, host-observed "
        "(data wait + dispatch + any blocking).",
    )
    obs_data_hist = registry.histogram(
        "train_data_wait_seconds",
        "Host time assembling the next batch before dispatch.",
    )
    obs_stall_gauge = registry.gauge(
        "train_data_stall_ratio",
        "Fraction of recent loop wall time spent waiting on data.",
    )
    obs_mem_gauge = registry.gauge(
        "train_device_memory_peak_mb",
        "High-water mark of allocated device memory (MB).",
    )
    obs_compile_counter = registry.counter(
        "train_compile_events_total",
        "Compilation-cache entries of the jitted train step "
        "(steady state must stay at 1 — a growing count means "
        "something retraces).",
    )
    obs_iter_counter = registry.counter(
        "train_iterations_total", "Optimizer steps completed."
    )
    obs_anomaly_counter = registry.counter(
        "train_anomaly_events_total",
        "Anomaly-guard interventions (train/anomaly.py).",
        labelnames=("kind",),
    )
    obs_ckpt_save_hist = registry.histogram(
        "ckpt_save_seconds",
        "Wall time of one checkpoint save job (serialize + write + "
        "certify + GC), wherever it ran (writer thread or inline).",
    )
    obs_ckpt_blocked_hist = registry.histogram(
        "ckpt_blocked_seconds",
        "Train-loop wall time blocked on checkpointing per periodic "
        "snapshot: back-pressure waiting for a still-in-flight async "
        "save (steady state ~0; growing = the disk cannot keep up "
        "with ckpt_interval).",
    )
    obs_ckpt_verify_failures = registry.counter(
        "ckpt_verify_failures_total",
        "Checkpoints that failed integrity verification (digest "
        "mismatch, truncation, missing manifest) and were skipped "
        "during resume resolution.",
    )
    obs_ckpt_save_failures = registry.counter(
        "ckpt_save_failures_total",
        "Periodic step-checkpoint saves that failed (the run continues "
        "but is less protected; a growing count means the checkpoint "
        "storage is broken).",
    )
    obs_watchdog_fires = registry.counter(
        "train_watchdog_fires_total",
        "Step-deadline watchdog fires (train/watchdog.py): a training "
        "iteration hung past step_deadline_s, or a peer's heartbeat "
        "silence coordinated an abort. The process exits with the "
        "hang code right after incrementing, so any scrape showing "
        ">0 is the post-mortem of a dying incarnation.",
    )
    obs_heartbeat_age = registry.gauge(
        "train_heartbeat_age_seconds",
        "Seconds since each peer process's heartbeat record last "
        "changed, judged by this host's monotonic clock "
        "(parallel/heartbeat.py). Healthy: ~heartbeat_interval_s; "
        "growing toward heartbeat_timeout_s: that peer is dying.",
        labelnames=("peer",),
    )
    if ckpt_auto_skipped:
        obs_ckpt_verify_failures.inc(ckpt_auto_skipped)
    tracer = (
        SpanTracer(cfg.trace_path, process_name="trainer")
        if cfg.trace_path and is_primary() else NOOP_TRACER
    )
    metrics_server = None
    if cfg.metrics_port > 0 and is_primary():
        metrics_server = start_metrics_server(registry, cfg.metrics_port)
        print(
            f"[obs] Prometheus sidecar: "
            f"http://0.0.0.0:{metrics_server.server_address[1]}/metrics"
        )

    if cfg.mesh.pipeline > 1:
        # Pipeline-parallel path: GPipe schedule over the pipeline axis
        # (parallel/pipeline.py); eval runs through the same pipeline.
        from differential_transformer_replication_tpu.parallel import create_mesh
        from differential_transformer_replication_tpu.parallel.pipeline import (
            create_pipeline_train_state,
            make_pipeline_eval_many,
            make_pipeline_train_step,
            pipeline_state_sharding,
        )

        mesh = create_mesh(cfg.mesh)
        print(f"Mesh: {dict(mesh.shape)}")
        state = create_pipeline_train_state(jax.random.PRNGKey(cfg.seed), cfg, mesh)
        best_val_loss = float("inf")
        if cfg.resume_from:
            host_state = gather_to_host(state)
            host_state, best_val_loss = load_checkpoint(cfg.resume_from, cfg, host_state, verify=resume_verify)
            sh = pipeline_state_sharding(host_state, mesh)
            state = jax.tree_util.tree_map(jax.device_put, host_state, sh)
            print(f"Resumed from {cfg.resume_from} at iter {int(jax.device_get(state['step']))}")
        train_step = make_pipeline_train_step(cfg, mesh, state)
        # eval feeds all eval_iters batches through the pipeline as ONE
        # microbatch stream: bubble amortized (P-1)/(K+P-1) instead of
        # (P-1)/P per batch (VERDICT r1 item 7)
        eval_many = make_pipeline_eval_many(cfg, mesh)
    elif cfg.mesh.n_devices > 1:
        # Sharded path: mesh + partitioned step (the DDP/NCCL replacement).
        from differential_transformer_replication_tpu.parallel import (
            create_mesh,
            make_sharded_train_step,
            shard_state,
        )
        from differential_transformer_replication_tpu.parallel.dp_step import (
            create_sharded_train_state,
        )

        mesh = create_mesh(cfg.mesh)
        # threaded into eval too; model_forward ignores it unless the mesh
        # has a >1 sequence axis (ring.use_ring), keeping eval and train
        # on the same attention path by construction
        eval_mesh = mesh
        print(f"Mesh: {dict(mesh.shape)}")
        state = create_sharded_train_state(jax.random.PRNGKey(cfg.seed), cfg, mesh)
        best_val_loss = float("inf")
        if cfg.resume_from:
            # the freshly-initialized state supplies the target pytree; on
            # multi-process pods its fsdp/tensor shards live on other
            # hosts' devices, so the host copy must be the collective
            # gather, and the re-placement below relies on device_put
            # accepting a global sharding when every process holds the
            # same full host value (which load_checkpoint guarantees)
            host_state = gather_to_host(state)
            host_state, best_val_loss = load_checkpoint(cfg.resume_from, cfg, host_state, verify=resume_verify)
            state = shard_state(host_state, mesh)
            print(f"Resumed from {cfg.resume_from} at iter {int(jax.device_get(state['step']))}")
        train_step = make_sharded_train_step(cfg, mesh, state)
    else:
        eval_mesh = None
        state = create_train_state(jax.random.PRNGKey(cfg.seed), cfg)
        best_val_loss = float("inf")
        if cfg.resume_from:
            state, best_val_loss = load_checkpoint(cfg.resume_from, cfg, state, verify=resume_verify)
            print(f"Resumed from {cfg.resume_from} at iter {int(state['step'])}")
        train_step = make_train_step(cfg)
    if cfg.mesh.pipeline <= 1:
        eval_many = make_eval_many(cfg, mesh=eval_mesh)

    # -- consumed-window accounting (elastic resume) -------------------
    # The epoch sampler's position is tracked in WINDOWS CONSUMED, not
    # derived from step arithmetic: a resumed run whose global batch
    # size changed (elastic resume) would otherwise fast-forward the
    # permutation to the wrong place. The base comes from the
    # checkpoint's recorded consumed_windows (elastic_resume_info);
    # everything after the base advances under THIS run's batch math.
    start_iter = int(jax.device_get(state["step"]))
    consumed_base_iter = start_iter
    if resume_info is not None and resume_info["consumed_windows"] is not None:
        consumed_base = resume_info["consumed_windows"]
    else:
        consumed_base = (
            start_iter * cfg.grad_acc_steps * cfg.micro_batch_size
        )

    def consumed_at(it: int) -> int:
        """Windows consumed once iteration ``it`` of THIS run has
        completed — the sampler fast-forward anchor and the
        consumed_windows every checkpoint save records."""
        return consumed_base + (it - consumed_base_iter) * (
            cfg.grad_acc_steps * cfg.micro_batch_size
        )

    if cfg.checkpoint_min_interval_s > 0:
        # The throttle's deferred-improvement snapshot pins a SECOND full
        # train state in HBM until the next write or exit; surface the
        # headroom risk at startup instead of OOM-ing a run that fit
        # without the throttle (advisor, round 4). Count DEVICE-0 shard
        # bytes, not global bytes — on sharded runs the snapshot adds only
        # each device's own shard.
        dev0 = jax.local_devices()[0]

        def _dev0_bytes(leaf):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                return sum(
                    s.data.nbytes for s in shards if s.device == dev0
                )
            return getattr(leaf, "nbytes", 0)

        state_bytes = sum(
            _dev0_bytes(leaf) for leaf in jax.tree_util.tree_leaves(state)
        )
        # None on the CPU backend, which keeps no memory stats
        stats = jax.local_devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit", 0)
        in_use = stats.get("bytes_in_use", 0)
        # in_use already counts the live state; the deferred snapshot pins
        # exactly ONE additional copy
        if limit and in_use + state_bytes > 0.92 * limit:
            import warnings

            warnings.warn(
                "checkpoint_min_interval_s > 0 keeps an on-device snapshot "
                f"of the full train state (~{state_bytes / 2**20:.0f} MiB) "
                "while a best-checkpoint write is deferred; estimated HBM "
                f"({(in_use + state_bytes) / 2**20:.0f} of "
                f"{limit / 2**20:.0f} MiB) leaves little headroom — a run "
                "that fits without the throttle may OOM with it. Set "
                "--checkpoint-min-interval-s 0 if memory-tight",
                stacklevel=2,
            )

    data_rng = np.random.default_rng(cfg.seed)
    eval_rng = np.random.default_rng(cfg.seed + 1)

    # Multi-process pods: every host draws the SAME offsets (the samplers
    # are seeded identically), takes its own disjoint batch-column slice,
    # gathers those windows host-side, and
    # jax.make_array_from_process_local_data assembles the global batch —
    # the working DistributedSampler replacement (train.py:8-10). Single
    # process keeps the device-resident gather.
    from differential_transformer_replication_tpu.parallel.multihost import (
        global_batch as assemble_global,
        local_batch_slice,
        process_count,
    )

    multihost_data = process_count() > 1 and cfg.mesh.n_devices > 1

    def _materialize(ds, offs: np.ndarray) -> dict:
        # (A|K, B) offsets -> device batch dict; used by BOTH the training
        # draw and eval (estimate_loss), so every data path is per-process
        # sliced + globally assembled on pods
        if multihost_data:
            start, per = local_batch_slice(cfg.micro_batch_size)
            local = ds.host_batches(offs[:, start : start + per])
            return assemble_global(local, mesh)
        return ds.batches(offs)

    if cfg.sampler == "epoch":
        # exact DataLoader-style epoch shuffle (train.py:184-191) via the
        # native O(1)-memory permutation
        from differential_transformer_replication_tpu.data.native import (
            EpochPermutation,
        )

        perm = EpochPermutation(len(train_ds), cfg.seed)
        # fast-forward past windows already consumed before a resume, so
        # the once-per-epoch guarantee survives checkpoint restarts —
        # from the checkpoint's RECORDED consumed count (consumed_at),
        # so an elastic resume under a changed global batch size keeps
        # the permutation position exact
        perm.epoch, perm.cursor = divmod(
            consumed_at(start_iter), len(train_ds)
        )

        def draw_batch():
            offs = perm.take(cfg.grad_acc_steps * cfg.micro_batch_size)
            return _materialize(
                train_ds, offs.reshape(cfg.grad_acc_steps, cfg.micro_batch_size)
            )
    elif cfg.sampler == "replacement":
        def draw_batch():
            offs = data_rng.integers(
                0, len(train_ds),
                size=(cfg.grad_acc_steps, cfg.micro_batch_size),
                dtype=np.int64,
            )
            return _materialize(train_ds, offs)
    else:
        raise ValueError(f"unknown sampler {cfg.sampler!r}")
    dropout_key = jax.random.PRNGKey(cfg.seed + 2)
    model_cfg = cfg.resolved_model()
    use_dropout = model_cfg.dropout > 0.0

    # Paper-level introspection (obs/introspect.py): jitted per-layer
    # lambda + param-norm summary fetched every eval interval, so the
    # lambda-evolution figure is reproducible from metrics.jsonl
    # (tools/lambda_report.py). The pipeline path stacks params per
    # stage — a layout the summary does not speak — so it is skipped
    # there, like the anomaly guard.
    param_summary = (
        make_param_summary(model_cfg) if cfg.mesh.pipeline <= 1 else None
    )

    # Continuous on-device profiling (obs/device_profile.py): every
    # profile_every iterations one step is wrapped in a jax.profiler
    # capture, parsed off-loop, and published as device_* gauges,
    # {"record":"device_profile"} metrics.jsonl rows, and a stitchable
    # device-lane trace. The FLOPs/HBM estimates feed the derived
    # device_mfu gauge with bench.py's exact 6*N*D accounting, so the
    # continuous samples and bench rounds are directly comparable.
    device_prof = None
    if cfg.profile_every > 0 and is_primary():
        from differential_transformer_replication_tpu.models import (
            param_count,
        )
        from differential_transformer_replication_tpu.obs import xprof
        from differential_transformer_replication_tpu.obs.device_profile import (
            DeviceProfileSampler,
        )

        n_params = param_count(state["params"])
        n_embed = xprof.embedding_param_count(
            model_cfg.model, model_cfg.vocab_size, model_cfg.n_embd,
            model_cfg.block_size,
        )
        tokens_per_step = (
            cfg.micro_batch_size * cfg.grad_acc_steps * model_cfg.block_size
        )
        # the parsed plane is ONE device's timeline and the MFU
        # denominator is ONE chip's peak, so the numerator must be the
        # PER-CHIP share of the step's work — on an n-device mesh each
        # chip executes ~1/n of the global FLOPs (data splits the
        # batch, tensor/fsdp/pipeline split the math), and the same
        # division approximates per-chip HBM traffic (right for
        # sharded params; an underestimate for DP-replicated ones,
        # which re-read the full set per chip — roofline-order only)
        n_dev = max(1, cfg.mesh.n_devices)
        # a utilization is a device number: the CPU backend (tests) has
        # no peak and publishes none; an accelerator the table does not
        # know raises
        dev0 = jax.devices()[0]
        peak_flops = (
            None if dev0.platform == "cpu"
            else xprof.device_peaks(dev0.device_kind)["bf16_flops_per_s"]
        )
        device_prof = DeviceProfileSampler(
            every=cfg.profile_every,
            spool_dir=cfg.resolved_profile_spool(),
            registry=registry,
            sink=logger.log_record,
            jsonl_path=None,  # rows ride the run's own metrics.jsonl
            tracer=tracer,
            process="trainer",
            flops_per_step=xprof.train_flops_per_step(
                n_params, n_embed, tokens_per_step
            ) / n_dev,
            hbm_bytes_per_step=(
                xprof.train_hbm_bytes_per_step(n_params) / n_dev
            ),
            peak_flops=peak_flops,
        )

    def _compile_entries():
        """Compile-cache size of the jitted step (None when the step
        wrapper does not expose one): steady state must hold at 1; a
        growing count is the retrace pathology the zero-recompile pins
        (tests/test_obs.py) guard against."""
        cache_size = getattr(train_step, "_cache_size", None)
        if cache_size is None:
            return None
        try:
            return int(cache_size())
        except Exception:
            return None

    # -- resilience layer (train/watchdog.py, parallel/heartbeat.py) --
    # Both are pure HOST-side daemon threads: they never touch traced
    # code, so the compile count stays pinned at 1 with them enabled
    # (tests/test_watchdog.py). The watchdog object also exists when
    # only the heartbeat is configured — a dead peer trips it directly
    # (coordinated abort), deadline monitor or not.
    watchdog = None
    heartbeat = None
    wd_warm = False  # becomes True once the first iteration compiled
    hb_iter = {"i": start_iter}  # host iter, read by the publisher
    if cfg.step_deadline_s > 0 or cfg.heartbeat_dir:
        watchdog = StepWatchdog(
            cfg.step_deadline_s,
            report_path=cfg.resolved_hang_report_path(),
            sink=logger.log_record,
            fires_counter=obs_watchdog_fires,
            context={
                "compile_events": _compile_entries,
                "device_profile": lambda: getattr(
                    device_prof, "last_record", None
                ),
                "process_index": jax.process_index,
            },
        )
    if cfg.heartbeat_dir:
        from differential_transformer_replication_tpu.parallel.heartbeat import (
            FileHeartbeatTransport,
            Heartbeat,
        )

        def _peer_dead(peer: int, age: float) -> None:
            # a silent peer means the next collective wedges every
            # surviving host: fire the watchdog NOW instead of waiting
            # out the step deadline inside a psum
            watchdog.trip(
                f"peer process {peer} heartbeat silent for {age:.1f}s "
                f"(timeout {cfg.heartbeat_timeout_s:.1f}s): "
                "coordinated abort"
            )

        heartbeat = Heartbeat(
            FileHeartbeatTransport(cfg.heartbeat_dir),
            process_index=jax.process_index(),
            num_processes=process_count(),
            interval_s=cfg.heartbeat_interval_s,
            timeout_s=cfg.heartbeat_timeout_s,
            iter_supplier=lambda: hb_iter["i"],
            on_dead=_peer_dead,
            age_gauge=obs_heartbeat_age,
        )
        watchdog.add_context(heartbeat_ages=heartbeat.peer_ages)

    # Anomaly guard (train/anomaly.py): the jitted step skips bad
    # updates on-device; the host side here keeps a periodic good-state
    # snapshot, rolls back to it when badness persists, and aborts when
    # rollbacks stop helping. Pipeline runs use a different step
    # (parallel/pipeline.py) that does not carry the guard state.
    guard_on = cfg.anomaly_guard and cfg.mesh.pipeline <= 1
    if cfg.anomaly_guard and cfg.mesh.pipeline > 1 and is_primary():
        print("[anomaly] guard is unsupported on the pipeline path; disabled")
    # the pipeline step's jit signature declares only {"x","y"} batches
    # (parallel/pipeline.py) — NaN injection is train-step-only, like
    # the guard that exists to catch it
    nan_fault_armed = faults.nan_armed() and cfg.mesh.pipeline <= 1
    if faults.nan_armed() and cfg.mesh.pipeline > 1 and is_primary():
        print("[faults] nan injection is unsupported on the pipeline "
              "path; disabled")
    rollbacks = 0

    # Durable rotating step checkpoints (train/ckpt_writer.py): every
    # ckpt_interval iterations the state is snapshotted to host and a
    # certified `step-NNNNNNNN` dir is written + GC'd — from a
    # background writer thread when ckpt_async (the loop then blocks
    # only for the device->host snapshot, with back-pressure if the
    # previous save is still in flight). The writer exists on the
    # primary only; other ranks just participate in the snapshot's
    # collective gather.
    ckpt_root = cfg.resolved_ckpt_dir()
    ckpt_writer = None
    ckpt_last_save_s = None  # sync-path mirror of writer.last_save_s
    if cfg.ckpt_interval > 0:
        if cfg.ckpt_keep_last < 1:
            raise ValueError(
                "ckpt_keep_last must be >= 1 when ckpt_interval > 0, "
                f"got {cfg.ckpt_keep_last}"
            )
        if cfg.ckpt_async and is_primary():
            ckpt_writer = AsyncCheckpointWriter(
                save_hist=obs_ckpt_save_hist,
                blocked_hist=obs_ckpt_blocked_hist,
            )

    print("Starting training...")
    t0 = time.time()
    tokens_seen = 0
    throughput = Throughput()
    # profile a short steady-state window past compile + warmup, relative
    # to wherever this run starts (fresh or resumed)
    profiler = ProfilerWindow(
        cfg.profile_dir, start=int(jax.device_get(state["step"])) + 10,
        tracer=tracer,
    )
    # Preemption safety (SURVEY.md section 5.3 — the reference has none):
    # SIGTERM requests a graceful stop; the finally block below writes a
    # resumable last-state checkpoint on ANY exit (preemption, Ctrl-C,
    # crash mid-run, or normal completion), so `--resume-from
    # <last_checkpoint_path>` always continues from the latest step.
    stop_requested = {"flag": False}

    def _on_sigterm(signum, frame):
        del signum, frame
        stop_requested["flag"] = True

    def _agreed_stop(iter_num: int) -> bool:
        """Whether to break the train loop THIS iteration. Single-process:
        the local SIGTERM flag, checked every iteration. Multi-process:
        the flag is OR-reduced across ranks at log_interval boundaries
        (where logging already forces a host sync), so every rank breaks
        at the SAME iteration — a rank leaving the loop early while peers
        still run train_step psums would mismatch collectives and hang
        the pod. Scheduler preemptions deliver SIGTERM to each rank at
        slightly different times; the agreement absorbs that skew at the
        cost of up to log_interval extra steps of grace period."""
        if process_count() == 1:
            return stop_requested["flag"]
        if iter_num % cfg.log_interval != 0:
            return False
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.float32(1.0 if stop_requested["flag"] else 0.0)
        )
        return bool(np.asarray(flags).sum() > 0)

    import signal

    prev_handler = None
    try:
        prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not the main thread (tests); SIGTERM stays default
    # Host-side iteration counter: the device `state["step"]` advances by
    # exactly 1 per call, and reading it back would force a host-device
    # sync every iteration, breaking async dispatch pipelining.
    iter_num = int(jax.device_get(state["step"]))
    metrics = None  # last step's metrics; gates the rescue save below
    last_ckpt_path = cfg.resolved_last_checkpoint_path()
    best_snapshot = None  # device-side best state not yet written to disk
    best_snapshot_iter = 0  # its iteration (consumed-window accounting)
    # seeded at loop entry: "at most one best write per interval" holds
    # from the start (interval 0 still writes on every improvement).
    # monotonic: a backward wall-clock step (NTP) must not defer writes
    last_best_write = time.monotonic() - cfg.checkpoint_min_interval_s
    # set by the except below — NOT derived from sys.exc_info(), which
    # would also be truthy when train() runs inside a caller's exception
    # handler (e.g. a retry wrapper) and would wrongly suppress the
    # multi-process rescue save on a clean run
    crashed = False
    # the guard's rollback target: seeded at loop entry so one always
    # exists, refreshed every anomaly_snapshot_interval good iterations.
    # Like the throttle snapshot above, it pins ONE extra train state in
    # HBM (device-0-shard-sized on sharded runs).
    good_snapshot = snapshot_state(state) if guard_on else None
    snapshot_iter = iter_num
    # per-log-interval telemetry accumulators (flushed into each
    # log_step record's extra fields and the registry gauges)
    obs_acc_step = obs_acc_data = 0.0
    obs_acc_n = 0
    ckpt_acc_blocked = 0.0  # back-pressure seconds since the last log
    # last observed in-state skip total: the Prometheus counter must
    # only ever move by POSITIVE deltas (a rollback rewinds the guard
    # state — and with it metrics["skipped"] — but an exported counter
    # that decreases reads as a process restart to rate()/increase())
    obs_prev_skipped = 0
    try:
        while iter_num < cfg.max_iters:
            if _agreed_stop(iter_num):
                if is_primary():
                    print(f"SIGTERM received: stopping at iter {iter_num}")
                break
            faults.fire(iter_num)  # injected raise/SIGTERM/SIGKILL points
            if watchdog is not None and wd_warm:
                # armed across the step's dispatch and the host syncs
                # that follow it; legitimately slow sections (eval,
                # checkpoint writes) run disarmed below. The FIRST
                # iteration of this process runs unarmed: its dispatch
                # traces + compiles the step (tens of seconds to
                # minutes), which is slow-but-alive, not a hang —
                # deadlining it would turn every cold start and every
                # supervised relaunch into a false watchdog fire.
                watchdog.arm(iter_num)
            # chaos stalls (train_hang / collective_skew) land INSIDE
            # the armed window — they simulate a wedged or lagging loop
            faults.train_stall(iter_num)
            if faults.corrupt_params_at(iter_num):
                # simulated state corruption (bitflip-class fault): NaN a
                # param leaf — batch skipping cannot cure this; only the
                # guard's rollback recovers the run
                leaves, treedef = jax.tree_util.tree_flatten(state["params"])
                leaves[0] = leaves[0] * jnp.float32(jnp.nan)
                state["params"] = jax.tree_util.tree_unflatten(treedef, leaves)
            t_iter = time.perf_counter()
            with tracer.span("data_wait", iter=iter_num):
                batch = draw_batch()
            data_wait = time.perf_counter() - t_iter
            if nan_fault_armed:
                # present in EVERY batch while armed, so the compiled
                # step's input structure never changes (train/step.py)
                scale = np.nan if faults.poison_at(iter_num) else 1.0
                batch["poison"] = np.full(
                    (cfg.grad_acc_steps,), scale, np.float32
                )
            rng = jax.random.fold_in(dropout_key, iter_num) if use_dropout else None
            # non-due steps pay one integer compare here; a due step
            # opens a capture window around exactly this dispatch
            capturing = (
                device_prof is not None
                and device_prof.maybe_begin(iter_num)
            )
            with tracer.span("dispatch", iter=iter_num):
                state, metrics = train_step(state, batch, rng)
            iter_num += 1
            hb_iter["i"] = iter_num  # heartbeat telemetry (off-loop read)
            if capturing:
                # closes the window (blocking on the step's loss so the
                # device work is inside it) and hands the trace to the
                # off-loop parse worker
                device_prof.end(sync=metrics["loss"])
            profiler.step(iter_num, sync=metrics["loss"])
            tokens_seen += cfg.micro_batch_size * cfg.grad_acc_steps * model_cfg.block_size

            if guard_on and iter_num % cfg.anomaly_check_interval == 0:
                # one replicated-scalar read: every rank computes the same
                # streak (the bad flag is a global value, train/anomaly
                # .py), so rollback/abort decisions agree with no
                # collective. This blocks on the step's completion —
                # anomaly_check_interval amortizes that pipeline bubble.
                with tracer.span("block", what="anomaly_streak"):
                    # deliberate sync, amortized by anomaly_check_interval
                    streak = int(jax.device_get(metrics["bad_streak"]))  # graftlint: disable=GL202 (anomaly_check_interval cadence)
                if streak == 0:
                    if iter_num - snapshot_iter >= cfg.anomaly_snapshot_interval:
                        good_snapshot = snapshot_state(state)
                        snapshot_iter = iter_num
                elif streak >= cfg.anomaly_rollback_after:
                    rollbacks += 1
                    if rollbacks > cfg.anomaly_max_rollbacks:
                        raise TrainingDivergedError(
                            f"{rollbacks - 1} rollback(s) did not recover "
                            f"the run: still {streak} consecutive bad "
                            f"steps at iter {iter_num}. Aborting without "
                            "overwriting the last good checkpoint."
                        )
                    if is_primary():
                        print(
                            f"[anomaly] {streak} consecutive bad steps at "
                            f"iter {iter_num}: rolling back to iter "
                            f"{snapshot_iter} (rollback {rollbacks}/"
                            f"{cfg.anomaly_max_rollbacks})"
                        )
                    if watchdog is not None:
                        # the full-state restore below is a legitimate
                        # slow recovery section, not a hang — it must
                        # not run against the deadline armed at the top
                        # of this iteration (and the completed dispatch
                        # already proved the step compiled)
                        watchdog.disarm()
                        wd_warm = True
                    # an in-HBM resume: restore the snapshot (copy — the
                    # donated step must not consume it) and rewind the
                    # epoch sampler to the matching position, exactly the
                    # checkpoint-resume fast-forward. The replacement
                    # sampler is stateless draws and simply continues.
                    state = snapshot_state(good_snapshot)
                    iter_num = snapshot_iter
                    metrics = None
                    if cfg.sampler == "epoch":
                        perm.epoch, perm.cursor = divmod(
                            consumed_at(iter_num), len(train_ds)
                        )
                    continue

            if watchdog is not None:
                # the slow tails below (checkpoint write, eval) are
                # legitimate; only the step+sync window is deadlined
                watchdog.disarm()
                wd_warm = True  # compile is done: deadline from now on

            # host-observed iteration accounting: wall time of the whole
            # loop body (dispatch-pipelined, so this is NOT device step
            # time — it is what the user waits for) and the data-wait
            # share of it. A rolled-back iteration skips this (its work
            # was discarded with the state).
            step_wall = time.perf_counter() - t_iter
            obs_step_hist.observe(step_wall)
            obs_data_hist.observe(data_wait)
            obs_iter_counter.inc()
            obs_acc_step += step_wall
            obs_acc_data += data_wait
            obs_acc_n += 1

            if cfg.ckpt_interval > 0 and iter_num % cfg.ckpt_interval == 0:
                # periodic certified step checkpoint: the snapshot
                # (collective gather -> host numpy) happens here on the
                # loop; serialization/IO/GC run on the writer thread
                # when async. A failed save must not kill a healthy
                # run — it is counted and printed instead.
                with tracer.span("ckpt_snapshot", iter=iter_num):
                    t_ck = time.perf_counter()
                    try:
                        blocked = save_step_checkpoint(
                            ckpt_root, state, best_val_loss, cfg,
                            tokenizer_fingerprint=tok_fp,
                            writer=ckpt_writer,
                            keep_last=cfg.ckpt_keep_last,
                            keep_every=cfg.ckpt_keep_every,
                            consumed_windows=consumed_at(iter_num),
                        )
                        ckpt_acc_blocked += blocked
                        if ckpt_writer is None and is_primary():
                            # sync path: the whole save ran inline here
                            ckpt_last_save_s = time.perf_counter() - t_ck
                            obs_ckpt_save_hist.observe(ckpt_last_save_s)
                    except Exception as e:  # noqa: BLE001
                        obs_ckpt_save_failures.inc()
                        if is_primary():
                            print(f"[ckpt] step-checkpoint save failed "
                                  f"at iter {iter_num} (continuing): {e!r}")

            if iter_num % cfg.log_interval == 0:
                extra = {}
                if watchdog is not None and wd_warm:
                    # the log-boundary device_get is where a wedged
                    # collective actually manifests on the host —
                    # deadline it like the dispatch window
                    watchdog.arm(iter_num)
                with tracer.span("block", what="log_metrics"):
                    # THE deliberate log-boundary sync, amortized by
                    # log_interval — one batched device_get instead of
                    # the two separate blocking float() fetches this
                    # block used to do (graftlint GL202 found both)
                    loss_f, lr_f = (
                        float(v) for v in jax.device_get(  # graftlint: disable=GL202 (log-boundary sync)
                            (metrics["loss"], metrics["learning_rate"])
                        )
                    )
                    if guard_on:
                        skipped = int(metrics["skipped"])  # graftlint: disable=GL202 (rides the log sync)
                        extra["skipped_steps"] = skipped
                        extra["rollbacks"] = rollbacks
                        if skipped > obs_prev_skipped:
                            obs_anomaly_counter.inc(
                                skipped - obs_prev_skipped, kind="skip"
                            )
                        # after a rollback the in-state total rewinds;
                        # re-base so replayed skips count as new events
                        obs_prev_skipped = skipped
                        # host-side `rollbacks` is monotone by
                        # construction, so set() cannot decrease it
                        obs_anomaly_counter.set(rollbacks, kind="rollback")
                if watchdog is not None:
                    watchdog.disarm()
                n = max(obs_acc_n, 1)
                extra["step_time_ms"] = round(1e3 * obs_acc_step / n, 3)
                extra["data_wait_frac"] = round(
                    obs_acc_data / max(obs_acc_step, 1e-9), 4
                )
                obs_stall_gauge.set(extra["data_wait_frac"])
                if cfg.ckpt_interval > 0:
                    # checkpoint health rides the same records: blocked
                    # time since the last log (back-pressure; ~0 when
                    # the disk keeps up) and the last completed save's
                    # duration, wherever it ran
                    extra["ckpt_blocked_ms"] = round(
                        1e3 * ckpt_acc_blocked, 3
                    )
                    last_save_s = (
                        ckpt_writer.last_save_s
                        if ckpt_writer is not None else ckpt_last_save_s
                    )
                    if last_save_s is not None:
                        extra["ckpt_save_ms"] = round(1e3 * last_save_s, 3)
                    ckpt_acc_blocked = 0.0
                compiles = _compile_entries()
                if compiles is not None:
                    obs_compile_counter.set(compiles)
                    extra["compile_events"] = compiles
                mem = device_memory_mb()  # one query: gauge + record
                if mem is not None:
                    obs_mem_gauge.set_max(mem)
                obs_acc_step = obs_acc_data = 0.0
                obs_acc_n = 0
                logger.log_step(
                    iter_num, loss_f, lr_f,
                    tokens_per_sec=throughput.update(tokens_seen),
                    extra=extra, gpu_memory_mb=mem,
                )

            if iter_num % cfg.eval_interval == 0:
                with tracer.span("eval", iter=iter_num):
                    losses = estimate_loss(
                        eval_many, state["params"], train_ds, val_ds, cfg,
                        eval_rng, materialize=_materialize,
                    )
                logger.log_eval(iter_num, losses["train"], losses["val"])
                if param_summary is not None:
                    # the lambda-evolution + per-group-norm record (see
                    # obs/introspect.py): control contributes norms only,
                    # diff one lambda per layer, ndiff one per term per
                    # layer — the acceptance contract
                    with tracer.span("block", what="introspection"):
                        # deliberate sync at eval cadence (the eval
                        # above already forced one)
                        summ = jax.device_get(param_summary(state["params"]))  # graftlint: disable=GL202 (eval cadence)
                        gnorm = (
                            None if metrics is None
                            else jax.device_get(  # graftlint: disable=GL202 (eval cadence)
                                metrics.get("grad_norm_groups")
                            )
                        )
                    logger.log_record({
                        "record": "introspection", "iter": iter_num,
                        **lambda_record(summ, model_cfg, grad_norms=gnorm),
                    })
                if losses["val"] < best_val_loss:  # train.py:307-317
                    best_val_loss = losses["val"]
                    if is_primary():
                        print(f"Saving best model with val loss: {best_val_loss:.4f}")
                    # Throttle the expensive best-state disk write: it
                    # cost ~3 min at recipe scale on the 2026-07
                    # installation (device->host 5-7 MB/s, BASELINE.md
                    # round 4; not measured on today's machine), and
                    # early training improves on EVERY eval.
                    # checkpoint_min_interval_s = 0 (default)
                    # keeps the reference's write-every-improvement
                    # behavior (train.py:307-317) with no extra copy.
                    # When a write is DEFERRED, the best state is
                    # snapshotted on-device instead (an HBM copy — note it
                    # pins a second full train state until flushed; memory-
                    # tight configs should keep the throttle at 0) and any
                    # pending snapshot is flushed at exit, so the final
                    # best.ckpt is identical under any throttle. The
                    # decision must AGREE across ranks (save_checkpoint is
                    # a collective): rank 0's clock decides.
                    write_now = (
                        time.monotonic() - last_best_write
                        >= cfg.checkpoint_min_interval_s
                    )
                    if process_count() > 1:
                        from jax.experimental import multihost_utils

                        flags = multihost_utils.process_allgather(
                            np.float32(1.0 if write_now else 0.0)
                        )
                        write_now = bool(np.asarray(flags).ravel()[0] > 0)
                    if write_now:
                        # collective host-gather inside; the primary writes
                        save_checkpoint(
                            cfg.checkpoint_path, state, best_val_loss, cfg,
                            tokenizer_fingerprint=tok_fp,
                            consumed_windows=consumed_at(iter_num),
                        )
                        best_snapshot = None
                        last_best_write = time.monotonic()
                    else:
                        best_snapshot = jax.tree_util.tree_map(
                            jnp.copy, state
                        )
                        best_snapshot_iter = iter_num

        dt = time.time() - t0
        if dt > 0:
            print(f"Training done: {tokens_seen} tokens in {dt:.1f}s "
                  f"({tokens_seen / dt:.0f} tokens/sec)")
    except BaseException:
        crashed = True
        raise
    finally:
        # these closes must not derail the rescue logic below, and above
        # all must not derail it ASYMMETRICALLY across ranks (a flush
        # error on one host only), so they are contained here
        def _stop_metrics_server():
            if metrics_server is not None:
                metrics_server.shutdown()
                metrics_server.server_close()

        def _close_tracer():
            tracer.close()
            if tracer.path:
                print(f"[obs] span trace written to {tracer.path}")

        def _drain_ckpt_writer():
            # drain the async writer BEFORE the rescue save below: an
            # in-flight step snapshot finishes (and certifies) rather
            # than being abandoned half-written; a job error stored in
            # the writer surfaces here and is printed, not raised
            if ckpt_writer is not None:
                ckpt_writer.close(timeout=600.0)

        def _drain_device_prof():
            # finish the queued device-profile parse (its record must
            # land in metrics.jsonl before logger.finish closes it) and
            # stop any capture window a crashed step left open
            if device_prof is not None:
                device_prof.close()

        def _close_resilience():
            # stop the watchdog monitor FIRST — the rescue save below
            # is a legitimately slow section and must not be deadlined
            # — then the heartbeat threads (peers see this process's
            # silence only after its heartbeat_timeout_s, by which
            # time a clean exit has already torn the job down)
            if watchdog is not None:
                watchdog.close()
            if heartbeat is not None:
                heartbeat.close()

        for closer in (_close_resilience, _drain_device_prof,
                       _drain_ckpt_writer,
                       profiler.close, logger.finish,
                       _close_tracer, _stop_metrics_server):
            try:
                closer()
            except Exception as e:  # noqa: BLE001
                print(f"shutdown cleanup failed (continuing): {e!r}")
        # On MULTI-process runs the rescue save embeds a collective
        # (gather_to_host); if this process is unwinding an exception the
        # other ranks may be anywhere (still in a train_step psum, or
        # crashed differently), and issuing a mismatched collective here
        # would turn one rank's crash into a fleet-wide hang. Skip the
        # rescue on that path — jax's coordination service tears the job
        # down when this process exits, and the last periodic
        # best-checkpoint remains. Deliberately NOT agreed via an
        # OR-reduce across ranks: that agreement would itself be a
        # collective issued from an asymmetric path (peers of a mid-loop
        # crash are still inside train_step psums, not here), i.e. the
        # exact hazard being avoided. Normal completion and the SIGTERM
        # graceful stop exit the loop in lockstep on every rank
        # (_agreed_stop), so their collective save is safe.
        # Single-process keeps the save on every exit path, crashes
        # included.
        skip_collective_rescue = crashed and process_count() > 1
        try:
            try:
                if ckpt_writer is not None and not ckpt_writer.drained:
                    # Drain-ordering invariant: the rescue save below
                    # must never interleave with an in-flight async
                    # periodic save (same rotation tree, racing GC).
                    # The closer above normally drained the writer; if
                    # that drain FAILED (stuck disk, timeout), retry
                    # here — and if it still will not drain, skip the
                    # rescue rather than interleave two writers
                    # (tests/test_ckpt.py pins the ordering with
                    # ckpt_hang).
                    try:
                        ckpt_writer.close(timeout=600.0)
                    except Exception as e:  # noqa: BLE001
                        print(f"checkpoint writer would not drain; "
                              f"skipping rescue save (the certified "
                              f"step tree remains): {e!r}")
                        last_ckpt_path = None
                if last_ckpt_path and not skip_collective_rescue:
                    # resumable last-state checkpoint, written whatever the
                    # exit path (save_checkpoint canonicalizes pipeline
                    # layouts; every process participates in its collective
                    # gather, the primary writes). The SIGTERM handler is
                    # still ours here, so a follow-up SIGTERM during this
                    # save cannot kill the write; the atomic rename inside
                    # save_checkpoint protects against harder kills.
                    finite = True
                    if metrics is not None:
                        # a NaN/diverged state must not overwrite the
                        # previous good rescue checkpoint — save-exceptions
                        # were already caught, but bad VALUES were not
                        finite = bool(
                            np.isfinite(float(jax.device_get(metrics["loss"])))
                        )
                    if finite:
                        save_checkpoint(
                            last_ckpt_path, state, best_val_loss, cfg,
                            tokenizer_fingerprint=tok_fp,
                            consumed_windows=consumed_at(iter_num),
                        )
                    elif is_primary():
                        print(
                            f"skipping last-checkpoint rescue save: "
                            f"non-finite loss at iter {iter_num} (previous "
                            f"checkpoint at {last_ckpt_path!r} left intact)"
                        )
            except Exception as e:  # noqa: BLE001
                # on the crash path the state itself may be poisoned
                # (device OOM) — never let the rescue save mask the real
                # exception
                print(f"last-checkpoint save failed: {e!r}")
            try:
                if best_snapshot is not None and not skip_collective_rescue:
                    # flush the throttled best-state snapshot AFTER the
                    # resumable rescue save above — under a bounded
                    # preemption grace window the last-ckpt (what resume
                    # needs) must land first; the best flush is the
                    # nice-to-have. On the multi-process CRASH path this
                    # (like the rescue save) is skipped — a deferred
                    # improvement is then lost and best.ckpt stays at the
                    # last written state; that is the throttle's one
                    # divergence from write-every-improvement (the
                    # collective gather cannot run from an asymmetric
                    # crash, see skip_collective_rescue above).
                    if is_primary():
                        print(
                            f"writing pending best checkpoint "
                            f"(val loss {best_val_loss:.4f})"
                        )
                    save_checkpoint(
                        cfg.checkpoint_path, best_snapshot, best_val_loss,
                        cfg, tokenizer_fingerprint=tok_fp,
                        consumed_windows=consumed_at(best_snapshot_iter),
                    )
                    best_snapshot = None
            except Exception as e:  # noqa: BLE001
                print(f"pending best-checkpoint save failed: {e!r}")
        finally:
            # restore the caller's SIGTERM handler on EVERY exit path —
            # including a KeyboardInterrupt mid-rescue-save (BaseException
            # escapes the inner except-Exception blocks)
            if prev_handler is not None:
                signal.signal(signal.SIGTERM, prev_handler)
    if cfg.mesh.pipeline > 1:
        # return the canonical list-of-blocks layout, like every other
        # path, so callers (tools/ppl_gap.py-style eval, model_forward)
        # work regardless of the training topology
        from differential_transformer_replication_tpu.train.checkpoint import (
            canonicalize_state,
        )

        state = canonicalize_state(
            gather_to_host(state),
            cfg.resolved_model().n_layer,
        )
    return state

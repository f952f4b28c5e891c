"""Metric logging with pluggable sinks.

Replicates the reference's observability surface (train.py:286-304):
stdout prints in the same format, the same metric names and cadence
(``iter``/``loss``/``learning_rate``/``gpu_memory`` every log_interval;
``train_loss``/``val_loss`` every eval_interval), with sinks:
  - stdout (always),
  - JSONL append (replaces wandb as the durable record; always unless
    disabled),
  - wandb (optional, only if installed and enabled — the reference hard
    -requires it, train.py:15,151).

Beyond the reference surface:
  - every record carries ``ts`` (unix wall-clock seconds) so records
    are joinable across restarts and supervisor relaunches,
  - each logger writes a one-time ``run_header`` record (config hash,
    jax version, device kind, process count) identifying the process
    that produced the records that follow it — a resumed/relaunched run
    appends a new header, so ``tools/metrics_report.py`` can segment
    the stream by incarnation,
  - ``gpu_memory`` keeps the reference's key name for drop-in dashboard
    compatibility but reports the accelerator's allocated bytes in MB —
    and is OMITTED (not logged as a misleading 0.0) on platforms
    without memory stats (CPU),
  - :meth:`MetricLogger.log_record` appends arbitrary typed records
    (the obs/introspect.py lambda summaries ride this).
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from typing import Optional

import jax

from differential_transformer_replication_tpu.config import TrainConfig


def device_memory_mb() -> Optional[float]:
    """Allocated device memory in MB (the reference logs
    torch.cuda.memory_allocated/1024**2, train.py:293), or None when the
    platform exposes no memory stats (the CPU backend returns None) —
    callers must OMIT the metric rather than log a misleading zero."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats or "bytes_in_use" not in stats:
        return None
    return stats["bytes_in_use"] / 1024**2


def config_hash(cfg: TrainConfig) -> str:
    """Stable short hash of the full recipe — the run identity key in
    ``run_header`` records (two streams with the same hash are the same
    experiment, whatever host/restart produced them)."""
    blob = json.dumps(cfg.to_dict(), sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


class MetricLogger:
    def __init__(self, cfg: TrainConfig, run_config: Optional[dict] = None):
        self.cfg = cfg
        self._jsonl = None
        self._wandb = None
        # records arrive from the train loop AND from background
        # producers (the device-profile sampler's parse worker routes
        # its rows through log_record) — TextIOWrapper writes are not
        # thread-safe, and a torn mid-line interleave would silently
        # drop records at metrics_report's json.loads
        self._emit_lock = threading.Lock()
        # multi-host: only process 0 writes logs/files (every process
        # would otherwise duplicate records and race on the jsonl)
        self._primary = jax.process_index() == 0
        if not self._primary:
            return
        if cfg.metrics_path:
            self._jsonl = open(cfg.metrics_path, "a", buffering=1)
            self._write_run_header()
        if cfg.use_wandb:
            try:
                import wandb

                wandb.init(
                    project=cfg.wandb_project,
                    name=cfg.wandb_run_name,
                    config=run_config or cfg.to_dict(),  # train.py:151
                )
                self._wandb = wandb
            except Exception as e:
                print(f"[metrics] wandb unavailable ({type(e).__name__}); continuing without")

    def _write_run_header(self) -> None:
        """One identity record per logger lifetime (i.e. per process
        incarnation): joins records across supervisor relaunches. JSONL
        only — wandb carries the config natively via init."""
        header = {
            "record": "run_header",
            "ts": round(time.time(), 3),
            "config_hash": config_hash(self.cfg),
            "jax_version": jax.__version__,
            "device_kind": jax.local_devices()[0].device_kind,
            "device_count": jax.device_count(),
            "process_count": jax.process_count(),
            "model": self.cfg.resolved_model().model,
        }
        self._jsonl.write(json.dumps(header) + "\n")

    # sentinel: "the caller did not sample memory — query it here";
    # distinct from None, which means "sampled and unavailable"
    _QUERY_MEMORY = object()

    def log_step(
        self,
        iter_num: int,
        loss: float,
        lr: float,
        tokens_per_sec: Optional[float] = None,
        extra: Optional[dict] = None,
        gpu_memory_mb=_QUERY_MEMORY,
    ) -> None:
        """Per-log_interval metrics (train.py:286-294), plus the natively
        measured tokens/sec the reference never recorded (SURVEY.md
        section 5.1; BASELINE.json north-star metric). ``extra`` carries
        run-health fields — anomaly-guard skipped_steps/rollbacks, the
        obs layer's step_time_ms/data_wait_frac/compile_events
        (train/trainer.py) — into the same record. ``gpu_memory_mb``
        lets a caller that already sampled :func:`device_memory_mb`
        (the trainer does, for its watermark gauge) pass the SAME value
        instead of paying a second memory_stats query per log."""
        if not self._primary:
            return
        print(f"iter {iter_num}: loss {loss:.4f}, lr {lr:.2e}")  # train.py:288
        payload = {
            "iter": iter_num,
            "loss": loss,
            "learning_rate": lr,
        }
        mem = (
            device_memory_mb()
            if gpu_memory_mb is MetricLogger._QUERY_MEMORY else gpu_memory_mb
        )
        if mem is not None:  # omitted, never a fake 0.0
            payload["gpu_memory"] = mem
        if tokens_per_sec is not None:
            payload["tokens_per_sec"] = round(tokens_per_sec, 1)
        if extra:
            payload.update(extra)
        self._emit(payload)

    def log_eval(self, iter_num: int, train_loss: float, val_loss: float) -> None:
        """Per-eval_interval metrics (train.py:297-304)."""
        if not self._primary:
            return
        print(
            f"step {iter_num}: train loss {train_loss:.4f}, val loss {val_loss:.4f}"
        )  # train.py:299
        self._emit({"iter": iter_num, "train_loss": train_loss, "val_loss": val_loss})

    def log_record(self, payload: dict) -> None:
        """Append one arbitrary record (e.g. ``{"record":
        "introspection", ...}`` from obs/introspect.py). JSONL + wandb,
        primary process only, ``ts`` added like every other record."""
        if not self._primary:
            return
        self._emit(dict(payload))

    def _emit(self, payload: dict) -> None:
        payload.setdefault("ts", round(time.time(), 3))
        with self._emit_lock:
            if self._jsonl is not None:
                self._jsonl.write(json.dumps(payload) + "\n")
            if self._wandb is not None:
                self._wandb.log(payload)

    def finish(self) -> None:
        if self._jsonl is not None:
            self._jsonl.close()
        if self._wandb is not None:
            self._wandb.finish()  # train.py:325

"""Continuous on-device profiling: a sampled capture-window scheduler.

``tools/profile_step.py`` gives the device-side breakdown once, when a
human runs it. This module makes that lens CONTINUOUS: every
``every``-th step of a production loop (trainer iteration, serving
engine iteration) is wrapped in a ``jax.profiler`` trace to a rotating
spool directory, parsed OFF-LOOP on a daemon worker thread
(obs/xprof.py — stdlib, no jax on the worker), and published three
ways:

- **registry** (obs/registry.py): ``device_step_ms_bucket{bucket=}``
  gauges (the step-time decomposition — flash_attention / fused_ffn /
  decode_attention / collectives / rest), ``device_busy_ms``,
  ``device_mfu`` (when the caller supplied a FLOPs estimate), and
  ``device_profile_captures_total`` / ``_failures_total`` /
  ``_skipped_total`` counters — scraped from ``/metrics`` like every
  other gauge;
- **metrics.jsonl**: one ``{"record": "device_profile", ...}`` row per
  capture through the caller's sink (the trainer passes
  ``MetricLogger.log_record``) or an owned JSONL file (the serving
  engine spools ``<spool>/metrics.jsonl``) — the machine-readable
  trajectory ``tools/metrics_report.py`` summarizes and
  ``tools/perf_gate.py`` gates;
- **device trace lane**: ``<spool>/device-NNNN.trace.json``, a Chrome
  trace of the captured window's device ops, anchored to the host wall
  clock and join-keyed (``capture`` arg) to the ``device_capture``
  host span this sampler emits through the caller's SpanTracer — so
  ``tools/trace_stitch.py`` merges host + device into ONE Perfetto
  timeline, HTTP request down to Pallas kernel.

Scheduling contract (the hot-loop invariants):

- **Uncaptured steps cost a host-side integer compare.**
  :meth:`maybe_begin` on a non-due step is ``step % every`` plus a
  comparison — no allocation, no lock, no syscall (measured ~0.1 µs;
  pinned loosely by test).
- **Capture wraps an ALREADY-COMPILED step.** The sampler never
  captures the FIRST step it sees (a fresh run's step 0 and a resumed
  run's restored iterate both compile) and adds no device ops, so the
  compile count stays pinned at 1 with profiling enabled (tests hold
  this under ``RecompileSentinel`` for both the trainer step and the
  engine's decode; see ANALYSIS.md).
- **Back-pressure by deferral** (the ckpt_writer model adapted for a
  sampler): at most one parse job is in flight; a capture that comes
  due while the worker is still parsing the previous one is SKIPPED
  and counted (``device_profile_skipped_total``) — the spool can never
  grow faster than the worker drains it, and the loop never blocks on
  parsing.
- **Errors surfaced, never fatal.** A failed ``start_trace`` (e.g. a
  ``ProfilerWindow`` already owns the global profiler), a missing
  xplane, or a malformed proto increments the failure counter,
  publishes an ``{"error": ...}`` row, prints once — and the loop keeps
  stepping.
- **Drained on exit.** :meth:`close` rides the caller's exit closers
  (trainer finally-block, ``ServingEngine.close``): it stops any
  still-open window, finishes the queued parse, and joins the worker.

The END of a window blocks on ``sync`` (the step's loss scalar / a
cache leaf) before ``stop_trace`` so the captured step's device work is
actually inside the window — one extra device sync every ``every``
steps, amortized exactly like the trainer's log-boundary sync.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import sys
import threading
import time
from typing import Callable, Optional

from differential_transformer_replication_tpu.obs import xprof
from differential_transformer_replication_tpu.obs.registry import Registry
from differential_transformer_replication_tpu.obs.spans import (
    NOOP_TRACER,
    annotate_spans,
)

_BUCKET_NAMES = tuple(name for name, _ in xprof.KERNEL_BUCKETS) + ("rest",)


def _jax_start_trace(path: str) -> None:
    import jax

    jax.profiler.start_trace(path)


def _jax_stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def _jax_block(sync) -> None:
    import jax

    jax.block_until_ready(sync)


class DeviceProfileSampler:
    """See module docstring. Constructor knobs:

    ``every``            capture cadence in steps (> 0; the first step
                         seen never captures — it compiles),
    ``spool_dir``        rotating capture spool; each window lands in
                         ``cap-NNNN/`` and its parsed lane in
                         ``device-NNNN.trace.json``; only the newest
                         ``keep`` of each survive,
    ``registry``         metrics registry to publish into (an owned one
                         is created when omitted),
    ``sink``             callable given each ``device_profile`` record
                         (the trainer's ``MetricLogger.log_record``),
    ``jsonl_path``       JSONL file to append records to; ``"auto"`` =
                         ``<spool>/metrics.jsonl``; None = sink only,
    ``tracer``           obs/spans.py SpanTracer for the
                         ``device_capture`` host span (join key of the
                         stitched device lane); NOOP-safe,
    ``flops_per_step`` / ``hbm_bytes_per_step`` / ``peak_flops``
                         estimates feeding :func:`xprof.derived_metrics`
                         (``device_mfu``; the peak comes from
                         :func:`xprof.device_peaks` for the device the
                         loop runs on); None = those fields omitted,
    ``start_fn`` / ``stop_fn`` / ``block_fn``
                         the profiler seam — default to jax.profiler
                         (imported lazily, so scheduler tests run
                         jax-free with fakes).
    """

    def __init__(
        self,
        every: int,
        spool_dir: str,
        registry: Optional[Registry] = None,
        sink: Optional[Callable[[dict], None]] = None,
        jsonl_path: Optional[str] = "auto",
        tracer=None,
        process: str = "trainer",
        keep: int = 2,
        flops_per_step: Optional[float] = None,
        hbm_bytes_per_step: Optional[float] = None,
        peak_flops: Optional[float] = None,
        start_fn: Optional[Callable[[str], None]] = None,
        stop_fn: Optional[Callable[[], None]] = None,
        block_fn: Optional[Callable[[object], None]] = None,
    ) -> None:
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        self._every = int(every)
        self._spool = spool_dir
        self._sink = sink
        self.last_record = None  # newest emitted row (hang reports)
        self._tracer = tracer or NOOP_TRACER
        self._process = process
        self._keep = max(1, int(keep))
        self._flops = flops_per_step
        self._hbm = hbm_bytes_per_step
        self._peak = peak_flops
        self._start = start_fn or _jax_start_trace
        self._stop = stop_fn or _jax_stop_trace
        self._block = block_fn or _jax_block
        os.makedirs(spool_dir, exist_ok=True)
        self._jsonl = None
        if jsonl_path == "auto":
            jsonl_path = os.path.join(spool_dir, "metrics.jsonl")
        if jsonl_path:
            self._jsonl = open(jsonl_path, "a", buffering=1)
        # records are emitted from the loop thread (start failures) AND
        # the parse worker; serialize the sink/file writes
        self._emit_lock = threading.Lock()

        self.registry = registry or Registry()
        self._captures = self.registry.counter(
            "device_profile_captures_total",
            "Device profile windows captured, parsed and published.",
        )
        self._failures = self.registry.counter(
            "device_profile_failures_total",
            "Capture windows that failed (profiler busy, missing or "
            "malformed xplane); surfaced, never fatal to the loop.",
        )
        self._skipped = self.registry.counter(
            "device_profile_skipped_total",
            "Due captures skipped because the parse worker was still "
            "busy (back-pressure by deferral).",
        )
        self._mfu_gauge = self.registry.gauge(
            "device_mfu",
            "Model FLOPs utilization of the last captured step "
            "(caller's FLOPs estimate / device-busy time / peak).",
        )
        self._busy_gauge = self.registry.gauge(
            "device_busy_ms",
            "Device-busy milliseconds of the last captured step.",
        )
        self._bucket_gauge = self.registry.gauge(
            "device_step_ms_bucket",
            "Step-time decomposition of the last captured step "
            "(ms attributed to each kernel bucket; obs/xprof.py).",
            labelnames=("bucket",),
        )

        # capture-window state (loop thread only)
        self._seq = 0
        self._first_step: Optional[int] = None
        self._active = False
        self._t0 = 0.0
        self._t0_wall_us = 0.0
        self._cap_dir = ""
        self._cap_step = 0
        self._warned = False
        # one-deep parse pipeline (worker thread)
        self._q: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._idle = threading.Event()
        self._idle.set()
        self._closed = False
        self._thread = threading.Thread(
            target=self._worker, name="device-profile", daemon=True
        )
        self._thread.start()

    # -- loop-side API --------------------------------------------------

    def maybe_begin(self, step: int) -> bool:
        """Start a capture window when ``step`` is due and the worker
        is idle. The non-due path — every uncaptured step — is a couple
        of integer compares. The FIRST step this sampler ever sees is
        never captured, whatever its number: a fresh run's step 0 and a
        resumed trainer's restored iterate both trace+compile the
        jitted step, and a capture window around a compile is exactly
        the misleading profile this module exists to avoid."""
        if self._first_step is None:
            self._first_step = step
        if step % self._every != 0 or step == self._first_step:
            return False
        if self._active or self._closed:
            return False
        if not self._idle.is_set():
            # the previous window is still being parsed: defer (skip)
            # rather than queue — back-pressure, sampler-style
            self._skipped.inc()
            return False
        cap_dir = os.path.join(self._spool, f"cap-{self._seq:04d}")
        try:
            os.makedirs(cap_dir, exist_ok=True)
            self._start(cap_dir)
        except Exception as e:  # profiler busy (ProfilerWindow), IO, ...
            self._failures.inc()
            # the failure must reach the metrics stream, not just the
            # counter: a run whose EVERY capture fails to start (spool
            # unwritable, another profiler owns the global state) would
            # otherwise leave zero device_profile rows and a vacuously
            # green metrics_report --max-capture-failures gate
            self._emit({
                "record": "device_profile", "step": step,
                "process": self._process,
                "error": f"capture failed to start: {e!r}",
                "capture_failures": self.failures,
            })
            if not self._warned:
                self._warned = True
                print(f"[device_profile] capture failed to start "
                      f"(continuing, counted): {e!r}", file=sys.stderr)
            return False
        self._active = True
        # the loop's spans inside the window go into the capture too
        annotate_spans(self._tracer, True)
        self._cap_dir = cap_dir
        self._cap_step = step
        self._t0 = time.perf_counter()
        self._t0_wall_us = time.time() * 1e6
        return True

    def end(self, sync=None) -> None:
        """Close the window opened by :meth:`maybe_begin` and hand the
        trace to the worker. ``sync`` is blocked on first so the
        captured step's device work lands inside the window. The
        published record's ``step`` is the value given to
        :meth:`maybe_begin` (same as the host span's)."""
        if not self._active:
            return
        self._active = False
        annotate_spans(self._tracer, False)
        try:
            if sync is not None:
                self._block(sync)
        finally:
            try:
                self._stop()
            except Exception as e:
                self._failures.inc()
                print(f"[device_profile] stop_trace failed "
                      f"(continuing, counted): {e!r}", file=sys.stderr)
                return
        t1 = time.perf_counter()
        # the host span the stitched device lane aligns under; the
        # capture seq is the join key trace_stitch matches
        self._tracer.complete(
            "device_capture", self._t0, t1,
            capture=self._seq, step=self._cap_step,
        )
        self._idle.clear()
        self._q.put((
            self._seq, self._cap_dir, self._cap_step,
            self._t0_wall_us, (t1 - self._t0) * 1e3,
        ))
        self._seq += 1

    def abort(self) -> None:
        """Stop a window a CRASHED step left open (the trace is torn —
        dropped and counted); the next due step captures normally.
        Called by crash-recovery paths (ServingEngine.reset_after_crash)
        and :meth:`close`."""
        if not self._active:
            return
        self._active = False
        annotate_spans(self._tracer, False)
        self._failures.inc()
        try:
            self._stop()
        except Exception:
            pass

    def close(self, timeout: Optional[float] = 30.0) -> None:
        """Drain: abort any still-open window, finish the queued parse,
        stop the worker, close the JSONL sink. Idempotent; rides the
        caller's exit closers."""
        self.abort()
        if not self._closed:
            self._closed = True
            self._q.put(None)
        self._thread.join(timeout)
        alive = self._thread.is_alive()
        if self._jsonl is not None and not alive:
            self._jsonl.close()
            self._jsonl = None
        if alive:
            raise RuntimeError(
                f"device-profile worker did not drain within {timeout}s"
            )

    # convenience counters (tests / JSON lines)
    @property
    def captures(self) -> int:
        return int(self._captures.value)

    @property
    def failures(self) -> int:
        return int(self._failures.value)

    @property
    def skipped(self) -> int:
        return int(self._skipped.value)

    # -- worker side ----------------------------------------------------

    def _worker(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            try:
                self._parse_one(*job)
            except Exception as e:  # noqa: BLE001 — surfaced, not fatal
                self._failures.inc()
                print(f"[device_profile] parse failed "
                      f"(continuing, counted): {e!r}", file=sys.stderr)
            finally:
                self._idle.set()

    def _parse_one(self, seq: int, cap_dir: str, step: int,
                   t0_wall_us: float, window_ms: float) -> None:
        record = {
            "record": "device_profile",
            "capture": seq,
            "step": step,
            "process": self._process,
            "window_ms": round(window_ms, 3),
        }
        picked = xprof.load_trace_plane(cap_dir)
        summary = (
            picked if isinstance(picked, str)
            else xprof.summarize_plane(picked[0], picked[1], steps=1)
        )
        if isinstance(summary, str):
            self._failures.inc()
            record["error"] = summary
            record["capture_failures"] = self.failures
            self._emit(record)
            return
        plane, kind = picked
        trace_path = os.path.join(
            self._spool, f"device-{seq:04d}.trace.json"
        )
        xprof.write_chrome_trace(
            trace_path,
            xprof.plane_to_chrome_events(
                plane, pid=0, anchor_us=t0_wall_us, capture=seq
            ),
        )
        busy = summary["busy_ms_per_step"]
        derived = xprof.derived_metrics(
            busy, flops_per_step=self._flops,
            hbm_bytes_per_step=self._hbm, peak_flops=self._peak,
        )
        # publish: gauges first (scrapers), then the jsonl record
        self._busy_gauge.set(busy)
        for name in _BUCKET_NAMES:
            self._bucket_gauge.set(
                summary["bucket_ms"].get(name, 0.0), bucket=name
            )
        if "mfu" in derived:
            self._mfu_gauge.set(derived["mfu"])
        self._captures.inc()
        record.update({
            "busy_ms": round(busy, 4),
            "bucket_ms": {
                k: round(v, 4) for k, v in summary["bucket_ms"].items()
            },
            "plane": summary["plane"],
            "plane_kind": summary["plane_kind"],
            "trace_file": trace_path,
            "captures": self.captures,
            "capture_failures": self.failures,
        })
        record.update({k: round(v, 4) for k, v in derived.items()})
        self._emit(record)
        self._gc(seq)

    def _emit(self, record: dict) -> None:
        record.setdefault("ts", round(time.time(), 3))
        # last published row, kept for the watchdog's hang report
        # (train/watchdog.py): "what was the device doing the last
        # time we could see it" is the first post-mortem question.
        # Plain attribute swap — atomic under the GIL, read-only
        # consumers (the hang report) tolerate a stale value.
        self.last_record = record
        with self._emit_lock:
            if self._sink is not None:
                self._sink(record)
            if self._jsonl is not None:
                self._jsonl.write(json.dumps(record) + "\n")

    def _gc(self, newest_seq: int) -> None:
        """Rotate the spool: keep the newest ``keep`` capture dirs and
        device-lane traces, delete the rest (single writer: this
        thread)."""
        floor = newest_seq - self._keep + 1
        for name in os.listdir(self._spool):
            n = None
            if name.startswith("cap-"):
                n = name[4:]
            elif name.startswith("device-") and name.endswith(
                ".trace.json"
            ):
                n = name[7:-len(".trace.json")]
            if n is None or not n.isdigit() or int(n) >= floor:
                continue
            path = os.path.join(self._spool, name)
            if os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)
            else:
                try:
                    os.unlink(path)
                except OSError:
                    pass

"""Tracing and throughput counters.

The reference's only observability is loss prints and GPU-memory numbers
(train.py:148,288,293; SURVEY.md section 5.1) — it has no profiler
integration and never measures tokens/sec, even though that is the
north-star metric (BASELINE.json). Here both are native:

  - ``trace(logdir)`` wraps ``jax.profiler`` so any code region can be
    captured and viewed in TensorBoard/Perfetto (XLA op-level timeline,
    HBM usage, fusion boundaries),
  - ``ProfilerWindow`` captures a fixed window of training iterations —
    the trainer drives it from the hot loop,
  - ``Throughput`` computes rolling tokens/sec between metric logs; the
    trainer attaches it to every log_step record.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax

from differential_transformer_replication_tpu.obs.spans import annotate_spans


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a jax.profiler trace of the enclosed region into
    ``logdir`` (inspect with TensorBoard's profile plugin or Perfetto)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class ProfilerWindow:
    """Capture iterations [start, start+n) of a training loop.

    Handles the edge cases an inline start/stop pair gets wrong: resuming
    from a checkpoint past the window start (never calls stop without a
    matching start) and loops that end inside the window (``close()``
    finalizes the trace so it is never left running/unwritten).
    ``tracer`` (obs/spans.py): the loop's host spans inside the window
    are written into the capture too, on the device's clock.
    """

    def __init__(self, logdir: Optional[str], start: int, n_steps: int = 5,
                 tracer=None):
        self.logdir = logdir
        self.start = start
        self.stop = start + n_steps
        self.active = False
        self._tracer = tracer

    def step(self, iter_num: int, sync=None) -> None:
        """Call once per loop iteration with the post-increment iteration
        number; ``sync`` (any jax value) is blocked on before finalizing
        so the trace covers completed device work."""
        if not self.logdir:
            return
        if not self.active and iter_num == self.start:
            jax.profiler.start_trace(self.logdir)
            annotate_spans(self._tracer, True)
            self.active = True
        elif self.active and iter_num >= self.stop:
            self._finalize(sync)

    def close(self, sync=None) -> None:
        """Finalize if the loop ended while the window was open."""
        if self.active:
            self._finalize(sync)

    def _finalize(self, sync) -> None:
        if sync is not None:
            jax.block_until_ready(sync)
        annotate_spans(self._tracer, False)
        jax.profiler.stop_trace()
        self.active = False
        print(f"Profiler trace written to {self.logdir}")


class Throughput:
    """Rolling tokens/sec between ``update`` calls.

    ``update(total_tokens)`` takes the cumulative token count and returns
    the rate since the previous call (None on the first call, when there
    is no interval yet). Wall-clock based, so it reflects everything the
    user waits for: device compute, host input pipeline, and dispatch.
    (bench.py's headline number is measured separately over an explicitly
    synced loop — this class is the trainer's rolling in-run view.)
    """

    def __init__(self) -> None:
        self._last_t: Optional[float] = None
        self._last_tokens = 0

    def update(self, total_tokens: int) -> Optional[float]:
        now = time.perf_counter()
        rate = None
        if self._last_t is not None and now > self._last_t:
            rate = (total_tokens - self._last_tokens) / (now - self._last_t)
        self._last_t = now
        self._last_tokens = total_tokens
        return rate

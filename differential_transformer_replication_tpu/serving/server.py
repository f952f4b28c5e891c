"""Minimal serving front-ends over the continuous-batching engine.

Three layers, smallest useful surface each:

- :class:`EngineRunner` — a background thread that owns a
  :class:`ServingEngine` (which is not thread-safe) and drains it:
  concurrent callers enqueue requests through a lock, the loop moves
  them into the engine and steps until idle, then parks on a condition
  variable. This is the concurrency boundary — everything device-side
  stays single-threaded. It is also the SUPERVISOR (the serving-side
  analog of tools/train_supervisor.py): a crashed engine step fails the
  in-flight requests with a typed, retriable
  :class:`~.engine.EngineCrashError`, rebuilds the slot pool from
  params after a bounded exponential backoff, and keeps serving — wait-
  queue entries survive the restart verbatim. A wall-time watchdog
  flags iterations that exceed ``ServingConfig.step_time_budget_s``;
  :meth:`EngineRunner.status` reports
  ``healthy | degraded | restarting | draining | failed``.
- :class:`ServingClient` — the programmatic client tests and the bench
  use: blocking ``generate()`` per caller thread, n callers = n
  concurrent streams batched by the engine. Runs fully in-process under
  ``JAX_PLATFORMS=cpu``.
- :func:`serve` / ``python -m ...serving.server`` — a stdlib
  ``http.server`` JSON endpoint (no new dependencies): POST /generate
  with ``{"prompt_ids": [...]}`` (or ``{"prompt": "text"}`` when a
  tokenizer dir is given), GET /health for engine state + stats, GET
  /ready for load-balancer admission (503 + Retry-After while draining
  or restarting). SIGTERM triggers a graceful drain: admission stops
  (503 + Retry-After), in-flight requests finish within
  ``ServingConfig.drain_timeout_s``, then the process exits.

Live migration (serving/migrate.py) rides four extra endpoints: the
router drains a replica by enumerating ``GET /inflight`` and POSTing
``/migrate/export {request_id, dest, migrate_id}`` per active request —
the source then probes the destination's radix tree (``/migrate/probe``,
dedup), exports the slot's checksummed wire image, lands it with
``POST dest /migrate/import``, releases the slot, and answers the
original blocked ``/generate`` with ``200 {"code": "migrated"}`` so the
router re-issues ``POST dest /migrate/await {migrate_id}`` and returns
the COMPLETE token list from the peer. Only the device touches (export
snapshot, release) run as engine-thread commands between steps; the
network legs (probe, transfer) stay on the HTTP handler thread, so a
slow destination never stalls co-resident decodes — the slot keeps
decoding between snapshot and release, and the destination regenerates
any post-snapshot tokens bit-exactly (the key chain is pure in ``t``).
"""

from __future__ import annotations

import json
import sys
import threading
import time
from collections import OrderedDict, deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional, Sequence

from differential_transformer_replication_tpu.obs.events import (
    NOOP_EVENTS,
)
from differential_transformer_replication_tpu.obs.registry import (
    CONTENT_TYPE as METRICS_CONTENT_TYPE,
)
from differential_transformer_replication_tpu.obs.spans import NOOP_TRACER
from differential_transformer_replication_tpu.obs.trace import (
    from_payload as trace_from_payload,
)
from differential_transformer_replication_tpu.serving.constrain import (
    ConstraintCompileError,
    ConstraintDeadEndError,
)
from differential_transformer_replication_tpu.serving.engine import (
    EngineCrashError,
    ServingEngine,
)
from differential_transformer_replication_tpu.serving.migrate import (
    MigrateExportError,
    MigratePayloadError,
    from_wire,
    to_wire,
)
from differential_transformer_replication_tpu.serving.pages import (
    PagePoolExhaustedError,
)
from differential_transformer_replication_tpu.serving.request import (
    RequestOutput,
    SamplingParams,
)
from differential_transformer_replication_tpu.serving.retry import (
    http_post_json_with_retries,
)
from differential_transformer_replication_tpu.serving.scheduler import (
    DeadlineExceededError,
    QueueFullError,
)
from differential_transformer_replication_tpu.utils.device import (
    device_summary,
    peak_memory_bytes,
    setup_compile_cache,
)


class ShuttingDownError(RuntimeError):
    """Admission refused: the server is draining (or already stopped).
    Retriable — against ANOTHER replica; HTTP maps it to 503 with a
    Retry-After so load balancers take the instance out of rotation."""

    retriable = True


class MigratedError(RuntimeError):
    """Settle marker, not a failure: this request's live decode state
    moved to a peer replica mid-flight (serving/migrate.py). The HTTP
    handler maps it to 200 ``{"code": "migrated", "dest", "migrate_id"}``
    so the router follows with ``POST dest /migrate/await`` and returns
    the peer's COMPLETE continuation to the caller."""

    def __init__(self, dest: str, migrate_id: str):
        super().__init__(f"request migrated to {dest}")
        self.dest = dest
        self.migrate_id = migrate_id


def _inc_stat(stats, key: str) -> None:
    """Bump one engine stat from outside the engine thread. Real engines
    carry a StatsMap whose ``inc`` is atomic (obs/registry.py); test
    doubles with plain dicts fall back to ``+=`` (their callers hold the
    runner lock, so the read-modify-write cannot tear)."""
    inc = getattr(stats, "inc", None)
    if inc is not None:
        inc(key)
    else:
        stats[key] += 1


class _Pending:
    """One submitted request's handle across the thread boundary."""

    __slots__ = ("prompt", "params", "deadline", "trace", "done",
                 "result", "error", "rid", "cancelled", "settled",
                 "journal_id")

    def __init__(self, prompt, params, deadline=None, trace=None,
                 journal_id=None):
        self.prompt = prompt
        self.params = params
        self.deadline = deadline  # absolute perf_counter ts, or None
        self.trace = trace        # TraceContext (obs/trace.py) or None
        self.done = threading.Event()
        self.result: Optional[RequestOutput] = None
        self.error: Optional[BaseException] = None
        self.rid: Optional[int] = None  # set once the engine admits it
        self.cancelled = False
        self.settled = False  # exactly-once delivery (drain accounting)
        # the router's replay-journal handle (serving/migrate.py):
        # echoed in GET /inflight so harvested token prefixes land in
        # the right journal entry
        self.journal_id = journal_id


class EngineRunner:
    """Owns + supervises the engine on a background thread; see module
    docstring. Supervision knobs come from the engine's
    ``ServingConfig``: ``max_restarts`` / ``restart_backoff_s`` /
    ``restart_backoff_max_s`` (crash recovery), ``step_time_budget_s``
    (watchdog), ``drain_timeout_s`` (graceful drain)."""

    def __init__(self, engine: ServingEngine):
        self.engine = engine
        serving = engine.serving
        self.max_restarts = serving.max_restarts
        self._backoff_base = serving.restart_backoff_s
        self._backoff_max = serving.restart_backoff_max_s
        self._step_budget = serving.step_time_budget_s
        self._cond = threading.Condition()
        self._incoming: deque = deque()  # _Pending not yet in the engine
        self._cancels: deque = deque()  # _Pending to cancel in the engine
        # engine-thread command queue (serving/migrate.py): migration
        # export/import thunks run here between steps, so a decode
        # iteration can never interleave with a half-exported slot
        self._commands: deque = deque()
        self._waiters: dict = {}  # request_id -> _Pending (engine thread)
        self._inflight: list = []  # last step's progress snapshot
        # migrate_id -> _Pending for imported requests; /migrate/await
        # blocks on these. Bounded: settled entries evict oldest-first.
        self._migrated: "OrderedDict[str, _Pending]" = OrderedDict()
        self._migrated_cap = 256
        self._stop = False
        self._abort = False  # drain budget blown: fail leftovers, exit
        self._draining = False
        self._failed = False  # restart budget exhausted
        self._restarting = False
        self._degraded = False  # last completed step blew the budget
        self._open = 0  # unsettled pendings (drain accounting)
        self.restarts = 0
        self._step_started: Optional[float] = None
        self.last_step_s: Optional[float] = None
        self._thread = threading.Thread(
            target=self._loop, name="serving-engine", daemon=True
        )
        self._thread.start()

    # -- observability -------------------------------------------------

    def status(self) -> str:
        """``healthy | degraded | restarting | draining | failed`` —
        what /health reports and /ready keys off. "degraded" covers
        both a completed iteration that blew ``step_time_budget_s`` and
        an iteration currently running past it (a hung device call
        cannot be interrupted, but it CAN be reported while stuck)."""
        now = time.perf_counter()
        with self._cond:
            if self._failed:
                return "failed"
            if self._draining or self._stop:
                return "draining"
            if self._restarting:
                return "restarting"
            started = self._step_started
            overrunning = (
                self._step_budget > 0 and started is not None
                and now - started > self._step_budget
            )
            if self._degraded or overrunning:
                return "degraded"
            return "healthy"

    def accepting(self) -> bool:
        """The /ready contract: route traffic here? False while
        draining/failed (submits are refused) AND while restarting
        (submits are accepted — they queue behind the rebuild — but a
        load balancer with other replicas should prefer them)."""
        return self.status() in ("healthy", "degraded")

    def stats_snapshot(self) -> dict:
        """Point-in-time engine stats for /health. Taken under the
        runner lock AND through StatsMap.snapshot (per-counter locks),
        so a snapshot never reads a counter mid-update from the engine
        thread — the old ``dict(engine.stats)`` shallow copy could.
        Plain-dict test doubles degrade to a locked dict() copy."""
        with self._cond:
            stats = self.engine.stats
            snap = getattr(stats, "snapshot", None)
            return snap() if snap is not None else dict(stats)

    # -- submission ----------------------------------------------------

    def submit(self, prompt: Sequence[int],
               params: Optional[SamplingParams] = None,
               deadline_s: Optional[float] = None,
               trace=None, journal_id=None, **kw) -> _Pending:
        """Thread-safe enqueue; returns the request's :class:`_Pending`
        handle. Raises :class:`QueueFullError` IMMEDIATELY when the
        admission bound (ServingConfig.max_queue_len) is hit — counting
        both the engine's wait queue and requests still in this runner's
        hand-off deque — so overload degrades into fast rejections the
        caller can act on; raises :class:`ShuttingDownError` while
        draining/closed. ``deadline_s`` is a server-side budget in
        seconds from now; the engine stops working on the request once
        it expires (the caller gets :class:`DeadlineExceededError`).
        ``trace`` is the request's cross-process TraceContext
        (obs/trace.py), forwarded to the engine for span stamping.
        Submissions during a supervised engine restart are accepted —
        they queue and run once the rebuilt engine is up."""
        params = params or SamplingParams(**kw)
        deadline = (
            time.perf_counter() + deadline_s
            if deadline_s is not None else None
        )
        pending = _Pending(list(prompt), params, deadline, trace,
                           journal_id=journal_id)
        with self._cond:
            if self._failed:
                err = EngineCrashError(
                    f"engine restart budget exhausted "
                    f"({self.max_restarts}); runner is dead"
                )
                # the class default says retriable, but THIS runner can
                # never recover — retry clients must fail over, not wait
                err.retriable = False
                raise err
            if self._draining or self._stop:
                raise ShuttingDownError(
                    "server is draining; retry against another replica"
                )
            maxq = self.engine.serving.max_queue_len
            # cancelled-but-undrained pendings no longer occupy the wait
            # queue they are counted against — a burst of client
            # timeouts must not cause spurious 503s for the next caller
            waiting = sum(1 for p in self._incoming if not p.cancelled)
            if maxq and waiting + self.engine.queue_len() >= maxq:
                _inc_stat(self.engine.stats, "rejected")
                raise QueueFullError(
                    f"admission queue full ({maxq} waiting); retry later"
                )
            self._incoming.append(pending)
            self._open += 1
            self._cond.notify()
        return pending

    def cancel(self, pending: _Pending) -> None:
        """Abandon a request: if still in the hand-off deque it is
        dropped before ever reaching the engine; if already admitted,
        the engine reclaims its queue entry / KV slot on the next loop
        pass (serving/engine.py:cancel). Safe to call concurrently with
        completion — a request that finished first just ignores it."""
        with self._cond:
            pending.cancelled = True
            self._cancels.append(pending)
            self._cond.notify()

    def generate(self, prompt: Sequence[int],
                 params: Optional[SamplingParams] = None,
                 timeout: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 trace=None, journal_id=None, **kw) -> RequestOutput:
        pending = self.submit(prompt, params, deadline_s=deadline_s,
                              trace=trace, journal_id=journal_id, **kw)
        if not pending.done.wait(timeout):
            # reclaim the engine-side resources before giving up — the
            # old behavior decoded to completion for nobody, pinning a
            # KV slot other callers were queued for
            self.cancel(pending)
            raise TimeoutError("generation timed out")
        if pending.error is not None:
            raise pending.error
        return pending.result

    # -- live migration (serving/migrate.py) ---------------------------

    def run_on_engine(self, fn, timeout: float = 30.0):
        """Run ``fn()`` ON the engine thread between steps and return
        its result (or re-raise its exception) to the calling thread.
        The engine is single-threaded by contract — this is the only
        sanctioned way for an HTTP handler to touch engine state.
        Accepted while draining (drain-time migration IS the point),
        refused once the runner is stopped or failed."""
        done = threading.Event()
        box: dict = {}

        def thunk():
            try:
                box["result"] = fn()
            except BaseException as e:
                box["error"] = e
            finally:
                done.set()

        with self._cond:
            if self._failed or self._stop:
                raise ShuttingDownError(
                    "runner is stopped; no engine thread to run on"
                )
            self._commands.append(thunk)
            self._cond.notify()
        if not done.wait(timeout):
            raise TimeoutError(
                f"engine command did not complete within {timeout}s"
            )
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def migrate_out(self, request_id: int, dest_url: str,
                    migrate_id: str, budget_s: float = 10.0) -> dict:
        """Migrate one in-flight request's live decode state to a peer
        replica: probe the destination's radix tree (dedup), export the
        slot's checksummed wire image, POST it to ``dest/migrate/import``
        under the transfer budget, then release the local slot and
        settle its waiter with :class:`MigratedError` — the blocked
        /generate handler answers 200 ``{"code": "migrated"}`` and the
        router awaits the peer.

        Only the device touches (export snapshot, release) run as
        engine-thread commands; the NETWORK legs (probe, transfer) run
        on the calling HTTP-handler thread. A slow or unreachable
        destination therefore costs the migrating request its budget —
        never the co-resident in-flight decodes, which keep stepping
        throughout. The slot also keeps decoding between snapshot and
        release; any tokens it emits past the snapshot are regenerated
        bit-exactly at the destination (the fold_in key chain is a pure
        function of ``t``), so a stale image is never a wrong image.
        Raises :class:`MigrateExportError` (typed ``code``) when any
        rung fails — the caller's fallback is replay."""
        budget = max(0.1, float(budget_s))
        deadline = time.monotonic() + budget

        def read_prompt():
            if self._waiters.get(request_id) is None:
                return None
            slot = self.engine._slot_for(request_id)
            return (
                [int(t) for t in slot.prompt]
                if slot is not None else []
            )

        prompt = self.run_on_engine(read_prompt)
        if prompt is None:
            # finished (or never admitted here): its /generate already
            # answered with the real result — nothing to move
            return {"outcome": "finished"}

        cached = 0
        if prompt:
            try:
                status, body, _ = http_post_json_with_retries(
                    dest_url + "/migrate/probe",
                    {"prompt_ids": prompt},
                    timeout=min(5.0, budget), max_retries=0,
                    deadline_s=max(0.1, deadline - time.monotonic()),
                )
                if status == 200:
                    cached = int(body.get("cached_pages", 0) or 0)
            except Exception:
                cached = 0  # probe is best-effort: dedup off

        def export():
            if self._waiters.get(request_id) is None:
                return None
            return self.engine.export_slot_state(
                request_id, dedup_pages=cached
            )

        blob = self.run_on_engine(export)
        if blob is None:
            return {"outcome": "finished"}

        status, body, _ = http_post_json_with_retries(
            dest_url + "/migrate/import",
            {"state": to_wire(blob), "migrate_id": migrate_id},
            timeout=max(0.1, deadline - time.monotonic()),
            max_retries=2,
            deadline_s=max(0.1, deadline - time.monotonic()),
        )
        if status != 200:
            code = body.get("code") if isinstance(body, dict) else None
            _inc_stat(self.engine.stats, "migrate_failed")
            raise MigrateExportError(
                f"destination import failed (status {status}, "
                f"code {code})", code="migrate_transfer",
            )

        def release():
            pending = self._waiters.get(request_id)
            if pending is None or pending.settled:
                # finished locally during the transfer: the real result
                # already answered the client; the imported copy decodes
                # the same tokens at dest and idles in its bounded
                # _migrated LRU until evicted
                return {"outcome": "finished"}
            self.engine.release_migrated(request_id)
            self._waiters.pop(request_id, None)
            self._settle(
                pending, error=MigratedError(dest_url, migrate_id)
            )
            return {
                "outcome": "migrated",
                "bytes": len(blob),
                "dedup_pages": cached,
                "dest": dest_url,
                "migrate_id": migrate_id,
            }

        return self.run_on_engine(release)

    def import_state(self, blob: bytes, migrate_id: str,
                     timeout: float = 30.0) -> int:
        """Land a migrated slot here: decode + CRC-verify the wire
        image, re-admit it through the zero-recompile swap-in path
        (serving/engine.py:import_state), and register a synthetic
        waiter under ``migrate_id`` for ``/migrate/await``. Runs on the
        engine thread. Raises :class:`MigratePayloadError` on a
        convicted transfer (never garbage KV), typed admission errors
        (QueueFullError, PagePoolExhaustedError) when full."""
        with self._cond:
            if self._draining or self._stop or self._failed:
                raise ShuttingDownError(
                    "replica is draining; migrate elsewhere"
                )

        def thunk():
            rid = self.engine.import_state(blob)
            pending = _Pending([], None)
            pending.rid = rid
            self._waiters[rid] = pending
            with self._cond:
                self._open += 1
                self._migrated[migrate_id] = pending
                while len(self._migrated) > self._migrated_cap:
                    oldest = next(iter(self._migrated))
                    if not self._migrated[oldest].settled:
                        break  # never drop a live import
                    self._migrated.popitem(last=False)
            return rid

        return self.run_on_engine(thunk, timeout=timeout)

    def migrated_pending(self, migrate_id: str) -> Optional[_Pending]:
        with self._cond:
            return self._migrated.get(migrate_id)

    def inflight_snapshot(self) -> list:
        """The last completed step's per-request progress (request_id,
        prompt_len, emitted tokens so far, journal_id when the router
        supplied one). Read lock-free by the router's probe loop into
        its ReplayJournal — a stale snapshot only means a few tokens
        get re-generated bit-exactly on replay."""
        with self._cond:
            return list(self._inflight)

    # -- shutdown ------------------------------------------------------

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: stop admission (new submits raise
        :class:`ShuttingDownError` -> HTTP 503 + Retry-After), wait for
        every accepted request to settle within the drain budget
        (``ServingConfig.drain_timeout_s`` unless overridden), then
        close the runner. Returns True when everything in flight
        completed; False when the budget expired and the stragglers
        were failed with :class:`ShuttingDownError`."""
        budget = (
            self.engine.serving.drain_timeout_s
            if timeout is None else timeout
        )
        end = time.monotonic() + budget
        with self._cond:
            self._draining = True
            self._cond.notify_all()
            while (
                (self._open > 0 or self._incoming
                 or self.engine.has_work())
                and self._thread.is_alive()
            ):
                left = end - time.monotonic()
                if left <= 0:
                    break
                self._cond.wait(min(left, 0.1))
            drained = (
                self._open == 0 and not self._incoming
                and not self.engine.has_work()
            )
            if not drained:
                # budget blown: the loop fails leftovers on its next
                # pass and exits — nobody is left hanging
                self._abort = True
                self._cond.notify_all()
        self.close()
        return drained

    def close(self, timeout: float = 30.0) -> None:
        """Stop the loop (after it finishes in-engine work) and join
        the thread. Raises RuntimeError when the thread does not stop
        within ``timeout`` — a stuck device call means engine state is
        untrusted, and silently leaking the thread (the old behavior)
        hid exactly the wedged-server condition operators must see."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout)
        if self._thread.is_alive():
            with self._cond:
                # a wedged engine is a FAILED runner, not a routine
                # drain — /health must say so for as long as it answers
                self._failed = True
            raise RuntimeError(
                f"serving-engine thread failed to stop within {timeout}s "
                "(stuck in an engine step?); leaking the thread — engine "
                "state is untrusted, do not reuse this runner"
            )
        # engine-side host resources (the device-profile sampler's
        # parse worker) drain only after the loop thread is down — the
        # engine is single-threaded by contract. getattr: test doubles
        # keep their narrow surface.
        engine_close = getattr(self.engine, "close", None)
        if engine_close is not None:
            engine_close()

    # -- internals -----------------------------------------------------

    def _settle(self, pending: _Pending, result=None, error=None) -> bool:
        """Exactly-once delivery + drain accounting. Cancelled requests
        are settled too (their caller already unwound; the bookkeeping
        must not wait on them forever)."""
        with self._cond:
            if pending.settled:
                return False
            pending.settled = True
            pending.result = result
            pending.error = error
            self._open -= 1
            self._cond.notify_all()
        pending.done.set()
        return True

    def _deliver(self, outs, waiters: dict) -> None:
        """Settle finished engine outputs with their waiters (normal
        completion or a typed deadline error)."""
        for out in outs:
            pending = waiters.pop(out.request_id, None)
            if pending is None:
                continue
            if out.finish_reason == "deadline":
                self._settle(pending, error=DeadlineExceededError(
                    f"request {out.request_id} exceeded its "
                    f"server-side deadline after {len(out.tokens)} "
                    "generated tokens", output=out,
                ))
            elif out.finish_reason == "page_exhausted":
                err = PagePoolExhaustedError(
                    f"request {out.request_id} shed at admission: KV "
                    "page pool exhausted; retry later"
                )
                err.output = out
                if out.retry_after is not None:
                    # drain-rate-derived backoff hint (PagePool.
                    # estimated_drain_s): serving/retry.py uses it as
                    # the backoff floor and the HTTP 503 echoes it in
                    # Retry-After, so clients wait for actual pool
                    # drain time instead of a static guess
                    err.retry_after = out.retry_after
                self._settle(pending, error=err)
            elif out.finish_reason == "constraint_dead_end":
                # typed retriable failure with the partial output
                # attached — the HTTP layer maps it to 400
                # "constraint_dead_end" (serving/constrain.py)
                self._settle(pending, error=ConstraintDeadEndError(
                    f"request {out.request_id} hit a constraint dead "
                    f"end after {len(out.tokens)} generated tokens",
                    output=out,
                ))
            else:
                self._settle(pending, result=out)

    def _handle_engine_crash(self, exc: BaseException, waiters: dict) -> bool:
        """Supervised recovery from a failed engine step. Returns True
        when the loop should continue on the rebuilt engine, False when
        it must exit (restart budget exhausted, or the engine cannot be
        rebuilt). Mirrors tools/train_supervisor.py: typed failure,
        bounded exponential backoff, restart budget."""
        if isinstance(exc, EngineCrashError):
            crash = exc
        else:
            crash = EngineCrashError(f"engine step failed: {exc!r}")
            crash.__cause__ = exc
        # requests that finished EARLIER in the crashed step were
        # already retired from the scheduler — deliver them now, or
        # they are reachable from nowhere (not lost, not queued) and
        # their callers hang, the exact failure this layer removes
        take = getattr(self.engine, "take_finished", None)
        if take is not None:
            self._deliver(take(), waiters)
        with self._cond:
            # /health reads restarts from HTTP handler threads; publish
            # the bump under the runner lock like every other state bit
            self.restarts += 1
        rebuild = getattr(self.engine, "reset_after_crash", None)
        fatal = rebuild is None or self.restarts > self.max_restarts
        lost: List[int] = []
        if not fatal:
            with self._cond:
                self._restarting = True
            try:
                # fresh slot pool from params; wait-queue entries
                # survive verbatim (same rids -> same waiters)
                lost = rebuild()
            except Exception as e:  # cannot rebuild: give up
                print(f"[serving] engine rebuild failed: {e!r}",
                      file=sys.stderr)
                fatal = True
        if fatal:
            crash.retriable = False  # no restart is coming
            with self._cond:
                self._failed = True
                self._stop = True
                incoming = list(self._incoming)
                self._incoming.clear()
                self._restarting = False
            for p in list(waiters.values()):
                self._settle(p, error=crash)
            waiters.clear()
            for p in incoming:
                self._settle(p, error=crash)
            print(
                f"[serving] engine crashed ({exc!r}); restart budget "
                f"exhausted ({self.max_restarts}) — runner failed",
                file=sys.stderr,
            )
            return False
        # in-flight requests lost device state: fail them typed; queued
        # ones ride through the restart untouched
        for rid in lost:
            p = waiters.pop(rid, None)
            if p is not None:
                self._settle(p, error=crash)
        delay = min(
            self._backoff_base * (2 ** (self.restarts - 1)),
            self._backoff_max,
        )
        print(
            f"[serving] engine crashed ({exc!r}); slot pool rebuilt, "
            f"restart {self.restarts}/{self.max_restarts}, resuming in "
            f"{delay:.2f}s ({len(lost)} in-flight failed, "
            f"{self.engine.queue_len()} queued preserved)",
            file=sys.stderr,
        )
        end = time.monotonic() + delay
        while time.monotonic() < end:
            with self._cond:
                if self._stop or self._abort:
                    break
            time.sleep(min(0.05, max(0.0, end - time.monotonic())))
        with self._cond:
            self._restarting = False
        return True

    def _intake(self, cancels, incoming, commands, waiters: dict) -> int:
        """What the loop took from the callers since its last look:
        cancellations, new requests (each goes to ``engine.submit``),
        and engine-thread commands. Returns the requests it handed
        ``engine.submit``."""
        handed = 0
        for pending in cancels:
            if pending.rid is not None:
                if self.engine.cancel(pending.rid):
                    w = waiters.pop(pending.rid, None)
                    if w is not None:
                        self._settle(w, error=TimeoutError("cancelled"))
            # rid None: either still in `incoming` (settled below) or
            # it finished before the cancel landed — nothing to undo
        for pending in incoming:
            if pending.cancelled:
                self._settle(
                    pending,
                    error=TimeoutError("cancelled before admission"),
                )
                continue
            try:
                # optional kwargs passed only when set, so plain
                # test-double engines keep their narrow signatures
                opt = {}
                if pending.deadline is not None:
                    opt["deadline"] = pending.deadline
                if pending.trace is not None:
                    opt["trace"] = pending.trace
                handed += 1
                pending.rid = self.engine.submit(
                    pending.prompt, params=pending.params, **opt
                )
                waiters[pending.rid] = pending
            except Exception as e:  # invalid request: fail the caller
                self._settle(pending, error=e)
        for thunk in commands:
            # migration export/import thunks (run_on_engine): each
            # captures its own exception and signals its caller
            thunk()
        return handed

    def _loop(self) -> None:
        waiters = self._waiters  # request_id -> _Pending (this thread's)
        # the loop's own host time goes to the engine's tracer, around
        # the engine's spans: ``intake`` before an iteration, ``deliver``
        # after it; the wait for work is inside neither. getattr: test
        # doubles keep their narrow surface.
        tracer = getattr(self.engine, "tracer", NOOP_TRACER)
        while True:
            with self._cond:
                while (
                    not self._incoming
                    and not self._cancels
                    and not self._commands
                    and not self.engine.has_work()
                    and not self._abort
                ):
                    if self._stop:
                        return
                    self._cond.wait()
                incoming = list(self._incoming)
                self._incoming.clear()
                cancels = list(self._cancels)
                self._cancels.clear()
                commands = list(self._commands)
                self._commands.clear()
                stopping = self._stop
                aborting = self._abort
            if aborting:
                err = ShuttingDownError(
                    "server shut down before completing this request "
                    "(drain budget expired)"
                )
                for p in list(waiters.values()):
                    self._settle(p, error=err)
                for p in incoming:
                    self._settle(p, error=err)
                return
            # a span's keyword args are copied when it is made, so the
            # count rides in a dict the span keeps and is filled in
            # before the span closes (as the ``decode`` span's ``moe``)
            submitted = {"requests": 0}
            with tracer.span("intake", submitted=submitted):
                submitted["requests"] = self._intake(
                    cancels, incoming, commands, waiters)
            try:
                t0 = time.perf_counter()
                # the watchdog state is read by status() from HTTP
                # handler threads — publish every transition under the
                # runner lock (the engine step itself runs unlocked)
                with self._cond:
                    self._step_started = t0
                outs = self.engine.step()
                dt = time.perf_counter() - t0
                announce_degraded = False
                with self._cond:
                    self._step_started = None
                    self.last_step_s = dt
                    if self._step_budget > 0:
                        if dt > self._step_budget and not self._degraded:
                            self._degraded = True
                            announce_degraded = True
                        elif dt <= self._step_budget and self._degraded:
                            self._degraded = False
                if announce_degraded:
                    print(
                        f"[serving] watchdog: engine iteration took "
                        f"{dt:.3f}s (budget {self._step_budget}s) — "
                        "marking degraded", file=sys.stderr,
                    )
            except Exception as e:
                with self._cond:
                    self._step_started = None
                if not self._handle_engine_crash(e, waiters):
                    return
                continue
            with tracer.span("deliver"):
                self._deliver(outs, waiters)
                progress = getattr(self.engine, "progress_snapshot", None)
                if progress is not None:
                    entries = progress()
                    for ent in entries:
                        p = waiters.get(ent.get("request_id"))
                        if p is not None and p.journal_id is not None:
                            ent["journal_id"] = p.journal_id
                    with self._cond:
                        self._inflight = entries
            if stopping and not self.engine.has_work():
                return


class ServingClient:
    """In-process client: one engine, blocking calls from any thread."""

    def __init__(self, engine: ServingEngine):
        self.runner = EngineRunner(engine)

    def generate(self, prompt: Sequence[int],
                 params: Optional[SamplingParams] = None,
                 timeout: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 trace=None, journal_id=None, **kw) -> RequestOutput:
        return self.runner.generate(
            prompt, params, timeout=timeout, deadline_s=deadline_s,
            trace=trace, journal_id=journal_id, **kw
        )

    def generate_batch(self, prompts: Sequence[Sequence[int]],
                       params: Optional[Sequence[SamplingParams]] = None,
                       timeout: Optional[float] = None,
                       **kw) -> List[RequestOutput]:
        """Submit all prompts, then wait — batched by the engine. A
        timeout cancels every still-unfinished request in the batch
        before raising (no orphaned decodes)."""
        shared = SamplingParams(**kw) if params is None else None
        handles = []
        try:
            for i, p in enumerate(prompts):
                handles.append(
                    self.runner.submit(p, shared if shared else params[i])
                )
        except Exception:
            # a mid-batch rejection (QueueFullError, closed runner) must
            # not orphan the prompts already accepted
            for h in handles:
                if not h.done.is_set():
                    self.runner.cancel(h)
            raise
        outs = []
        for pending in handles:
            ok = pending.done.wait(timeout)
            if not ok or pending.error is not None:
                # timeout OR one request failing: reclaim every still-
                # running sibling before raising — nothing may keep
                # decoding for a caller that is about to unwind
                for h in handles:
                    if not h.done.is_set():
                        self.runner.cancel(h)
                if not ok:
                    raise TimeoutError("generation timed out")
                raise pending.error
            outs.append(pending.result)
        return outs

    @property
    def stats(self) -> dict:
        return self.runner.stats_snapshot()

    @property
    def registry(self):
        """The engine's metrics registry (obs/registry.py) — what the
        HTTP server renders at GET /metrics; None on engines built
        without one (test doubles)."""
        return getattr(self.runner.engine, "registry", None)

    def status(self) -> str:
        return self.runner.status()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown; see :meth:`EngineRunner.drain`."""
        return self.runner.drain(timeout)

    def close(self) -> None:
        self.runner.close()


def _make_handler(client: ServingClient, tokenizer=None, events=None,
                  slo=None):
    events = events or NOOP_EVENTS

    class Handler(BaseHTTPRequestHandler):
        def _reply(self, code: int, payload: dict,
                   headers: Optional[dict] = None) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _retry_after(self) -> dict:
            # how long a well-behaved client should back off before
            # retrying this replica; draining lasts up to the drain
            # budget, everything else clears within ~a restart backoff
            serving = client.runner.engine.serving
            if client.runner.status() == "draining":
                secs = max(1, int(serving.drain_timeout_s))
            else:
                secs = max(1, int(serving.restart_backoff_s))
            return {"Retry-After": str(secs)}

        def do_GET(self):
            if self.path == "/metrics":
                registry = client.registry
                if registry is None:
                    self._reply(404, {"error": "no metrics registry"})
                    return
                if slo is not None:
                    # refresh the slo_* burn-rate gauges so every
                    # scrape carries a current judgment (obs/slo.py)
                    slo.evaluate()
                body = registry.render().encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type", METRICS_CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif self.path == "/health":
                status = client.status()
                payload = {
                    "ok": status in ("healthy", "degraded"),
                    "status": status,
                    "restarts": client.runner.restarts,
                    "last_step_s": client.runner.last_step_s,
                    "stats": client.stats,
                    # which device this replica runs on, as JAX reports
                    # it, and its memory high-water mark (None on CPU)
                    "device": {**device_summary(),
                               "peak_bytes_in_use": peak_memory_bytes()},
                }
                # compile-cache sizes, so fleet chaos tests can pin
                # "zero added recompiles" on REMOTE replicas too
                compile_stats = getattr(
                    client.runner.engine, "compile_stats", None
                )
                if compile_stats is not None:
                    payload["compiles"] = compile_stats()
                # paged-KV pool snapshot (serving/pages.py): page
                # counts + prefix-cache hit/miss/eviction counters, so
                # operators and fleet chaos tests see capacity and
                # cache behavior without scraping /metrics
                page_stats = getattr(
                    client.runner.engine, "page_stats", None
                )
                if page_stats is not None:
                    pages = page_stats()
                    if pages is not None:
                        payload["kv_pages"] = pages
                # speculative-decoding snapshot (serving/spec.py):
                # mode, draft rung, proposed/accepted counters and
                # acceptance rate — the per-replica view the fleet
                # aggregation sums from /metrics
                spec_stats = getattr(
                    client.runner.engine, "spec_stats", None
                )
                if spec_stats is not None:
                    spec = spec_stats()
                    if spec is not None:
                        payload["spec"] = spec
                # structured-decoding snapshot (serving/constrain.py):
                # in-flight constrained requests + compile-cache
                # entries/bytes/hit/miss/eviction counters
                constrain_stats = getattr(
                    client.runner.engine, "constrain_stats", None
                )
                if constrain_stats is not None:
                    payload["constraints"] = constrain_stats()
                # host-tier snapshot (serving/host_tier.py): byte
                # budget/usage, cached/stashed entries, and the
                # demote/promote/preempt/resume/fallback counters —
                # the "Serving under memory pressure" runbook's
                # first-stop view
                tier_stats = getattr(
                    client.runner.engine, "tier_stats", None
                )
                if tier_stats is not None:
                    tier = tier_stats()
                    if tier is not None:
                        payload["host_tier"] = tier
                # per-priority-class queue depths: a saturating batch
                # class is visible as ITS queue growing, not as an
                # opaque aggregate number
                queue_depths = getattr(
                    client.runner.engine, "queue_depths", None
                )
                if queue_depths is not None:
                    payload["queue_by_class"] = queue_depths()
                self._reply(200, payload)
            elif self.path == "/ready":
                if client.runner.accepting():
                    self._reply(200, {"ready": True,
                                      "status": client.status()})
                else:
                    self._reply(
                        503, {"ready": False, "status": client.status()},
                        headers=self._retry_after(),
                    )
            elif self.path == "/inflight":
                # per-request progress for the router: replay-journal
                # harvest + drain-time migration enumeration
                self._reply(
                    200, {"inflight": client.runner.inflight_snapshot()}
                )
            else:
                self._reply(404, {"error": f"unknown path {self.path}"})

        # -- live migration endpoints (serving/migrate.py) ------------

        def _read_json(self) -> dict:
            n = int(self.headers.get("Content-Length", "0"))
            return json.loads(self.rfile.read(n) or b"{}")

        def _migrate_probe(self) -> None:
            """How many leading prompt pages this replica's radix tree
            already holds — the source ships holes for them (dedup)."""
            try:
                req = self._read_json()
                prompt = [int(t) for t in req.get("prompt_ids") or []]
                pool = getattr(client.runner.engine, "_pages", None)
                cached = (
                    pool.probe_prefix(prompt)
                    if pool is not None and prompt else 0
                )
                self._reply(200, {"cached_pages": int(cached)})
            except Exception as e:
                self._reply(400, {"error": str(e), "code": "bad_request"})

        def _migrate_import(self) -> None:
            """Land a migrated slot: decode + CRC-verify, re-admit via
            the zero-recompile swap-in path. A convicted (corrupt/torn)
            payload answers a typed 409 — garbage KV never lands."""
            try:
                req = self._read_json()
                migrate_id = str(req.get("migrate_id") or "")
                if not migrate_id or "state" not in req:
                    raise ValueError("migrate_id and state required")
                blob = from_wire(str(req["state"]))
                rid = client.runner.import_state(blob, migrate_id)
            except MigratePayloadError as e:
                self._reply(409, {"error": str(e),
                                  "code": "migrate_corrupt"})
            except MigrateExportError as e:
                self._reply(409, {"error": str(e), "code": e.code})
            except (ValueError, TypeError, json.JSONDecodeError) as e:
                self._reply(400, {"error": str(e), "code": "bad_request"})
            except QueueFullError as e:
                self._reply(503, {"error": str(e), "code": "queue_full"},
                            headers=self._retry_after())
            except PagePoolExhaustedError as e:
                self._reply(503, {"error": str(e),
                                  "code": "page_pool_exhausted"},
                            headers=self._retry_after())
            except ShuttingDownError as e:
                self._reply(503, {"error": str(e),
                                  "code": "shutting_down"},
                            headers=self._retry_after())
            except TimeoutError as e:
                self._reply(503, {"error": str(e),
                                  "code": "migrate_timeout"})
            except Exception as e:
                self._reply(500, {"error": str(e) or repr(e),
                                  "code": "internal"})
            else:
                events.emit("migrate_imported", migrate_id=migrate_id,
                            request_id=rid)
                self._reply(200, {"request_id": rid,
                                  "migrate_id": migrate_id})

        def _migrate_export(self) -> None:
            """Drain-side trigger: move one in-flight request to
            ``dest``. Any typed failure (contiguous layout, transfer
            death, dest full) answers non-200 so the router falls back
            to replay — the request itself is NEVER harmed (the slot
            keeps decoding unless the hand-off fully landed)."""
            try:
                req = self._read_json()
                result = client.runner.migrate_out(
                    int(req["request_id"]),
                    str(req["dest"]).rstrip("/"),
                    str(req.get("migrate_id") or ""),
                    budget_s=float(req.get("budget_s", 10.0)),
                )
            except MigrateExportError as e:
                self._reply(409, {"error": str(e), "code": e.code})
            except (ValueError, TypeError, KeyError,
                    json.JSONDecodeError) as e:
                self._reply(400, {"error": str(e), "code": "bad_request"})
            except ShuttingDownError as e:
                self._reply(503, {"error": str(e),
                                  "code": "shutting_down"})
            except TimeoutError as e:
                self._reply(503, {"error": str(e),
                                  "code": "migrate_timeout"})
            except Exception as e:
                self._reply(500, {"error": str(e) or repr(e),
                                  "code": "internal"})
            else:
                events.emit("migrate_exported",
                            outcome=result.get("outcome"),
                            dest=result.get("dest"))
                self._reply(200, result)

        def _run_generate(self, req: dict, ctx) -> RequestOutput:
            """Parse a /generate body into SamplingParams and run it;
            raises the typed errors do_POST's ladder maps to HTTP."""
            prompt_ids = req.get("prompt_ids")
            if prompt_ids is None and "prompt" in req:
                if tokenizer is None:
                    raise ValueError(
                        "text prompts need the server started with a "
                        "tokenizer dir; send prompt_ids instead"
                    )
                prompt_ids = tokenizer.encode(req["prompt"]).ids
            if not prompt_ids:
                raise ValueError("prompt_ids (or prompt) required")
            top_k = req.get("top_k")
            eos = req.get("eos_token_id")
            choices = req.get("choices")
            stop = req.get("stop")
            # json_schema arrives as a JSON VALUE (object) or a
            # pre-encoded string; SamplingParams wants the string
            schema = req.get("json_schema")
            if schema is not None and not isinstance(schema, str):
                schema = json.dumps(schema)
            params = SamplingParams(
                max_new_tokens=int(req.get("max_new_tokens", 16)),
                temperature=float(req.get("temperature", 1.0)),
                top_k=None if top_k is None else int(top_k),
                seed=int(req.get("seed", 0)),
                eos_token_id=None if eos is None else int(eos),
                json_schema=schema,
                regex=req.get("regex"),
                choices=choices,
                repetition_penalty=float(
                    req.get("repetition_penalty", 1.0)
                ),
                presence_penalty=float(
                    req.get("presence_penalty", 0.0)
                ),
                frequency_penalty=float(
                    req.get("frequency_penalty", 0.0)
                ),
                stop=(
                    None if stop is None
                    else tuple(
                        tuple(int(t) for t in seq) for seq in stop
                    )
                ),
                logprobs=int(req.get("logprobs", 0)),
                priority=str(req.get("priority", "normal")),
                # resume-by-replay (serving/migrate.py): the router
                # resubmits prompt+emitted with the key-chain position
                key_offset=int(req.get("key_offset", 0)),
            )
            deadline_s = req.get("deadline_s")
            # "received", not "admitted": a QueueFullError /
            # ShuttingDownError raised inside generate() means the
            # scheduler never accepted this request — true
            # admission is the engine's trace-stamped `admit`
            # instant; this event marks arrival at the handler
            events.emit("request_received", trace_id=ctx.trace_id,
                        prompt_len=len(prompt_ids))
            jid = req.get("journal_id")
            return client.generate(
                [int(t) for t in prompt_ids], params,
                timeout=float(req.get("timeout", 600.0)),
                deadline_s=(
                    None if deadline_s is None else float(deadline_s)
                ),
                trace=ctx,
                journal_id=None if jid is None else str(jid),
            )

        def do_POST(self):
            if self.path == "/migrate/probe":
                return self._migrate_probe()
            if self.path == "/migrate/import":
                return self._migrate_import()
            if self.path == "/migrate/export":
                return self._migrate_export()
            # /migrate/await shares /generate's error ladder and reply
            # shape — it IS a /generate whose work arrived by migration
            if self.path not in ("/generate", "/migrate/await"):
                self._reply(404, {"error": f"unknown path {self.path}"})
                return
            ctx = None  # TraceContext once the body parses

            def _fail(code: int, payload: dict, headers=None,
                      event: str = "request_failed") -> None:
                # every error reply carries the request's trace id (when
                # the body parsed far enough to have one) and lands one
                # structured event, so a failed request is findable in
                # both the stitched timeline and the event log
                if ctx is not None:
                    payload.setdefault("trace_id", ctx.trace_id)
                events.emit(event, status=code,
                            code=payload.get("code"),
                            trace_id=payload.get("trace_id"))
                self._reply(code, payload, headers=headers)

            try:
                n = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(n) or b"{}")
                # the traceparent JSON field is the cross-process trace
                # contract (obs/trace.py): the router mints and injects
                # one; a directly-hit replica mints its own, so replies
                # ALWAYS carry a trace_id a stitched timeline can find
                ctx = trace_from_payload(req)
                if self.path == "/migrate/await":
                    # pick up a migrated continuation: block on the
                    # imported request's waiter and reply in the exact
                    # /generate shape (COMPLETE token list — the slot
                    # restored the source's emitted tokens, so no
                    # router-side stitching is needed)
                    migrate_id = str(req.get("migrate_id") or "")
                    pending = client.runner.migrated_pending(migrate_id)
                    if pending is None:
                        _fail(404, {
                            "error": f"unknown migrate_id {migrate_id!r}",
                            "code": "unknown_migrate_id",
                        })
                        return
                    if not pending.done.wait(
                        float(req.get("timeout", 600.0))
                    ):
                        client.runner.cancel(pending)
                        raise TimeoutError("generation timed out")
                    if pending.error is not None:
                        raise pending.error
                    out = pending.result
                else:
                    out = self._run_generate(req, ctx)
            except ConstraintCompileError as e:
                # must precede the ValueError branch (it IS one): a
                # malformed/unsupported constraint spec fails typed at
                # submit with the engine untouched — a distinct code so
                # clients can tell "fix your schema" from "bad request"
                _fail(400, {"error": str(e),
                            "code": "constraint_compile_failed"})
                return
            except (ValueError, TypeError, json.JSONDecodeError) as e:
                _fail(400, {"error": str(e), "code": "bad_request"})
                return
            except ConstraintDeadEndError as e:
                # the constraint FSM hit an all-zero mask mid-decode:
                # typed 400 with the partial output — retriable per the
                # error's flag, but a retry of the SAME spec dead-ends
                # again unless the fault was injected chaos
                _fail(400, {
                    "error": str(e),
                    "code": "constraint_dead_end",
                    "partial_tokens": (
                        e.output.tokens if e.output is not None else []
                    ),
                })
                return
            except QueueFullError as e:
                # overload: reject fast with the retryable status so
                # load balancers/clients back off instead of piling on.
                # Every error reply carries a machine-readable "code" —
                # serving/retry.py gates retries on it and the bench
                # classifies by it, so rewording the human text cannot
                # silently change client behavior.
                _fail(
                    503,
                    {"error": f"server overloaded: {e}",
                     "code": "queue_full"},
                    headers=self._retry_after(),
                )
                return
            except PagePoolExhaustedError as e:
                # the paged-KV shed path: same retryable 503 contract
                # as queue_full (the pool drains as requests retire and
                # cached prefixes evict); a never-fits request carries
                # retriable=False — no Retry-After, clients must not
                # burn their budget re-sending it here
                if getattr(e, "retriable", True):
                    # prefer the engine's drain-rate-derived estimate
                    # (seconds until enough pages free at the observed
                    # eviction/release throughput) over the static
                    # restart-backoff default
                    ra = getattr(e, "retry_after", None)
                    _fail(503, {"error": str(e),
                                "code": "page_pool_exhausted"},
                          headers=(
                              {"Retry-After":
                               str(max(1, int(round(ra))))}
                              if ra is not None
                              else self._retry_after()
                          ))
                else:
                    _fail(503, {"error": str(e),
                                "code": "page_pool_unfit"})
                return
            except ShuttingDownError as e:
                _fail(503, {"error": str(e),
                            "code": "shutting_down"},
                      headers=self._retry_after())
                return
            except EngineCrashError as e:
                if getattr(e, "retriable", True):
                    # the supervised restart is already underway — a
                    # retry after the backoff lands on the rebuilt engine
                    _fail(
                        503, {"error": f"engine crashed: {e}",
                              "code": "engine_crash"},
                        headers=self._retry_after(),
                    )
                else:
                    # restart budget exhausted: this replica will NEVER
                    # recover — no Retry-After, non-retriable code, so
                    # clients fail over instead of burning their budget
                    _fail(503, {"error": str(e),
                                "code": "engine_failed"})
                return
            except DeadlineExceededError as e:
                _fail(504, {
                    "error": str(e),
                    "code": "deadline",
                    "partial_tokens": (
                        e.output.tokens if e.output is not None else []
                    ),
                })
                return
            except TimeoutError:
                # the request burned its FULL generation timeout — a
                # retry would re-add that same load to a server at its
                # slowest, so: no Retry-After, non-retriable code
                _fail(503, {"error": "generation timed out",
                            "code": "timeout"})
                return
            except MigratedError as e:
                # not a failure: the live state moved to a peer mid-
                # flight — 200 with the forwarding pointer, and the
                # router picks the continuation up at dest's
                # /migrate/await
                payload = {"code": "migrated", "dest": e.dest,
                           "migrate_id": e.migrate_id}
                if ctx is not None:
                    payload["trace_id"] = ctx.trace_id
                events.emit("request_migrated", dest=e.dest,
                            trace_id=payload.get("trace_id"))
                self._reply(200, payload)
                return
            except Exception as e:  # unexpected failure — still typed:
                # the router (serving/router.py) and retry client key
                # retriability off the machine-readable "code"; an
                # untyped stack-trace 500 would strand them guessing
                _fail(500, {"error": str(e) or repr(e),
                            "code": "internal"})
                return
            payload = {
                "request_id": out.request_id,
                "prompt_ids": out.prompt,
                "tokens": out.tokens,
                "finish_reason": out.finish_reason,
                "ttft_ms": round(out.ttft * 1e3, 3),
                "trace_id": out.trace_id or ctx.trace_id,
            }
            if out.token_logprobs is not None:
                payload["token_logprobs"] = out.token_logprobs
                payload["top_logprobs"] = [
                    [[tid, lp] for tid, lp in row]
                    for row in out.top_logprobs
                ]
            if out.quality is not None:
                payload["quality"] = out.quality
            if tokenizer is not None:
                payload["text"] = tokenizer.decode(out.tokens)
            events.emit("request_finished",
                        trace_id=payload["trace_id"],
                        reason=out.finish_reason,
                        tokens=len(out.tokens),
                        ttft_ms=payload["ttft_ms"])
            self._reply(200, payload)

        def log_message(self, *a):  # quiet by default
            pass

    return Handler


def serve(client: ServingClient, host: str = "127.0.0.1", port: int = 8000,
          tokenizer=None, events=None, slo=None) -> ThreadingHTTPServer:
    """Build the HTTP server (not yet serving; call serve_forever()).
    ``events`` is an obs/events.py EventLog (None = off); ``slo`` an
    obs/slo.py SLOMonitor evaluated on every /metrics scrape."""
    return ThreadingHTTPServer(
        (host, port), _make_handler(client, tokenizer, events, slo)
    )


def main() -> None:
    """CLI: serve a checkpoint (or a random-init demo model) over HTTP."""
    import argparse

    import jax

    from differential_transformer_replication_tpu.config import (
        ModelConfig,
        ServingConfig,
    )

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--checkpoint", default=None,
                   help="training checkpoint dir (meta.json + "
                        "state.msgpack); omit for a random-init demo model")
    p.add_argument("--tokenizer", default=None,
                   help="tokenizer dir enabling text prompts "
                        "(vocab.json + merges.txt)")
    p.add_argument("--model", default="control",
                   help="demo model family when no checkpoint is given")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--num-slots", type=int, default=8)
    p.add_argument("--prefill-chunk", type=int, default=128)
    p.add_argument("--prefill-budget", type=int, default=256)
    p.add_argument("--max-seq-len", type=int, default=0)
    p.add_argument("--decode-attention-impl", default="",
                   choices=("", "xla", "pallas"),
                   help="decode attention backend: the fused Pallas "
                        "single-query kernel (ops/decode_attention.py) "
                        "or plain XLA; '' keeps the model config")
    p.add_argument("--kv-cache-dtype", default="",
                   choices=("", "auto", "bf16", "int8"),
                   help="KV-cache storage dtype; int8 stores per-head-"
                        "scale quantized K/V — about half the bf16 HBM "
                        "bytes per slot, so ~2x slot capacity at equal "
                        "memory; '' keeps the model config")
    p.add_argument("--kv-page-size", type=int, default=0,
                   help="paged KV cache (serving/pages.py): tokens per "
                        "page (must divide block_size); admission then "
                        "keys on free pages, not slots, so short "
                        "requests stop paying worst-case-context HBM. "
                        "0 = contiguous per-slot rings")
    p.add_argument("--kv-pool-pages", type=int, default=0,
                   help="total physical pages in the paged pool; 0 = "
                        "auto (num_slots * block_size / page_size). "
                        "Sizing below auto converts short-context "
                        "traffic into more concurrent slots at equal "
                        "HBM")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the radix-tree shared-prefix cache "
                        "(on by default when --kv-page-size > 0): "
                        "retired prompts donate KV pages so requests "
                        "sharing a system prompt skip its prefill")
    p.add_argument("--prefix-cache-pages", type=int, default=0,
                   help="extra pool pages reserved as cached-prefix "
                        "headroom on top of the auto sizing")
    p.add_argument("--host-tier-bytes", type=int, default=0,
                   help="host-RAM KV page tier (serving/host_tier.py), "
                        "in bytes (needs --kv-page-size): evicted "
                        "radix-cached prefixes DEMOTE here instead of "
                        "vanishing and promote back with a copy, never "
                        "a recompute; preempted requests stash their "
                        "live KV here and resume bit-exact. 0 = off")
    p.add_argument("--priority-aging", type=float, default=10.0,
                   help="anti-starvation aging (seconds): every this "
                        "many seconds waited improves a queued "
                        "request's effective priority by one class, so "
                        "batch traffic cannot starve under sustained "
                        "high-priority load (0 = strict classes)")
    p.add_argument("--priority-max-slots", default="",
                   help="per-class slot bounds as 'class:N,...' (e.g. "
                        "'batch:6') capping how many slots one class "
                        "may hold; '' = no bounds")
    p.add_argument("--spec-mode", default="",
                   choices=("", "ngram", "model"),
                   help="speculative decoding (serving/spec.py): "
                        "'ngram' = drafter-free prompt lookup over "
                        "each request's own tokens; 'model' = a small "
                        "drafter checkpoint (--spec-drafter-ckpt) "
                        "proposing greedily on its own KV pool. The "
                        "target verifies k drafted tokens per slot in "
                        "ONE fused multi-row step — greedy output "
                        "stays bit-identical to non-spec decoding")
    p.add_argument("--spec-draft-len", type=int, default=4,
                   help="draft tokens verified per slot per iteration "
                        "(the compiled k rung; per-request lengths "
                        "ride as runtime arrays)")
    p.add_argument("--spec-drafter-ckpt", default="",
                   help="drafter checkpoint dir for --spec-mode model "
                        "(loaded like --checkpoint: manifest "
                        "verification and --quantize-weights apply); "
                        "must share the target's tokenizer/vocab")
    p.add_argument("--spec-verify", default="exact",
                   choices=("exact", "batched"),
                   help="verify-step formulation: 'exact' (unrolled, "
                        "greedy bit-identical to non-spec at any "
                        "size) or 'batched' (each slot's KV streamed "
                        "once for all k+1 rows through the fused "
                        "multi-query kernel — the TPU-bandwidth "
                        "formulation)")
    p.add_argument("--quantize-weights", default=None,
                   choices=("int8",),
                   help="per-channel int8 quantize + dequant of every "
                        "matmul weight at checkpoint load "
                        "(tolerance-gated accuracy)")
    p.add_argument("--max-queue-len", type=int, default=0,
                   help="reject (HTTP 503) submissions past this many "
                        "waiting requests; 0 = unbounded")
    p.add_argument("--default-deadline", type=float, default=0.0,
                   help="server-side deadline (seconds) applied to "
                        "requests that do not send deadline_s; expired "
                        "requests are shed instead of decoded (0 = none)")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="graceful-drain budget on SIGTERM: stop "
                        "admission, finish in-flight within this many "
                        "seconds, then exit")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="supervised engine-restart budget; a crashed "
                        "engine step rebuilds the slot pool up to this "
                        "many times before the server fails hard")
    p.add_argument("--restart-backoff", type=float, default=0.5,
                   help="first-restart backoff seconds (doubles per "
                        "restart, like tools/train_supervisor.py)")
    p.add_argument("--restart-backoff-max", type=float, default=30.0,
                   help="restart backoff cap in seconds")
    p.add_argument("--step-time-budget", type=float, default=0.0,
                   help="watchdog: mark the engine degraded on /health "
                        "when one decode iteration exceeds this many "
                        "seconds (0 = off)")
    p.add_argument("--profile-every", type=int, default=0,
                   help="continuous on-device profiling "
                        "(obs/device_profile.py): every N engine "
                        "iterations capture ONE iteration's device "
                        "profile, parse it off-loop, and publish "
                        "device_* gauges on /metrics, device_profile "
                        "JSONL rows and a stitchable device-lane trace "
                        "under --profile-dir; 0 = off")
    p.add_argument("--profile-dir", default="device_profiles",
                   help="rotating spool for --profile-every captures")
    p.add_argument("--trace-path", default=None,
                   help="write a Chrome-trace-event JSON of engine "
                        "iterations (schedule/prefill/decode/sample/emit "
                        "spans + per-request trace-stamped lifecycle; "
                        "open in Perfetto or merge fleet-wide with "
                        "tools/trace_stitch.py) to this path")
    p.add_argument("--event-log", default=None,
                   help="append structured JSONL events (request "
                        "received/finished/failed with trace ids; "
                        "obs/events.py) to this path")
    p.add_argument("--event-log-max-bytes", type=int, default=0,
                   help="rotate --event-log when it reaches this many "
                        "bytes (atomic rename cascade, whole lines "
                        "only; 0 = never rotate)")
    p.add_argument("--event-log-keep", type=int, default=3,
                   help="rotated --event-log generations to keep "
                        "(events.jsonl.1 ... .N; 0 = truncate)")
    p.add_argument("--quality-telemetry", action="store_true",
                   help="compute per-token model-quality signals "
                        "(sampled-distribution entropy, top-1 logit "
                        "margin, repetition runs) inside the jitted "
                        "decode step (obs/quality.py): per-request "
                        "quality stats on responses, "
                        "serving_token_entropy / serving_logit_margin "
                        "histograms and serving_lambda_mean{layer=} / "
                        "serving_quality_drift gauges on /metrics")
    p.add_argument("--quality-fingerprint", default=None,
                   help="reference quality fingerprint JSON to compare "
                        "live traffic against (PSI drift score as "
                        "serving_quality_drift; recorded earlier with "
                        "--quality-record); implies --quality-telemetry")
    p.add_argument("--quality-record", default=None,
                   help="write this replica's quality fingerprint "
                        "(quantile sketches of the live entropy/margin "
                        "distributions) to this path at drain/shutdown; "
                        "implies --quality-telemetry")
    p.add_argument("--slo-ttft", type=float, default=1.0,
                   help="TTFT latency objective bound in seconds "
                        "(obs/slo.py; burn rates exposed as slo_* "
                        "gauges on /metrics)")
    p.add_argument("--slo-itl", type=float, default=0.25,
                   help="inter-token latency objective bound in seconds")
    p.add_argument("--slo-target", type=float, default=0.99,
                   help="latency objectives' target fraction of "
                        "requests under the bound")
    p.add_argument("--slo-availability-target", type=float,
                   default=0.999,
                   help="availability objective target (completed vs "
                        "rejected/deadline-expired)")
    p.add_argument("--no-verify-checkpoint", action="store_true",
                   help="skip integrity-manifest verification of "
                        "--checkpoint (needed for pre-manifest "
                        "checkpoints; or certify them once with "
                        "tools/ckpt_doctor.py --adopt-legacy)")
    args = p.parse_args()

    setup_compile_cache()
    meta = None
    if args.checkpoint:
        from differential_transformer_replication_tpu.train.checkpoint import (
            load_params_for_inference,
        )

        params, model_cfg, meta = load_params_for_inference(
            args.checkpoint, verify=not args.no_verify_checkpoint,
            quantize=args.quantize_weights,
        )
    else:
        from differential_transformer_replication_tpu.models import init_model

        model_cfg = ModelConfig(
            model=args.model, vocab_size=512, n_embd=64, n_head=2,
            n_layer=2, block_size=128, compute_dtype="float32",
        )
        from differential_transformer_replication_tpu.train.checkpoint import (
            apply_weight_quantization,
        )

        params = apply_weight_quantization(
            init_model(jax.random.PRNGKey(0), model_cfg),
            args.quantize_weights,
        )
        print("[serve] no checkpoint given: random-init demo model")

    tokenizer = None
    # id -> decoded-string table for the constraint FSM compiler
    # (serving/constrain.py). Without a tokenizer the demo model maps
    # printable-ASCII ids to their characters so constrained requests
    # still work against the random-init model ("" = never allowed).
    vocab = [
        chr(i) if 32 <= i < 127 else ""
        for i in range(model_cfg.vocab_size)
    ]
    if args.tokenizer:
        from differential_transformer_replication_tpu.data.tokenizer import (
            check_tokenizer_matches,
            load_tokenizer,
            vocab_strings,
        )

        tokenizer = load_tokenizer(args.tokenizer)
        vocab = vocab_strings(tokenizer, model_cfg.vocab_size)
        if meta is not None:
            # refuse to serve text through a tokenizer that cannot belong
            # to the checkpoint (same guard as sample.py — a clobbered
            # shared tokenizer dir would silently emit garbage text)
            check_tokenizer_matches(
                tokenizer, model_cfg.vocab_size,
                meta.get("tokenizer_fingerprint"), context=args.checkpoint,
            )

    serving = ServingConfig(
        num_slots=args.num_slots, prefill_chunk=args.prefill_chunk,
        prefill_budget=args.prefill_budget, max_seq_len=args.max_seq_len,
        decode_attention_impl=args.decode_attention_impl,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_page_size=args.kv_page_size,
        kv_pool_pages=args.kv_pool_pages,
        prefix_cache=not args.no_prefix_cache,
        prefix_cache_pages=args.prefix_cache_pages,
        host_tier_bytes=args.host_tier_bytes,
        priority_aging_s=args.priority_aging,
        priority_max_slots=args.priority_max_slots,
        max_queue_len=args.max_queue_len,
        default_deadline_s=args.default_deadline,
        drain_timeout_s=args.drain_timeout,
        max_restarts=args.max_restarts,
        restart_backoff_s=args.restart_backoff,
        restart_backoff_max_s=args.restart_backoff_max,
        step_time_budget_s=args.step_time_budget,
        profile_every=args.profile_every,
        profile_dir=args.profile_dir,
        spec_mode=args.spec_mode,
        spec_draft_len=args.spec_draft_len,
        spec_drafter_ckpt=args.spec_drafter_ckpt,
        spec_verify=args.spec_verify,
        # recording or comparing a fingerprint both need the in-step
        # telemetry tail, so either flag arms it
        quality_telemetry=(args.quality_telemetry
                           or bool(args.quality_fingerprint)
                           or bool(args.quality_record)),
        quality_fingerprint=args.quality_fingerprint or "",
    )
    spec_drafter = None
    if args.spec_mode == "model" and args.spec_drafter_ckpt:
        # load the drafter through the SAME verified/quantized path as
        # the target, so --no-verify-checkpoint / --quantize-weights
        # apply to it too
        from differential_transformer_replication_tpu.train.checkpoint import (
            load_params_for_inference as _load_drafter,
        )

        d_params, d_cfg, _ = _load_drafter(
            args.spec_drafter_ckpt,
            verify=not args.no_verify_checkpoint,
            quantize=args.quantize_weights,
        )
        spec_drafter = (d_params, d_cfg)
    tracer = None
    if args.trace_path:
        from differential_transformer_replication_tpu.obs.spans import (
            SpanTracer,
        )

        tracer = SpanTracer(args.trace_path, process_name="serving-engine")
    events = None
    if args.event_log:
        from differential_transformer_replication_tpu.obs.events import (
            EventLog,
        )

        events = EventLog(args.event_log, process="replica",
                          max_bytes=args.event_log_max_bytes,
                          keep=args.event_log_keep)
    engine = ServingEngine(params, model_cfg, serving, tracer=tracer,
                           spec_drafter=spec_drafter, vocab=vocab)
    client = ServingClient(engine)

    # process identity on /metrics: lets the router's aggregated
    # /fleet/metrics tell replicas apart and spot config drift
    import dataclasses as _dc
    import hashlib as _hashlib

    from differential_transformer_replication_tpu.obs.registry import (
        set_build_info,
    )
    from differential_transformer_replication_tpu.obs.slo import (
        SLOMonitor,
        default_serving_objectives,
    )

    cfg_hash = _hashlib.sha1(
        json.dumps(_dc.asdict(model_cfg), sort_keys=True,
                   default=str).encode()
    ).hexdigest()[:12]
    set_build_info(engine.registry, role="replica", config_hash=cfg_hash,
                   version=jax.__version__)
    slo_latency, slo_availability = default_serving_objectives(
        ttft_threshold_s=args.slo_ttft, itl_threshold_s=args.slo_itl,
        latency_target=args.slo_target,
        availability_target=args.slo_availability_target,
    )
    slo = SLOMonitor(engine.registry, latency=slo_latency,
                     availability=slo_availability)
    httpd = serve(client, args.host, args.port, tokenizer,
                  events=events, slo=slo)

    import signal

    drained = {"done": False}
    fingerprint_saved = {"done": False}

    def _save_quality_fingerprint():
        """Snapshot the live quality sketches to --quality-record;
        idempotent (drain path and main finally both call it)."""
        if not args.quality_record or fingerprint_saved["done"]:
            return
        fingerprint_saved["done"] = True
        try:
            from differential_transformer_replication_tpu.obs.quality import (
                save_fingerprint,
            )

            rec = engine.quality_fingerprint(
                meta={"model": model_cfg.model, "config_hash": cfg_hash}
            )
            save_fingerprint(args.quality_record, rec)
            print(f"[serve] quality fingerprint written to "
                  f"{args.quality_record}", file=sys.stderr)
        except Exception as e:  # forensics must not block shutdown
            print(f"[serve] quality fingerprint save failed: {e!r}",
                  file=sys.stderr)

    def _graceful(signum, frame):
        del frame
        print(f"[serve] signal {signum}: draining (budget "
              f"{serving.drain_timeout_s}s) — admission stopped",
              file=sys.stderr)

        def _drain_then_stop():
            try:
                ok = client.drain()
                print(f"[serve] drain {'complete' if ok else 'TIMED OUT'}; "
                      "shutting down", file=sys.stderr)
            except Exception as e:
                # close() refuses to bless a stuck engine thread; a
                # second close from main() would just block 30s more on
                # the same wedged thread
                print(f"[serve] drain failed: {e!r}", file=sys.stderr)
            finally:
                # buffered telemetry must land BEFORE the process goes
                # away: a SIGTERM'd replica used to rely on the main
                # thread's finally block alone, which a wedged drain
                # could starve — close here (idempotent; the atexit net
                # in obs/spans.py+obs/events.py is the last resort)
                _save_quality_fingerprint()
                if tracer is not None:
                    tracer.close()
                if events is not None:
                    events.emit("drained")
                    events.close()
                # the HTTP loop must stop regardless, or SIGTERM leaves
                # a zombie serving 503s forever
                drained["done"] = True
                httpd.shutdown()

        # a thread, because httpd.shutdown() deadlocks when called from
        # the serve_forever thread, and signal handlers must not block
        threading.Thread(target=_drain_then_stop, daemon=True).start()

    signal.signal(signal.SIGTERM, _graceful)
    print(
        f"[serve] {model_cfg.model} model, {serving.num_slots} slots — "
        f"POST http://{args.host}:{args.port}/generate, metrics at "
        f"GET http://{args.host}:{args.port}/metrics"
    )
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        if not drained["done"]:
            client.close()
        _save_quality_fingerprint()
        if tracer is not None:
            tracer.close()
            print(f"[serve] span trace written to {args.trace_path}")
        if events is not None:
            events.close()


if __name__ == "__main__":
    main()

"""Serving resilience layer (ISSUE 3): engine supervision, server-side
deadlines, graceful drain, watchdog, retry client.

The load-bearing contracts:

- a mid-batch engine crash NEVER hangs a caller — every in-flight
  request fails with a typed, retriable ``EngineCrashError`` while the
  supervised runner rebuilds the slot pool from params and keeps
  serving; wait-queue entries ride through the restart verbatim and the
  restarted engine is bit-identical to a fresh one;
- expired requests are shed at admission and retired mid-decode (KV
  slot reclaimed) with a typed ``DeadlineExceededError``;
- ``drain()`` stops admission (503 + Retry-After over HTTP), finishes
  everything in flight within the budget, and loses nothing;
- all of it is host-side bookkeeping: zero new compiles (pinned below).

Quick tier: deterministic fault-point tests. Slow tier: chaos tests
under real concurrent load (mirrors tests/test_faults.py's tiering).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from functools import lru_cache
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from differential_transformer_replication_tpu.config import (
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import (
    generate_cached,
    init_model,
)
from differential_transformer_replication_tpu.serving import (
    DeadlineExceededError,
    EngineCrashError,
    EngineRunner,
    QueueFullError,
    Scheduler,
    ServingClient,
    ServingEngine,
    ShuttingDownError,
    backoff_delay,
    call_with_retries,
    http_post_json_with_retries,
    serve,
)
from differential_transformer_replication_tpu.serving.request import Request
from differential_transformer_replication_tpu.serving.scheduler import (
    ACTIVE,
    FREE,
)
from differential_transformer_replication_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _cfg(kind, vocab=61):
    return ModelConfig(
        model=kind, vocab_size=vocab, n_embd=32, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
    )


@lru_cache(maxsize=None)
def _setup(kind, vocab=61):
    cfg = _cfg(kind, vocab)
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _prompts(lens, vocab, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=L).tolist() for L in lens]


def _ref_greedy(params, cfg, prompt, n):
    out = generate_cached(
        params, jnp.asarray(prompt, jnp.int32)[None], cfg, n,
        jax.random.PRNGKey(0), temperature=0.0,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


def _serving(**kw):
    kw.setdefault("num_slots", 1)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefill_budget", 8)
    kw.setdefault("restart_backoff_s", 0.01)
    kw.setdefault("restart_backoff_max_s", 0.05)
    return ServingConfig(**kw)


# -- fault-spec parsing -------------------------------------------------


class TestServeFaultSpec:
    def test_parse_and_one_shot(self):
        faults.arm("serve_raise@3,serve_corrupt@5")
        assert faults.armed()
        faults.serve_fire(2)  # not armed for 2: no-op
        with pytest.raises(faults.FaultInjected, match="iteration 3"):
            faults.serve_fire(3)
        faults.serve_fire(3)  # one-shot: a replayed iteration is safe
        assert faults.serve_corrupt_at(4) is False
        assert faults.serve_corrupt_at(5) is True
        assert faults.serve_corrupt_at(5) is False  # one-shot

    def test_hang_honors_env_override(self, monkeypatch):
        monkeypatch.setenv(faults.HANG_ENV_VAR, "0.15")
        faults.arm("serve_hang@1")
        t0 = time.perf_counter()
        faults.serve_fire(1)
        assert time.perf_counter() - t0 >= 0.14
        t0 = time.perf_counter()
        faults.serve_fire(1)  # disarmed
        assert time.perf_counter() - t0 < 0.1

    def test_unknown_kind_still_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.arm("serve_explode@3")


# -- scheduler deadline bookkeeping ------------------------------------


class TestSchedulerDeadlines:
    def _sched(self, **kw):
        return Scheduler(ServingConfig(**kw))

    def test_shed_expired_only_drops_expired(self):
        s = self._sched(num_slots=1)
        for i, dl in enumerate([0.0, 5.0, 100.0]):  # 0.0 = no deadline
            s.submit(Request.make(i, [1, 2]), np.ones(2, np.int32), 0.0, dl)
        shed = s.shed_expired(now=10.0)
        assert [e[0].request_id for e in shed] == [1]
        assert s.queue_len() == 2  # no-deadline + future-deadline stay
        assert s.shed_expired(now=10.0) == []  # idempotent

    def test_deadline_rides_admission_into_slot(self):
        s = self._sched(num_slots=1)
        s.submit(Request.make(0, [1, 2]), np.ones(2, np.int32), 0.0, 42.0)
        s.plan()
        slot = s.slots[0]
        assert slot.deadline == 42.0
        assert s.expired_slots(now=41.0) == []
        assert s.expired_slots(now=42.0) == [slot]
        s.retire(slot)
        assert slot.deadline == 0.0  # reset with the rest of the slot

    def test_cancel_still_works_with_deadline_entries(self):
        s = self._sched(num_slots=1)
        s.submit(Request.make(0, [1, 2]), np.ones(2, np.int32), 0.0, 9.0)
        assert s.cancel(0) is True
        assert s.queue_len() == 0


# -- retry helpers ------------------------------------------------------


class TestRetryHelpers:
    def test_backoff_envelope_and_retry_after_floor(self):
        import random

        rng = random.Random(0)
        for attempt in range(6):
            d = backoff_delay(attempt, base=0.1, cap=2.0, rng=rng)
            assert 0.0 <= d <= min(2.0, 0.1 * 2 ** attempt)
        # the server's Retry-After floors the jittered delay
        d = backoff_delay(0, base=0.1, cap=2.0, retry_after=7.5, rng=rng)
        assert d >= 7.5

    def test_call_with_retries_counts_and_rethrows_typed(self):
        sleeps = []
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise QueueFullError("full")
            return "ok"

        out, retries = call_with_retries(
            flaky, max_retries=5, retriable=(QueueFullError,),
            sleep=sleeps.append,
        )
        assert out == "ok" and retries == 2 and len(sleeps) == 2

        def always():
            raise EngineCrashError("dead")

        with pytest.raises(EngineCrashError):  # typed error survives
            call_with_retries(
                always, max_retries=1, retriable=(EngineCrashError,),
                sleep=sleeps.append,
            )
        with pytest.raises(ValueError):  # non-retriable: immediate
            call_with_retries(
                lambda: (_ for _ in ()).throw(ValueError("bad")),
                max_retries=5, retriable=(QueueFullError,),
                sleep=sleeps.append,
            )

    def test_retriable_false_instance_short_circuits(self):
        """A permanently failed engine raises the same CLASS as a
        restarting one but with retriable=False — no retries, and the
        attempts burned are reported on the exception."""

        def dead():
            e = EngineCrashError("restart budget exhausted")
            e.retriable = False
            raise e

        sleeps = []
        with pytest.raises(EngineCrashError) as ei:
            call_with_retries(dead, max_retries=5,
                              retriable=(EngineCrashError,),
                              sleep=sleeps.append)
        assert sleeps == []  # failed over immediately
        assert ei.value.retry_attempts == 0

    def test_http_non_retriable_503_codes_return_immediately(self):
        hits = {"n": 0}

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                hits["n"] += 1
                body = json.dumps(
                    {"error": "generation timed out", "code": "timeout"}
                ).encode()
                self.send_response(503)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            status, body, retries = http_post_json_with_retries(
                f"http://127.0.0.1:{httpd.server_address[1]}/x", {},
                max_retries=5, sleep=lambda s: None,
            )
            # a timeout-coded 503 already burned its full generation
            # budget server-side: retrying it amplifies the overload
            assert status == 503 and retries == 0 and hits["n"] == 1
            assert body["code"] == "timeout"
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_http_retries_honor_retry_after_on_503(self):
        hits = {"n": 0}

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                hits["n"] += 1
                body = json.dumps({"ok": hits["n"]}).encode()
                code = 503 if hits["n"] == 1 else 200
                self.send_response(code)
                if code == 503:
                    self.send_header("Retry-After", "0.05")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            sleeps = []
            status, body, retries = http_post_json_with_retries(
                f"http://127.0.0.1:{httpd.server_address[1]}/x", {},
                max_retries=3, sleep=sleeps.append,
            )
            assert status == 200 and body == {"ok": 2} and retries == 1
            assert sleeps and sleeps[0] >= 0.05  # honored Retry-After
        finally:
            httpd.shutdown()
            httpd.server_close()


# -- server-side deadlines ----------------------------------------------


def test_deadline_sheds_expired_at_admission():
    """A request whose deadline passed while queued never gets a slot:
    finish_reason 'deadline', zero tokens, no device work burned."""
    cfg, params = _setup("control")
    eng = ServingEngine(params, cfg, _serving())
    p = _prompts([4], cfg.vocab_size, seed=20)[0]
    rid = eng.submit(p, max_new_tokens=4, temperature=0.0,
                     deadline=time.perf_counter() - 1.0)
    outs = eng.step()
    assert [o.request_id for o in outs] == [rid]
    assert outs[0].finish_reason == "deadline"
    assert outs[0].tokens == []
    assert eng.stats["deadline_expired"] == 1
    assert eng.stats["prefill_tokens"] == 0  # truly shed, never prefilled
    assert all(s.state == FREE for s in eng.scheduler.slots)
    assert not eng.scheduler.has_work()


def test_deadline_retires_slot_mid_decode_and_reclaims_it():
    """An ACTIVE slot whose deadline passes mid-decode is retired with
    its partial tokens; the reclaimed slot serves the next request with
    bit-exact output (ring-mask invariant, same as cancel)."""
    cfg, params = _setup("control")
    eng = ServingEngine(params, cfg, _serving())
    p = _prompts([5], cfg.vocab_size, seed=21)[0]
    rid = eng.submit(p, max_new_tokens=24, temperature=0.0,
                     deadline=time.perf_counter() + 3600)
    for _ in range(3):  # prefill + a couple of decode steps
        eng.step()
    slot = eng.scheduler.slots[0]
    assert slot.state == ACTIVE and slot.request.request_id == rid
    n_before = len(slot.generated)
    assert n_before >= 1
    slot.deadline = time.perf_counter() - 1.0  # force expiry mid-decode
    outs = eng.step()
    assert [o.request_id for o in outs] == [rid]
    assert outs[0].finish_reason == "deadline"
    assert len(outs[0].tokens) == n_before  # partial output delivered
    assert outs[0].tokens == _ref_greedy(params, cfg, p, 24)[:n_before]
    assert eng.scheduler.slots[0].state == FREE  # KV slot reclaimed
    p2 = _prompts([6], cfg.vocab_size, seed=22)[0]
    out = eng.generate([p2], max_new_tokens=4, temperature=0.0)[0]
    assert out.tokens == _ref_greedy(params, cfg, p2, 4)


def test_default_deadline_from_config():
    cfg, params = _setup("control")
    eng = ServingEngine(
        params, cfg, _serving(default_deadline_s=0.5),
    )
    eng.submit(_prompts([4], cfg.vocab_size)[0], max_new_tokens=4)
    _req, _p, t_submit, deadline, _trace = eng.scheduler.queue[0]
    assert deadline == pytest.approx(t_submit + 0.5, abs=0.05)


def test_runner_delivers_typed_deadline_error():
    """Through the runner/client: an expired request raises
    DeadlineExceededError carrying the partial output, not a hang or a
    bare timeout."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(params, cfg, _serving()))
    try:
        with pytest.raises(DeadlineExceededError) as ei:
            client.generate(
                _prompts([4], cfg.vocab_size, seed=23)[0],
                max_new_tokens=4, temperature=0.0,
                deadline_s=0.0, timeout=60,
            )
        assert ei.value.output is not None
        assert ei.value.output.finish_reason == "deadline"
        assert client.stats["deadline_expired"] == 1
        # the engine is unharmed: a normal request still completes
        p = _prompts([4], cfg.vocab_size, seed=24)[0]
        out = client.generate(p, max_new_tokens=4, temperature=0.0,
                              timeout=60)
        assert out.tokens == _ref_greedy(params, cfg, p, 4)
    finally:
        client.close()


# -- engine supervision -------------------------------------------------


def test_step_exception_fails_pendings_promptly_without_restart():
    """THE hang-bug regression: with the restart budget at zero, an
    exception inside the engine step must fail every queued/in-flight
    pending promptly with a typed error — the old behavior delivered
    the raw exception only to admitted waiters and relied on the dead
    thread's stop flag for the rest."""
    cfg, params = _setup("control")
    serving = _serving(max_restarts=0)
    client = ServingClient(ServingEngine(params, cfg, serving))
    faults.arm("serve_raise@1")
    prompts = _prompts([4, 5, 6], cfg.vocab_size, seed=25)
    handles = [
        client.runner.submit(p, max_new_tokens=8, temperature=0.0)
        for p in prompts
    ]
    for h in handles:
        assert h.done.wait(60), "pending stranded after engine crash"
        assert isinstance(h.error, EngineCrashError)
    assert client.status() == "failed"
    with pytest.raises(EngineCrashError):  # submissions refused, typed
        client.runner.submit(prompts[0], max_new_tokens=2)
    client.close()


def test_supervised_restart_preserves_queue_and_is_bit_identical():
    """Tentpole pin: a mid-batch crash fails the slot-holding request
    with EngineCrashError, preserves wait-queue entries verbatim, and
    the rebuilt engine finishes them with exactly the tokens an
    uncrashed engine produces."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg, _serving(max_restarts=2),
    ))
    p_infl, p_queued = _prompts([5, 7], cfg.vocab_size, seed=26)
    faults.arm("serve_raise@2")  # request 0 holds the slot by then
    try:
        a = client.runner.submit(p_infl, max_new_tokens=16, temperature=0.0)
        b = client.runner.submit(p_queued, max_new_tokens=6, temperature=0.0)
        assert a.done.wait(60) and b.done.wait(60)
        assert isinstance(a.error, EngineCrashError)  # in-flight: typed fail
        assert b.error is None  # queued: rode through the restart
        assert b.result.tokens == _ref_greedy(params, cfg, p_queued, 6)
        assert client.runner.restarts == 1
        assert client.stats["engine_restarts"] == 1
        # the restarted engine serves a fresh request bit-identically
        p = _prompts([6], cfg.vocab_size, seed=27)[0]
        out = client.generate(p, max_new_tokens=6, temperature=0.0,
                              timeout=60)
        assert out.tokens == _ref_greedy(params, cfg, p, 6)
        assert client.status() == "healthy"
    finally:
        client.close()


def test_slot_corruption_trips_finite_guard_and_recovers():
    """serve_corrupt NaN-poisons an active slot's KV rows: the sampler's
    finite-logits guard turns that into EngineCrashError (never a
    silently-garbage token), and the supervised rebuild recovers."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg, _serving(max_restarts=2),
    ))
    faults.arm("serve_corrupt@2")
    try:
        a = client.runner.submit(
            _prompts([5], cfg.vocab_size, seed=28)[0],
            max_new_tokens=16, temperature=0.0,
        )
        assert a.done.wait(60)
        assert isinstance(a.error, EngineCrashError)
        assert "non-finite" in str(a.error)
        p = _prompts([4], cfg.vocab_size, seed=29)[0]
        out = client.generate(p, max_new_tokens=4, temperature=0.0,
                              timeout=60)
        assert out.tokens == _ref_greedy(params, cfg, p, 4)
    finally:
        client.close()


def test_outputs_finished_before_mid_step_crash_survive():
    """A request that finishes EARLY in a step whose decode then
    crashes is already retired from the scheduler — invisible to both
    the lost-list and the preserved queue. take_finished() must hand it
    back, or its caller hangs forever (code-review regression). Since
    the late read (ISSUE 41) the error is raised where the corrupted
    step's rows are READ, an iteration after the one that corrupted the
    slot: what is pinned is the slot, the request, and that nothing of
    that step was delivered while the prompt that finished before it
    was."""
    cfg, params = _setup("control")
    eng = ServingEngine(params, cfg, _serving(num_slots=2))
    p_long, p_short = _prompts([5, 4], cfg.vocab_size, seed=40)
    rid_b = eng.submit(p_long, max_new_tokens=16, temperature=0.0)
    eng.step()  # B prefills + goes ACTIVE
    (slot_b,) = eng.scheduler.active_slots()
    faults.arm(f"serve_corrupt@{eng.stats['iterations']}")
    # A's one token is sampled in the next step's PREFILL phase; the
    # corruption then poisons ACTIVE B and its decode step is dispatched
    rid_a = eng.submit(p_short, max_new_tokens=1, temperature=0.0)
    with pytest.raises(
        EngineCrashError,
        match=rf"decoding slot\(s\) \[{slot_b.index}\] "
              rf"\(request\(s\) \[{rid_b}\]\)",
    ):
        eng.run()
    # B holds what was sampled before the corruption (its first token
    # and the sound step dispatched with it), nothing of the step after
    assert slot_b.generated == _ref_greedy(params, cfg, p_long, 2)
    outs = eng.take_finished()
    assert [o.request_id for o in outs] == [rid_a]
    assert outs[0].finish_reason == "length"
    assert outs[0].tokens == _ref_greedy(params, cfg, p_short, 1)
    assert eng.reset_after_crash() == [rid_b]
    assert eng.take_finished() == []  # drained exactly once


def test_runner_delivers_pre_crash_outputs_to_waiters():
    """Runner-level delivery of the buffer: the finished-before-crash
    request gets its RESULT; only the genuinely lost one gets the
    typed error."""

    class _CrashAfterFinish:
        def __init__(self):
            self.serving = ServingConfig(num_slots=1, max_restarts=1)
            self.stats = {"rejected": 0}
            self.q = []
            self.crashed = False

        def queue_len(self):
            return len(self.q)

        def has_work(self):
            return bool(self.q)

        def submit(self, prompt, params=None):
            self.q.append(len(self.q))
            return len(self.q) - 1

        def cancel(self, rid):
            return False

        def take_finished(self):
            if not self.crashed:
                return []
            from differential_transformer_replication_tpu.serving import (
                RequestOutput,
            )

            return [RequestOutput(request_id=0, prompt=[1], tokens=[7],
                                  finish_reason="length")]

        def reset_after_crash(self):
            self.q.clear()
            return [1]  # rid 1 was "in flight"

        def step(self):
            if len(self.q) < 2:  # wait until both requests are in hand
                time.sleep(0.002)
                return []
            self.crashed = True
            raise RuntimeError("boom mid-step")

    runner = EngineRunner(_CrashAfterFinish())
    try:
        h0 = runner.submit([1], max_new_tokens=2)
        h1 = runner.submit([2], max_new_tokens=2)
        assert h0.done.wait(30) and h1.done.wait(30)
        assert h0.error is None and h0.result.tokens == [7]
        assert isinstance(h1.error, EngineCrashError)
    finally:
        runner.close()


def test_restart_budget_exhaustion_fails_hard():
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg, _serving(max_restarts=1),
    ))
    faults.arm("serve_raise@1,serve_raise@2,serve_raise@3")
    try:
        handles = [
            client.runner.submit(p, max_new_tokens=8, temperature=0.0)
            for p in _prompts([4, 5], cfg.vocab_size, seed=30)
        ]
        for h in handles:
            assert h.done.wait(60)
            assert isinstance(h.error, EngineCrashError)
        assert client.status() == "failed"
        assert client.runner.restarts == 2  # 1 rebuild + the fatal one
    finally:
        client.close()


def test_deadline_drain_restart_machinery_adds_zero_recompiles():
    """Compile pin (satellite): deadlines, drain bookkeeping and a
    full crash-restart cycle are host-side only — not one new cache
    entry on any of the engine's jitted closures."""
    cfg, params = _setup("control", vocab=47)  # fresh compile-cache key
    serving = _serving(num_slots=2, max_restarts=3)
    eng = ServingEngine(params, cfg, serving)
    eng.generate(_prompts([3, 9, 6], cfg.vocab_size, seed=31),
                 max_new_tokens=4, temperature=0.0)
    baseline = eng.compile_stats()
    assert baseline["decode"] == 1

    # deadline wave: one shed at admission, one expiring mid-decode
    eng.submit(_prompts([4], cfg.vocab_size, seed=32)[0],
               max_new_tokens=4, deadline=time.perf_counter() - 1.0)
    eng.submit(_prompts([5], cfg.vocab_size, seed=33)[0],
               max_new_tokens=12, temperature=0.0,
               deadline=time.perf_counter() + 3600)
    eng.step(); eng.step()
    for s in eng.scheduler.slots:
        if s.state != FREE:
            s.deadline = time.perf_counter() - 1.0
    eng.run()
    # crash/restart cycle on the same engine
    faults.arm(f"serve_raise@{eng.stats['iterations']}")
    eng.submit(_prompts([6], cfg.vocab_size, seed=34)[0],
               max_new_tokens=4, temperature=0.0)
    with pytest.raises(faults.FaultInjected):
        eng.run()
    eng.reset_after_crash()
    eng.run()
    assert eng.compile_stats() == baseline  # zero new compiles


# -- graceful drain -----------------------------------------------------


def test_drain_completes_inflight_rejects_new_and_closes():
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg, _serving(num_slots=2, drain_timeout_s=60),
    ))
    prompts = _prompts([5, 8, 4], cfg.vocab_size, seed=35)
    handles = [
        client.runner.submit(p, max_new_tokens=6, temperature=0.0)
        for p in prompts
    ]
    done = client.drain()
    assert done is True
    for p, h in zip(prompts, handles):  # zero lost in-flight requests
        assert h.done.is_set() and h.error is None
        assert h.result.tokens == _ref_greedy(params, cfg, p, 6)
    assert client.status() == "draining"
    with pytest.raises(ShuttingDownError):
        client.runner.submit(prompts[0], max_new_tokens=2)


def test_drain_budget_expiry_fails_stragglers_typed():
    """A drain that cannot finish in budget still never hangs anyone:
    leftovers get ShuttingDownError when the loop aborts."""

    class _NeverFinishes:
        def __init__(self):
            self.serving = ServingConfig(num_slots=1)
            self.stats = {"rejected": 0}
            self._q = []

        def queue_len(self):
            return len(self._q)

        def has_work(self):
            return bool(self._q)

        def submit(self, prompt, params=None):
            self._q.append(len(self._q))
            return len(self._q) - 1

        def cancel(self, rid):
            return False

        def step(self):
            time.sleep(0.005)
            return []

    runner = EngineRunner(_NeverFinishes())
    h = runner.submit([1], max_new_tokens=4)
    t0 = time.monotonic()
    assert runner.drain(timeout=0.3) is False
    assert time.monotonic() - t0 < 10
    assert h.done.wait(10)
    assert isinstance(h.error, ShuttingDownError)


def test_close_raises_on_stuck_engine_thread():
    """Satellite: close() must surface a thread that outlives its join
    timeout (wedged device call) instead of silently leaking it."""

    class _Stuck:
        def __init__(self):
            self.serving = ServingConfig(num_slots=1)
            self.stats = {"rejected": 0}
            self.release = threading.Event()
            self._q = []

        def queue_len(self):
            return len(self._q)

        def has_work(self):
            return bool(self._q)

        def submit(self, prompt, params=None):
            self._q.append(0)
            return 0

        def cancel(self, rid):
            return False

        def step(self):
            self.release.wait(30)  # a wedged device call
            self._q.clear()
            return []

    eng = _Stuck()
    runner = EngineRunner(eng)
    runner.submit([1], max_new_tokens=2)
    deadline = time.time() + 5
    while runner._step_started is None and time.time() < deadline:
        time.sleep(0.01)  # wait until the loop is inside step()
    with pytest.raises(RuntimeError, match="failed to stop"):
        runner.close(timeout=0.2)
    # a wedged engine reports FAILED, not a routine drain
    assert runner.status() == "failed"
    eng.release.set()  # unwedge so the daemon thread exits


# -- watchdog -----------------------------------------------------------

def test_watchdog_marks_degraded_then_recovers():
    class _Slow:
        def __init__(self):
            self.serving = ServingConfig(num_slots=1,
                                         step_time_budget_s=0.05)
            self.stats = {"rejected": 0}
            self._q = []
            self.durations = []
            self._rid = 0

        def queue_len(self):
            return len(self._q)

        def has_work(self):
            return bool(self._q)

        def submit(self, prompt, params=None):
            self._q.append(self._rid)
            self._rid += 1
            return self._rid - 1

        def cancel(self, rid):
            return False

        def step(self):
            if self.durations:
                time.sleep(self.durations.pop(0))
            if self._q:
                self._q.pop(0)
            return []  # requests never complete; irrelevant here

    eng = _Slow()
    runner = EngineRunner(eng)
    try:
        assert runner.status() == "healthy"
        eng.durations.append(0.4)  # 8x over budget
        runner.submit([1], max_new_tokens=2)
        deadline = time.time() + 10
        seen_degraded = False
        while time.time() < deadline:
            if runner.status() == "degraded":
                seen_degraded = True
                break
            time.sleep(0.005)
        assert seen_degraded  # flagged while (or right after) overrun
        eng.durations.append(0.0)
        runner.submit([1], max_new_tokens=2)  # a fast step clears it
        deadline = time.time() + 10
        while runner.status() != "healthy" and time.time() < deadline:
            time.sleep(0.005)
        assert runner.status() == "healthy"
        assert runner.last_step_s is not None
    finally:
        runner.close(timeout=10)


# -- HTTP surface -------------------------------------------------------


def test_http_health_ready_and_drain_503_with_retry_after():
    """/health carries status, /ready flips to 503 + Retry-After once
    draining, and /generate during drain is a typed 503."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(params, cfg, _serving()))
    httpd = serve(client, port=0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30
        ) as r:
            health = json.load(r)
        assert health["ok"] is True
        assert health["status"] == "healthy"
        assert "deadline_expired" in health["stats"]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/ready", timeout=30
        ) as r:
            assert json.load(r)["ready"] is True

        assert client.drain(timeout=30) is True

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/ready", timeout=30)
        assert ei.value.code == 503
        assert float(ei.value.headers["Retry-After"]) >= 1
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt_ids": [1, 2],
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
        assert "Retry-After" in ei.value.headers
        # machine-readable error typing — what retry clients key off
        assert json.loads(ei.value.read())["code"] == "shutting_down"
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30
        ) as r:
            health = json.load(r)
        assert health["ok"] is False and health["status"] == "draining"
    finally:
        httpd.shutdown()
        httpd.server_close()


# -- chaos (slow tier) --------------------------------------------------


@pytest.mark.slow
def test_chaos_engine_crash_under_concurrent_http_load():
    """Acceptance pin: a mid-batch engine crash under concurrent HTTP
    load -> every client gets a typed retriable failure or a successful
    retried response within its timeout (no hangs), and the restarted
    engine serves bit-identical greedy output for a fresh request."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg,
        _serving(num_slots=2, max_restarts=3),
    ))
    httpd = serve(client, port=0)
    port = httpd.server_address[1]
    url = f"http://127.0.0.1:{port}/generate"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    faults.arm("serve_raise@4")
    prompts = _prompts([5, 8, 3, 11, 6, 9], cfg.vocab_size, seed=36)
    results = [None] * len(prompts)

    def post(i):
        import random

        status, body, _r = http_post_json_with_retries(
            url, {"prompt_ids": prompts[i], "max_new_tokens": 8,
                  "temperature": 0.0, "timeout": 120},
            timeout=120, max_retries=4, base=0.05, cap=0.5,
            rng=random.Random(i),
        )
        results[i] = (status, body)

    try:
        threads = [
            threading.Thread(target=post, args=(i,))
            for i in range(len(prompts))
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
            assert not t.is_alive(), "client hung after engine crash"
        assert time.monotonic() - t0 < 180
        n_ok = 0
        for i, (status, body) in enumerate(results):
            assert status in (200, 503), (i, status, body)
            if status == 200:
                n_ok += 1
                assert body["tokens"] == _ref_greedy(
                    params, cfg, prompts[i], 8
                )
        assert n_ok >= 1  # retries landed on the rebuilt engine
        assert client.stats["engine_restarts"] >= 1
        # fresh request on the restarted engine: bit-identical
        p = _prompts([7], cfg.vocab_size, seed=37)[0]
        out = client.generate(p, max_new_tokens=8, temperature=0.0,
                              timeout=120)
        assert out.tokens == _ref_greedy(params, cfg, p, 8)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30
        ) as r:
            health = json.load(r)
        assert health["status"] == "healthy"
        assert health["restarts"] >= 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        client.close()


@pytest.mark.slow
def test_chaos_drain_under_load_loses_nothing():
    """Acceptance pin: drain() under concurrent load -> new requests
    rejected 503 + Retry-After, every accepted request completes
    bit-identically, drain finishes inside its budget."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg, _serving(num_slots=2, drain_timeout_s=120),
    ))
    httpd = serve(client, port=0)
    port = httpd.server_address[1]
    url = f"http://127.0.0.1:{port}/generate"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    prompts = _prompts([9, 6, 12, 5, 8], cfg.vocab_size, seed=38)
    codes = [None] * len(prompts)
    bodies = [None] * len(prompts)

    def post(i):
        req = urllib.request.Request(
            url, data=json.dumps({
                "prompt_ids": prompts[i], "max_new_tokens": 16,
                "temperature": 0.0, "timeout": 120,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                codes[i], bodies[i] = r.status, json.load(r)
        except urllib.error.HTTPError as e:
            codes[i] = e.code
            bodies[i] = {"retry_after": e.headers.get("Retry-After")}

    try:
        threads = [
            threading.Thread(target=post, args=(i,))
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        # wait until the engine actually has the load in hand
        deadline = time.time() + 60
        while time.time() < deadline and (
            client.runner.engine.stats["iterations"] < 1
        ):
            time.sleep(0.005)
        t0 = time.monotonic()
        drained = client.drain()  # budget 120s
        drain_wall = time.monotonic() - t0
        assert drained is True
        assert drain_wall < 120
        # post-drain: a new request is a fast 503 with Retry-After
        late = urllib.request.Request(
            url, data=json.dumps({"prompt_ids": prompts[0],
                                  "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(late, timeout=30)
        assert ei.value.code == 503
        assert "Retry-After" in ei.value.headers
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "request lost in drain"
        for i, code in enumerate(codes):
            # accepted -> completed bit-identically; the ones that hit
            # the drain window get the retriable 503
            assert code in (200, 503), (i, code, bodies[i])
            if code == 200:
                assert bodies[i]["tokens"] == _ref_greedy(
                    params, cfg, prompts[i], 16
                )
        assert codes.count(200) >= 1  # load was genuinely in flight
    finally:
        httpd.shutdown()
        httpd.server_close()


@pytest.mark.slow
def test_serve_bench_http_smoke_reports_error_breakdown():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "tools",
                      "serve_bench.py"),
         "--smoke", "--http"],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["http"] is True
    assert line["n_requests"] == 8
    assert line["failed"] == 0
    assert set(line["errors"]) == {
        "queue_full", "engine_crash", "deadline", "timeout",
        "shutting_down", "other",
    }


# -- the late read (ISSUE 41): what reaches the engine from outside while a
# -- decode step is in flight ---------------------------------------------------


def _with_a_step_in_flight(eng, rid, n=3):
    """Step until request ``rid`` has been read ``n`` tokens and a step
    that holds its row is dispatched and not read."""
    for _ in range(200):
        slot = eng._slot_for(rid)
        if (slot is not None and len(slot.generated) >= n
                and eng._inflight is not None
                and any(r == rid for _, r in eng._inflight.rows)):
            assert slot.dispatched == len(slot.generated) + 1
            return slot
        eng.step()
    raise AssertionError(f"request {rid} never had a step in flight")


@pytest.mark.parametrize("event", ["cancel", "deadline", "preempt_resume",
                                   "export_import", "reset_after_crash"])
def test_what_reaches_the_engine_with_a_step_in_flight(event):
    """A cancel, a deadline, a preemption and its resume, an export and
    its import on a peer, and a crash reset, each arriving while a decode
    step that holds the request's row is dispatched and not yet read:
    the row's in-flight token is dropped (cancel, deadline, reset) or
    read first (what snapshots the slot's host state whole), no token
    reaches a caller twice or after the end, the neighbour serves what
    it serves alone, and every slot and page comes back once."""
    cfg, params = _setup("control")
    a_p, b_p = _prompts([6, 9], cfg.vocab_size, seed=61)
    a_ref = _ref_greedy(params, cfg, a_p, 12)
    b_ref = _ref_greedy(params, cfg, b_p, 12)
    kw = dict(max_new_tokens=12, temperature=0.0)
    paged = dict(kv_page_size=8, kv_pool_pages=12)
    serving = {
        "cancel": _serving(num_slots=2),
        "deadline": _serving(num_slots=2),
        "preempt_resume": _serving(num_slots=2, host_tier_bytes=1 << 30,
                                   **paged),
        "export_import": _serving(num_slots=2, **paged),
        "reset_after_crash": _serving(num_slots=1),
    }[event]
    eng = ServingEngine(params, cfg, serving)
    a = eng.submit(a_p, **kw)
    b = eng.submit(b_p, **kw)
    slot = _with_a_step_in_flight(eng, a)
    read = list(slot.generated)
    assert read == a_ref[:len(read)]
    compiled = eng.compile_stats()  # shared with other tests' pools
    outs = {}

    if event == "cancel":
        assert eng.cancel(a) is True
        assert slot.state == FREE and eng._inflight is not None
        outs = {o.request_id: o for o in eng.run()}
        assert a not in outs
        assert eng.stats["lookahead_dropped_rows"] == 1
    elif event == "deadline":
        slot.deadline = time.perf_counter() - 1.0
        outs = {o.request_id: o for o in eng.run()}
        assert outs[a].finish_reason == "deadline"
        assert outs[a].tokens == read  # nothing after what was read
        assert eng.stats["lookahead_dropped_rows"] == 1
    elif event == "preempt_resume":
        eng._preempt_slot(slot)  # what the scheduler's hook calls
        assert eng.stats["preemptions"] == 1 and slot.state == FREE
        # the step in flight was read first: the snapshot holds its token
        assert eng._resume[a]["generated"] == a_ref[:len(read) + 1]
        outs = {o.request_id: o for o in eng.run()}
        assert eng.stats["resumes"] == 1
        assert outs[a].tokens == a_ref
        assert eng.stats["lookahead_dropped_rows"] == 0
    elif event == "export_import":
        dst = ServingEngine(params, cfg, serving)
        blob = eng.export_slot_state(a)
        assert len(slot.generated) == len(read) + 1  # read first
        assert eng.release_migrated(a) is True
        new = dst.import_state(blob)
        (moved,) = dst.run()
        assert moved.request_id == new and moved.tokens == a_ref
        outs = {o.request_id: o for o in eng.run()}
        assert a not in outs
    else:  # reset_after_crash: B still waits in the queue (one slot)
        assert eng.reset_after_crash() == [a]
        assert eng._inflight is None and not eng._firsts
        assert eng.take_finished() == []
        outs = {o.request_id: o for o in eng.run()}
        assert a not in outs
        assert eng.stats["engine_restarts"] == 1

    assert outs[b].tokens == b_ref and outs[b].finish_reason == "length"
    assert not eng.has_work() and eng._inflight is None
    assert all(s.state == FREE for s in eng.scheduler.slots)
    # nothing compiled for it; the paged programs of a swap or a copy
    # apart, which these events run for the first time
    assert eng.compile_stats()["decode"] == compiled["decode"]
    if eng.page_stats() is not None:
        st = eng.page_stats()
        assert st["free"] + st["cached"] == st["total"]


def test_a_read_outstanding_is_work_and_a_step_with_nothing_to_dispatch_reads_it():
    """``has_work()`` stays true while a dispatched step is unread, with
    no request left in the scheduler, and the ``step`` that finds nothing
    to dispatch reads it: the last token of the last request is delivered
    without another arrival, and a cancelled request's is dropped."""
    cfg, params = _setup("control")
    prompt = _prompts([5], cfg.vocab_size, seed=62)[0]
    eng = ServingEngine(params, cfg, _serving())
    rid = eng.submit(prompt, max_new_tokens=3, temperature=0.0)
    done = []
    while not done:
        # its last token dispatched, the slot waits for the read alone
        done = eng.step()
        assert eng.has_work() == (not done)
    assert done[0].tokens == _ref_greedy(params, cfg, prompt, 3)
    rid = eng.submit(prompt, max_new_tokens=8, temperature=0.0)
    _with_a_step_in_flight(eng, rid, n=2)
    assert eng.cancel(rid) is True
    assert not eng.scheduler.has_work() and eng.has_work()
    assert eng.step() == [] and not eng.has_work()
    assert eng.stats["lookahead_dropped_rows"] == 1

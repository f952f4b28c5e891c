"""End-to-end request tracing, fleet metrics aggregation, SLO gating
(ISSUE 7).

The load-bearing contracts:

- traceparent mint/parse round-trips, and anything malformed degrades
  into a fresh trace (never a failed request);
- spans emitted under a trace context carry ``trace_id``/``span_id``/
  ``parent_id`` args, and ONE ``trace_id`` links two in-process hops
  (a router-side forward span and the engine's request span parented
  to it) across two separate trace files — what tools/trace_stitch.py
  merges into one timeline;
- the engine stamps per-request lifecycle (admit / first_token /
  finish instants, a submit→finish ``request`` span) without touching
  its jitted closures: the decode compile count is PINNED at 1 with
  tracing and per-request trace contexts on;
- ``/fleet/metrics`` aggregation sums counters/histograms across
  replica bodies and labels gauges per replica, one TYPE per name;
- SLO burn-rate math matches hand-computed histograms, conservatively
  at non-bucket-edge thresholds;
- the structured event log records request/replica events with trace
  ids, append-mode, crash-tolerant;
- tools/slo_report.py and tools/trace_stitch.py gate/stitch from the
  command line (subprocess, like the other tool tests).

Quick tier throughout, except the slow fleet chaos test at the bottom:
a fault-injected retried request over a real 2-replica fleet whose
three trace files stitch into one validated timeline.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from functools import lru_cache
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import numpy as np
import pytest

from differential_transformer_replication_tpu.config import (
    ModelConfig,
    RouterConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import init_model
from differential_transformer_replication_tpu.obs import (
    EventLog,
    NOOP_EVENTS,
    Registry,
    SpanTracer,
    set_build_info,
)
from differential_transformer_replication_tpu.obs import trace as trace_mod
from differential_transformer_replication_tpu.obs.slo import (
    AvailabilityObjective,
    LatencyObjective,
    SLOMonitor,
    burn_rate,
    histogram_from_samples,
    latency_error_ratio,
)
from differential_transformer_replication_tpu.serving import (
    ServingClient,
    ServingEngine,
    serve,
)
from differential_transformer_replication_tpu.serving.router import (
    Router,
    aggregate_fleet_metrics,
    serve_router,
)
from differential_transformer_replication_tpu.utils import faults

from test_obs import assert_histogram_valid, parse_exposition

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _cfg(kind="control", vocab=61):
    return ModelConfig(
        model=kind, vocab_size=vocab, n_embd=32, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, compute_dtype="float32",
    )


@lru_cache(maxsize=None)
def _setup(kind="control", vocab=61):
    cfg = _cfg(kind, vocab)
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _prompts(lens, vocab, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=L).tolist() for L in lens]


# -- traceparent mint/parse ---------------------------------------------


class TestTraceContext:
    def test_mint_parse_round_trip(self):
        ctx = trace_mod.mint()
        assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
        parsed = trace_mod.parse_traceparent(ctx.to_traceparent())
        assert parsed == ctx

    def test_child_keeps_trace_changes_span(self):
        ctx = trace_mod.mint()
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.span_id != ctx.span_id

    @pytest.mark.parametrize("bad", [
        None, 42, "", "nonsense", "00-zz-bb-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # all-zero trace
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",   # short trace
        "00-" + "a" * 32 + "-" + "b" * 15 + "-01",   # short span
        "00-" + "a" * 32 + "-" + "b" * 16,            # missing flags
    ])
    def test_malformed_parses_to_none(self, bad):
        assert trace_mod.parse_traceparent(bad) is None

    def test_from_payload_mints_or_parses(self):
        ctx = trace_mod.mint()
        got = trace_mod.from_payload(
            {"traceparent": ctx.to_traceparent()}
        )
        assert got == ctx
        minted = trace_mod.from_payload({"traceparent": "garbage"})
        assert minted is not None and minted.trace_id != ctx.trace_id
        assert trace_mod.from_payload({}, mint_if_absent=False) is None

    def test_mint_is_unique(self):
        assert len({trace_mod.mint_trace_id() for _ in range(100)}) == 100


# -- parented spans across two in-process hops --------------------------


def test_parented_spans_link_two_hops_across_trace_files(tmp_path):
    """Hop 1 (a router) emits a ``forward`` span and serializes its
    child context to the wire; hop 2 (a replica) parses it and emits a
    ``request`` span. Both files are valid Chrome traces, share ONE
    trace_id, and the replica span's parent_id equals the forward
    span's span_id — the exact join trace_stitch aligns on."""
    router_path = str(tmp_path / "router.trace.json")
    replica_path = str(tmp_path / "replica.trace.json")
    t_router = SpanTracer(router_path, process_name="router")
    t_replica = SpanTracer(replica_path, process_name="replica")

    root = trace_mod.mint()
    fwd = root.child()
    wire = None
    with t_router.span("forward", replica="r0",
                       trace_id=root.trace_id, span_id=fwd.span_id,
                       parent_id=root.span_id):
        wire = fwd.to_traceparent()
        # hop 2: the "replica" parses the wire context
        ctx = trace_mod.parse_traceparent(wire)
        args = trace_mod.child_span_args(ctx)
        with t_replica.span("request", rid=0, **args):
            time.sleep(0.001)
    t_router.close()
    t_replica.close()

    router_events = json.load(open(router_path))
    replica_events = json.load(open(replica_path))
    fwd_ev = next(e for e in router_events
                  if e.get("name") == "forward")
    req_ev = next(e for e in replica_events
                  if e.get("name") == "request")
    assert fwd_ev["args"]["trace_id"] == root.trace_id
    assert req_ev["args"]["trace_id"] == root.trace_id
    # the replica hop parents to the forward hop's span id
    assert req_ev["args"]["parent_id"] == fwd_ev["args"]["span_id"]
    assert req_ev["args"]["span_id"] != fwd_ev["args"]["span_id"]


def test_noop_tracer_accepts_trace_calls():
    from differential_transformer_replication_tpu.obs import NOOP_TRACER

    ctx = trace_mod.mint()
    with NOOP_TRACER.span("x", **trace_mod.child_span_args(ctx)):
        pass
    NOOP_TRACER.complete("request", 0.0, 1.0,
                         **trace_mod.child_span_args(ctx))
    NOOP_TRACER.instant("admit", **trace_mod.instant_args(ctx))


# -- engine lifecycle stamping ------------------------------------------


def test_engine_stamps_request_lifecycle_with_trace(tmp_path):
    cfg, params = _setup("control")
    path = str(tmp_path / "engine.trace.json")
    tracer = SpanTracer(path, process_name="engine")
    eng = ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=8, prefill_budget=16),
        tracer=tracer,
    )
    ctx = trace_mod.mint()
    rid = eng.submit(_prompts([5], cfg.vocab_size)[0],
                     max_new_tokens=3, trace=ctx)
    outs = eng.run()
    tracer.close()
    assert outs[0].trace_id == ctx.trace_id

    events = json.load(open(path))
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    admit = by_name["admit"][0]
    # the instant (a span of that name holds the first token's dispatch;
    # the instant comes with the late read of the token)
    first = next(e for e in by_name["first_token"] if e["ph"] != "X")
    finish = by_name["finish"][0]
    request = by_name["request"][0]
    for ev in (admit, first, finish, request):
        assert ev["args"]["trace_id"] == ctx.trace_id
        assert ev["args"]["rid"] == rid
    # lifecycle instants hang off the caller's hop; the request span
    # is a child of it
    assert request["args"]["parent_id"] == ctx.span_id
    assert request["ph"] == "X" and request["dur"] > 0
    assert finish["args"]["reason"] == "length"
    # the batched decode span names the traces it advanced
    decode = by_name["decode"]
    assert any(
        ctx.trace_id in (e["args"].get("trace_ids") or [])
        for e in decode
    )


def test_untraced_requests_emit_lifecycle_without_trace_args(tmp_path):
    cfg, params = _setup("control")
    path = str(tmp_path / "e2.trace.json")
    tracer = SpanTracer(path)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=8, prefill_budget=16),
        tracer=tracer,
    )
    eng.submit(_prompts([4], cfg.vocab_size)[0], max_new_tokens=2)
    outs = eng.run()
    tracer.close()
    assert outs[0].trace_id is None
    events = json.load(open(path))
    req = next(e for e in events if e["name"] == "request")
    assert "trace_id" not in req["args"]


def test_tracing_with_trace_contexts_adds_zero_recompiles():
    """THE compile pin: trace stamping is host-side strings — decode
    compiles once whether requests are traced, untraced, or the tracer
    is off (the train-step twin is pinned in test_obs.py, which runs a
    traced trainer and asserts compile_events == 1)."""
    cfg, params = _setup("control", vocab=47)  # fresh compile-cache key
    serving = ServingConfig(num_slots=2, prefill_chunk=8,
                            prefill_budget=16)
    eng = ServingEngine(params, cfg, serving)
    eng.generate(_prompts([3, 9], cfg.vocab_size), max_new_tokens=3,
                 temperature=0.0)
    baseline = eng.compile_stats()
    assert baseline["decode"] == 1

    class _Sink:
        def span(self, name, **a):
            from differential_transformer_replication_tpu.obs.spans import (
                _NOOP_SPAN,
            )
            return _NOOP_SPAN

        def instant(self, *a, **k):
            pass

        def complete(self, *a, **k):
            pass

        counter = flush = close = instant

    eng2 = ServingEngine(params, cfg, serving, tracer=_Sink())
    # same prompt SHAPES as the baseline run — only the trace contexts
    # differ, and they must not add a single cache entry
    for i, p in enumerate(_prompts([3, 9], cfg.vocab_size)):
        eng2.submit(p, max_new_tokens=3,
                    trace=trace_mod.mint() if i % 2 == 0 else None)
    outs = eng2.run()
    assert len(outs) == 2
    assert eng2.compile_stats() == baseline  # zero new compiles


# -- server + router HTTP propagation -----------------------------------


class _EchoReplica(BaseHTTPRequestHandler):
    """Canned replica recording each request body; replies 200 with
    the received traceparent echoed."""

    received = None  # list, set per subclass
    script = None    # optional list of (status, body) before the 200s

    def do_POST(self):
        n = int(self.headers.get("Content-Length", "0"))
        payload = json.loads(self.rfile.read(n) or b"{}")
        self.received.append(payload)
        if self.script:
            status, body = self.script.pop(0)
        else:
            status, body = 200, {
                "request_id": 1, "prompt_ids": [1], "tokens": [2, 3],
                "finish_reason": "length", "ttft_ms": 1.0,
                "echo_traceparent": payload.get("traceparent"),
            }
        data = json.dumps(body).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *a):
        pass


def _echo_server(script=None):
    received = []
    handler = type("H", (_EchoReplica,),
                   {"received": received,
                    "script": list(script) if script else None})
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}", received


def _router_cfg(**kw):
    kw.setdefault("probe_interval_s", 0.05)
    kw.setdefault("probe_backoff_s", 0.05)
    kw.setdefault("retry_base_s", 0.001)
    kw.setdefault("retry_cap_s", 0.01)
    kw.setdefault("wait_for_replica_s", 0.0)
    return RouterConfig(**kw)


def _mark_up(*replicas):
    for r in replicas:
        r.note_probe_success(True, "healthy", {}, now=0.0)


class TestRouterTracePropagation:
    def test_router_mints_propagates_and_stamps(self, tmp_path):
        httpd, url, received = _echo_server()
        trace_path = str(tmp_path / "router.trace.json")
        events_path = str(tmp_path / "router.events.jsonl")
        router = Router(
            [url], _router_cfg(),
            tracer=SpanTracer(trace_path, process_name="router"),
            events=EventLog(events_path, process="router"),
        )
        _mark_up(*router.replicas)
        try:
            status, body, headers = router.handle_generate(
                {"prompt_ids": [1]}
            )
            assert status == 200
            # reply carries the minted trace id; the forwarded payload
            # carried a traceparent of the SAME trace, different span
            tid = body["trace_id"]
            assert len(tid) == 32
            fwd = trace_mod.parse_traceparent(
                received[0]["traceparent"]
            )
            assert fwd.trace_id == tid
        finally:
            router.tracer.close()
            router.events.close()
            httpd.shutdown()
            httpd.server_close()

        events = json.load(open(trace_path))
        names = {e["name"] for e in events if e["ph"] in ("X", "i")}
        assert {"pick", "forward"} <= names
        fwd_ev = next(e for e in events if e["name"] == "forward")
        assert fwd_ev["args"]["trace_id"] == tid
        # the traceparent the replica saw IS the forward span's id —
        # replica spans will parent to it in the stitched timeline
        assert fwd_ev["args"]["span_id"] == fwd.span_id
        log = [json.loads(l) for l in open(events_path)]
        fin = next(e for e in log if e["event"] == "request_finished")
        assert fin["trace_id"] == tid and fin["process"] == "router"

    def test_client_supplied_traceparent_is_honored(self):
        httpd, url, received = _echo_server()
        router = Router([url], _router_cfg())
        _mark_up(*router.replicas)
        try:
            ctx = trace_mod.mint()
            status, body, _ = router.handle_generate(
                {"prompt_ids": [1], "traceparent": ctx.to_traceparent()}
            )
            assert status == 200
            assert body["trace_id"] == ctx.trace_id
            fwd = trace_mod.parse_traceparent(
                received[0]["traceparent"]
            )
            assert fwd.trace_id == ctx.trace_id
            assert fwd.span_id != ctx.span_id  # a child hop, not a copy
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_retry_keeps_one_trace_and_logs_events(self, tmp_path):
        """A failed-over request: both attempts carry the SAME
        trace_id on DIFFERENT forward hops, the trace file shows the
        retry instant, the event log shows request_retried."""
        ha, url_a, rec_a = _echo_server(
            script=[(503, {"code": "queue_full"})]
        )
        hb, url_b, rec_b = _echo_server()
        trace_path = str(tmp_path / "r.trace.json")
        events_path = str(tmp_path / "r.events.jsonl")
        router = Router(
            [url_a, url_b], _router_cfg(),
            tracer=SpanTracer(trace_path),
            events=EventLog(events_path, process="router"),
        )
        _mark_up(*router.replicas)
        try:
            router._affinity["s"] = router.replicas[0]
            status, body, _ = router.handle_generate(
                {"prompt_ids": [1], "session_id": "s"}
            )
            assert status == 200 and body["attempts"] == 2
            tid = body["trace_id"]
            fwd_a = trace_mod.parse_traceparent(
                rec_a[0]["traceparent"]
            )
            fwd_b = trace_mod.parse_traceparent(
                rec_b[0]["traceparent"]
            )
            assert fwd_a.trace_id == tid == fwd_b.trace_id
            assert fwd_a.span_id != fwd_b.span_id
        finally:
            router.tracer.close()
            router.events.close()
            for h in (ha, hb):
                h.shutdown()
                h.server_close()
        trace = json.load(open(trace_path))
        retry = [e for e in trace if e["name"] == "retry"]
        assert retry and retry[0]["args"]["trace_id"] == tid
        forwards = [e for e in trace if e["name"] == "forward"]
        assert len(forwards) == 2
        log = [json.loads(l) for l in open(events_path)]
        retried = next(
            e for e in log if e["event"] == "request_retried"
        )
        assert retried["trace_id"] == tid
        assert retried["code"] == "queue_full"


def test_server_round_trip_emits_trace_and_events(tmp_path):
    """Replica server end to end: a posted traceparent reaches the
    engine, the reply echoes its trace_id, the trace file carries the
    parented request span, the event log records received+finished."""
    cfg, params = _setup("control")
    trace_path = str(tmp_path / "replica.trace.json")
    events_path = str(tmp_path / "replica.events.jsonl")
    tracer = SpanTracer(trace_path, process_name="replica")
    events = EventLog(events_path, process="replica")
    client = ServingClient(ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=8, prefill_budget=16),
        tracer=tracer,
    ))
    httpd = serve(client, port=0, events=events)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    ctx = trace_mod.mint()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({
                "prompt_ids": _prompts([5], cfg.vocab_size)[0],
                "max_new_tokens": 3, "temperature": 0.0,
                "traceparent": ctx.to_traceparent(),
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            body = json.load(r)
        assert body["trace_id"] == ctx.trace_id
        # an untraced request still gets a trace id (server-minted)
        req2 = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({
                "prompt_ids": _prompts([4], cfg.vocab_size)[0],
                "max_new_tokens": 2, "temperature": 0.0,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req2, timeout=120) as r:
            body2 = json.load(r)
        assert len(body2["trace_id"]) == 32
        assert body2["trace_id"] != ctx.trace_id
    finally:
        httpd.shutdown()
        httpd.server_close()
        client.close()
        tracer.close()
        events.close()
    trace = json.load(open(trace_path))
    req_span = next(
        e for e in trace
        if e["name"] == "request"
        and e.get("args", {}).get("trace_id") == ctx.trace_id
    )
    assert req_span["args"]["parent_id"] == ctx.span_id
    log = [json.loads(l) for l in open(events_path)]
    kinds = [e["event"] for e in log]
    assert kinds.count("request_received") == 2
    assert kinds.count("request_finished") == 2
    fin = next(e for e in log if e["event"] == "request_finished")
    assert fin["trace_id"] == ctx.trace_id


# -- /fleet/metrics aggregation -----------------------------------------


_REPLICA_BODY_A = """\
# HELP serving_requests_completed_total Requests finished normally.
# TYPE serving_requests_completed_total counter
serving_requests_completed_total 10
# TYPE serving_requests_finished_total counter
serving_requests_finished_total{reason="length"} 8
serving_requests_finished_total{reason="eos"} 2
# TYPE serving_slot_occupancy gauge
serving_slot_occupancy 2
# TYPE serving_ttft_seconds histogram
serving_ttft_seconds_bucket{le="0.1"} 4
serving_ttft_seconds_bucket{le="1"} 9
serving_ttft_seconds_bucket{le="+Inf"} 10
serving_ttft_seconds_sum 3.5
serving_ttft_seconds_count 10
"""

_REPLICA_BODY_B = """\
# TYPE serving_requests_completed_total counter
serving_requests_completed_total 30
# TYPE serving_requests_finished_total counter
serving_requests_finished_total{reason="length"} 30
# TYPE serving_slot_occupancy gauge
serving_slot_occupancy 4
# TYPE serving_ttft_seconds histogram
serving_ttft_seconds_bucket{le="0.1"} 10
serving_ttft_seconds_bucket{le="1"} 25
serving_ttft_seconds_bucket{le="+Inf"} 30
serving_ttft_seconds_sum 12.5
serving_ttft_seconds_count 30
"""


class TestFleetMetricsAggregation:
    def test_counters_sum_gauges_get_replica_labels(self):
        text = aggregate_fleet_metrics({
            "a:8101": _REPLICA_BODY_A, "b:8102": _REPLICA_BODY_B,
        })
        types, samples = parse_exposition(text)  # oracle: must parse
        vals = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        # counters sum across replicas, per label set
        assert vals[("serving_requests_completed_total", ())] == 40
        assert vals[("serving_requests_finished_total",
                     (("reason", "length"),))] == 38
        assert vals[("serving_requests_finished_total",
                     (("reason", "eos"),))] == 2
        # gauges keep per-replica identity
        assert vals[("serving_slot_occupancy",
                     (("replica", "a:8101"),))] == 2
        assert vals[("serving_slot_occupancy",
                     (("replica", "b:8102"),))] == 4
        # histograms sum per bucket and stay valid histograms
        assert types["serving_ttft_seconds"] == "histogram"
        assert_histogram_valid(samples, "serving_ttft_seconds")
        assert vals[("serving_ttft_seconds_bucket",
                     (("le", "0.1"),))] == 14
        assert vals[("serving_ttft_seconds_count", ())] == 40
        assert vals[("serving_ttft_seconds_sum", ())] == 16.0
        # exactly one TYPE line per family
        assert text.count("# TYPE serving_ttft_seconds ") == 1

    def test_own_metrics_pass_through_and_merge_types(self):
        own = (
            "# TYPE router_requests_total counter\n"
            'router_requests_total{replica="a:8101"} 7\n'
            "# TYPE build_info gauge\n"
            'build_info{role="router"} 1\n'
        )
        body = (
            "# TYPE build_info gauge\n"
            'build_info{role="replica"} 1\n'
        )
        text = aggregate_fleet_metrics({"a:8101": body}, own=own)
        types, samples = parse_exposition(text)
        assert text.count("# TYPE build_info ") == 1
        roles = {
            (l.get("role"), l.get("replica"))
            for n, l, v in samples if n == "build_info"
        }
        assert roles == {("router", None), ("replica", "a:8101")}
        vals = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        assert vals[("router_requests_total",
                     (("replica", "a:8101"),))] == 7

    def test_router_http_fleet_metrics_endpoint(self):
        router = Router(
            ["http://127.0.0.1:19101", "http://127.0.0.1:19102"],
            _router_cfg(),
        )
        a, b = router.replicas
        _mark_up(a, b)
        with a.lock:
            a.metrics_text = _REPLICA_BODY_A
        with b.lock:
            b.metrics_text = _REPLICA_BODY_B
        httpd = serve_router(router, port=0)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            with urllib.request.urlopen(
                url + "/fleet/metrics", timeout=30
            ) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
            types, samples = parse_exposition(text)
            vals = {n: v for n, l, v in samples if not l}
            # fleet-wide sum from canned replica bodies
            assert vals["serving_requests_completed_total"] == 40
            # the router's own metrics ride along...
            assert "router_replicas" in types
            # ...as does its build_info identity and the synthesized
            # per-replica up gauge
            assert types["build_info"] == "gauge"
            assert any(
                n == "build_info" and l.get("role") == "router"
                for n, l, v in samples
            )
            ups = {
                l["replica"]: v for n, l, v in samples
                if n == "fleet_replica_up"
            }
            assert set(ups) == {a.name, b.name}
            assert all(v == 1 for v in ups.values())
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_build_info_gauge_renders_through_oracle(self):
        reg = Registry()
        set_build_info(reg, role="replica", config_hash="abc123",
                       version="0.4.37")
        types, samples = parse_exposition(reg.render())
        assert types["build_info"] == "gauge"
        assert types["process_start_time_seconds"] == "gauge"
        info = next(l for n, l, v in samples if n == "build_info")
        assert info == {"role": "replica", "config_hash": "abc123",
                        "jax_version": "0.4.37"}
        start = next(
            v for n, l, v in samples
            if n == "process_start_time_seconds"
        )
        assert abs(start - time.time()) < 60


# -- SLO burn-rate math -------------------------------------------------


class TestSLOMath:
    # hand-computed: bounds (0.1, 0.5, 1.0), cumulative (60, 90, 99),
    # count 100 -> 1 observation above 1.0, 10 above 0.5, 40 above 0.1
    BOUNDS = (0.1, 0.5, 1.0)
    CUM = (60, 90, 99)

    def test_error_ratio_at_bucket_edges(self):
        assert latency_error_ratio(
            self.BOUNDS, self.CUM, 100, 0.5
        ) == pytest.approx(0.10)
        assert latency_error_ratio(
            self.BOUNDS, self.CUM, 100, 1.0
        ) == pytest.approx(0.01)
        assert latency_error_ratio(
            self.BOUNDS, self.CUM, 100, 0.1
        ) == pytest.approx(0.40)

    def test_threshold_between_edges_rounds_conservatively(self):
        # 0.75 sits between 0.5 and 1.0: only <=0.5 is provably good
        assert latency_error_ratio(
            self.BOUNDS, self.CUM, 100, 0.75
        ) == pytest.approx(0.10)
        # below every bound: nothing provably good
        assert latency_error_ratio(
            self.BOUNDS, self.CUM, 100, 0.05
        ) == pytest.approx(1.0)

    def test_burn_rate_math(self):
        # 10% errors against a 99% target = 10x budget burn
        assert burn_rate(0.10, 0.99) == pytest.approx(10.0)
        assert burn_rate(0.01, 0.99) == pytest.approx(1.0)
        assert burn_rate(0.0, 0.99) == 0.0
        assert burn_rate(None, 0.99) is None
        assert latency_error_ratio(self.BOUNDS, self.CUM, 0, 1.0) is None

    def test_monitor_evaluates_against_live_registry(self):
        reg = Registry()
        h = reg.histogram("ttft_seconds", "", buckets=(0.1, 0.5, 1.0))
        # 8 fast, 2 slow -> 20% above 0.5
        for _ in range(8):
            h.observe(0.05)
        for _ in range(2):
            h.observe(0.7)
        reg.counter("ok_total", "").inc(99)
        reg.counter("bad_total", "").inc(1)
        mon = SLOMonitor(
            reg,
            latency=[LatencyObjective("ttft", "ttft_seconds", 0.5, 0.9)],
            availability=[AvailabilityObjective(
                "availability", good=("ok_total",), bad=("bad_total",),
                target=0.99,
            )],
        )
        out = mon.evaluate()
        assert out["ttft"]["error_ratio"] == pytest.approx(0.2)
        assert out["ttft"]["burn_rate"] == pytest.approx(2.0)
        assert out["availability"]["error_ratio"] == pytest.approx(0.01)
        assert out["availability"]["burn_rate"] == pytest.approx(1.0)
        # results are re-exposed as gauges in the SAME registry
        types, samples = parse_exposition(reg.render())
        vals = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        assert vals[("slo_burn_rate",
                     (("objective", "ttft"),))] == pytest.approx(2.0)
        assert vals[("slo_target",
                     (("objective", "availability"),))] == 0.99
        # windowed burn: a clean second window reports zero burn even
        # though the lifetime ratio stays dirty
        for _ in range(10):
            h.observe(0.05)
        out2 = mon.evaluate()
        assert out2["ttft"]["window_error_ratio"] == pytest.approx(0.0)
        assert out2["ttft"]["error_ratio"] == pytest.approx(0.1)

    def test_histogram_from_samples_round_trips_exposition(self):
        reg = Registry()
        h = reg.histogram("x_seconds", "", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 2.0):
            h.observe(v)
        _, samples = parse_exposition(reg.render())
        bounds, cumulative, count = histogram_from_samples(
            samples, "x_seconds"
        )
        assert bounds == [0.1, 1.0]
        assert cumulative == [1, 2]
        assert count == 3
        assert latency_error_ratio(
            bounds, cumulative, count, 1.0
        ) == pytest.approx(1 / 3)

    def test_histogram_from_samples_sums_labeled_children(self):
        """A labeled histogram (two replicas' worth of children) must
        aggregate to ONE valid histogram — per-bound sums and a summed
        count — not interleave the children's ladders."""
        reg = Registry()
        h = reg.histogram("y_seconds", "", labelnames=("replica",),
                          buckets=(0.5,))
        for v in (0.1, 0.1, 0.1, 9.0):       # a: 3 fast, 1 slow
            h.observe(v, replica="a")
        for v in (0.1, 0.1, 9.0, 9.0, 9.0, 9.0):  # b: 2 fast, 4 slow
            h.observe(v, replica="b")
        _, samples = parse_exposition(reg.render())
        bounds, cumulative, count = histogram_from_samples(
            samples, "y_seconds"
        )
        assert bounds == [0.5]
        assert cumulative == [5]   # 3 + 2 fast across both children
        assert count == 10
        assert latency_error_ratio(
            bounds, cumulative, count, 0.5
        ) == pytest.approx(0.5)
        # match narrows to one child
        bounds, cumulative, count = histogram_from_samples(
            samples, "y_seconds", match={"replica": "a"}
        )
        assert cumulative == [3] and count == 4

    def test_slo_gauges_ride_the_server_metrics_endpoint(self):
        cfg, params = _setup("control")
        from differential_transformer_replication_tpu.obs.slo import (
            default_serving_objectives,
        )

        engine = ServingEngine(
            params, cfg,
            ServingConfig(num_slots=2, prefill_chunk=8,
                          prefill_budget=16),
        )
        latency, availability = default_serving_objectives()
        mon = SLOMonitor(engine.registry, latency=latency,
                         availability=availability)
        client = ServingClient(engine)
        httpd = serve(client, port=0, slo=mon)
        port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        try:
            client.generate(_prompts([4], cfg.vocab_size)[0],
                            max_new_tokens=2, timeout=120)
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30
            ) as r:
                text = r.read().decode()
            types, samples = parse_exposition(text)
            assert types["slo_burn_rate"] == "gauge"
            burns = {
                l["objective"]: v
                for n, l, v in samples if n == "slo_burn_rate"
            }
            # a single fast CPU request burns nothing
            assert burns.get("availability", 0.0) == 0.0
        finally:
            httpd.shutdown()
            httpd.server_close()
            client.close()


# -- structured event log -----------------------------------------------


class TestEventLog:
    def test_emit_flush_close_and_append(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        log = EventLog(path, process="test", flush_every=100)
        log.emit("request_finished", trace_id="abc", status=200)
        log.flush()
        first = [json.loads(l) for l in open(path)]
        assert first[0]["event"] == "request_finished"
        assert first[0]["process"] == "test"
        assert first[0]["trace_id"] == "abc"
        assert abs(first[0]["ts"] - time.time()) < 60
        log.close()
        log.close()  # idempotent
        log.emit("late")  # dropped, never corrupts the closed file
        # append mode: a relaunch extends, not truncates
        log2 = EventLog(path, process="test")
        log2.emit("relaunched")
        log2.close()
        lines = [json.loads(l) for l in open(path)]
        assert [e["event"] for e in lines] == [
            "request_finished", "relaunched"
        ]

    def test_unserializable_fields_degrade_to_repr(self, tmp_path):
        path = str(tmp_path / "e.jsonl")
        log = EventLog(path)
        log.emit("weird", obj=object())
        log.close()
        rec = json.loads(open(path).read())
        assert "object" in rec["obj"]

    def test_noop_is_silent(self):
        NOOP_EVENTS.emit("x", a=1)
        NOOP_EVENTS.flush()
        NOOP_EVENTS.close()


# -- tools: slo_report + trace_stitch -----------------------------------


class TestSLOReportTool:
    def _exposition(self, slow_count):
        reg = Registry()
        h = reg.histogram("serving_ttft_seconds", "",
                          buckets=(0.1, 0.5, 1.0))
        for _ in range(100 - slow_count):
            h.observe(0.05)
        for _ in range(slow_count):
            h.observe(2.0)
        reg.histogram("serving_itl_seconds", "",
                      buckets=(0.1, 0.5)).observe(0.01)
        reg.counter("serving_requests_completed_total", "").inc(100)
        reg.counter("serving_requests_rejected_total", "").inc(0)
        return reg.render()

    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(TOOLS, "slo_report.py"),
             *argv],
            capture_output=True, text=True, timeout=60,
        )

    def test_check_passes_inside_budget(self, tmp_path):
        path = str(tmp_path / "metrics.txt")
        open(path, "w").write(self._exposition(slow_count=1))
        r = self._run(path, "--check", "--ttft", "1.0",
                      "--target", "0.99")
        assert r.returncode == 0, r.stderr
        summary = json.loads(r.stdout)
        assert summary["ok"] is True
        assert summary["ttft"]["burn_rate"] == pytest.approx(1.0)
        assert summary["availability"]["error_ratio"] == 0.0

    def test_check_fails_on_burn(self, tmp_path):
        path = str(tmp_path / "metrics.txt")
        open(path, "w").write(self._exposition(slow_count=10))
        r = self._run(path, "--check", "--ttft", "1.0",
                      "--target", "0.99")
        assert r.returncode == 1
        assert "objective ttft" in r.stderr
        summary = json.loads(r.stdout)
        assert summary["ttft"]["burn_rate"] == pytest.approx(10.0)

    def test_from_metrics_jsonl_shared_input(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with open(path, "w") as fh:
            for i in range(10):
                fh.write(json.dumps({
                    "iter": i, "loss": 2.0, "learning_rate": 1e-3,
                    "step_time_ms": 80.0 if i else 5000.0,
                    "skipped_steps": 0,
                }) + "\n")
        # 1/10 steps above 500ms vs target 0.99 -> burn 10 -> fail
        r = self._run("--from-metrics-jsonl", path, "--check",
                      "--step-time-ms", "500", "--target", "0.99")
        assert r.returncode == 1
        assert "step_time" in r.stderr
        # metrics_report accepts the same flag spelling (satellite)
        r2 = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "metrics_report.py"),
             "--from-metrics-jsonl", path],
            capture_output=True, text=True, timeout=60,
        )
        assert r2.returncode == 0, r2.stderr
        assert json.loads(r2.stdout)["step_records"] == 10

    def test_no_traffic_is_not_an_outage(self, tmp_path):
        reg = Registry()
        reg.histogram("serving_ttft_seconds", "", buckets=(1.0,))
        path = str(tmp_path / "empty.txt")
        open(path, "w").write(reg.render())
        r = self._run(path, "--check")
        assert r.returncode == 0, r.stderr
        r = self._run(path, "--check", "--require-traffic")
        assert r.returncode == 1


class TestTraceStitch:
    def _make_traces(self, tmp_path, skew_us=0.0):
        """A router file + a replica file for one traced request; the
        replica's clock optionally skewed."""
        router_path = str(tmp_path / "router.trace.json")
        replica_path = str(tmp_path / "replica.trace.json")
        t_r = SpanTracer(router_path, process_name="router")
        t_p = SpanTracer(replica_path, process_name="replica")
        root = trace_mod.mint()
        fwd = root.child()
        with t_r.span("forward", trace_id=root.trace_id,
                      span_id=fwd.span_id, parent_id=root.span_id):
            with t_p.span("request",
                          **trace_mod.child_span_args(fwd)):
                time.sleep(0.01)
            time.sleep(0.002)
        t_r.close()
        t_p.close()
        if skew_us:
            events = json.load(open(replica_path))
            for e in events:
                if "ts" in e:
                    e["ts"] += skew_us
            json.dump(events, open(replica_path, "w"))
        return router_path, replica_path, root.trace_id

    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(TOOLS, "trace_stitch.py"),
             *argv],
            capture_output=True, text=True, timeout=60,
        )

    def test_stitch_merges_lanes_and_aligns_skewed_clocks(self, tmp_path):
        router_path, replica_path, tid = self._make_traces(
            tmp_path, skew_us=5_000_000.0  # replica clock 5s ahead
        )
        out = str(tmp_path / "stitched.json")
        r = self._run(router_path, replica_path, "-o", out)
        assert r.returncode == 0, r.stderr
        summary = json.loads(r.stdout)
        assert summary["files"] == 2
        # the 5s skew was detected and removed (to within the span)
        assert abs(summary["offsets_us"][1] + 5_000_000.0) < 50_000
        events = json.load(open(out))
        fwd = next(e for e in events if e.get("name") == "forward")
        req = next(e for e in events if e.get("name") == "request")
        assert fwd["pid"] != req["pid"]  # per-file lanes
        # after alignment the replica span lies inside its cause again
        assert fwd["ts"] <= req["ts"]
        assert req["ts"] + req["dur"] <= fwd["ts"] + fwd["dur"] + 1
        # process lanes keep their names
        names = {
            (e.get("args") or {}).get("name")
            for e in events if e.get("ph") == "M"
        }
        assert any(n and n.startswith("router") for n in names)

    def test_trace_id_filter(self, tmp_path):
        router_path, replica_path, tid = self._make_traces(tmp_path)
        out = str(tmp_path / "one.json")
        r = self._run(router_path, replica_path, "-o", out,
                      "--trace-id", tid)
        assert r.returncode == 0, r.stderr
        events = json.load(open(out))
        spans = [e for e in events if e.get("ph") != "M"]
        assert spans and all(
            tid == (e.get("args") or {}).get("trace_id")
            or tid in ((e.get("args") or {}).get("trace_ids") or [])
            for e in spans
        )
        # an unknown id exits nonzero (gate-style)
        r = self._run(router_path, replica_path,
                      "-o", str(tmp_path / "none.json"),
                      "--trace-id", "f" * 32)
        assert r.returncode == 1

    def test_truncated_input_is_repaired(self, tmp_path):
        router_path, replica_path, tid = self._make_traces(tmp_path)
        # simulate a crashed process: valid "[" + events, no "]"
        text = open(replica_path).read()
        torn = text.rstrip().rstrip("]").rstrip()
        torn = torn + '\n{"name": "torn'  # half-written tail
        open(replica_path, "w").write(torn)
        out = str(tmp_path / "s.json")
        r = self._run(router_path, replica_path, "-o", out)
        assert r.returncode == 0, r.stderr
        events = json.load(open(out))
        assert any(e.get("name") == "request" for e in events)


# -- serve_bench exemplars (satellite) ----------------------------------


def test_serve_bench_smoke_reports_slow_exemplars(tmp_path, capsys):
    """In-process --smoke run: every request minted a trace context,
    so the JSON line carries p99 exemplar trace ids and --trace-dir
    lands the engine's span trace next to them."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench_t", os.path.join(TOOLS, "serve_bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    trace_dir = str(tmp_path / "traces")
    argv = sys.argv
    sys.argv = ["serve_bench.py", "--smoke", "--trace-dir", trace_dir]
    try:
        bench.main()
    finally:
        sys.argv = argv
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["failed"] == 0
    exemplars = line["slow_exemplars"]
    assert 1 <= len(exemplars) <= 10
    for e in exemplars:
        assert len(e["trace_id"]) == 32 and e["ttft_ms"] > 0
    # slowest-first ordering
    ttfts = [e["ttft_ms"] for e in exemplars]
    assert ttfts == sorted(ttfts, reverse=True)
    assert line["trace_dir"] == trace_dir
    trace_file = os.path.join(trace_dir,
                              "serve_bench.engine.trace.json")
    events = json.load(open(trace_file))
    stamped = {
        (e.get("args") or {}).get("trace_id")
        for e in events if e.get("name") == "request"
    }
    # the exemplar ids are findable in the engine's own trace
    assert {e["trace_id"] for e in exemplars} <= stamped


# -- chaos (slow tier): retried request -> stitched fleet timeline ------


@pytest.mark.slow
def test_chaos_retried_request_produces_stitched_timeline(tmp_path):
    """Acceptance pin (ISSUE 7): a 2-replica fleet (tools/fleet.py,
    every process writing its own trace + event log) serves a request
    whose first attempt CRASHES mid-decode on replica A (injected
    ``serve_raise``); the router fails it over to replica B. One
    ``trace_id`` must then span router pick -> forward to A -> failed
    attempt on A -> retry -> forward to B -> B's admit/first_token/
    finish + decode spans, all inside ONE stitched Perfetto file
    (tools/trace_stitch.py), validated structurally. Engine compile
    pins hold on both replicas (decode == 1: tracing + the supervised
    restart added no shapes)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fleet", os.path.join(TOOLS, "fleet.py")
    )
    fleet_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_mod)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("DTX_FAULTS", None)
    replica_trace = str(tmp_path / "replica-{replica}.trace.json")
    replica_events = str(tmp_path / "replica-{replica}.events.jsonl")
    fleet = fleet_mod.Fleet(
        2,
        server_args=[
            "--num-slots", "2", "--prefill-chunk", "16",
            "--prefill-budget", "32", "--drain-timeout", "30",
            "--restart-backoff", "0.2",
            "--trace-path", replica_trace,
            "--event-log", replica_events,
        ],
        env=env,
        # the injected fault arms replica 0 ONLY: its engine raises at
        # engine iteration 2 — mid-decode of our traced request
        replica_env={0: {"DTX_FAULTS": "serve_raise@2"}},
        max_restarts=3, backoff_base=0.2, backoff_max=2.0,
        ready_timeout_s=180.0,
        fleet_log=str(tmp_path / "fleet.events.jsonl"),
    )
    router_trace = str(tmp_path / "router.trace.json")
    router_events = str(tmp_path / "router.events.jsonl")
    router = None
    try:
        fleet.start()
        cfg = RouterConfig(
            probe_interval_s=0.05, probe_backoff_s=0.05,
            eject_after=3, readmit_after=2, max_attempts=4,
            retry_base_s=0.02, retry_cap_s=0.2, retry_after_cap_s=0.5,
            default_deadline_s=120.0, wait_for_replica_s=5.0,
        )
        router = Router(
            fleet.urls, cfg,
            tracer=SpanTracer(router_trace, process_name="router"),
            events=EventLog(router_events, process="router"),
        ).start()
        rep_a, rep_b = router.replicas

        # pin the session to replica A so the FIRST attempt lands on
        # the armed fault deterministically
        router._affinity["s"] = rep_a
        status, body, _ = router.handle_generate({
            "prompt_ids": [1, 2, 3, 4],
            "max_new_tokens": 8, "temperature": 0.0, "seed": 0,
            "session_id": "s",
        })
        assert status == 200, body
        assert body["attempts"] == 2
        assert body["replica"] == rep_b.name  # failed over A -> B
        tid = body["trace_id"]
        assert len(tid) == 32

        # compile pins on BOTH replicas: the crashed+rebuilt engine on
        # A and the healthy engine on B each sit at decode == 1
        for r_url in fleet.urls:
            deadline = time.time() + 60
            while True:
                with urllib.request.urlopen(r_url + "/health",
                                            timeout=30) as r:
                    health = json.load(r)
                if health["status"] == "healthy":
                    break
                assert time.time() < deadline, (r_url, health)
                time.sleep(0.1)
            assert health["compiles"]["decode"] == 1, (r_url, health)
        # the crash was real: A's engine restarted once
        with urllib.request.urlopen(fleet.urls[0] + "/health",
                                    timeout=30) as r:
            assert json.load(r)["stats"]["engine_restarts"] == 1
    finally:
        if router is not None:
            router.close()
            router.tracer.close()
            router.events.close()
        fleet.stop()  # SIGTERM: replicas drain + close their tracers

    # -- stitch all three processes into one timeline -------------------
    trace_a = replica_trace.replace("{replica}", "0")
    trace_b = replica_trace.replace("{replica}", "1")
    stitched_path = str(tmp_path / "stitched.trace.json")
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "trace_stitch.py"),
         router_trace, trace_a, trace_b, "-o", stitched_path,
         "--trace-id", tid],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout)
    assert summary["files"] == 3 and summary["span_events"] > 0

    events = json.load(open(stitched_path))
    spans = [e for e in events if e.get("ph") != "M"]
    # every surviving event belongs to OUR trace
    for e in spans:
        args = e.get("args") or {}
        assert (args.get("trace_id") == tid
                or tid in (args.get("trace_ids") or [])), e
    by_lane = {}
    for e in spans:
        by_lane.setdefault(e["pid"], []).append(e["name"])
    # lane 0 = router: pick, two forwards (A then B), the retry marker
    assert by_lane[0].count("forward") == 2
    assert "pick" in by_lane[0] and "retry" in by_lane[0]
    # lane 1 = replica A: the FAILED attempt still left its admission
    # (and decode work) in the timeline
    assert "admit" in by_lane[1], by_lane
    # lane 2 = replica B: the successful attempt end to end
    for name in ("admit", "first_token", "finish", "request"):
        assert name in by_lane[2], by_lane
    assert "decode" in by_lane[2]
    # B's request span parents to the router's SECOND forward hop
    fwd_span_ids = [
        e["args"]["span_id"] for e in spans
        if e["name"] == "forward"
    ]
    req_b = next(e for e in spans
                 if e["name"] == "request" and e["pid"] == 2)
    assert req_b["args"]["parent_id"] in fwd_span_ids
    # clocks are one host: alignment applied only µs-scale offsets
    assert all(abs(o) < 1e6 for o in summary["offsets_us"])

    # -- and the event logs tell the same story by trace_id -------------
    router_log = [json.loads(l) for l in open(router_events)]
    assert any(e["event"] == "request_retried"
               and e["trace_id"] == tid for e in router_log)
    assert any(e["event"] == "request_finished"
               and e["trace_id"] == tid for e in router_log)
    a_log = [json.loads(l)
             for l in open(replica_events.replace("{replica}", "0"))]
    failed = next(e for e in a_log if e["event"] == "request_failed")
    assert failed["code"] == "engine_crash"
    assert failed["trace_id"] == tid
    b_log = [json.loads(l)
             for l in open(replica_events.replace("{replica}", "1"))]
    assert any(e["event"] == "request_finished"
               and e["trace_id"] == tid for e in b_log)

"""Telemetry-layer tests (obs/): registry + Prometheus exposition, span
tracer, /metrics endpoints, engine/trainer instrumentation, and the
zero-overhead pins (no added recompiles in the jitted hot paths).

All quick (tier-1): tiny models, in-process HTTP servers on ephemeral
ports, a ~10-iteration trainer run.
"""

import json
import math
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request
from functools import lru_cache

import jax
import numpy as np
import pytest

from differential_transformer_replication_tpu.config import (
    ModelConfig,
    ServingConfig,
    TrainConfig,
)
from differential_transformer_replication_tpu.models import init_model
from differential_transformer_replication_tpu.obs import (
    NOOP_TRACER,
    Registry,
    SpanTracer,
    start_metrics_server,
)
from differential_transformer_replication_tpu.obs.introspect import (
    lambda_record,
    make_param_summary,
)
from differential_transformer_replication_tpu.obs.registry import StatsMap
from differential_transformer_replication_tpu.serving import (
    ServingClient,
    ServingEngine,
    serve,
)
from differential_transformer_replication_tpu.utils import faults

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")

TINY_MODEL = dict(vocab_size=256, n_embd=32, n_head=2, n_layer=2,
                  block_size=16, dropout=0.0, compute_dtype="float32")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _cfg(kind="control", vocab=59):
    return ModelConfig(
        model=kind, vocab_size=vocab, n_embd=32, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
    )


@lru_cache(maxsize=None)
def _setup(kind="control", vocab=59):
    cfg = _cfg(kind, vocab)
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _prompts(lens, vocab, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=L).tolist() for L in lens]


# -- a minimal Prometheus text-exposition parser (the test oracle) ------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+)$'
)
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text: str):
    """-> (types {name: kind}, samples [(name, {label: value}, float)]).
    Raises on malformed lines — the validity check itself."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert kind in ("counter", "gauge", "histogram"), line
            types[name] = kind
            continue
        if line.startswith("#"):
            assert line.startswith("# HELP "), f"stray comment: {line!r}"
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        labels = {}
        if m.group(2):
            for lm in _LABEL_RE.finditer(m.group(2)):
                labels[lm.group(1)] = (
                    lm.group(2).replace('\\"', '"')
                    .replace("\\n", "\n").replace("\\\\", "\\")
                )
        samples.append((m.group(1), labels, float(m.group(3))))
    return types, samples


def _hist_buckets(samples, name, match=None):
    """le -> cumulative count for one histogram child, in exposition
    order."""
    out = []
    for n, labels, v in samples:
        if n != f"{name}_bucket":
            continue
        if match and any(labels.get(k) != mv for k, mv in match.items()):
            continue
        out.append((labels["le"], v))
    return out


def assert_histogram_valid(samples, name, match=None):
    buckets = _hist_buckets(samples, name, match)
    assert buckets, f"no buckets for {name}"
    assert buckets[-1][0] == "+Inf"
    counts = [c for _, c in buckets]
    assert counts == sorted(counts), f"{name} buckets not monotone"
    count = [v for n, l, v in samples if n == f"{name}_count"
             and (not match or all(l.get(k) == mv
                                   for k, mv in (match or {}).items()))]
    assert count and count[0] == counts[-1]  # _count == +Inf bucket


# -- registry + exposition ---------------------------------------------


class TestRegistry:
    def test_exposition_names_types_and_values(self):
        reg = Registry()
        c = reg.counter("requests_total", "Requests.")
        c.inc()
        c.inc(2)
        g = reg.gauge("queue_depth", "Depth.")
        g.set(7)
        h = reg.histogram("latency_seconds", "Latency.",
                          buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        types, samples = parse_exposition(reg.render())
        assert types == {"requests_total": "counter",
                         "queue_depth": "gauge",
                         "latency_seconds": "histogram"}
        vals = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
        assert vals[("requests_total", ())] == 3
        assert vals[("queue_depth", ())] == 7
        assert_histogram_valid(samples, "latency_seconds")
        assert vals[("latency_seconds_count", ())] == 4
        assert abs(vals[("latency_seconds_sum", ())] - 55.55) < 1e-9
        # exact cumulative ladder
        assert _hist_buckets(samples, "latency_seconds") == [
            ("0.1", 1), ("1", 2), ("10", 3), ("+Inf", 4)
        ]

    def test_labels_and_escaping(self):
        reg = Registry()
        c = reg.counter("events_total", 'Help with \\ and\nnewline.',
                        labelnames=("kind",))
        nasty = 'quote " backslash \\ newline \n end'
        c.inc(kind=nasty)
        c.inc(kind="plain")
        text = reg.render()
        # escaping keeps the exposition line-oriented: exactly one HELP
        # line despite the raw newline in the help text / label value
        assert sum(
            1 for l in text.splitlines() if l.startswith("# HELP")
        ) == 1
        types, samples = parse_exposition(text)
        labels = {l["kind"] for n, l, v in samples if n == "events_total"}
        assert labels == {nasty, "plain"}  # round-trips through escaping

    def test_histogram_label_children_are_independent(self):
        reg = Registry()
        h = reg.histogram("op_seconds", "", labelnames=("op",),
                          buckets=(1.0,))
        h.observe(0.5, op="a")
        h.observe(2.0, op="b")
        _, samples = parse_exposition(reg.render())
        assert_histogram_valid(samples, "op_seconds", match={"op": "a"})
        assert_histogram_valid(samples, "op_seconds", match={"op": "b"})
        assert ("op_seconds_count", {"op": "a"}, 1.0) in samples

    def test_name_and_type_guards(self):
        reg = Registry()
        with pytest.raises(ValueError):
            reg.counter("bad name", "")
        with pytest.raises(ValueError):
            reg.counter("1leading", "")
        reg.counter("ok_total", "")
        with pytest.raises(ValueError):  # same name, different type
            reg.gauge("ok_total", "")
        with pytest.raises(ValueError):
            reg.histogram("h", "", buckets=(1.0, 1.0))
        with pytest.raises(ValueError):
            reg.counter("neg_total", "").inc(-1)

    def test_get_or_create_returns_same_metric(self):
        reg = Registry()
        assert reg.counter("a_total", "") is reg.counter("a_total", "")

    def test_concurrent_increments_do_not_tear(self):
        reg = Registry()
        c = reg.counter("n_total", "")

        def bump():
            for _ in range(1000):
                c.inc()

        threads = [threading.Thread(target=bump) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000

    def test_stats_map_is_dict_compatible(self):
        reg = Registry()
        stats = StatsMap(reg, {
            "completed": ("x_completed_total", ""),
            "rejected": ("x_rejected_total", ""),
        })
        stats.inc("completed")
        stats["rejected"] += 2  # the compat path
        assert stats["completed"] == 1 and stats["rejected"] == 2
        assert dict(stats) == {"completed": 1, "rejected": 2}
        assert stats.snapshot() == {"completed": 1, "rejected": 2}
        assert "completed" in stats and len(stats) == 2
        # the registry sees the same values — one source of truth
        _, samples = parse_exposition(reg.render())
        vals = {n: v for n, l, v in samples}
        assert vals["x_completed_total"] == 1
        assert vals["x_rejected_total"] == 2


# -- span tracer --------------------------------------------------------


class TestSpanTracer:
    def test_nested_and_threaded_spans_emit_valid_chrome_json(self, tmp_path):
        path = str(tmp_path / "t.trace.json")
        tracer = SpanTracer(path, process_name="test", flush_every=3)

        with tracer.span("outer", step=1):
            with tracer.span("inner"):
                time.sleep(0.002)
            tracer.instant("marker", note="hi")

        # a thread id is reused as soon as its thread has ended: hold
        # all three at a barrier until each has emitted its span, so
        # that the three ids are those of three threads alive at once
        emitted = threading.Barrier(3)

        def worker(i):
            with tracer.span("worker", idx=i):
                time.sleep(0.001)
            emitted.wait(timeout=30)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        tracer.close()
        tracer.close()  # idempotent

        events = json.load(open(path))  # valid JSON array
        assert isinstance(events, list)
        by_name = {}
        for ev in events:
            assert {"name", "ph", "pid"} <= set(ev)
            if ev["ph"] in ("X", "i"):
                assert "ts" in ev
            by_name.setdefault(ev["name"], []).append(ev)
        outer, inner = by_name["outer"][0], by_name["inner"][0]
        for ev in (outer, inner):
            assert ev["ph"] == "X" and ev["dur"] >= 0
        # nesting: inner lies within outer on the SAME thread track
        assert inner["tid"] == outer["tid"]
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        # three worker spans, each carrying its own thread id
        workers = by_name["worker"]
        assert len(workers) == 3
        assert len({w["tid"] for w in workers}) == 3
        assert by_name["marker"][0]["ph"] == "i"
        # metadata names the process for the viewer
        assert any(e["ph"] == "M" for e in events)

    def test_late_events_after_close_are_dropped(self, tmp_path):
        path = str(tmp_path / "t2.trace.json")
        tracer = SpanTracer(path)
        tracer.instant("a")
        tracer.close()
        tracer.instant("b")  # must not corrupt the closed file
        events = json.load(open(path))
        assert "b" not in {e["name"] for e in events}

    def test_noop_tracer_is_free_and_silent(self):
        with NOOP_TRACER.span("x", a=1):
            pass
        NOOP_TRACER.instant("y")
        NOOP_TRACER.flush()
        NOOP_TRACER.close()

    def test_annotate_enters_spans_as_profiler_annotations(
        self, tmp_path, monkeypatch
    ):
        """While ``annotate`` is on (a capture window flips it through
        ``annotate_spans``) every span is also entered as a
        ``jax.profiler.TraceAnnotation`` of its name, and still
        recorded; off, none is; NOOP_TRACER and None have no switch."""
        from differential_transformer_replication_tpu.obs.spans import (
            annotate_spans,
        )

        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(("enter", self.name))

            def __exit__(self, *exc):
                entered.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        path = str(tmp_path / "a.trace.json")
        tracer = SpanTracer(path)
        assert tracer.annotate is False
        with tracer.span("before"):
            pass
        annotate_spans(tracer, True)
        with tracer.span("outer", iteration=3):
            # a window that closes inside a span leaves it whole
            annotate_spans(tracer, False)
            with tracer.span("inner"):
                pass
        tracer.close()
        assert entered == [("enter", "outer"), ("exit", "outer")]
        names = [e["name"] for e in json.load(open(path)) if e["ph"] == "X"]
        assert names == ["before", "inner", "outer"]
        annotate_spans(NOOP_TRACER, True)  # nothing to flip, nothing raised
        annotate_spans(None, True)
        assert not hasattr(NOOP_TRACER, "annotate")


# -- serving instrumentation -------------------------------------------


def test_engine_populates_latency_histograms_and_gauges():
    cfg, params = _setup("control")
    eng = ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=8, prefill_budget=16),
    )
    outs = eng.generate(_prompts([3, 9, 5], cfg.vocab_size, seed=2),
                        max_new_tokens=4, temperature=0.0)
    assert len(outs) == 3
    types, samples = parse_exposition(eng.registry.render())
    assert types["serving_ttft_seconds"] == "histogram"
    assert types["serving_itl_seconds"] == "histogram"
    assert types["serving_queue_wait_seconds"] == "histogram"
    assert types["serving_slot_occupancy"] == "gauge"
    assert types["serving_kv_utilization"] == "gauge"
    for h in ("serving_ttft_seconds", "serving_itl_seconds",
              "serving_queue_wait_seconds", "serving_engine_step_seconds"):
        assert_histogram_valid(samples, h)
    vals = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
    # one TTFT observation per request; ITL fills the remaining tokens
    assert vals[("serving_ttft_seconds_count", ())] == 3
    assert vals[("serving_itl_seconds_count", ())] == 3 * (4 - 1)
    assert vals[("serving_queue_wait_seconds_count", ())] == 3
    # idle engine: gauges fell back to zero after the last retirement
    assert vals[("serving_slot_occupancy", ())] == 0
    assert vals[("serving_kv_utilization", ())] == 0
    assert vals[("serving_slots", ())] == 2
    # finish-reason labels
    assert vals[("serving_requests_finished_total",
                 (("reason", "length"),))] == 3


def test_engine_stats_and_registry_agree_after_chaos_restart():
    """The StatsMap satellite: engine.stats and the /metrics counters
    are the SAME values — including across a crash + slot-pool rebuild
    (reset_after_crash keeps the registry)."""
    cfg, params = _setup("control", vocab=43)  # fresh compile-cache key
    eng = ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=8, prefill_budget=16),
    )
    eng.generate(_prompts([3, 6], cfg.vocab_size, seed=3),
                 max_new_tokens=3, temperature=0.0)
    faults.arm(f"serve_raise@{eng.stats['iterations']}")
    eng.submit(_prompts([4], cfg.vocab_size, seed=4)[0], max_new_tokens=3)
    with pytest.raises(faults.FaultInjected):
        eng.run()
    eng.reset_after_crash()
    eng.run()

    snap = eng.stats.snapshot()
    assert snap["engine_restarts"] == 1
    _, samples = parse_exposition(eng.registry.render())
    vals = {n: v for n, l, v in samples if not l}
    from differential_transformer_replication_tpu.serving.engine import (
        _STAT_SPEC,
    )
    for key, (metric_name, _) in _STAT_SPEC.items():
        assert vals[metric_name] == snap[key], key


def test_engine_observability_adds_zero_recompiles():
    """Overhead pin: histograms, gauges, stats and spans are host-side
    only — the decode closure still compiles exactly once however
    requests come and go, tracer on or off."""
    cfg, params = _setup("control", vocab=41)  # fresh compile-cache key
    serving = ServingConfig(num_slots=2, prefill_chunk=8, prefill_budget=8)
    eng = ServingEngine(params, cfg, serving)
    eng.generate(_prompts([2, 7, 5], cfg.vocab_size, seed=5),
                 max_new_tokens=4, temperature=0.0)
    baseline = eng.compile_stats()
    assert baseline["decode"] == 1

    class _CountingTracer:
        def __init__(self):
            self.spans = 0

        def span(self, name, **a):
            self.spans += 1
            return NOOP_TRACER.span(name)

        instant = complete = flush = close = staticmethod(
            lambda *a, **k: None
        )

    tracer = _CountingTracer()
    eng2 = ServingEngine(params, cfg, serving, tracer=tracer)
    eng2.generate(_prompts([4, 9, 3, 6], cfg.vocab_size, seed=6),
                  max_new_tokens=5, temperature=0.7, top_k=3, seed=11)
    assert tracer.spans > 0  # instrumentation actually ran
    assert eng2.compile_stats() == baseline  # zero new compiles


def test_http_metrics_endpoint_round_trip():
    """GET /metrics on a live server returns valid Prometheus text
    exposition including the TTFT/ITL histograms and slot gauges (the
    acceptance criterion)."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=8, prefill_budget=16),
    ))
    httpd = serve(client, port=0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({
                "prompt_ids": _prompts([5], cfg.vocab_size, seed=7)[0],
                "max_new_tokens": 4, "temperature": 0.0,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as r:
            assert r.status == 200
            ctype = r.headers["Content-Type"]
            body = r.read().decode("utf-8")
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        types, samples = parse_exposition(body)
        assert types["serving_ttft_seconds"] == "histogram"
        assert types["serving_itl_seconds"] == "histogram"
        assert types["serving_slot_occupancy"] == "gauge"
        assert_histogram_valid(samples, "serving_ttft_seconds")
        assert_histogram_valid(samples, "serving_itl_seconds")
        vals = {n: v for n, l, v in samples if not l}
        assert vals["serving_ttft_seconds_count"] >= 1
        assert vals["serving_requests_completed_total"] == 1
        # /health still carries the dict view of the SAME counters
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30
        ) as r:
            health = json.load(r)
        assert health["stats"]["completed"] == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        client.close()


def test_stats_snapshot_is_consistent_under_load():
    """The locking satellite: /health-style snapshots taken WHILE the
    engine thread hammers the counters never tear (every value is a
    plausible monotone int, never a half-written update)."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=8, prefill_budget=16),
    ))
    stop = threading.Event()
    seen = []
    errors = []

    def snapshotter():
        last = {}
        while not stop.is_set():
            snap = client.stats
            for k, v in snap.items():
                if not isinstance(v, int) or v < last.get(k, 0):
                    errors.append((k, v, last.get(k)))
            last = {k: max(v, last.get(k, 0)) for k, v in snap.items()}
            seen.append(snap)

    t = threading.Thread(target=snapshotter, daemon=True)
    t.start()
    try:
        outs = client.generate_batch(
            _prompts([3, 8, 5, 6], cfg.vocab_size, seed=8),
            max_new_tokens=6, temperature=0.0, timeout=120,
        )
        assert len(outs) == 4
    finally:
        stop.set()
        t.join(timeout=10)
        client.close()
    assert not errors, errors[:5]
    assert seen and seen[-1]["completed"] <= 4


# -- sidecar exporter ---------------------------------------------------


def test_sidecar_metrics_server_round_trip():
    reg = Registry()
    reg.counter("train_iterations_total", "Steps.").inc(5)
    server = start_metrics_server(reg, port=0, host="127.0.0.1")
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as r:
            assert r.status == 200
            body = r.read().decode()
        types, samples = parse_exposition(body)
        assert types["train_iterations_total"] == "counter"
        assert ("train_iterations_total", {}, 5.0) in samples
        # unknown paths 404
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=30
            )
    finally:
        server.shutdown()
        server.server_close()


# -- introspection ------------------------------------------------------


class TestIntrospection:
    def test_family_lambda_shapes(self):
        for kind, expect in (("control", None), ("diff", (2,)),
                             ("ndiff", (2, 3))):
            cfg, params = _setup(kind)
            out = jax.device_get(make_param_summary(cfg)(params))
            if expect is None:
                assert "lambdas" not in out
            else:
                assert np.asarray(out["lambdas"]).shape == expect
            assert np.asarray(out["param_norms"]["blocks"]).shape == (2,)

    def test_zero_init_lambda_equals_schedule(self):
        """Fresh params have zero lambda vectors, so the effective
        lambda IS the init schedule (diff) — the paper's t=0 point."""
        from differential_transformer_replication_tpu.ops.lambdas import (
            lambda_init_schedule,
        )

        cfg, params = _setup("diff")
        lams = np.asarray(
            jax.device_get(make_param_summary(cfg)(params))["lambdas"]
        )
        for li in range(2):
            assert abs(lams[li] - lambda_init_schedule(li + 1)) < 1e-6

    def test_lambda_record_key_contract(self):
        cfg, params = _setup("ndiff")
        out = jax.device_get(make_param_summary(cfg)(params))
        rec = lambda_record(out, cfg, grad_norms=np.ones(4))
        assert "lambda_l1_t0" in rec and "lambda_l2_t2" in rec
        assert "lambda_init_l1" in rec
        assert {"param_norm_embed", "param_norm_l1", "param_norm_l2",
                "param_norm_head"} <= set(rec)
        assert {"grad_norm_embed", "grad_norm_l1", "grad_norm_l2",
                "grad_norm_head"} <= set(rec)
        json.dumps(rec)  # JSONL-safe


# -- trainer integration ------------------------------------------------


def _train_cfg(tmp_path, kind="diff", **kw):
    defaults = dict(
        vocab_size=256, dataset="synthetic", num_train_samples=200,
        micro_batch_size=4, grad_acc_steps=1, max_iters=10,
        eval_interval=5, eval_iters=2, log_interval=5,
        learning_rate=3e-3, min_lr=3e-4, warmup_iters=5,
        control_head_multiplier=1,
        tokenizer_dir=str(tmp_path / "tokenizer"),
        checkpoint_path=str(tmp_path / "ckpt"),
        last_checkpoint_path=str(tmp_path / "last_ckpt"),
        metrics_path=str(tmp_path / "metrics.jsonl"),
        trace_path=str(tmp_path / "trace.json"),
        seed=7,
    )
    return TrainConfig(
        model=ModelConfig(model=kind, **TINY_MODEL),
        **{**defaults, **kw},
    )


class TestTrainerObservability:
    def test_tiny_run_emits_telemetry_and_stays_compiled_once(
        self, tmp_path
    ):
        """One tiny diff run covers the trainer tentpole end to end:
        run-header + ts on every record, step-time/data-wait extras,
        introspection records with per-layer lambdas, a valid Chrome
        trace, and the compile-event pin at 1 (obs adds no retraces)."""
        from differential_transformer_replication_tpu.train.trainer import (
            train,
        )

        cfg = _train_cfg(tmp_path)
        train(cfg)

        lines = [json.loads(l) for l in open(cfg.metrics_path)]
        assert lines[0]["record"] == "run_header"
        assert {"config_hash", "jax_version", "device_kind",
                "process_count", "ts"} <= set(lines[0])
        assert all("ts" in l for l in lines)
        step_lines = [l for l in lines if "step_time_ms" in l]
        assert step_lines, "no step records with obs extras"
        for rec in step_lines:
            assert rec["step_time_ms"] > 0
            assert 0.0 <= rec["data_wait_frac"] <= 1.0
            # THE overhead pin: instrumentation added zero retraces
            assert rec["compile_events"] == 1
            # no-memory-stats platforms (the suite's pinned CPU) omit
            # the key rather than logging a fake 0.0
            if rec.get("gpu_memory") is not None:
                assert rec["gpu_memory"] > 0
        intro = [l for l in lines if l.get("record") == "introspection"]
        assert len(intro) == 2  # one per eval interval
        assert {"lambda_l1", "lambda_l2", "lambda_init_l1",
                "param_norm_embed", "param_norm_l1",
                "grad_norm_l1"} <= set(intro[-1])
        # the reference zero-inits BOTH lambda vectors, so exp(lq*lk)
        # starts at a saddle (d/dlq = lk*exp(..) = 0): after 10 steps
        # the effective lambda still sits ON the init schedule — exactly
        # the kind of training pathology this introspection exists to
        # make visible from metrics.jsonl
        assert intro[-1]["lambda_l1"] == pytest.approx(
            intro[-1]["lambda_init_l1"], abs=1e-4
        )

        events = json.load(open(cfg.trace_path))
        names = {e["name"] for e in events if e["ph"] == "X"}
        assert {"data_wait", "dispatch", "eval"} <= names

    def test_control_run_logs_norms_but_no_lambdas(self, tmp_path):
        from differential_transformer_replication_tpu.train.trainer import (
            train,
        )

        cfg = _train_cfg(tmp_path, kind="control", trace_path=None,
                         max_iters=5, eval_interval=5)
        train(cfg)
        lines = [json.loads(l) for l in open(cfg.metrics_path)]
        intro = [l for l in lines if l.get("record") == "introspection"]
        assert intro
        assert not any(k.startswith("lambda_") for k in intro[-1])
        assert "param_norm_l1" in intro[-1]


# -- report tools -------------------------------------------------------


class TestReportTools:
    def _write_stream(self, path):
        recs = [
            {"record": "run_header", "ts": 1.0, "config_hash": "abc",
             "jax_version": "0", "device_kind": "cpu", "process_count": 1},
            {"iter": 5, "loss": 5.0, "learning_rate": 1e-3, "ts": 2.0,
             "step_time_ms": 80.0, "data_wait_frac": 0.1,
             "compile_events": 1, "skipped_steps": 0, "rollbacks": 0,
             "tokens_per_sec": 1000.0},
            {"iter": 5, "train_loss": 5.0, "val_loss": 5.1, "ts": 2.5},
            {"record": "introspection", "iter": 5, "ts": 2.6,
             "lambda_l1": 0.21, "lambda_init_l1": 0.2,
             "param_norm_embed": 3.0, "param_norm_l1": 2.0,
             "param_norm_head": 1.0},
            {"iter": 10, "loss": 4.0, "learning_rate": 5e-4, "ts": 3.0,
             "step_time_ms": 90.0, "data_wait_frac": 0.2,
             "compile_events": 1, "skipped_steps": 1, "rollbacks": 0,
             "tokens_per_sec": 1100.0},
        ]
        with open(path, "w") as fh:
            for r in recs:
                fh.write(json.dumps(r) + "\n")
            fh.write('{"torn line')  # killed-run tail must not crash

    def test_metrics_report_summary_and_check(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        self._write_stream(path)
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "metrics_report.py"),
             path, "--check", "--require-loss-decrease",
             "--max-skipped", "1", "--max-compile-events", "1"],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        summary = json.loads(r.stdout)
        assert summary["loss_first"] == 5.0
        assert summary["loss_last"] == 4.0
        assert summary["step_time_ms_p50"] == 80.0
        assert summary["skipped_steps_total"] == 1
        assert summary["run_headers"] == 1

    def test_metrics_report_check_fails_on_bad_run(self, tmp_path):
        path = str(tmp_path / "bad.jsonl")
        with open(path, "w") as fh:
            fh.write(json.dumps({"iter": 5, "loss": 4.0,
                                 "learning_rate": 1e-3}) + "\n")
            fh.write(json.dumps({"iter": 10, "loss": 5.0,
                                 "learning_rate": 1e-3}) + "\n")
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "metrics_report.py"),
             path, "--check", "--require-loss-decrease"],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 1
        assert "loss did not decrease" in r.stderr

    def test_lambda_report_ascii(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        self._write_stream(path)
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "lambda_report.py"),
             path, "--ascii"],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        assert "L1" in r.stdout and "0.2100" in r.stdout

    def test_lambda_report_no_lambdas_is_clean(self, tmp_path):
        path = str(tmp_path / "empty.jsonl")
        with open(path, "w") as fh:
            fh.write(json.dumps({"iter": 1, "loss": 1.0}) + "\n")
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "lambda_report.py"),
             path],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0
        assert "no lambda records" in r.stdout


# -- MetricLogger satellites -------------------------------------------


class TestMetricLogger:
    def test_device_memory_none_or_positive(self):
        """The satellite contract: either real stats (positive MB) or
        None — never a fabricated 0.0. The suite's conftest pins the
        CPU backend, where memory_stats() is None."""
        from differential_transformer_replication_tpu.train.metrics import (
            device_memory_mb,
        )

        mem = device_memory_mb()
        assert mem is None or mem > 0

    def test_records_carry_ts_and_omit_memory(self, tmp_path):
        from differential_transformer_replication_tpu.train.metrics import (
            MetricLogger,
        )

        cfg = _train_cfg(tmp_path, metrics_path=str(tmp_path / "x.jsonl"))
        logger = MetricLogger(cfg)
        t0 = time.time()
        logger.log_step(5, 1.25, 1e-3, tokens_per_sec=10.0,
                        extra={"custom": 1})
        logger.log_eval(5, 1.2, 1.3)
        logger.log_record({"record": "introspection", "iter": 5})
        logger.finish()
        lines = [json.loads(l) for l in open(cfg.metrics_path)]
        assert lines[0]["record"] == "run_header"
        step = lines[1]
        assert step["custom"] == 1
        if "gpu_memory" in step:  # only on platforms with memory stats
            assert step["gpu_memory"] > 0
        for rec in lines:
            assert abs(rec["ts"] - t0) < 60
        assert lines[3]["record"] == "introspection"

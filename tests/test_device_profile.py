"""Continuous on-device profiling (ISSUE 12): obs/xprof.py parsing +
bucket attribution against a committed synthetic xplane fixture,
obs/device_profile.py capture-scheduler semantics (cadence,
back-pressure, failure-surfaced-not-fatal, drain-on-exit), the
compile-count pins with ``--profile-every`` enabled on both the trainer
and the serving engine, the trace_stitch device-lane merge, and the
perf_gate / bench_trend / metrics_report tooling gates."""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
FIXTURE = REPO / "tests" / "fixtures" / "synthetic_tpu.xplane.pb"
# synthetic round archives in the shape a driver wraps bench.py's line
# in ({"rc", "tail", "parsed"}); made-up values, not measurements
BENCH_ROUNDS = REPO / "tests" / "fixtures" / "bench_rounds"

from differential_transformer_replication_tpu.obs import xprof
from differential_transformer_replication_tpu.obs.device_profile import (
    DeviceProfileSampler,
)
from differential_transformer_replication_tpu.obs.registry import Registry


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


# -- a tiny deterministic xplane ENCODER (stdlib protobuf writer) --------
#
# The committed fixture tests/fixtures/synthetic_tpu.xplane.pb is
# generated by exactly this code (test_fixture_matches_generator pins
# the bytes), so the parser oracle and its input can never drift apart.


def _vint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _fv(fno: int, v: int) -> bytes:  # varint field
    return _vint((fno << 3) | 0) + _vint(v)


def _fb(fno: int, b: bytes) -> bytes:  # length-delimited field
    return _vint((fno << 3) | 2) + _vint(len(b)) + b


def _event(meta_id: int, offset_ps: int, dur_ps: int) -> bytes:
    return _fv(1, meta_id) + _fv(2, offset_ps) + _fv(3, dur_ps)


def _line(name: str, ts_ns: int, events) -> bytes:
    body = _fb(2, name.encode()) + _fv(3, ts_ns)
    for ev in events:
        body += _fb(4, _event(*ev))
    return body


def _plane(name: str, lines, metadata) -> bytes:
    body = _fb(2, name.encode())
    for ln in lines:
        body += _fb(3, _line(*ln))
    for mid, mname in sorted(metadata.items()):
        entry = _fv(1, mid) + _fb(2, _fv(1, mid) + _fb(2, mname.encode()))
        body += _fb(4, entry)
    return body


def _space(planes) -> bytes:
    return b"".join(_fb(1, _plane(*p)) for p in planes)


def synthetic_tpu_space() -> bytes:
    """One TPU plane (XLA Ops line exercising every kernel bucket +
    collectives + rest, plus a decoy line) and one host plane (must
    NOT be picked while the TPU plane exists). Durations in ps."""
    # event names as the chip writes them: the whole HLO instruction,
    # operands included; the kernels under their kernel_names.py
    # names (one under vmap). %convert.7 READS a kernel's result and a
    # collective's and is neither: it is the "rest".
    meta = {
        1: "%decode_dattn_fwd.1 = bf16[8] custom-call(bf16[8] %p.1)",
        2: "%fused_ffn_fwd.3 = bf16[8] custom-call(bf16[8] %p.2)",
        3: "%vmap_fused_add_norm_bwd_.2 = bf16[8] custom-call(bf16[8] %p.3)",
        4: "%flash_fwd_chunk.4 = bf16[8] custom-call(bf16[8] %p.4)",
        5: "%all-reduce.5 = f32[8] all-reduce(f32[8] %fusion.9)",
        6: "%convert.7 = f32[8] convert(bf16[8] %flash_fwd_chunk.4, "
           "f32[8] %all-reduce.5)",
    }
    xla_ops = [
        (1, 0, 2_000_000_000),          # decode_attention 2 ms
        (1, 2_000_000_000, 2_000_000_000),  # decode_attention +2 ms
        (2, 4_000_000_000, 3_000_000_000),  # fused_ffn 3 ms
        (3, 7_000_000_000, 1_000_000_000),  # fused_ffn (addnorm) 1 ms
        (4, 8_000_000_000, 1_500_000_000),  # flash_attention 1.5 ms
        (5, 9_500_000_000, 500_000_000),    # collectives 0.5 ms
        (6, 10_000_000_000, 250_000_000),   # rest 0.25 ms
    ]
    decoy = [(6, 0, 9_000_000_000)]  # a non-"XLA Ops" line: ignored
    host = [(1, 0, 5_000_000_000)]
    return _space([
        ("/host:CPU", [("python", 1000, host)], {1: "host_frame"}),
        ("/device:TPU:0 (pinned)", [
            ("XLA Ops", 2000, xla_ops),
            ("Steps", 2000, decoy),
        ], meta),
    ])


# expected bucket attribution of the fixture's XLA Ops line (ms)
FIXTURE_BUCKETS = {
    "decode_attention": 4.0,
    "fused_ffn": 4.0,
    "flash_attention": 1.5,
    "collectives": 0.5,
}
FIXTURE_BUSY = 10.25  # + 0.25 ms rest


class TestXprofParser:
    def test_fixture_matches_generator(self):
        """The committed fixture is byte-identical to its in-repo
        generator — the oracle's input can never silently drift."""
        assert FIXTURE.read_bytes() == synthetic_tpu_space()

    def test_fixture_bucket_attribution(self):
        planes = xprof.parse_xspace(FIXTURE.read_bytes())
        assert [p.name for p in planes] == [
            "/host:CPU", "/device:TPU:0 (pinned)"
        ]
        plane, kind = xprof.pick_plane(planes)
        assert kind == "tpu" and plane.name.startswith("/device:TPU")
        s = xprof.summarize_plane(plane, kind, steps=1)
        assert s["kernel_buckets"] == pytest.approx(FIXTURE_BUCKETS)
        assert s["busy_ms_per_step"] == pytest.approx(FIXTURE_BUSY)
        assert s["bucket_ms"]["rest"] == pytest.approx(0.25)
        # bucket_ms is the full decomposition: it sums to busy
        assert sum(s["bucket_ms"].values()) == pytest.approx(
            s["busy_ms_per_step"]
        )
        # every op name reaches the totals/counts tables
        names = {n.split(" = ")[0]: n for n in s["totals"]}
        assert s["totals"][names["%all-reduce.5"]] == pytest.approx(0.5)
        assert s["counts"][names["%decode_dattn_fwd.1"]] == 2
        # per-step division
        s2 = xprof.summarize_plane(plane, kind, steps=2)
        assert s2["busy_ms_per_step"] == pytest.approx(FIXTURE_BUSY / 2)

    def test_bucket_is_decided_by_the_instructions_own_name(self):
        """The buckets hold the names of kernel_names.py, matched
        against the instruction's own name: under vmap the name is
        wrapped, an unnamed kernel (what every kernel was before the
        table) is "rest", and so is an op that only reads a kernel."""
        assert xprof.bucket_for("%decode_dattn_fwd.4 = x") == "decode_attention"
        assert xprof.bucket_for("%vmap_fused_ffn_fwd_.1 = x") == "fused_ffn"
        assert xprof.bucket_for("%flash_bwd_tm_packed.9 = x") == "flash_attention"
        assert xprof.bucket_for("%transpose_jvp___.3 = x") is None
        assert xprof.bucket_for("%fusion.7 = f32[8] fusion(%flash_fwd.2)") is None

    def test_tf_proto_agreement(self):
        """The stdlib wire reader agrees field-for-field with
        tensorflow's generated proto on the same bytes."""
        xplane_pb2 = pytest.importorskip(
            "tensorflow.tsl.profiler.protobuf.xplane_pb2"
        )
        data = synthetic_tpu_space()
        xs = xplane_pb2.XSpace()
        xs.ParseFromString(data)
        mine = xprof.parse_xspace(data)
        assert [p.name for p in xs.planes] == [p.name for p in mine]
        for a, b in zip(xs.planes, mine):
            assert [(l.name, l.timestamp_ns, len(l.events))
                    for l in a.lines] == [
                (l.name, l.timestamp_ns, len(l.events)) for l in b.lines
            ]
            for la, lb in zip(a.lines, b.lines):
                assert [
                    (e.metadata_id, e.offset_ps, e.duration_ps)
                    for e in la.events
                ] == [
                    (e.metadata_id, e.offset_ps, e.duration_ps)
                    for e in lb.events
                ]
            assert {
                k: v.name for k, v in a.event_metadata.items()
            } == b.event_names

    def test_plane_fallbacks_and_errors(self, tmp_path):
        host_only = xprof.parse_xspace(_space([
            ("/host:CPU", [("python", 0, [(1, 0, 1000)])], {1: "f"}),
        ]))
        plane, kind = xprof.pick_plane(host_only)
        assert kind == "host"
        err = xprof.pick_plane(host_only, host_fallback=False)
        assert isinstance(err, str) and "no TPU plane" in err
        # summarize_trace degrades to strings, never raises
        assert "no xplane.pb" in xprof.summarize_trace(str(tmp_path))
        bad = tmp_path / "plugins" / "profile" / "x"
        bad.mkdir(parents=True)
        (bad / "h.xplane.pb").write_bytes(b"\xff\xff\xff garbage")
        out = xprof.summarize_trace(str(tmp_path))
        assert isinstance(out, str) and "cannot parse" in out

    def test_derived_metrics(self):
        d = xprof.derived_metrics(
            10.0, flops_per_step=1e12, hbm_bytes_per_step=8e9,
            peak_flops=2e14,
        )
        # 1e12 flops / 0.01 s / 2e14 peak = 0.5 MFU; 8e9 B / 0.01 s
        assert d["mfu"] == pytest.approx(0.5)
        assert d["hbm_gbps"] == pytest.approx(800.0)
        assert xprof.derived_metrics(10.0) == {}
        assert xprof.derived_metrics(0.0, flops_per_step=1e12) == {}
        # the 6*N*D convention shared with bench.py
        assert xprof.train_flops_per_step(100, 40, 10) == 6 * 60 * 10

    def test_device_peaks_keyed_by_kind_unknown_is_error(self):
        v5e = xprof.device_peaks("TPU v5 lite")
        assert v5e["bf16_flops_per_s"] == 197e12
        assert v5e["hbm_bytes_per_s"] == 819e9
        # no default: a chip the table does not hold (or the CPU) raises
        for kind in ("TPU v9", "cpu"):
            with pytest.raises(KeyError, match="no published peaks"):
                xprof.device_peaks(kind)
        # and without a peak no utilization is derived
        assert "mfu" not in xprof.derived_metrics(10.0, flops_per_step=1e12)

    def test_plane_to_chrome_events(self):
        planes = xprof.parse_xspace(FIXTURE.read_bytes())
        plane, _ = xprof.pick_plane(planes)
        events = xprof.plane_to_chrome_events(
            plane, pid=3, anchor_us=1_000_000.0, capture=7
        )
        xs = [e for e in events if e.get("ph") == "X"]
        names = {e["name"] for e in events}
        assert "capture_window" in names
        win = next(e for e in xs if e["name"] == "capture_window")
        assert win["args"]["capture"] == 7
        # anchored: earliest op lands exactly at anchor_us, and the
        # window encloses every op
        ops = [e for e in xs if e["name"] != "capture_window"]
        assert min(e["ts"] for e in ops) == pytest.approx(1_000_000.0)
        assert all(
            win["ts"] <= e["ts"] and
            e["ts"] + e["dur"] <= win["ts"] + win["dur"] + 1e-6
            for e in ops
        )
        assert all(e["pid"] == 3 for e in events)
        # line labels ride thread_name metas
        assert any(
            e["ph"] == "M" and e["name"] == "thread_name"
            for e in events
        )
        json.dumps(events)  # valid JSON payload


# -- capture-scheduler semantics (fake profiler seam; jax-free) ----------


class _FakeProfiler:
    """start_fn/stop_fn seam: 'captures' by dropping a canned xplane
    into the spool dir at stop, like jax.profiler does."""

    def __init__(self, data=None, fail_start=False):
        self.data = data if data is not None else synthetic_tpu_space()
        self.fail_start = fail_start
        self.active_dir = None
        self.starts = 0

    def start(self, path):
        if self.fail_start:
            raise RuntimeError("profiler already active")
        self.starts += 1
        self.active_dir = path

    def stop(self):
        d = Path(self.active_dir) / "plugins" / "profile" / "run"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(self.data)
        self.active_dir = None


def _sampler(tmp_path, every=2, prof=None, **kw):
    prof = prof or _FakeProfiler()
    s = DeviceProfileSampler(
        every=every, spool_dir=str(tmp_path / "spool"),
        registry=kw.pop("registry", Registry()),
        start_fn=prof.start, stop_fn=prof.stop,
        block_fn=lambda sync: None, **kw,
    )
    return s, prof


class TestCaptureScheduler:
    def test_sampling_cadence(self, tmp_path):
        rows = []
        s, prof = _sampler(tmp_path, every=3, sink=rows.append,
                           jsonl_path=None)
        for i in range(10):
            if s.maybe_begin(i):
                s.end()
                s._idle.wait(10)  # keep the worker drained: pure cadence
        s.close()
        # due at 3, 6, 9 — NEVER at 0 (the compile step)
        assert prof.starts == 3
        assert s.captures == 3 and s.skipped == 0 and s.failures == 0
        assert [r["step"] for r in rows] == [3, 6, 9]
        assert all(r["record"] == "device_profile" for r in rows)
        assert rows[0]["bucket_ms"]["decode_attention"] == pytest.approx(
            4.0
        )

    def test_uncaptured_step_overhead_is_an_integer_compare(self, tmp_path):
        """The hot-loop contract: a non-due step costs ~a modulo. Pin
        it loosely (well under 50 us/call even on a loaded CI box)."""
        s, _ = _sampler(tmp_path, every=1_000_000)
        n = 20_000
        t0 = time.perf_counter()
        for i in range(1, n + 1):
            s.maybe_begin(i)
        per_call = (time.perf_counter() - t0) / n
        s.close()
        assert per_call < 50e-6, f"{per_call * 1e6:.1f} us/call"

    def test_back_pressure_defers_never_queues(self, tmp_path):
        gate = threading.Event()
        release = threading.Event()

        def slow_sink(row):
            gate.set()
            release.wait(10)

        s, prof = _sampler(tmp_path, every=2, sink=slow_sink,
                           jsonl_path=None)
        assert not s.maybe_begin(1)  # first-seen step never captures
        assert s.maybe_begin(2)
        s.end()
        assert gate.wait(10)  # worker is now stuck inside the sink
        # the next due captures are SKIPPED (counted), not queued
        assert not s.maybe_begin(4)
        assert not s.maybe_begin(6)
        assert s.skipped == 2 and prof.starts == 1
        release.set()
        s.close()
        assert s.captures == 1

    def test_start_failure_surfaced_not_fatal(self, tmp_path):
        rows = []
        s, prof = _sampler(tmp_path, every=2,
                           prof=_FakeProfiler(fail_start=True),
                           sink=rows.append, jsonl_path=None)
        assert not s.maybe_begin(1)  # first-seen step never captures
        assert not s.maybe_begin(2)
        assert not s.maybe_begin(4)
        assert s.failures == 2 and s.captures == 0
        s.end()  # no window open: a no-op, never a crash
        s.close()
        # the failures reach the metrics stream, not just the counter —
        # a run whose every capture fails to start must not leave a
        # vacuously green --max-capture-failures gate
        assert [r["capture_failures"] for r in rows] == [1, 2]
        assert all(
            r["record"] == "device_profile" and "error" in r
            for r in rows
        )

    def test_parse_failure_surfaced_not_fatal(self, tmp_path):
        """A malformed capture publishes an {"error": ...} row and
        counts a failure; the NEXT capture works normally."""
        rows = []
        prof = _FakeProfiler(data=b"\xff\xfe garbage not a proto")
        s, prof = _sampler(tmp_path, every=2, prof=prof,
                           sink=rows.append, jsonl_path=None)
        assert not s.maybe_begin(1)  # first-seen step never captures
        assert s.maybe_begin(2)
        s.end()
        s._idle.wait(10)
        prof.data = synthetic_tpu_space()  # next capture is healthy
        assert s.maybe_begin(4)
        s.end()
        s.close()
        assert s.failures == 1 and s.captures == 1
        assert "error" in rows[0] and rows[0]["capture_failures"] == 1
        assert rows[1]["busy_ms"] == pytest.approx(FIXTURE_BUSY)

    def test_drain_on_close_and_spool_rotation(self, tmp_path):
        s, _ = _sampler(tmp_path, every=1, keep=2)
        assert not s.maybe_begin(0)  # first-seen step never captures
        for i in range(1, 6):
            if s.maybe_begin(i):
                s.end()
                s._idle.wait(10)
        s.close()  # drains the worker; idempotent
        s.close()
        spool = tmp_path / "spool"
        assert s.captures == 5
        # rotation kept only the newest `keep` capture dirs + lanes
        caps = sorted(p.name for p in spool.glob("cap-*"))
        lanes = sorted(p.name for p in spool.glob("device-*.trace.json"))
        assert caps == ["cap-0003", "cap-0004"]
        assert lanes == ["device-0003.trace.json",
                        "device-0004.trace.json"]
        # the owned JSONL sink holds every record, flushed by close
        rows = [
            json.loads(l)
            for l in (spool / "metrics.jsonl").read_text().splitlines()
        ]
        assert len(rows) == 5
        assert not s._thread.is_alive()

    def test_resumed_start_never_captures_its_first_step(self, tmp_path):
        """A trainer resumed at iter 10000 with every=200 must NOT
        capture its first post-resume step (10000 % 200 == 0, but that
        step traces + compiles); the next due step captures."""
        s, prof = _sampler(tmp_path, every=200)
        assert not s.maybe_begin(10_000)
        for i in range(10_001, 10_200):
            assert not s.maybe_begin(i)
        assert s.maybe_begin(10_200)
        s.end()
        s.close()
        assert prof.starts == 1 and s.captures == 1 and s.failures == 0

    def test_abort_recovers_a_crashed_window(self, tmp_path):
        s, prof = _sampler(tmp_path, every=2)
        assert not s.maybe_begin(1)  # first-seen step never captures
        assert s.maybe_begin(2)
        s.abort()  # the step crashed mid-capture: torn trace dropped
        assert s.failures == 1
        assert s.maybe_begin(4)  # the sampler is not wedged
        s.end()
        s.close()
        assert s.captures == 1

    def test_registry_surface(self, tmp_path):
        reg = Registry()
        s, _ = _sampler(tmp_path, every=2, registry=reg,
                        flops_per_step=1e9, peak_flops=1e12)
        assert not s.maybe_begin(1)  # first-seen step never captures
        assert s.maybe_begin(2)
        s.end()
        s.close()
        text = reg.render()
        for name in ("device_profile_captures_total 1",
                     "device_profile_failures_total 0",
                     "device_profile_skipped_total 0",
                     "device_busy_ms", "device_mfu",
                     'device_step_ms_bucket{bucket="decode_attention"}'):
            assert name in text, f"{name} missing from exposition"


# -- trainer + engine integration (CPU, tiny; compile pins) --------------


TINY = dict(vocab_size=97, n_embd=32, n_head=2, n_layer=2,
            block_size=32, dropout=0.0, compute_dtype="float32")


class TestTrainerIntegration:
    def test_tiny_run_profiles_continuously_compile_pin(self, tmp_path):
        """--profile-every on a tiny CPU run: captures parsed +
        published to metrics.jsonl, a device-lane trace written, the
        device_capture host span emitted, and compile_events stays 1
        (the capture wraps an already-compiled step)."""
        from differential_transformer_replication_tpu.config import (
            ModelConfig,
            TrainConfig,
        )
        from differential_transformer_replication_tpu.train.trainer import (
            train,
        )

        cfg = TrainConfig(
            model=ModelConfig(model="diff", **TINY),
            vocab_size=97, dataset="synthetic", num_train_samples=200,
            micro_batch_size=4, grad_acc_steps=1, max_iters=8,
            eval_interval=4, eval_iters=2, log_interval=4,
            learning_rate=3e-3, min_lr=3e-4, warmup_iters=5,
            control_head_multiplier=1,
            tokenizer_dir=str(tmp_path / "tok"),
            checkpoint_path=str(tmp_path / "ckpt"),
            last_checkpoint_path=str(tmp_path / "last"),
            metrics_path=str(tmp_path / "metrics.jsonl"),
            trace_path=str(tmp_path / "trace.json"),
            profile_every=3, seed=7,
        )
        train(cfg)
        lines = [json.loads(l) for l in open(cfg.metrics_path)]
        dev = [l for l in lines if l.get("record") == "device_profile"]
        assert len(dev) >= 1
        for rec in dev:
            assert rec["process"] == "trainer"
            assert "bucket_ms" in rec and "busy_ms" in rec
            # the trainer wires bench.py's 6*N*D estimate and the
            # device's published peak -> derived mfu; the CPU backend
            # has no peak, so a CPU run publishes no utilization
            assert "mfu" not in rec
            assert os.path.exists(rec["trace_file"])
        # THE pin: continuous profiling adds zero retraces
        steps = [l for l in lines if "compile_events" in l]
        assert steps and all(r["compile_events"] == 1 for r in steps)
        # the host span the stitched device lane aligns under
        trace = json.load(open(cfg.trace_path))
        caps = [e for e in trace if e.get("name") == "device_capture"]
        assert caps and all(
            "capture" in e["args"] and "step" in e["args"] for e in caps
        )
        spool = cfg.resolved_profile_spool()
        assert any(
            n.startswith("device-") for n in os.listdir(spool)
        )


class TestEngineIntegration:
    def test_engine_profiles_continuously_decode_pin(self, tmp_path):
        """profile_every on the serving engine: at least one capture
        parsed + published to the engine registry, JSONL rows and a
        device lane under the spool — with the decode compile count
        still exactly 1."""
        import jax

        from differential_transformer_replication_tpu.config import (
            ModelConfig,
            ServingConfig,
        )
        from differential_transformer_replication_tpu.models import (
            init_model,
        )
        from differential_transformer_replication_tpu.serving import (
            ServingClient,
            ServingEngine,
        )

        cfg = ModelConfig(model="control", **TINY)
        params = init_model(jax.random.PRNGKey(0), cfg)
        serving = ServingConfig(
            num_slots=4, prefill_chunk=8, prefill_budget=16,
            profile_every=3, profile_dir=str(tmp_path / "prof"),
        )
        engine = ServingEngine(params, cfg, serving)
        client = ServingClient(engine)
        outs = client.generate_batch(
            [[1, 2, 3], [4, 5, 6, 7], [8, 9]], max_new_tokens=12,
            temperature=0.0, seed=0, timeout=120,
        )
        assert [len(o.tokens) for o in outs] == [12, 12, 12]
        client.close()  # drains the sampler through engine.close()
        dp = engine._device_prof
        assert dp is not None and dp.captures >= 1
        assert dp.failures == 0
        # decode stays one compiled program with profiling enabled
        assert engine.compile_stats()["decode"] == 1
        rows = [
            json.loads(l)
            for l in open(tmp_path / "prof" / "metrics.jsonl")
        ]
        assert rows and all(
            r["record"] == "device_profile" and r["process"] == "serving"
            for r in rows
        )
        assert list((tmp_path / "prof").glob("device-*.trace.json"))
        text = engine.registry.render()
        assert "device_profile_captures_total" in text
        assert "device_step_ms_bucket" in text


# -- trace_stitch device-lane merge --------------------------------------


class TestTraceStitchDeviceLane:
    def test_device_lane_aligns_to_host_capture_span(self, tmp_path):
        """A skewed device lane (5 s off) merges into ONE valid
        Perfetto timeline with its capture window aligned inside the
        host device_capture span that wrapped the step."""
        from differential_transformer_replication_tpu.obs.spans import (
            SpanTracer,
        )

        host_path = str(tmp_path / "host.trace.json")
        tracer = SpanTracer(host_path, process_name="trainer")
        t0 = time.perf_counter()
        tracer.complete("device_capture", t0, t0 + 0.5,
                        capture=0, step=3)
        tracer.close()
        host_events = json.load(open(host_path))
        span = next(
            e for e in host_events if e["name"] == "device_capture"
        )

        planes = xprof.parse_xspace(FIXTURE.read_bytes())
        plane, _ = xprof.pick_plane(planes)
        # anchor the lane 5 s BEFORE the host span: injected skew
        dev_path = str(tmp_path / "device-0000.trace.json")
        xprof.write_chrome_trace(dev_path, xprof.plane_to_chrome_events(
            plane, anchor_us=span["ts"] - 5e6, capture=0,
        ))

        out = str(tmp_path / "stitched.trace.json")
        r = subprocess.run(
            [sys.executable, str(REPO / "tools" / "trace_stitch.py"),
             host_path, dev_path, "-o", out],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr
        summary = json.loads(r.stdout)
        assert summary["files"] == 2
        assert summary["device_lanes"] == 1
        # the recovered offset is the injected 5 s skew
        assert summary["offsets_us"][1] == pytest.approx(5e6, abs=1.0)
        merged = json.load(open(out))  # one VALID timeline
        win = next(
            e for e in merged if e["name"] == "capture_window"
        )
        moved = next(
            e for e in merged if e["name"] == "device_capture"
        )
        # aligned: the device window starts exactly at the host span
        assert win["ts"] == pytest.approx(moved["ts"], abs=1.0)
        # distinct pid lanes per input file
        assert {e["pid"] for e in merged} == {0, 1}
        # device line labels survived the merge
        assert any(e.get("name") == "thread_name" for e in merged)


# -- perf_gate.py exit codes + committed-history smoke -------------------


def _run_gate(args, cwd=None):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_gate.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=cwd,
    )


class TestPerfGate:
    def test_round_archive_history_passes_schema_pinned(self):
        """The gate runs over a trajectory of driver-wrapped round
        archives (the synthetic fixture rounds), passes, and the
        summary schema is pinned."""
        rounds = sorted(str(p) for p in BENCH_ROUNDS.glob("BENCH_r0*.json"))
        assert len(rounds) >= 3
        r = _run_gate([*rounds, "--key", "value", "--key", "mfu_6nd"])
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert {"metric", "samples", "window", "max_regress",
                "mad_factor", "keys", "regressed", "insufficient",
                "ok"} <= set(doc)
        assert doc["metric"] == "perf_gate" and doc["ok"] is True
        assert doc["samples"] == len(rounds)
        for k in doc["keys"]:
            assert {"key", "path", "direction", "n", "status", "newest",
                    "baseline_median", "baseline_mad", "allowed_slack",
                    "delta_vs_baseline", "window_n"} <= set(k)
            assert k["status"] == "ok"
        assert {k["direction"] for k in doc["keys"]} == {"higher"}

    def test_regressed_trajectory_exits_nonzero(self, tmp_path):
        hist = tmp_path / "hist.jsonl"
        hist.write_text("".join(
            json.dumps({"value": v}) + "\n"
            for v in (100, 101, 99, 100, 102, 60)
        ))
        r = _run_gate([str(hist), "--key", "value"])
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert doc["regressed"] == ["value"] and doc["ok"] is False
        assert "CHECK FAILED" in r.stderr

    def test_latency_keys_gate_lower_is_better(self, tmp_path):
        hist = tmp_path / "itl.jsonl"
        hist.write_text("".join(
            json.dumps({"itl_ms": {"p95": v}}) + "\n"
            for v in (10.0, 10.5, 9.8, 10.1, 30.0)  # latency DOUBLED
        ))
        r = _run_gate([str(hist), "--key", "itl_ms.p95"])
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert doc["keys"][0]["direction"] == "lower"

    def test_noise_aware_baseline_tolerates_mad_scale_dips(self, tmp_path):
        """A dip within the history's own noise (3 MAD-sigmas) does not
        flap the gate even when it exceeds --max-regress."""
        hist = tmp_path / "noisy.jsonl"
        hist.write_text("".join(
            json.dumps({"value": v}) + "\n"
            for v in (100, 140, 80, 120, 90, 75)
        ))
        r = _run_gate([str(hist), "--key", "value",
                       "--max-regress", "0.05"])
        assert r.returncode == 0, r.stdout

    def test_insufficient_history_exits_2(self, tmp_path):
        hist = tmp_path / "short.jsonl"
        hist.write_text(json.dumps({"value": 100}) + "\n")
        r = _run_gate([str(hist)])
        assert r.returncode == 2
        assert json.loads(r.stdout)["insufficient"] == ["value"]

    def test_device_profile_stream_gates_mfu_and_buckets(self, tmp_path):
        mp = tmp_path / "metrics.jsonl"
        with open(mp, "w") as f:
            f.write(json.dumps({"record": "run_header"}) + "\n")
            for mfu, flash in ((0.5, 1.0), (0.52, 1.1), (0.51, 0.9),
                               (0.5, 1.0), (0.3, 4.0)):
                f.write(json.dumps({
                    "record": "device_profile", "mfu": mfu,
                    "bucket_ms": {"flash_attention": flash},
                }) + "\n")
        r = _run_gate(["--from-metrics-jsonl", str(mp),
                       "--key", "mfu",
                       "--key", "bucket_ms.flash_attention"])
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert set(doc["regressed"]) == {
            "mfu", "bucket_ms.flash_attention"
        }

    def test_bench_round_archives_unwrap_parsed(self, tmp_path):
        """Driver round archives ({"parsed": {...}}) and bare one-line
        docs mix in one trajectory."""
        a = tmp_path / "r1.json"
        a.write_text(json.dumps({"rc": 0, "parsed": {"value": 100.0}}))
        b = tmp_path / "r2.json"
        b.write_text(json.dumps({"value": 101.0}))
        c = tmp_path / "r3.json"
        c.write_text(json.dumps({"rc": 0, "parsed": {"value": 99.0}}))
        r = _run_gate([str(a), str(b), str(c), "--key", "value"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["keys"][0]["n"] == 3


class TestBenchTrend:
    def test_round_archive_trajectory_summary(self):
        rounds = sorted(str(p) for p in BENCH_ROUNDS.glob("BENCH_r0*.json"))
        r = subprocess.run(
            [sys.executable, str(REPO / "tools" / "bench_trend.py"),
             "--ascii", *rounds],
            capture_output=True, text=True, timeout=120, cwd=str(REPO),
        )
        assert r.returncode == 0, r.stderr
        doc = json.loads(r.stdout)
        assert doc["metric"] == "bench_trend"
        assert doc["rounds"] == sorted(doc["rounds"])
        assert len(doc["vs_baseline"]) == len(doc["rounds"]) >= 3
        # shares perf_gate's baseline math
        assert doc["baseline"]["value"] is not None
        assert {"median", "mad", "window_n"} <= set(
            doc["baseline"]["value"]
        )
        assert "bench_trend" in r.stderr  # --ascii sparklines


class TestMetricsReportDeviceProfile:
    def _stream(self, tmp_path, failures=0):
        mp = tmp_path / "metrics.jsonl"
        with open(mp, "w") as f:
            f.write(json.dumps({
                "record": "run_header", "config_hash": "x",
                "jax_version": "0", "ts": 1.0,
            }) + "\n")
            f.write(json.dumps({
                "iter": 1, "loss": 2.0, "learning_rate": 1e-3,
                "ts": 2.0,
            }) + "\n")
            f.write(json.dumps({
                "record": "device_profile", "capture": 0, "step": 3,
                "busy_ms": 12.5, "mfu": 0.55,
                "bucket_ms": {"flash_attention": 4.0},
                "capture_failures": failures, "ts": 3.0,
            }) + "\n")
            f.write(json.dumps({
                "record": "some_future_kind", "ts": 4.0,
            }) + "\n")
        return mp

    def test_summary_learns_device_profile_records(self, tmp_path):
        mp = self._stream(tmp_path)
        r = subprocess.run(
            [sys.executable, str(REPO / "tools" / "metrics_report.py"),
             str(mp)],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr
        doc = json.loads(r.stdout)
        assert doc["device_profile_records"] == 1
        assert doc["device_mfu_last"] == pytest.approx(0.55)
        assert doc["device_busy_ms_last"] == pytest.approx(12.5)
        assert doc["device_profile_capture_failures"] == 0
        # unknown typed records are now counted, not silently dropped
        assert doc["unknown_records"] == {"some_future_kind": 1}

    def test_check_gates_capture_failures(self, tmp_path):
        mp = self._stream(tmp_path, failures=2)
        r = subprocess.run(
            [sys.executable, str(REPO / "tools" / "metrics_report.py"),
             str(mp), "--check"],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 1
        assert "capture failures" in r.stderr
        # a loosened budget passes the same stream
        r2 = subprocess.run(
            [sys.executable, str(REPO / "tools" / "metrics_report.py"),
             str(mp), "--check", "--max-capture-failures", "2"],
            capture_output=True, text=True, timeout=120,
        )
        assert r2.returncode == 0, r2.stderr

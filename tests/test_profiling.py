"""Throughput counter and profiler-window tests (SURVEY.md section 5.1)."""

import time

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.utils import (
    ProfilerWindow,
    Throughput,
    trace,
)


def test_throughput_first_call_is_none():
    t = Throughput()
    assert t.update(100) is None


def test_throughput_rate():
    t = Throughput()
    t.update(0)
    time.sleep(0.05)
    rate = t.update(500)
    assert rate is not None and 1000 < rate < 11000  # ~10k tok/s nominal


def test_trace_context_manager_captures(tmp_path):
    d = str(tmp_path / "trace")
    with trace(d):
        _ = jnp.sum(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    assert (tmp_path / "trace").exists()


def test_profiler_window_disabled_is_noop():
    w = ProfilerWindow(None, start=10)
    for i in range(20):
        w.step(i)
    w.close()
    assert not w.active


def test_profiler_window_normal_capture(tmp_path):
    d = str(tmp_path / "p1")
    w = ProfilerWindow(d, start=2, n_steps=2)
    x = jnp.ones((8, 8))
    for i in range(1, 6):
        x = x + 1
        w.step(i, sync=x)
    assert not w.active  # stopped at start+n_steps
    assert (tmp_path / "p1").exists()
    w.close()  # idempotent


def test_profiler_window_resume_past_start_never_stops_unstarted():
    """Resuming at an iteration inside/past the window must not call
    stop_trace without a matching start."""
    w = ProfilerWindow("/tmp/never-used-profile-dir", start=10, n_steps=5)
    for i in range(12, 20):  # resumed past start
        w.step(i)
    w.close()
    assert not w.active


def test_profiler_window_early_exit_finalizes(tmp_path):
    d = str(tmp_path / "p2")
    w = ProfilerWindow(d, start=1, n_steps=100)
    w.step(1)
    assert w.active
    w.close(sync=jnp.ones(()))  # loop ended inside the window
    assert not w.active
    assert (tmp_path / "p2").exists()


def test_profiler_window_capture_holds_the_tracers_spans(tmp_path):
    """A real capture, nothing patched: the spans a SpanTracer records
    while the window is open are in the capture's host plane under their
    names (obs/spans.py:annotate_spans), those before and after it are
    not. On the chip the same spans lie inside the device ops' window
    (PERF.md section 6, PR 24)."""
    from differential_transformer_replication_tpu.obs import xprof
    from differential_transformer_replication_tpu.obs.spans import SpanTracer

    tracer = SpanTracer(str(tmp_path / "host.trace.json"))
    w = ProfilerWindow(str(tmp_path / "p"), start=2, n_steps=2, tracer=tracer)
    x = jnp.ones((8, 8))
    for i in range(1, 6):
        with tracer.span(f"step_{i}"):
            x = x + 1
        w.step(i, sync=x)
    tracer.close()
    assert not w.active and tracer.annotate is False
    with open(xprof.find_xplane_pb(str(tmp_path / "p")), "rb") as f:
        planes = xprof.parse_xspace(f.read())
    names = {p.event_name(ev.metadata_id) for p in planes
             if p.name.startswith("/host:")
             for line in p.lines for ev in line.events}
    # the window opens after step 2's span closed and closes after step 4's
    assert {"step_3", "step_4"} <= names
    assert not {"step_1", "step_2", "step_5"} & names

"""The readers of the engine's sub-spans and of the sampler program's
scopes (PR 38; ``lib/host_share.py`` and the twelve metric files that
read through it), against a hand-built run: host spans and a few device
intervals on ONE clock, and the same spans on the recorder's. On the
CPU, no chip:

    python3 -m pytest benchmark/tests -q

The tier-1 command collects ``tests/`` only; ``tests/
test_benchmark_layer_metrics.py`` imports these cases and runs them under
their own names (and takes ``plane_with_paths`` from here).
"""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

import selftest  # noqa: E402  (benchmark/selftest.py: the xplane writer)
from lib import harness, host_share, op_phases, xplane  # noqa: E402
from lib.spans import SpanRecorder  # noqa: E402

SERVE_CELLS = ("serve-diff-recipe-chat", "serve-jamba2-3b-reason-chat",
               "serve-kimi-linear-5l-ep2-doc-chat",
               "serve-trinity-large-5l-ep16-mixed-len")
SPAN_METRICS = ("decode_h2d_ms_per_iter", "decode_dispatch_ms_per_iter",
                "sample_operands_ms_per_iter", "sample_dispatch_ms_per_iter",
                "token_read_ms_per_iter", "intake_ms_per_request",
                "sampler_rows_asking_pct")
TRACE_METRICS = ("token_read_idle_ms_per_iter",
                 "decode_host_idle_ms_per_iter",
                 "sampler_device_ms_per_iter", "sampler_logprobs_ms_per_iter",
                 "sampler_pipeline_ms_per_iter")


def plane_with_paths(name, lines, paths, by_ref=()):
    """``selftest._plane`` plus, on the metadata of every event named in
    ``paths``, its op_name under ``tf_op`` (a reference for ``by_ref``),
    and on every metadata a stat that is not the op_name."""
    ld, vi = selftest._ld, selftest._vi
    ids, body = {}, b""
    for lname, events in lines:
        evs = b""
        for ename, start_us, dur_us in events:
            mid = ids.setdefault(ename, len(ids) + 1)
            evs += ld(4, vi(1, mid) + vi(2, start_us * 10**6)
                      + vi(3, dur_us * 10**6))
        body += ld(3, ld(2, lname.encode()) + vi(3, 1000) + evs)
    stat_names, meta = {1: op_phases.OP_NAME_STAT, 2: "flops"}, b""
    for ename, mid in ids.items():
        stats = ld(5, vi(1, 2) + vi(3, 7))
        path = paths.get(ename)
        if path is not None and ename in by_ref:
            sid = 10 + len(stat_names)
            stat_names[sid] = path
            stats += ld(5, vi(1, 1) + vi(7, sid))
        elif path is not None:
            stats += ld(5, vi(1, 1) + ld(5, path.encode()))
        meta += ld(4, vi(1, mid) + ld(2, vi(1, mid) + ld(2, ename.encode())
                                      + stats))
    for sid, sname in stat_names.items():
        meta += ld(5, vi(1, sid) + ld(2, vi(1, sid) + ld(2, sname.encode())))
    return ld(2, name.encode()) + body + meta


# -- the hand-built run -----------------------------------------------------------
# Two iterations, microseconds (start, end). The first only decodes; the
# second completes a prompt first. A sub-span lies inside its parent.
_IT = [{"iteration": 0}, {"iteration": 1}]
_DEC = [dict(it, path="decode") for it in _IT]
_USE = {"masked": 0, "penalized": 0, "logprobs": 1, "tempered": 2,
        "asking": 2}
_HOST = [
    ("schedule", 0, 10, _IT[0]),
    ("decode_inputs", 10, 20, _IT[0]),
    ("decode", 20, 60, dict(_IT[0], active=3)),
    ("decode_h2d", 22, 40, dict(_IT[0], arrays=3, bytes=36)),
    ("decode_dispatch", 40, 58, _IT[0]),
    ("sample", 60, 200, _IT[0]),
    ("sample_operands", 62, 80, dict(_DEC[0], rows=4, active=3, **_USE)),
    ("sample_dispatch", 80, 100, _DEC[0]),
    ("token_read", 100, 196, _DEC[0]),
    ("emit", 200, 220, _IT[0]),
    ("step_tail", 220, 230, None),
    ("deliver", 230, 240, None),
    ("intake", 240, 260, {"submitted": {"requests": 2}}),
    ("schedule", 260, 270, _IT[1]),
    ("prefill", 270, 420, dict(_IT[1], chunks=1)),
    ("prefill_call", 272, 300, dict(_IT[1], size=8)),
    ("first_token", 300, 418, _IT[1]),
    ("sample_operands", 302, 310,
     dict(_IT[1], path="prefill", rows=1, active=1, masked=0, penalized=0,
          logprobs=0, tempered=0, asking=0)),
    ("sample_dispatch", 310, 330, dict(_IT[1], path="prefill")),
    ("token_read", 330, 400, dict(_IT[1], path="prefill")),
    ("decode_inputs", 420, 430, _IT[1]),
    ("decode", 430, 470, dict(_IT[1], active=4)),
    ("decode_h2d", 432, 450, dict(_IT[1], arrays=3, bytes=36)),
    ("decode_dispatch", 450, 468, _IT[1]),
    ("sample", 470, 600, _IT[1]),
    ("sample_operands", 472, 490, dict(_DEC[1], rows=4, active=4, **_USE)),
    ("sample_dispatch", 490, 510, _DEC[1]),
    ("token_read", 510, 596, _DEC[1]),
    ("emit", 600, 620, _IT[1]),
    # an intake that took nobody in: its time counts, no request
    ("intake", 622, 626, {"submitted": {"requests": 0}}),
]
SUB_SPANS = {"decode_h2d", "decode_dispatch", "sample_operands",
             "sample_dispatch", "token_read", "load_read"}
# The device: an op of the iteration before, the decode program while the
# host is in `sample`, the sampler program (a seam of 5 us after the
# step), both again with a prefill program between, a last op. The first
# `token_read` is covered by ops up to 170 of its 100..196: half of it.
_S = "jit(_sample)/sampler/"
_F = "%fusion.{} = f32[8] fusion(f32[8] %p)"
_OPS = [
    (_F.format(1), 0, 5, "jit(_decode)/ffn/dot_general"),
    (_F.format(2), 45, 100, "jit(_decode)/attn/dot_general"),
    (_F.format(3), 150, 6, _S + "logit_pipeline/jit(_where)/select_n"),
    (_F.format(4), 156, 10, _S + "sampler_logprobs/jit(log_softmax)/sub"),
    ("%conditional.5 = s32[8] conditional(pred[] %p)", 166, 4,
     _S + "sampler_draw/cond"),
    (_F.format(6), 290, 50, "jit(_prefill)/attn/dot_general"),
    (_F.format(7), 340, 2, _S + "logit_pipeline/jit(_where)/select_n"),
    (_F.format(8), 342, 6, _S + "sampler_logprobs/top_k"),
    (_F.format(9), 348, 2, _S + "sampler_draw/cond"),
    (_F.format(10), 455, 100, "jit(_decode)/attn/dot_general"),
    (_F.format(11), 560, 6, _S + "logit_pipeline/jit(_where)/select_n"),
    (_F.format(12), 566, 10, _S + "sampler_logprobs/jit(log_softmax)/sub"),
    (_F.format(13), 576, 4, _S + "sampler_draw/cond"),
    (_F.format(14), 640, 10, "jit(_decode)/attn/dot_general"),
]
_MODS = [("jit__decode(1)", 0, 5), ("jit__decode(1)", 45, 100),
         ("jit__sample(2)", 150, 20), ("jit__prefill(3)", 290, 50),
         ("jit__sample(4)", 340, 10), ("jit__decode(1)", 455, 100),
         ("jit__sample(2)", 560, 20), ("jit__decode(1)", 640, 10)]
WINDOW_US, BUSY_US, ITERATIONS = 650, 315, 2
# idle microseconds by the path of the innermost covering span
IDLE_US = {
    "schedule": 5 + 10, "decode_inputs": 10 + 10, "decode": 2 + 2,
    "decode/decode_h2d": 18 + 18, "decode/decode_dispatch": 5 + 5,
    "sample/token_read": 26 + 16, "sample": 4 + 4, "emit": 20 + 20,
    "step_tail": 10, "deliver": 10, "intake": 20 + 4, "prefill": 2 + 2,
    "prefill/prefill_call": 18, "prefill/first_token/token_read": 50,
    "prefill/first_token": 18, host_share.SEAMS: 5 + 5,
    host_share.NO_SPAN: 20 - 4,
}
DECODE_SIDE_IDLE_US = (20 + 4 + 36 + 10) + (42 + 8) + 40
WANT = {
    "decode_h2d_ms_per_iter": (18 + 18) / 2 * 1e-3,
    "decode_dispatch_ms_per_iter": (18 + 18) / 2 * 1e-3,
    "sample_operands_ms_per_iter": (18 + 18) / 2 * 1e-3,  # not the prefill's 8
    "sample_dispatch_ms_per_iter": (20 + 20) / 2 * 1e-3,
    "token_read_ms_per_iter": (96 + 86) / 2 * 1e-3,       # not the prefill's 70
    "intake_ms_per_request": (20 + 4) / 2 * 1e-3,
    "sampler_rows_asking_pct": 100.0 * (2 + 0 + 2) / (4 + 1 + 4),
    "token_read_idle_ms_per_iter": (26 + 16) / 2 * 1e-3,
    "decode_host_idle_ms_per_iter": DECODE_SIDE_IDLE_US / 2 * 1e-3,
    "sampler_device_ms_per_iter": (20 + 10 + 20) / 2 * 1e-3,
    "sampler_logprobs_ms_per_iter": (10 + 6 + 10) / 2 * 1e-3,
    "sampler_pipeline_ms_per_iter": (6 + 2 + 6) / 2 * 1e-3,
}


def read(metric: str, run):
    return harness._reader_for(metric)(run)


def hand_built_run(monkeypatch, tmp_path, cell_name=SERVE_CELLS[0],
                   parent=False):
    """The run above, its trace file where ``harness.Profile`` writes one.
    ``parent``: the program before PR 38, which records no sub-span, no
    new argument and no scope inside ``sampler``."""
    host = [h for h in _HOST if not (parent and h[0] in SUB_SPANS)]
    paths = {n: (_S + "mul" if parent and p.startswith(_S) else p)
             for n, _, _, p in _OPS}
    data = (selftest._ld(1, plane_with_paths(
                "/device:TPU:0",
                [("XLA Ops", [(n, a, d) for n, a, d, _ in _OPS]),
                 ("XLA Modules", _MODS)], paths))
            + selftest._ld(1, selftest._plane(
                "/host:CPU", [("engine", [("bench:" + n, a, b - a)
                                          for n, a, b, _ in host]),
                              ("other", [("tsl::something", 0, 650)])])))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    d = tmp_path / "trace" / (cell_name + "-7") / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(data)
    rec = SpanRecorder()
    rec.spans = [(n, a * 1e-6, b * 1e-6,
                  None if parent and n == "intake" else args)
                 for n, a, b, args in host]
    # a span after the measured window is not counted
    rec.spans.append(("decode_h2d", 1.5, 1.6, {"iteration": 9}))
    run = harness.Run(harness.find_cell(harness.load_benchmark(), cell_name),
                      harness.Env([], None), spans=rec,
                      planes=xplane.parse_xspace(data))
    run.values["measured_window"] = (0.0, 1.0)
    return run


def test_host_spans_are_named_by_their_path(monkeypatch, tmp_path):
    run = hand_built_run(monkeypatch, tmp_path)
    spans = host_share.host_spans(run.planes, "bench:")
    assert len(spans) == len(_HOST)  # the profiler's own events are none
    by_path = {}
    for a, b, path in spans:
        by_path.setdefault(path, []).append((a, b))
    assert set(by_path) >= {
        "decode/decode_h2d", "decode/decode_dispatch",
        "sample/sample_operands", "sample/sample_dispatch",
        "sample/token_read", "prefill/first_token/token_read",
        "prefill/first_token/sample_operands", "prefill/prefill_call",
        "schedule", "intake", "emit"}
    assert len(by_path["sample/token_read"]) == 2
    assert len(by_path["prefill/first_token/token_read"]) == 1
    assert host_share.traced_iterations(run) == ITERATIONS


@pytest.mark.parametrize("path,want", [
    ("decode_inputs", True), ("decode", True), ("decode/decode_h2d", True),
    ("sample/token_read", True), ("sample/load_read", True), ("emit", True),
    ("token_read", True),  # the speculative path's: between decode and emit
    ("prefill/first_token/token_read", False), ("prefill/first_token", False),
    ("prefill/prefill_call", False), ("intake", False), ("schedule", False),
    (host_share.SEAMS, False), (host_share.NO_SPAN, False),
])
def test_which_paths_are_an_iterations_decode_half(path, want):
    assert host_share.on_decode_path(path) is want
    assert host_share.decode_token_read(path) is (
        want and path.endswith("token_read"))


def test_idle_by_span_adds_up_to_the_windows_idle_time(monkeypatch,
                                                       tmp_path):
    """Every name, the seams and what no span covers under their own: the
    sum is the idle share x the window (what ``device_idle_pct.serve``
    reads), and the part under the decode half is the metric's."""
    run = hand_built_run(monkeypatch, tmp_path)
    got = host_share.idle_by_span(run)
    assert {k: round(v * 1e6, 6) for k, v in got.items()} == {
        k: float(v) for k, v in IDLE_US.items()}
    busy, window = xplane.busy_and_window(run.planes)
    assert busy * 1e6 == pytest.approx(BUSY_US)
    assert window * 1e6 == pytest.approx(WINDOW_US)
    assert sum(got.values()) == pytest.approx(window - busy)
    idle_pct = harness._reader_for("device_idle_pct.serve")(run)
    assert sum(got.values()) == pytest.approx(idle_pct / 100 * window)
    assert sum(v for k, v in got.items() if host_share.on_decode_path(k)
               ) * 1e6 == pytest.approx(DECODE_SIDE_IDLE_US)
    # the frozen breakdown gives the same seconds to the same innermost
    # spans, under their bare names and cut at ten
    frozen = dict(xplane.idle_gaps_by_host_span(run.planes, "bench:",
                                                limit=99))
    by_leaf = {}
    for k, v in got.items():
        by_leaf[k.rsplit("/", 1)[-1]] = by_leaf.get(
            k.rsplit("/", 1)[-1], 0.0) + v
    assert frozen == pytest.approx(by_leaf)


@pytest.mark.parametrize("metric", SPAN_METRICS + TRACE_METRICS)
def test_host_share_readers_by_hand(monkeypatch, tmp_path, metric):
    run = hand_built_run(monkeypatch, tmp_path)
    got = read(metric, run)
    assert got == pytest.approx(WANT[metric]) and isinstance(got, float)


@pytest.mark.parametrize("metric", SPAN_METRICS + TRACE_METRICS)
def test_host_share_reader_with_nothing_to_read_returns_none(
        monkeypatch, tmp_path, metric):
    """The parent under this PR's benchmark files, a run without a trace,
    a run without a record: None, never 0 and never an exception; but
    what the parent's program already had reads there what it reads
    here."""
    parent = hand_built_run(monkeypatch, tmp_path, parent=True)
    got = read(metric, parent)
    if metric in ("decode_host_idle_ms_per_iter",
                  "sampler_device_ms_per_iter"):
        # the old spans and the program's name mean what they meant
        assert got == pytest.approx(WANT[metric])
    else:
        assert got is None
    bare = hand_built_run(monkeypatch, tmp_path / "b")
    bare.planes = None
    assert (read(metric, bare) is None) == (metric in TRACE_METRICS)
    bare.spans = None
    assert read(metric, bare) is None
    empty = hand_built_run(monkeypatch, tmp_path / "e")
    empty.spans = SpanRecorder()
    empty.planes = xplane.parse_xspace(selftest._ld(1, selftest._plane(
        "/device:TPU:0", [("XLA Ops", [(_F.format(1), 0, 5)])])))
    assert read(metric, empty) is None


@pytest.mark.parametrize("metric", SPAN_METRICS + TRACE_METRICS)
def test_host_share_metrics_are_declared_for_the_four_serve_cells(metric):
    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == metric)
    assert entry["workloads"] == list(SERVE_CELLS)
    decl = harness.load_json("layer_metrics", metric + ".json")
    assert (decl["unit"], decl["layer"], decl["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["moves"] == "itl_mean_ms"
    assert entry["layer"] == ("decode step and kernel"
                              if metric.startswith("sampler_")
                              else "engine loop")
    assert entry["source"] == (
        "device_trace" if metric in TRACE_METRICS else
        "program_counter" if metric == "sampler_rows_asking_pct" else
        "program_span")
    # every serve cell reports it: the cell's own list holds the metric
    for cell in SERVE_CELLS:
        assert metric in [m["name"] for m in harness.find_cell(
            harness.load_benchmark(), cell).per_layer]

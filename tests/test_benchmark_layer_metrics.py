"""The names and spans of ISSUE 24, and the benchmark readers that read
them (``benchmark/layer_metrics/``), at no chip time:

- every name of ``kernel_names.py`` falls in the intended bucket
  under BOTH the program's ``obs/xprof.py`` and the benchmark's frozen
  ``benchmark/lib/xplane.py``, and the needle sets of the new metrics
  are disjoint over the table;
- a hand-made xplane with named kernels, ``op_name`` paths in the event
  metadata and ``bench:`` host spans, and a hand-made span record: each
  of the new readers returns the value computed by hand, and a reader
  with nothing to read returns None;
- a real ``ServingEngine`` under its ``EngineRunner`` at toy size with a
  recording tracer: the new spans' names, nesting and args, the
  ``iteration`` rule (only spans between an iteration's ``schedule``
  start and its ``emit`` end are stamped), what the readers the
  benchmark already had read from it, and ``tracer=None`` sending every
  site to ``NOOP_TRACER``;
- ISSUE 38: the sub-spans inside ``decode``, ``sample`` and
  ``first_token`` (their nesting, their cover of the parent, ``path``,
  ``rows`` and the counts of what the rows asked of the sampler, on the
  plain and on the speculative path), ``intake``'s count of requests, the
  served tokens and ``engine.stats`` with and without a tracer, and the
  scopes inside the sampler program; the readers' cases are
  ``benchmark/tests/test_benchmark_host_share.py``'s, imported below.
"""

import importlib.util
import sys
import threading
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmark"))

import selftest  # noqa: E402  (benchmark/selftest.py: the xplane writer)
from lib import harness, op_phases, xplane  # noqa: E402
from lib.spans import SpanRecorder  # noqa: E402

# PR 38: the cases of the sub-spans' and the sampler scopes' readers live
# with the benchmark (benchmark/tests/); the tier-1 command collects
# ``tests/`` only, so they are imported here and run under their own
# names, as tests/test_benchmark_program.py does with its own
_spec = importlib.util.spec_from_file_location(
    "benchmark_host_share_cases",
    REPO / "benchmark" / "tests" / "test_benchmark_host_share.py")
_host_share_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_host_share_cases)
globals().update({name: fn for name, fn in vars(_host_share_cases).items()
                  if name.startswith("test_")})

# PR 40 added a fifth serve cell to the lists of the host-share metrics
# that still read something (not to the two sampler metrics that fell
# silent with PR 39). The benchmark's own case pins the FOUR cells of PR 38
# and is a file no later PR may edit, so its case of that name is replaced
# here by the same checks against the lists as they stand; run on its own
# (`pytest benchmark/tests`) the old one fails until a `benchmark` PR
# updates it (PERF.md section 7).
DSV2_CELL = "serve-deepseek-v2-5l-ep8-code-chat"
# PR 42 added a sixth, to the same lists
NEMOTRON_CELL = "serve-nemotron3-super-11l-ep4-agent-turns"
# PR 47 a seventh
LFM2_CELL = "serve-lfm2-24b-a2b-5l-extract-rag"
_SILENT = ("sampler_logprobs_ms_per_iter", "sampler_pipeline_ms_per_iter")


@pytest.mark.parametrize("metric", _host_share_cases.SPAN_METRICS
                         + _host_share_cases.TRACE_METRICS)
def test_host_share_metrics_are_declared_for_the_four_serve_cells(metric):
    cells = list(_host_share_cases.SERVE_CELLS)
    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == metric)
    assert entry["workloads"] == cells + (
        [] if metric in _SILENT else [DSV2_CELL, NEMOTRON_CELL, LFM2_CELL])
    decl = harness.load_json("layer_metrics", metric + ".json")
    assert (decl["unit"], decl["layer"], decl["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["moves"] == "itl_mean_ms"
    assert entry["layer"] == ("decode step and kernel"
                              if metric.startswith("sampler_")
                              else "engine loop")
    assert entry["source"] == (
        "device_trace" if metric in _host_share_cases.TRACE_METRICS else
        "program_counter" if metric == "sampler_rows_asking_pct" else
        "program_span")
    for cell in entry["workloads"]:
        assert metric in [m["name"] for m in harness.find_cell(
            harness.load_benchmark(), cell).per_layer]


from differential_transformer_replication_tpu.config import (  # noqa: E402
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import init_model  # noqa: E402
from differential_transformer_replication_tpu.obs import spans as obs_spans  # noqa: E402
from differential_transformer_replication_tpu.obs import xprof  # noqa: E402
from differential_transformer_replication_tpu import kernel_names  # noqa: E402
from differential_transformer_replication_tpu.serving import (  # noqa: E402
    SamplingParams,
    ServingEngine,
)
from differential_transformer_replication_tpu.serving.server import (  # noqa: E402
    EngineRunner,
)

TRAIN_CELL, SERVE_CELL = "train-diff-recipe", "serve-diff-recipe-chat"
NEW_TRAIN = ("attn_kernel_ms_per_step", "attn_bwd_kernel_ms_per_step",
             "ffn_kernel_ms_per_step", "norm_kernel_ms_per_step",
             "flash_attention_roofline", "ffn_fwd_kernel_ms_per_step",
             "ffn_bwd_kernel_ms_per_step", "fused_ffn_roofline")
TRAIN_PHASES = ("xla_attn_ms_per_step", "xla_ffn_ms_per_step",
                "xla_vocab_ms_per_step", "xla_optimizer_ms_per_step",
                "xla_unscoped_ms_per_step")
SERVE_PHASES = ("decode_attn_ms_per_step", "decode_kv_write_ms_per_step",
                "decode_ffn_ms_per_step", "decode_kv_merge_ms_per_step",
                "decode_rest_ms_per_step")
NEW_SERVE = ("prefill_ms_per_iter", "prefill_calls_per_iter",
             "first_token_sync_ms_per_iter", "between_iterations_ms",
             "prefill_tokens_per_call")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _needles(metric: str):
    return harness.load_json("layer_metrics", metric + ".json")["source"]["needles"]


def read(metric: str, run):
    return harness._reader_for(metric)(run)


# -- the table of names -------------------------------------------------------

# (family of the table, the bucket of obs/xprof.py and benchmark/lib/xplane.py)
BUCKET_OF_FAMILY = {"flash_attention": "flash_attention",
                    "fused_ffn": "fused_ffn", "fused_norm": "fused_ffn",
                    "decode_attention": "decode_attention",
                    "kv_write": "kv_write", "ssm": "ssm", "kda": "kda",
                    "moe": "moe", "ring_attention": "ring_attention",
                    "mla": "mla"}
# the benchmark's needles are frozen (PR 23): a kernel named after them
# is one more Pallas kernel to that reader, by the call's target
FROZEN_READER = {"kv_write": "pallas", "ssm": "pallas", "kda": "pallas",
                 "moe": "pallas", "ring_attention": "pallas", "mla": "pallas"}
NAMES = [(fam, name) for fam, names in kernel_names.FAMILIES.items()
         for name in names]


def _instruction(name: str, n: int = 3) -> str:
    """The event's name on the chip: the whole HLO instruction."""
    return (f"%{name}.{n} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p.1), "
            'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("family,name", NAMES, ids=[n for _, n in NAMES])
def test_name_falls_in_its_bucket_under_both_readers(family, name):
    want = BUCKET_OF_FAMILY[family]
    for text in (_instruction(name), _instruction(f"vmap_{name}_", 1)):
        assert xprof.bucket_for(text) == want
        assert xplane.bucket_for(text) == FROZEN_READER.get(family, want)
    # an op that READS the kernel's result is not the kernel
    reader = f"%fusion.9 = f32[8]{{0}} fusion(bf16[8]{{0}} %{name}.3)"
    assert xprof.bucket_for(reader) is None
    assert xplane.bucket_for(reader) is None
    if family == "flash_attention":
        assert name.startswith(("flash_fwd", "flash_bwd"))


def test_table_is_whole_and_the_metrics_needles_are_disjoint():
    assert len(kernel_names.ALL) == len(set(kernel_names.ALL)) == 26
    assert sorted(kernel_names.ALL) == sorted(n for _, n in NAMES)
    sets = {
        "attn": ["flash"],  # every needle-reader of the flash family
        "ffn": _needles("ffn_kernel_ms_per_step"),
        "norm": _needles("norm_kernel_ms_per_step"),
    }
    family_of = {"attn": "flash_attention", "ffn": "fused_ffn",
                 "norm": "fused_norm"}
    for key, needles in sets.items():
        hit = {n for n in kernel_names.ALL if any(x in n for x in needles)}
        assert hit == set(kernel_names.FAMILIES[family_of[key]]), key
    bwd = {n for n in kernel_names.ALL
           if any(x in n for x in _needles("attn_bwd_kernel_ms_per_step"))}
    assert bwd == {n for n in kernel_names.FLASH if n.startswith("flash_bwd")}
    assert all("_dattn_" in n for n in kernel_names.DECODE)
    assert not any("_dattn_" in n for n in kernel_names.ALL
                   if n not in kernel_names.DECODE)


@pytest.mark.parametrize("metric, kernel", [
    ("ssm_state_update_roofline", kernel_names.SSM_STATE_UPDATE),
    ("ssm_scan_roofline", kernel_names.SSM_SCAN_FWD),
    ("kda_state_update_roofline", kernel_names.KDA_STATE_UPDATE),
])
def test_an_older_state_kernel_s_needles_do_not_catch_the_mamba2_update(
        metric, kernel):
    """PR 42's `ssm_ssd_state_update` starts `ssm_` (its family's rule) and
    holds none of the accepted readers' needles: the jamba cell's rooflines
    go on reading jamba's kernels alone, and the nemotron_h cell reads its
    own by scope (`ssm_state`), not by name."""
    needles = _needles(metric)
    assert any(x in kernel for x in needles)
    assert not any(x in kernel_names.SSD_STATE_UPDATE for x in needles)
    assert kernel_names.SSD_STATE_UPDATE.startswith("ssm_")
    assert kernel_names.SSD_STATE_UPDATE in kernel_names.FAMILIES["ssm"]


# -- a hand-made trace ----------------------------------------------------------
# One chip, two traced steps, microseconds: the named kernels back to back,
# then a fusion that READS a flash kernel (and is none), a gap of 100 us that
# the host spends inside `prefill`: 30 under `prefill_call`, 70 under
# `first_token` (the innermost span names a gap), and a last op.
_CC = ', custom_call_target="tpu_custom_call"'
_OPS = [
    (f"%flash_fwd_tm_packed.1 = bf16[8] custom-call(bf16[8] %p){_CC}", 0, 30),
    (f"%flash_bwd_tm_packed.2 = bf16[8] custom-call(bf16[8] %p){_CC}", 30, 60),
    (f"%fused_ffn_fwd.3 = bf16[8] custom-call(bf16[8] %p){_CC}", 90, 20),
    (f"%fused_ffn_bwd.4 = bf16[8] custom-call(bf16[8] %p){_CC}", 110, 40),
    (f"%vmap_fused_add_norm_fwd_.5 = bf16[8] custom-call(bf16[8] %p){_CC}", 150, 10),
    (f"%fused_add_norm_bwd.6 = bf16[8] custom-call(bf16[8] %p){_CC}", 160, 15),
    ("%fusion.7 = f32[8] fusion(bf16[8] %flash_fwd_tm_packed.1)", 175, 25),
    ("%fusion.8 = f32[8] fusion(f32[8] %p)", 300, 10),
]
_HOST = [("bench:prefill", 190, 115), ("bench:prefill_call", 195, 35),
         ("bench:first_token", 230, 70), ("other", 0, 400)]
TRACE_STEPS = 2


@pytest.fixture(scope="module")
def planes():
    data = (selftest._ld(1, selftest._plane(
                "/device:TPU:0", [("XLA Ops", _OPS)]))
            + selftest._ld(1, selftest._plane(
                "/host:CPU", [("engine", _HOST)])))
    return xplane.parse_xspace(data)


def _train_run(planes, peaks=PEAKS):
    cell = harness.find_cell(harness.load_benchmark(), TRAIN_CELL)
    run = harness.Run(cell, harness.Env([], peaks), planes=planes)
    run.values.update(trace_steps=TRACE_STEPS, rows_per_chip=64, seq_len=512)
    return run


@pytest.mark.parametrize("metric,want_ms", [
    ("attn_kernel_ms_per_step", (30 + 60) / 2 * 1e-3),
    ("attn_bwd_kernel_ms_per_step", 60 / 2 * 1e-3),
    ("ffn_kernel_ms_per_step", (20 + 40) / 2 * 1e-3),
    ("norm_kernel_ms_per_step", (10 + 15) / 2 * 1e-3),
    ("ffn_fwd_kernel_ms_per_step", 20 / 2 * 1e-3),
    ("ffn_bwd_kernel_ms_per_step", 40 / 2 * 1e-3),
    ("pallas_kernels_ms_per_step", 175 / 2 * 1e-3),  # the three above
])
def test_kernel_time_readers_on_the_named_trace(planes, metric, want_ms):
    assert read(metric, _train_run(planes)) == pytest.approx(want_ms)


def test_flash_attention_roofline_by_hand(planes, capsys):
    # diff recipe: 8 layers, 4 heads, 2 streams of 96, values of 192;
    # 64 rows of 512 tokens: 131,328 causal pairs a head
    pairs = 512 * 513 // 2
    flops = 8 * 64 * 4 * 2 * pairs * (3 * 2 * 96 + 3 * 192)
    qk, vo = 2 * 4 * 512 * 96 * 2, 4 * 512 * 192 * 2
    nbytes = 8 * 64 * (6 * qk + 6 * vo)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9  # memory-bound at T=512
    got = read("flash_attention_roofline", _train_run(planes))
    assert got == pytest.approx(100 * least / 45e-6)
    said = capsys.readouterr().out
    assert "memory-bound" in said and f"{flops:.4g} operations" in said
    # the control recipe needs the same: 8 heads of one stream of 96
    cell = harness.find_cell(harness.load_benchmark(), "train-control-recipe")
    run = _train_run(planes)
    run.cell = cell
    assert read("flash_attention_roofline", run) == pytest.approx(got)


def test_fused_ffn_roofline_by_hand(planes, capsys):
    # 8 layers, 64 * 512 tokens, 768 wide, hidden 3072: gate and xform
    # forward, dWg and dWx backward, 2 operations a multiply-add
    M, E, F = 64 * 512, 768, 3072
    flops = 8 * 4 * 2 * M * E * F
    nbytes = 8 * (2 * 2 * (M * E + 2 * E * F + M * F)
                  + 2 * 2 * M * F + 4 * 2 * E * F)
    least = flops / 197e12
    assert least > nbytes / 819e9  # compute-bound
    got = read("fused_ffn_roofline", _train_run(planes))
    assert got == pytest.approx(100 * least / 30e-6)  # (20 + 40) us / 2 steps
    said = capsys.readouterr().out
    assert "compute-bound" in said and f"{flops:.4g} operations" in said


def test_breakdown_names_kernels_and_gaps(planes):
    summary = xplane.summary(planes, "bench:")
    ops = dict(summary["breakdown"]["device_ops"])
    assert not any(k.startswith("pallas:") for k in ops)
    assert ops["flash_attention"] == pytest.approx(90e-6)
    assert ops["fused_ffn"] == pytest.approx(85e-6)  # FFN and norm kernels
    assert ops["fusion"] == pytest.approx(35e-6)
    gaps = dict(summary["breakdown"]["idle_gaps"])
    assert gaps["prefill_call"] == pytest.approx(30e-6)
    assert gaps["first_token"] == pytest.approx(70e-6)
    assert "prefill" not in gaps and "no-host-span" not in gaps


# -- the phase scopes, from the trace's metadata ---------------------------------
# A chip trace names an event by its instruction's text; the instruction's
# op_name, scopes and all, is the `tf_op` stat of the event's METADATA, as a
# string or as a reference to a stat_metadata entry whose name is the string.


# two traced train steps, microseconds; a cond holds an op of its body and
# an unscoped while holds an op of attention: time goes to the innermost
_F = "%fusion.{} = f32[8] fusion(f32[8] %p)"
_STEP_OPS = [
    (_F.format(1), 0, 30, "jit(step)/jvp(attn)/dot_general"),
    (f"%flash_fwd_tm.2 = bf16[8] custom-call(bf16[8] %p){_CC}", 30, 20,
     "jit(step)/jvp(attn)/flash_fwd_tm/pallas_call"),
    (_F.format(3), 50, 40, "jit(step)/transpose(jvp(ffn))/dot_general:"),
    ("%cond.4 = f32[8] conditional(pred[] %p)", 100, 60,
     "jit(step)/optimizer/cond"),
    (_F.format(5), 110, 20, "jit(step)/optimizer/cond/branch_1_fun/mul"),
    ("%copy.6 = f32[8] copy(f32[8] %p)", 160, 10, None),
    (_F.format(7), 170, 20, "jit(step)/jvp(lm_head_loss)/reduce_sum"),
    ("%gather.8 = f32[8] gather(f32[8] %p)", 190, 5, "jit(step)/embed/gather"),
    (_F.format(9), 195, 5, "jit(step)/jvp(ffn_norm)/add"),
    (_F.format(10), 200, 10, "jit(step)/grad_norm_clip/reduce_sum"),
    ("%while.11 = f32[8] while(f32[8] %p)", 220, 40, None),
    (_F.format(12), 230, 10, "jit(step)/while/body/attn_norm/mul"),
]
STEP_PHASE_US = {"xla_attn_ms_per_step": 30 + 10, "xla_ffn_ms_per_step": 40 + 5,
                 "xla_vocab_ms_per_step": 20 + 5,
                 "xla_optimizer_ms_per_step": 60 + 10,
                 "xla_unscoped_ms_per_step": 10 + 30}
# two executions of the decode program with a prefill program between them
_D = "jit(_decode)/"
_DECODE_OPS = [
    (_F.format(1), 0, 20, _D + "attn/dot_general"),
    ("%dynamic-update-slice.2 = f32[8] dynamic-update-slice(f32[8] %p)", 20,
     30, _D + "attn/kv_write/dynamic_update_slice"),
    (f"%vmap_fused_ffn_fwd_.3 = bf16[8] custom-call(bf16[8] %p){_CC}", 50, 20,
     _D + "ffn/vmap(fused_ffn_fwd)/pallas_call"),
    (_F.format(4), 70, 10, _D + "lm_head/dot_general"),
    ("%select.7 = f32[8] select(pred[8] %p)", 80, 5, _D + "kv_merge/select_n"),
    ("%copy.5 = f32[8] copy(f32[8] %p)", 85, 10, None),
]
_SERVE_OPS = (_DECODE_OPS
              + [(_F.format(6), 100, 40, "jit(_prefill)/attn/dot_general")]
              + [(n, a + 200, d, p) for n, a, d, p in _DECODE_OPS])
_SERVE_MODS = [("jit__decode(1)", 0, 100), ("jit__prefill(2)", 100, 50),
               ("jit__decode(1)", 200, 100)]
DECODE_PHASE_US = {"decode_attn_ms_per_step": 20,
                   "decode_kv_write_ms_per_step": 30,
                   "decode_ffn_ms_per_step": 20,
                   "decode_kv_merge_ms_per_step": 5,
                   "decode_rest_ms_per_step": 10 + 10}


def _traced_run(monkeypatch, tmp_path, cell_name, ops, mods=(), scoped=True):
    """A run whose trace file lies where ``harness.Profile`` writes one."""
    paths = {n: p for n, _, _, p in ops if scoped}
    lines = [("XLA Ops", [(n, a, d) for n, a, d, _ in ops])]
    if mods:
        lines.append(("XLA Modules", list(mods)))
    data = selftest._ld(1, _host_share_cases.plane_with_paths(
        "/device:TPU:0", lines, paths, by_ref={_F.format(3)}))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    # an older trace of another cell whose name starts the same: not this one
    for tag, blob in ((cell_name + "-dp4-5", b""), (cell_name + "-7", data)):
        d = tmp_path / "trace" / tag / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        (d / "h.xplane.pb").write_bytes(blob)
    cell = harness.find_cell(harness.load_benchmark(), cell_name)
    run = harness.Run(cell, harness.Env([], PEAKS),
                      planes=xplane.parse_xspace(data))
    run.values.update(trace_steps=TRACE_STEPS)
    return run


def test_phase_of_takes_the_innermost_scope():
    assert op_phases.phase_of("jit(step)/transpose(jvp(ffn))/dot_general:") == "ffn"
    assert op_phases.phase_of("jit(_decode)/attn/kv_write/dus") == "kv_write"
    assert op_phases.phase_of("jit(step)/jvp(jit(_attn))/mul") is None
    assert op_phases.phase_of("") is None


@pytest.mark.parametrize("metric", TRAIN_PHASES)
def test_train_phase_readers_by_hand(monkeypatch, tmp_path, metric):
    run = _traced_run(monkeypatch, tmp_path, TRAIN_CELL, _STEP_OPS)
    assert read(metric, run) == pytest.approx(
        STEP_PHASE_US[metric] / TRACE_STEPS * 1e-3)
    if metric == TRAIN_PHASES[0]:
        # the parts and the Pallas kernel add up to the union of the ops
        parts = sum(read(m, run) for m in TRAIN_PHASES)
        busy, _ = xplane.busy_and_window(run.planes)
        assert parts + 20 / TRACE_STEPS * 1e-3 == pytest.approx(
            busy * 1e3 / TRACE_STEPS)


@pytest.mark.parametrize("metric", SERVE_PHASES)
def test_decode_phase_readers_by_hand(monkeypatch, tmp_path, metric):
    run = _traced_run(monkeypatch, tmp_path, SERVE_CELL, _SERVE_OPS,
                      _SERVE_MODS)
    assert read(metric, run) == pytest.approx(DECODE_PHASE_US[metric] * 1e-3)


@pytest.mark.parametrize("metric", TRAIN_PHASES + SERVE_PHASES)
def test_phase_reader_with_nothing_to_read_returns_none(
        monkeypatch, tmp_path, metric):
    """No trace, or a trace of a program without the scopes (the parent
    under this PR's benchmark files): None, the unscoped share too."""
    cell, ops, mods = ((TRAIN_CELL, _STEP_OPS, ()) if metric in TRAIN_PHASES
                       else (SERVE_CELL, _SERVE_OPS, _SERVE_MODS))
    run = _traced_run(monkeypatch, tmp_path, cell, ops, mods, scoped=False)
    assert read(metric, run) is None
    run.planes = None
    assert read(metric, run) is None
    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == metric)
    assert cell in entry["workloads"] and entry["source"] == "device_trace"
    decl = harness.load_json("layer_metrics", metric + ".json")
    assert (decl["unit"], decl["layer"], decl["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])


# -- a hand-made span record --------------------------------------------------
# Two iterations, seconds. Iteration 0 prefills two chunks, one of which
# completes a prompt; iteration 1 only decodes. What lies after `emit`
# carries no stamp.
def _recorded() -> SpanRecorder:
    rec = SpanRecorder()
    it0, it1 = {"iteration": 0}, {"iteration": 1}
    rec.spans = [
        ("schedule", 0.000, 0.001, it0),
        ("prefill_call", 0.001, 0.003, dict(it0, size=16)),
        ("prefill_call", 0.003, 0.006, dict(it0, size=8)),
        ("first_token", 0.006, 0.010, it0),
        ("prefill", 0.001, 0.011, dict(it0, chunks=2)),
        ("decode_inputs", 0.011, 0.012, it0),
        ("decode", 0.012, 0.013, dict(it0, active=3)),
        ("sample", 0.013, 0.019, it0),
        ("emit", 0.019, 0.020, it0),
        ("step_tail", 0.020, 0.021, None),
        ("deliver", 0.021, 0.023, None),
        ("intake", 0.023, 0.024, None),
        ("schedule", 0.024, 0.025, it1),
        ("decode_inputs", 0.025, 0.026, it1),
        ("decode", 0.026, 0.027, dict(it1, active=4)),
        ("sample", 0.027, 0.039, it1),
        ("emit", 0.039, 0.040, it1),
        ("step_tail", 0.040, 0.0405, None),
        ("deliver", 0.0405, 0.041, None),
        # after the window: not counted
        ("intake", 1.5, 1.6, None),
    ]
    return rec


def _serve_run(rec):
    cell = harness.find_cell(harness.load_benchmark(), SERVE_CELL)
    run = harness.Run(cell, harness.Env([], None), spans=rec)
    run.values["measured_window"] = (0.0, 1.0)
    return run


@pytest.mark.parametrize("metric,want", [
    ("prefill_ms_per_iter", 10.0 / 2),
    ("prefill_calls_per_iter", 2 / 2),
    ("first_token_sync_ms_per_iter", 4.0 / 2),
    ("between_iterations_ms", (1.0 + 2.0 + 1.0 + 0.5 + 0.5) / 2),
    ("prefill_tokens_per_call", (16 + 8) / 2),
    ("engine_iter_p50_ms", (20.0 + 16.0) / 2),  # schedule start to emit end
])
def test_span_readers_by_hand(metric, want):
    assert read(metric, _serve_run(_recorded())) == pytest.approx(want)


def _parent_like(rec: SpanRecorder) -> SpanRecorder:
    """What the program recorded before this PR: no new span, no new arg."""
    old = SpanRecorder()
    old.spans = [s for s in rec.spans
                 if s[0] in ("schedule", "prefill", "decode", "sample", "emit")]
    return old


@pytest.mark.parametrize("metric", NEW_TRAIN + NEW_SERVE)
def test_reader_with_nothing_to_read_returns_none(metric):
    """No trace, no spans, or a program that lacks the names and spans
    (the parent under this PR's benchmark files): None, never 0 and
    never an exception."""
    bench = harness.load_benchmark()
    if metric in NEW_TRAIN:
        unnamed = xplane.parse_xspace(selftest._ld(1, selftest._plane(
            "/device:TPU:0", [("XLA Ops", [
                (f"%jvp__.3 = bf16[8] custom-call(bf16[8] %p){_CC}", 0, 20),
                (f"%transpose_jvp___.4 = bf16[8] custom-call(bf16[8] %p){_CC}",
                 20, 40)])])))
        assert read(metric, _train_run(None)) is None
        assert read(metric, _train_run(unnamed)) is None
        cell = TRAIN_CELL
    else:
        assert read(metric, _serve_run(None)) is None
        assert read(metric, _serve_run(SpanRecorder())) is None
        got = read(metric, _serve_run(_parent_like(_recorded())))
        if metric in ("prefill_ms_per_iter", "prefill_calls_per_iter"):
            assert got is not None  # the parent has `prefill` and `chunks`
        else:
            assert got is None
        cell = SERVE_CELL
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert cell in entry["workloads"]
    decl = harness.load_json("layer_metrics", metric + ".json")
    assert (decl["unit"], decl["layer"], decl["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])


@pytest.mark.parametrize("rows, want", [
    ((32, 64), 48.0),      # the mean over the window's decode spans
    ((None, 32), 32.0),    # a span without the argument is no step of it
    ((None, None), None),  # the parent: `active` alone
    (None, None),          # no record at all
], ids=["both", "one", "parent", "no-spans"])
def test_decode_attn_rows_read_per_step_by_hand(rows, want):
    """PR 33: the decode spans' ``attend_rows`` (models/decode.py
    ``attend_rows`` of the engine's mask), a step; None, never 0 and
    never an exception, for a program that records none."""
    metric = "decode_attn_rows_read_per_step"
    rec = None
    if rows is not None:
        rec = _recorded()
        given = iter(rows)
        rec.spans = [
            (n, a, b, dict(args, attend_rows=r) if n == "decode" and (
                r := next(given)) is not None else args)
            for n, a, b, args in rec.spans]
        # a step after the window is not counted
        rec.spans.append(("decode", 1.5, 1.6, {"active": 1,
                                               "attend_rows": 256}))
    got = read(metric, _serve_run(rec))
    assert got == want and (want is None or isinstance(got, float))
    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == metric)
    assert entry["workloads"] == [SERVE_CELL]
    decl = harness.load_json("layer_metrics", metric + ".json")
    assert (decl["unit"], decl["layer"], decl["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"]) == (
        "rows", "decode step and kernel", "itl_mean_ms")


# -- the engine's spans, from a real engine under its runner ------------------

STAMPED = {"schedule", "prefill", "prefill_call", "first_token",
           "decode_inputs", "decode", "sample", "emit"}
UNSTAMPED = {"step_tail", "intake", "deliver"}
# ISSUE 38: the parts of `decode`, `sample` and `first_token`, stamped
# like their parents
SUB_SPANS = {"decode_h2d", "decode_dispatch", "sample_operands",
             "sample_dispatch", "token_read"}
USE = ("masked", "penalized", "logprobs", "tempered", "asking")
PROMPTS = [list(range(1, 1 + n)) for n in (5, 9, 20, 3)]  # 20 > one chunk
NEW_TOKENS = 4


def _toy():
    cfg = ModelConfig(model="diff", vocab_size=61, n_embd=32, n_head=2,
                      n_layer=2, block_size=32, dropout=0.0,
                      compute_dtype="float32")
    serving = ServingConfig(num_slots=4, prefill_chunk=8, prefill_budget=32)
    return cfg, init_model(jax.random.PRNGKey(0), cfg), serving


def _serve(tracer):
    """The toy engine under its runner through PROMPTS; returns the
    request ids in submission order."""
    cfg, params, serving = _toy()
    runner = EngineRunner(ServingEngine(params, cfg, serving, tracer=tracer))
    try:
        pend = [runner.submit(p, SamplingParams(max_new_tokens=NEW_TOKENS))
                for p in PROMPTS]
        for p in pend:
            assert p.done.wait(120) and p.error is None, p.error
        return [p.result.request_id for p in pend]
    finally:
        runner.drain(timeout=30)


@pytest.fixture(scope="module")
def served():
    rec = SpanRecorder()
    rids = _serve(rec)
    return rec, rids


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_span_names_args_and_the_iteration_rule(served):
    rec, rids = served
    by = {}
    for s in rec.spans:
        by.setdefault(s[0], []).append(s)
    assert STAMPED | UNSTAMPED <= set(by)
    for name in STAMPED:
        assert all("iteration" in (s[3] or {}) for s in by[name]), name
    for name in UNSTAMPED:
        assert all("iteration" not in (s[3] or {}) for s in by[name]), name
    # what the readers of PR 23 read keeps its names and args
    assert all({"iteration", "active"} <= set(s[3]) for s in by["decode"])
    admits = [a for n, _, a in rec.instants if n == "admit"]
    assert sorted(a["rid"] for a in admits) == sorted(rids)
    # prefill: its sub-spans inside it, one prefill_call a chunk; no arg
    # that nothing reads
    chunks = 0
    for p in by["prefill"]:
        args = p[3]
        assert {"iteration", "chunks"} == set(args)
        calls = [c for c in by["prefill_call"]
                 if c[3]["iteration"] == args["iteration"]]
        firsts = [f for f in by["first_token"]
                  if f[3]["iteration"] == args["iteration"]]
        assert len(calls) == args["chunks"]
        assert all(_inside(s, p) for s in calls + firsts)
        assert all({"iteration", "size"} == set(c[3]) for c in calls)
        assert all({"iteration"} == set(f[3]) for f in firsts)
        # the sampler call of a completed prompt, taken apart: dispatched
        # and not read there (ISSUE 41: the token is read with the next
        # read of the decode path, inside `sample`)
        for f in firsts:
            parts = [c for n in SUB_SPANS - {"decode_h2d", "decode_dispatch"}
                     for c in by[n] if _inside(c, f)]
            assert sorted(c[0] for c in parts) == [
                "sample_dispatch", "sample_operands"]
            assert all(c[3]["path"] == "prefill"
                       and c[3]["iteration"] == args["iteration"]
                       for c in parts)
        chunks += args["chunks"]
    # every chunk the scheduler planned is one prefill_call, at most 8 long
    assert chunks == len(by["prefill_call"]) >= 1 + 2 + 3 + 1
    assert all(c[3]["size"] <= 8 for c in by["prefill_call"])
    assert sum(c[3]["size"] for c in by["prefill_call"]) == sum(
        map(len, PROMPTS))
    assert len(by["first_token"]) == len(PROMPTS)
    # every stamped span lies between its iteration's schedule start and
    # the end of its emit (or of its prefill, where nothing decoded yet,
    # or of its sample, where a step was dispatched and nothing was in
    # flight to read and emit: the first step of the late read)
    sched = {s[3]["iteration"]: s for s in by["schedule"]}
    last = {}
    for s in by["emit"] + by["prefill"] + by["sample"]:
        it = s[3]["iteration"]
        last[it] = max(last.get(it, 0.0), s[2])
    for name in STAMPED | SUB_SPANS:
        for s in by[name]:
            it = s[3]["iteration"]
            assert sched[it][1] <= s[1] and s[2] <= last[it], (name, it)
    # decode_inputs ends before its decode starts
    dec = {s[3]["iteration"]: s for s in by["decode"]}
    for s in by["decode_inputs"]:
        assert s[2] <= dec[s[3]["iteration"]][1]
    assert not any(s[3] for name in ("step_tail", "deliver")
                   for s in by[name])
    # intake: the requests it handed engine.submit, and nothing else
    assert all(set(s[3]) == {"submitted"} for s in by["intake"])
    assert sum(s[3]["submitted"]["requests"] for s in by["intake"]) == len(
        PROMPTS)
    # step_tail, deliver and intake lie outside every iteration
    spans_of_iter = [(sched[it][1], end) for it, end in last.items()]
    for name in UNSTAMPED:
        for s in by[name]:
            assert not any(a < s[2] and s[1] < b for a, b in spans_of_iter), name


def test_readers_over_the_engines_own_spans(served):
    rec, _ = served
    run = _serve_run(rec)
    t0 = min(s[1] for s in rec.spans) - 1.0
    t1 = max(s[2] for s in rec.spans) + 1.0
    run.values["measured_window"] = (t0, t1)
    by = {}
    for s in rec.spans:
        by.setdefault(s[0], []).append(s)
    iters = {s[3]["iteration"] for s in by["schedule"]}
    ms = lambda spans: sum(b - a for _, a, b, _ in spans) * 1e3  # noqa: E731
    n = len(iters)
    assert read("prefill_ms_per_iter", run) == pytest.approx(ms(by["prefill"]) / n)
    assert read("prefill_calls_per_iter", run) == pytest.approx(
        len(by["prefill_call"]) / n)
    assert read("first_token_sync_ms_per_iter", run) == pytest.approx(
        ms(by["first_token"]) / n)
    assert read("between_iterations_ms", run) == pytest.approx(
        ms(by["step_tail"] + by["deliver"] + by["intake"]) / n)
    assert read("prefill_tokens_per_call", run) == pytest.approx(
        sum(map(len, PROMPTS)) / len(by["prefill_call"]))
    # engine_iter_p50_ms reads what it read before the new spans: from an
    # iteration's schedule start to the end of its emit (or prefill)
    from lib.stats import percentile

    first = {s[3]["iteration"]: s[1] for s in by["schedule"]}
    last = {}
    for s in by["emit"] + by["prefill"]:
        last[s[3]["iteration"]] = max(last.get(s[3]["iteration"], 0.0), s[2])
    want = percentile([(last[i] - first[i]) * 1e3 for i in first], 50)
    assert read("engine_iter_p50_ms", run) == pytest.approx(want)
    old = _serve_run(_parent_like(rec))
    old.values["measured_window"] = (t0, t1)
    assert read("engine_iter_p50_ms", old) == pytest.approx(want)
    # queue_wait_p50_ms and slot_occupancy_pct, from what the cell's
    # driver takes of the record (the admit instants' rid, decode's active)
    run.values["decode_active_share"] = [s[3]["active"] / 4 for s in by["decode"]]
    assert 0 < read("slot_occupancy_pct", run) <= 100
    run.values["queue_wait_ms"] = [1.0, 3.0]
    assert read("queue_wait_p50_ms", run) == pytest.approx(2.0)


def test_tracing_off_sends_every_site_to_the_noop_tracer(monkeypatch):
    seen = []
    real = obs_spans._NoopTracer.span

    def counting(self, name, **args):
        seen.append((name, args, threading.current_thread().name))
        return real(self, name, **args)

    monkeypatch.setattr(obs_spans._NoopTracer, "span", counting)
    cfg, params, serving = _toy()
    engine = ServingEngine(params, cfg, serving, tracer=None)
    assert engine.tracer is obs_spans.NOOP_TRACER and not engine._tracing
    _serve(None)
    names = {n for n, _, _ in seen}
    assert STAMPED | UNSTAMPED | SUB_SPANS <= names
    # nothing was counted or listed for a span nobody records
    for name, args, _ in seen:
        if name == "prefill":
            assert set(args) == {"iteration", "chunks"}
        if name == "decode":
            # `attend_rows` (PR 33) is one number, which the engine's
            # counter `decode_attend_rows` takes whether traced or not;
            # `lookahead` and `inflight_dropped` (ISSUE 41) are the loop's
            # own state, counted in `engine.stats` either way
            assert set(args) == {"iteration", "active", "attend_rows",
                                 "lookahead", "inflight_dropped"}
        # ISSUE 38: the bytes and the counts of use cost a loop each
        if name == "decode_h2d":
            assert set(args) == {"iteration"}
        if name == "sample_operands":
            assert set(args) == {"iteration", "path", "rows", "active"}


# -- ISSUE 38: the host's share of an iteration, taken apart --------------------


def _by_name(rec):
    by = {}
    for s in rec.spans:
        by.setdefault(s[0], []).append(s)
    return by


def _children(by, parent, names):
    return [c for n in names for c in by.get(n, ()) if _inside(c, parent)]


def _cover(by, parent_name, names) -> float:
    """The share of the parents' time that their sub-spans cover, summed
    over the record."""
    whole = sum(p[2] - p[1] for p in by[parent_name])
    parts = sum(c[2] - c[1] for p in by[parent_name]
                for c in _children(by, p, names))
    return parts / whole


def test_sub_spans_lie_inside_their_parents_and_cover_them(served):
    rec, _ = served
    by = _by_name(rec)
    assert SUB_SPANS <= set(by)
    for d in by["decode"]:
        parts = _children(by, d, ("decode_h2d", "decode_dispatch"))
        assert [c[0] for c in sorted(parts, key=lambda c: c[1])] == [
            "decode_h2d", "decode_dispatch"]
        assert all(c[3]["iteration"] == d[3]["iteration"] for c in parts)
        h2d = next(c for c in parts if c[0] == "decode_h2d")
        # ONE packed int32 array (ISSUE 41): token, position, active and
        # from_host a row of the pool
        rows = 4  # the toy's slots
        assert set(h2d[3]) == {"iteration", "arrays", "bytes"}
        assert (h2d[3]["arrays"], h2d[3]["bytes"]) == (1, rows * 4 * 4)
    dispatched, read_late = 0, 0
    for smp in by["sample"]:
        parts = _children(by, smp, SUB_SPANS)
        # the step's sampler call, then the ONE read: of the iteration
        # before (`of_iteration`), which the first step has none of and
        # the last iteration, with nothing left to dispatch, is all of
        names = [c[0] for c in sorted(parts, key=lambda c: c[1])]
        reads = [c for c in parts if c[0] == "token_read"]
        assert names[:len(names) - len(reads)] in (
            ["sample_operands", "sample_dispatch"], []), names
        # the first tokens of the iteration before are read (and emitted)
        # ahead of its step's rows: `path` tells the two reads apart
        assert [c[3]["path"] for c in reads] in (
            [], ["decode"], ["prefill"], ["prefill", "decode"]), names
        assert all(c[3]["iteration"] == smp[3]["iteration"] for c in parts)
        assert all(c[3]["path"] == "decode" for c in parts
                   if c[0] != "token_read")
        dispatched += "sample_dispatch" in names
        for c in reads:
            assert c[3]["of_iteration"] < c[3]["iteration"]
            read_late += c[3]["path"] == "decode"
    assert read_late >= dispatched - 1 > 0
    # every sub-span has a parent of the right name
    for name, parents in (("decode_h2d", ("decode",)),
                          ("decode_dispatch", ("decode",)),
                          ("sample_operands", ("sample", "first_token")),
                          ("sample_dispatch", ("sample", "first_token")),
                          ("token_read", ("sample",))):
        for c in by[name]:
            held = [p for n in parents for p in by[n] if _inside(c, p)]
            assert len(held) == 1, (name, c)
            assert held[0][3]["iteration"] == c[3]["iteration"]
            if "decode" not in parents and name != "token_read":
                # the sampler's dispatch says which call it is
                assert c[3]["path"] == (
                    "prefill" if held[0][0] == "first_token" else "decode")
    assert _cover(by, "decode", ("decode_h2d", "decode_dispatch")) >= 0.9
    assert _cover(by, "sample", SUB_SPANS) >= 0.9
    # the default request draws at temperature 1 and asks nothing else
    for c in by["sample_operands"]:
        assert set(c[3]) == {"iteration", "path", "rows", "active", *USE,
                             "plain"}
        assert [c[3][k] for k in USE] == [
            0, 0, 0, c[3]["active"], c[3]["active"]]
        assert c[3]["plain"] == 0  # every row draws: the full arm
        assert c[3]["rows"] == (1 if c[3]["path"] == "prefill" else 4)


MIXED = [  # one row of each kind, and what it asks of the sampler
    (dict(temperature=0.0), ()),
    (dict(temperature=0.8, top_k=5, seed=3), ("tempered",)),
    (dict(temperature=0.0, logprobs=2), ("logprobs",)),
    (dict(temperature=0.0, repetition_penalty=1.3), ("penalized",)),
]


def _drive(tracer, serving=None, prompts=None, params=None):
    """The toy engine stepped by hand (no runner: every request is
    submitted before the first iteration, so every decode step holds all
    of them); returns (outputs in submission order, the engine's stats)."""
    cfg, weights, toy_serving = _toy()
    engine = ServingEngine(weights, cfg, serving or toy_serving,
                           tracer=tracer)
    prompts = prompts or [list(range(1 + i, 6 + i)) for i in range(4)]
    params = params or [SamplingParams(max_new_tokens=NEW_TOKENS, **kw)
                        for kw, _ in MIXED]
    outs = engine.generate(prompts, params=params)
    return outs, dict(engine.stats)


def test_sample_operands_counts_what_the_rows_asked_for():
    rec = SpanRecorder()
    _drive(rec)
    ops = _by_name(rec)["sample_operands"]
    first = [c[3] for c in ops if c[3]["path"] == "prefill"]
    assert len(first) == len(MIXED)
    for args, (_, asks) in zip(first, MIXED):
        assert (args["rows"], args["active"]) == (1, 1)
        assert {k for k in USE if args[k]} == set(asks) | (
            {"asking"} if asks else set())
        # a call takes the plain arm where none of its rows asks
        assert args["plain"] == (0 if asks else 1)
    steps = [c[3] for c in ops if c[3]["path"] == "decode"]
    assert len(steps) == NEW_TOKENS - 1
    for args in steps:  # all four rows in every decode step
        assert (args["rows"], args["active"]) == (4, 4)
        assert [args[k] for k in USE] == [0, 1, 1, 1, 3]
        assert args["plain"] == 0


def test_plain_calls_reader_by_hand_and_with_nothing_to_read(monkeypatch,
                                                             tmp_path):
    """ISSUE 39: ``sampler_plain_calls_pct`` is the share of the window's
    ``sample_operands`` spans whose ``plain`` is 1; a program from before
    the argument (the hand-built run as it stands: PR 38's), a run
    without a record and a span after the window give it nothing."""
    read = harness._reader_for("sampler_plain_calls_pct")
    run = _host_share_cases.hand_built_run(monkeypatch, tmp_path)
    assert read(run) is None
    # the first decode step asks, the completed prompt and the second do not
    calls = iter((0, 1, 1))
    run.spans.spans = [
        (n, a, b, dict(args, plain=next(calls)) if n == "sample_operands"
         else args) for n, a, b, args in run.spans.spans]
    run.spans.spans.append(("sample_operands", 1.5, 1.6, {"plain": 0}))
    got = read(run)
    assert got == pytest.approx(100.0 * 2 / 3) and isinstance(got, float)
    run.spans = None
    assert read(run) is None
    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == "sampler_plain_calls_pct")
    assert entry["workloads"] == list(_host_share_cases.SERVE_CELLS) + [
        DSV2_CELL, NEMOTRON_CELL, LFM2_CELL]
    decl = harness.load_json("layer_metrics", "sampler_plain_calls_pct.json")
    assert (decl["unit"], decl["layer"], decl["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"]) == (
            "%", "decode step and kernel", "itl_mean_ms")
    assert entry["source"] == "program_counter"


def test_the_toy_engines_plain_calls_are_what_the_reader_counts():
    """The engine's own record through the reader: of MIXED's four first
    tokens one is a plain call, and no decode step is (three rows ask)."""
    rec = SpanRecorder()
    _drive(rec)
    run = harness.Run(harness.find_cell(harness.load_benchmark(),
                                        _host_share_cases.SERVE_CELLS[0]),
                      harness.Env([], None), spans=rec, planes=None)
    run.values["measured_window"] = (0.0, float("inf"))
    calls = len(_by_name(rec)["sample_operands"])
    assert calls == len(MIXED) + NEW_TOKENS - 1
    assert harness._reader_for("sampler_plain_calls_pct")(run) == (
        pytest.approx(100.0 / calls))


def test_lookahead_reader_by_hand_and_with_nothing_to_read(monkeypatch,
                                                           tmp_path):
    """ISSUE 41: ``decode_lookahead_iters_pct`` is the share of the
    window's ``decode`` spans whose ``lookahead`` is 1, over those that
    carry the argument; a program from before the late read (the
    hand-built run as it stands), a run without a record and a span after
    the window give it nothing."""
    read = harness._reader_for("decode_lookahead_iters_pct")
    run = _host_share_cases.hand_built_run(monkeypatch, tmp_path)
    steps = sum(1 for n, *_ in run.spans.spans if n == "decode")
    assert steps == 2 and read(run) is None
    # the first step had to read first, the second was dispatched ahead
    depth = iter((0, 1))
    run.spans.spans = [
        (n, a, b, dict(args, lookahead=next(depth)) if n == "decode"
         else args) for n, a, b, args in run.spans.spans]
    run.spans.spans.append(("decode", 1.5, 1.6, {"lookahead": 1}))
    got = read(run)
    assert got == pytest.approx(50.0) and isinstance(got, float)
    run.spans = None
    assert read(run) is None
    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == "decode_lookahead_iters_pct")
    assert entry["workloads"] == list(_host_share_cases.SERVE_CELLS) + [
        DSV2_CELL, NEMOTRON_CELL, LFM2_CELL]
    decl = harness.load_json("layer_metrics",
                             "decode_lookahead_iters_pct.json")
    assert (decl["unit"], decl["layer"], decl["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"]) == (
            "%", "engine loop", "itl_mean_ms")
    assert (entry["source"], entry["better"]) == ("program_counter", "higher")


@pytest.mark.parametrize("traffic", ["greedy", "mixed"])
def test_the_toy_engines_lookahead_is_what_the_reader_counts(traffic):
    """The engine's own record through the reader: greedy rows that ask
    nothing take the late read in every decode step (100, no row dropped);
    with a penalised row among them (MIXED) every step it is live in
    reads first (0)."""
    rec = SpanRecorder()
    if traffic == "greedy":
        _drive(rec, params=[SamplingParams(max_new_tokens=NEW_TOKENS,
                                           temperature=0.0)] * len(MIXED))
    else:
        _drive(rec)
    run = harness.Run(harness.find_cell(harness.load_benchmark(),
                                        _host_share_cases.SERVE_CELLS[0]),
                      harness.Env([], None), spans=rec, planes=None)
    run.values["measured_window"] = (0.0, float("inf"))
    steps = [s[3] for s in _by_name(rec)["decode"]]
    assert len(steps) == NEW_TOKENS - 1
    want = 100.0 if traffic == "greedy" else 0.0
    assert harness._reader_for("decode_lookahead_iters_pct")(run) == (
        pytest.approx(want))
    if traffic == "greedy":
        assert all(s["inflight_dropped"] == 0 for s in steps)
    else:
        assert {s["drain"] for s in steps} == {"penalty"}


def test_served_tokens_and_stats_do_not_depend_on_the_tracer():
    """The hoisted transfers, the shared sampler helper and the counts
    made for a recording tracer change nothing that is served."""
    untraced, stats = _drive(None)
    traced, stats_traced = _drive(SpanRecorder())
    assert [o.tokens for o in traced] == [o.tokens for o in untraced]
    assert [o.finish_reason for o in traced] == [
        o.finish_reason for o in untraced]
    assert traced[2].token_logprobs == untraced[2].token_logprobs
    assert stats_traced == stats and stats["decode_tokens"] > 0


def test_speculative_path_reads_its_tokens_inside_token_read():
    """``_decode_spec``: the operands' build under ``decode_inputs``, the
    transfers and the call inside ``decode``, and the blocking read, which
    no span held, in a ``token_read`` of path ``decode`` between its
    ``decode`` and its ``emit``."""
    _, _, toy_serving = _toy()
    import dataclasses

    serving = dataclasses.replace(toy_serving, spec_mode="ngram",
                                  spec_draft_len=2)
    rec = SpanRecorder()
    # a prompt that repeats itself: the n-gram drafter has proposals
    outs, stats = _drive(rec, serving, prompts=[[7, 8, 9] * 4],
                         params=[SamplingParams(max_new_tokens=8)])
    assert stats["spec_proposed"] > 0
    by = _by_name(rec)
    sampled = {s[3]["iteration"] for s in by.get("sample", ())}
    spec = [d for d in by["decode"] if d[3]["iteration"] not in sampled]
    assert spec, "no speculative step ran"
    for d in spec:
        it = d[3]["iteration"]
        mine = {n: [c for c in by.get(n, ()) if c[3]["iteration"] == it]
                for n in ("decode_inputs", "decode_h2d", "decode_dispatch",
                          "token_read", "sample", "emit")}
        assert not mine["sample"]  # the verify step samples on the device
        (inputs,), (h2d,), (call,), (read_,), (emit,) = (
            mine[n] for n in ("decode_inputs", "decode_h2d",
                              "decode_dispatch", "token_read", "emit"))
        assert inputs[2] <= d[1]
        assert _inside(h2d, d) and _inside(call, d) and h2d[2] <= call[1]
        assert d[2] <= read_[1] and read_[2] <= emit[1]
        assert read_[3] == {"iteration": it, "path": "decode"}
        assert h2d[3]["arrays"] == 1  # the one packed operand
    # the untraced engine serves the same tokens
    same, _ = _drive(None, serving, prompts=[[7, 8, 9] * 4],
                     params=[SamplingParams(max_new_tokens=8)])
    assert [o.tokens for o in same] == [o.tokens for o in outs]


SAMPLER_SCOPES = {"sampler", "logit_pipeline", "sampler_topk",
                  "sampler_draw", "sampler_logprobs", "sampler_finite"}


def _scopes_in(text: str) -> set:
    """Every path component of every ``op_name`` of a compiled text."""
    import re

    return {part for path in re.findall(r'op_name="([^"]*)"', text)
            for part in path.split("/")}


@pytest.mark.parametrize("quality", [False, True], ids=["plain", "quality"])
@pytest.mark.parametrize("program", ["sample", "spec_verify"])
def test_sampler_programs_carry_the_scope_names(program, quality):
    """Metadata only: the parts of the sampler program, and of the
    speculative verify's sampler half where it has them, reach
    ``op_name``, which is what ``benchmark/lib/scopes.py`` reads of a chip
    trace (a compile on the CPU is enough: no kernel is involved)."""
    import dataclasses

    import jax.numpy as jnp

    cfg, weights, serving = _toy()
    serving = dataclasses.replace(
        serving, quality_telemetry=quality,
        **(dict(spec_mode="ngram", spec_draft_len=2)
           if program == "spec_verify" else {}))
    engine = ServingEngine(weights, cfg, serving)
    B, V, S = engine._rows, cfg.vocab_size, jax.ShapeDtypeStruct
    if program == "sample":
        lowered = engine._sample_fn.lower(
            S((B, 10 if quality else 9), jnp.int32), S((B, V), jnp.float32),
            S((B, V), jnp.bool_), S((B, V), jnp.int32))
        want = SAMPLER_SCOPES
    else:
        B, L = serving.num_slots, 3
        lowered = engine._spec_fn[True].lower(
            engine.params, S((B, 3 * L + 2 + 10), jnp.int32), engine.cache,
            S((B, L, V), jnp.bool_), S((B, V), jnp.int32), None)
        want = SAMPLER_SCOPES
    got = _scopes_in(lowered.compile().as_text())
    assert want | ({"sampler_quality"} if quality else set()) <= got

"""The `lfm2-24b-a2b-5l` configuration, its cell, its need functions and the
readers of its spans and scopes (PR 47). On the CPU, no chip:

    python3 -m pytest benchmark/tests/test_benchmark_lfm2.py -q

The tier-1 command collects ``tests/`` only; ``tests/
test_benchmark_program.py`` imports these cases and runs them under their
own names. Nothing here pins the cell's PLACE in a list of
``BENCHMARK.json`` (the last entry today is not the last after the next
PR, and this file is one no later PR may edit: PERF.md section 7).
"""

import importlib.util
import json
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

import selftest  # noqa: E402
from lib import (  # noqa: E402
    check,
    harness,
    kimi_linear_sizes,
    lfm2_sizes,
    nemotron_h_sizes,
    program,
    scopes,
    traffic,
    xplane,
)

from differential_transformer_replication_tpu.config import (  # noqa: E402
    ModelConfig,
)

_spec = importlib.util.spec_from_file_location(
    "benchmark_host_share_helpers",
    REPO / "benchmark" / "tests" / "test_benchmark_host_share.py")
_helpers = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_helpers)

BENCH = harness.load_benchmark()
CELL = "serve-lfm2-24b-a2b-5l-extract-rag"
CONFIG = "lfm2-24b-a2b-5l"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_READERS = (
    "lfm2_decode_step_roofline", "lfm2_moe_experts_roofline",
    "lfm2_prefill_moe_experts_roofline", "lfm2_decode_attn_roofline",
    "decode_conv_ms_per_step", "prefill_conv_ms_per_call",
    "lfm2_moe_experts_hit_per_step", "lfm2_moe_expert_load_max_over_mean")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WINDOWS = 4 * 2 * 2048 * 2  # a slot's four windows of two gated inputs
LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]


def test_the_shipped_lfm2_model_block_builds_the_published_stage():
    config = harness.find_cell(BENCH, CELL).config
    assert "train" not in config and set(config["correct"]) == {"serve"}
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    # the driver refuses a `why` or `source` of more than 200 characters
    assert all(1 <= len(entry[k]) <= 200 and entry[k].isprintable()
               for k in ("why", "source"))
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "num_dense_layers", "num_hidden_layers"]
    want = ModelConfig(
        model="lfm2", vocab_size=65536, n_embd=2048, n_head=32, kv_heads=8,
        n_layer=5, block_size=8192, norm_eps=1e-5, layer_types=LAYERS,
        first_dense_layers=1, ffn_hidden=11776, num_experts=64,
        experts_per_token=4, moe_hidden=1536, routed_scaling=1.0,
        held_experts=(0, 64), rope_theta=1e6, conv_taps=3, router_eps=1e-6,
        tie_embeddings=True, compute_dtype="bfloat16",
        param_dtype="bfloat16")
    assert program.served_model(config) == want
    # the published config.json's own keys beside `model`: every width as
    # published, the two cuts of `reduced` alone changed; NOT the experts,
    # NOT the vocabulary
    m = config["model"]
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert config["layer_types"][1:6] == m["layer_types"] == LAYERS
    assert len(config["layer_types"]) == 40
    assert config["layer_types"][:2] == ["conv", "conv"]  # both dense
    assert m["held_experts"] == [0, config["num_experts"]]
    for key, field in (("hidden_size", "n_embd"),
                       ("num_attention_heads", "n_head"),
                       ("num_key_value_heads", "kv_heads"),
                       ("intermediate_size", "ffn_hidden"),
                       ("moe_intermediate_size", "moe_hidden"),
                       ("num_experts", "num_experts"),
                       ("num_experts_per_tok", "experts_per_token"),
                       ("routed_scaling_factor", "routed_scaling"),
                       ("conv_L_cache", "conv_taps"),
                       ("vocab_size", "vocab_size"),
                       ("num_dense_layers", "first_dense_layers"),
                       ("num_hidden_layers", "n_layer"),
                       ("norm_eps", "norm_eps")):
        assert config[key] == m[field], key
    assert config["rope_parameters"]["rope_theta"] == m["rope_theta"] == 1e6
    assert config["conv_bias"] is False and config["use_expert_bias"] is True
    assert config["norm_topk_prob"] is True
    for key in ("tie_word_embeddings", "head_dim", "conv", "router",
                "intermediate_size", "weights", "window dtype"):
        assert key in config["assumed"], key
    # the deployment the file states
    for said in ("EIGHT chips", "five layers a chip", "no expert axis",
                 "all 64 experts", "whole vocabulary", "2,700.65 M",
                 "5.40 GB", "33.8%"):
        assert said in config["what"], said


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_every_published_lfm2_number_stands_but_the_two_cuts():
    row = next(json.loads(line) for line in CATALOG.open()
               if '"name": "LFM2-24B-A2B"' in line)
    config = harness.find_cell(BENCH, CELL).config
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert config["source"] == entry["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"])
    # `reduced` names no width
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))]


def test_the_lfm2_mix_is_the_one_the_issue_gives():
    cell = harness.find_cell(BENCH, CELL)
    mix, model = cell.traffic, cell.config["model"]
    assert mix["arrival"]["process"] == "poisson_trace"
    assert mix["arrival"]["ramp_s"] == 30.0
    # ISSUE 47's mix, or its one fallback: (prompts, answers, mean prompt,
    # mean answer of the clipped lognormals)
    first = ({"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 32,
              "max": 6144},
             {"dist": "lognormal", "median": 64, "sigma": 0.8, "min": 8,
              "max": 512}, 750, 88)
    fallback = ({"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
                 "max": 4096},
                {"dist": "lognormal", "median": 48, "sigma": 0.7, "min": 8,
                 "max": 256}, 380, 61)
    stands = next(m for m in (first, fallback)
                  if (mix["prompt_len"], mix["output_len"]) == m[:2])
    assert ("FALLBACK" in mix["what"]) == (stands is fallback)
    assert mix["max_total"] == 8192 == model["block_size"]
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["engine"] == {"num_slots": 64, "prefill_chunk": 1024,
                             "prefill_budget": 2048, "max_queue_len": 0,
                             "decode_attention_impl": "xla"}
    assert mix["check"]["sample_requests"] == 8
    assert "shared_prefix" not in mix  # the family takes no prefix cache
    # the rate: at least 16/s, a multiple of 1.6/s, so that the ramp of 30 s
    # and a window of 10 s hold whole blocks of the generator's 16 arrivals
    rate = mix["arrival"]["rate_per_s"]
    assert rate >= 16 and abs(rate / 1.6 - round(rate / 1.6)) < 1e-9
    plan = traffic.open_loop_plan(mix, 2**31 + 5, 10.0, model["vocab_size"])
    again = traffic.open_loop_plan(mix, 7, 10.0, model["vocab_size"])
    size = lambda p: sorted((len(r.prompt), r.max_new_tokens) for r in p)  # noqa: E731
    assert size(plan) == size(again)
    assert all(len(r.prompt) + r.max_new_tokens <= 8192 for r in plan)
    assert all(0 <= t < model["vocab_size"] for r in plan for t in r.prompt[:8])
    ramp = mix["arrival"]["ramp_s"]
    window = [r for r in plan if r.due_s >= ramp]
    parts = [r for r in plan if r.due_s < ramp], window
    assert len(window) == round(10 * rate)
    for part in parts:
        assert len(part) % traffic.BLOCK == 0
    # the stated means, in both parts of the chosen draw, to 7%
    for part in parts:
        prompt = sum(len(r.prompt) for r in part) / len(part)
        answer = sum(r.max_new_tokens for r in part) / len(part)
        assert 0.93 * stands[2] <= prompt <= 1.07 * stands[2], prompt
        assert 0.93 * stands[3] <= answer <= 1.07 * stands[3], answer
    # the window holds a prompt of three chunks of 1,024 or more, whose
    # windows are handed on twice
    assert max(len(r.prompt) for r in window) > 2048
    assert str(mix["shape_seed"]) in mix["what"]
    # the sweep's rows stand in the file
    assert "sweep" in mix["what"] and "knee" in mix["what"].lower()


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(_decode)/conv/conv_taps/mul", "conv_taps", True),
    ("jit(_decode)/conv/conv_taps/mul", "conv", True),
    ("jit(_decode)/conv/dot_general", "conv_taps", False),
    ("jit(_decode)/ssm/ssm_conv/mul", "conv", False),
    ("jit(_prefill)/conv/conv_taps/dynamic_slice", "conv", True),
    ("jit(_decode)/moe/moe_experts/moe_grouped_matmul", "moe_experts", True),
    ("jit(_decode)/moe/moe_experts/moe_grouped_matmul", "moe_shared", False),
    ("jit(_decode)/attn/attn_full/ring_gqa_decode_fwd", "attn_full", True),
    ("jit(_decode)/attn/kv_write/kv_row_write", "attn_full", False),
])
def test_scope_matching_finds_the_lfm2_scopes(path, scope, inside):
    assert scopes.in_scope(path, scope) is inside


def test_the_lfm2_need_functions_count_the_published_stage():
    model = harness.find_cell(BENCH, CELL).config["model"]
    s, p = lfm2_sizes.sizes(model), lfm2_sizes.param_parts(model)
    assert (s["layers"], s["conv"], s["attn"], s["dense"], s["moe"],
            s["held"]) == (5, 4, 1, 1, 4, 64)
    # ISSUE 47's table, to the fourth digit
    assert round(p["embed"] / 1e6, 2) == 134.22
    assert round(p["conv"] / 1e6, 2) == 16.78
    assert round(p["attn"] / 1e6, 2) == 10.49
    assert round(p["dense"] / 1e6, 2) == 72.35
    assert round(p["router"] / 1e6, 2) == 0.13
    assert p["expert"] == 3 * 2048 * 1536 and round(
        p["expert"] / 1e6, 3) == 9.437
    assert round(64 * p["expert"] / 1e6, 2) == 603.98
    assert p["norms"] == 4096
    cfg = program.served_model(harness.find_cell(BENCH, CELL).config)
    import jax

    from differential_transformer_replication_tpu.models import init_model
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    count = lfm2_sizes.param_count(model)
    assert count == sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    # 134.2 + 89.1 + 614.6 + 3 x 620.9 = 2,700.6 of rounded parts; exactly:
    assert count == 2_700_654_976 and round(2 * count / 1e9, 2) == 5.40
    # an untied head would be a second table; a share holds fewer experts
    assert lfm2_sizes.param_count(dict(model, tie_embeddings=False)) == (
        count + p["embed"])
    assert lfm2_sizes.param_count(dict(model, held_experts=[0, 8])) == (
        count - 4 * 56 * p["expert"])
    # a slot: four windows of 8 KB and one ring of 2 KB a position
    assert lfm2_sizes.window_bytes(model) == WINDOWS == 32768
    assert lfm2_sizes.position_bytes(model) == 2048
    assert lfm2_sizes.slot_bytes(model) == 32768 + 8192 * 2048
    from differential_transformer_replication_tpu.models.decode import (
        init_cache,
    )
    pool = jax.eval_shape(lambda: init_cache(cfg, 2))
    assert sum(a.size * a.dtype.itemsize for a in
               jax.tree_util.tree_leaves(pool)) == 2 * lfm2_sizes.slot_bytes(model)
    # the experts that got a row are read once, 18.9 MB each in bfloat16:
    # ISSUE 47's reckoning, 50 of 64 a layer at 24 rows
    load = {"held": 24 * 4 * 4.0, "experts_hit": 200.0, "max_expert": 9.0}
    routed = lfm2_sizes.experts_need(model, load)
    assert routed["bytes"] == 200 * p["expert"] * 2 + 384 * 2 * 2048 * 2
    assert 3.7e9 < routed["bytes"] < 3.9e9
    assert routed["flops"] == 2.0 * p["expert"] * 384
    step = lfm2_sizes.decode_need(model, load, 24.0, 24 * 600.0)
    fixed = (4 * p["conv"] + p["attn"] + p["dense"] + 4 * p["router"]
             + 5 * p["norms"] + 2048 + p["embed"])
    assert step["bytes"] == (fixed * 2 + 24 * 2048 * 2 + routed["bytes"]
                             + 24 * 600 * 2048 + 24 * 2 * WINDOWS)
    # 0.57 GB outside the experts (the head 0.27), 4.3-4.4 GB a step
    assert 0.56e9 < fixed * 2 < 0.58e9 and 4.3e9 < step["bytes"] < 4.45e9
    # even routing: 36 of 64 a layer at 13 rows, 50 at 24, 61 at 48
    for rows, hit in ((13, 36), (24, 50), (48, 61)):
        assert round(lfm2_sizes.expected_experts_hit(model, rows)) == hit
    # a prefill call of 1,024 tokens reads every expert (4.83 GB, and its
    # 16,384 assignments' rows in and out) and is memory-bound; its
    # products are 0.31 TFLOP in the experts
    call = lfm2_sizes.prefill_experts_need(model, 1024)
    assert 4.96e9 < call["bytes"] < 4.97e9
    assert call["bytes"] - 16384 * 8192 == pytest.approx(
        256 * p["expert"] * 2, rel=1e-9)
    assert call["flops"] == 4 * 2.0 * p["expert"] * 4096
    assert call["bytes"] / 819e9 > call["flops"] / 197e12
    assert lfm2_sizes.prefill_experts_need(model, 32)["bytes"] < 4.3e9
    # the ring read: 2 KB a live position, 4 x 64 x 32 operations
    ring = lfm2_sizes.attn_need(model, 1000.0)
    assert ring == {"flops": 1000 * 32 * 4.0 * 64, "bytes": 1000 * 2048.0}
    with pytest.raises(ValueError, match="jamba"):
        lfm2_sizes.sizes(dict(model, model="jamba"))


# -- the new readers on a hand-made trace and span record ---------------------
# Two executions of the decode program with a prefill program between
# them, microseconds (start, duration).
_CC = ', custom_call_target="tpu_custom_call"'
_F = "%fusion.{} = f32[8] fusion(f32[8] %p)"
_D, _P = "jit(_decode)/", "jit(_prefill)/"
_DECODE_OPS = [
    (_F.format(1), 0, 60, _D + "conv/dot_general"),
    (_F.format(2), 60, 20, _D + "conv/conv_taps/mul"),
    (f"%kv_row_write.3 = bf16[8] custom-call(bf16[8] %p){_CC}", 80, 10,
     _D + "attn/kv_write/kv_row_write"),
    (f"%ring_gqa_decode_fwd.4 = bf16[8] custom-call(bf16[8] %p){_CC}", 90,
     110, _D + "attn/attn_full/ring_gqa_decode_fwd"),
    (_F.format(5), 200, 20, _D + "moe/moe_router/dot_general"),
    (f"%moe_grouped_matmul.6 = bf16[8] custom-call(bf16[8] %p){_CC}", 220,
     5000, _D + "moe/moe_experts/moe_grouped_matmul"),
    (_F.format(7), 5220, 380, _D + "lm_head/dot_general"),
]
_OPS = (_DECODE_OPS
        + [(_F.format(8), 6000, 400, _P + "conv/dot_general"),
           (_F.format(9), 6400, 100, _P + "conv/conv_taps/mul"),
           (f"%moe_grouped_matmul.10 = bf16[8] custom-call(bf16[8] %p){_CC}",
            6500, 8000, _P + "moe/moe_experts/moe_grouped_matmul")]
        + [(n, a + 20000, d, p) for n, a, d, p in _DECODE_OPS])
_MODS = [("jit__decode(1)", 0, 5600), ("jit__prefill(2)", 6000, 8500),
         ("jit__decode(1)", 20000, 5600)]
_SPANS = [
    ("decode", 0.0, 1.0, {"active": 20, "live_state_bytes": 20 * WINDOWS,
                          "moe": {"held": 320, "max_expert": 12,
                                  "experts_hit": 180}}),
    ("decode", 1.0, 2.0, {"active": 28, "live_state_bytes": 28 * WINDOWS,
                          "moe": {"held": 448, "max_expert": 16,
                                  "experts_hit": 220}}),
    ("decode", 9.0, 11.0, {"active": 7, "live_state_bytes": 7 * WINDOWS,
                           "moe": {"held": 112, "max_expert": 5,
                                   "experts_hit": 90}}),  # ends past the window
    ("prefill_call", 8.0, 8.5, {"iteration": 3, "size": 700}),
    ("prefill_call", 3.0, 3.5, {"iteration": 1, "size": 90}),  # not traced
    ("sample", 2.0, 3.0, {"iteration": 2}),
]
LIVE = 24 * 600.0  # live positions a traced step, summed over its rows


def _traced(monkeypatch, tmp_path, scoped=True, spans=_SPANS):
    paths = {n: p for n, _, _, p in _OPS if scoped}
    data = selftest._ld(1, _helpers.plane_with_paths(
        "/device:TPU:0", [("XLA Ops", [(n, a, d) for n, a, d, _ in _OPS]),
                          ("XLA Modules", list(_MODS))], paths))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    d = tmp_path / "trace" / (CELL + "-7") / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(data)
    cell = harness.find_cell(BENCH, CELL)
    return harness.Run(cell, harness.Env([], PEAKS),
                       planes=xplane.parse_xspace(data),
                       spans=types.SimpleNamespace(spans=list(spans)),
                       values={"measured_window": (0.0, 10.0),
                               "trace_steps": 2, "decode_rows": 24.0,
                               "decode_live_positions": LIVE})


def _least(need):
    return max(need["flops"] / 197e12, need["bytes"] / 819e9)


def test_every_new_lfm2_reader_on_the_trace_fixture(monkeypatch, tmp_path):
    run = _traced(monkeypatch, tmp_path)
    read = lambda name: harness._reader_for(name)(run)  # noqa: E731
    model = run.cell.config["model"]
    state = nemotron_h_sizes.state_load(run)
    assert (state["bytes"], state["active"], state["steps"]) == (
        24.0 * WINDOWS, 24.0, 2)
    load = kimi_linear_sizes.expert_load(run)
    assert (load["held"], load["experts_hit"]) == (384.0, 200.0)
    assert read("lfm2_moe_experts_hit_per_step") == 200.0
    # the fullest expert's 14 rows (summed over the layers) over the mean
    # expert's 384 / 64
    assert read("lfm2_moe_expert_load_max_over_mean") == pytest.approx(
        14.0 * 64 / 384.0)
    # device time under the scopes, an execution of each program
    assert read("decode_conv_ms_per_step") == pytest.approx(0.080)
    assert read("prefill_conv_ms_per_call") == pytest.approx(0.500)
    assert scopes.scope_ms(run, "conv_taps", "jit__decode") == (
        pytest.approx(0.020))
    assert scopes.scope_ms(run, "moe_shared", "jit__decode") is None
    # the accepted scope readers the cell joins read the same trace
    assert read("decode_moe_experts_ms_per_step") == pytest.approx(5.000)
    assert read("decode_moe_ms_per_step") == pytest.approx(5.020)
    need = lfm2_sizes.experts_need(model, load)
    assert read("lfm2_moe_experts_roofline") == pytest.approx(
        100 * _least(need) / 5000e-6)
    need = lfm2_sizes.attn_need(model, LIVE)
    assert read("lfm2_decode_attn_roofline") == pytest.approx(
        100 * _least(need) / 110e-6)
    # the one traced prefill call held 700 tokens (the one of 90 ended
    # before the traced part of the window)
    assert nemotron_h_sizes.traced_prefill_calls(run) == [700]
    need = lfm2_sizes.prefill_experts_need(model, 700)
    assert read("lfm2_prefill_moe_experts_roofline") == pytest.approx(
        100 * _least(need) / 8000e-6)
    need = lfm2_sizes.decode_need(model, load, 24.0, LIVE)
    assert read("lfm2_decode_step_roofline") == pytest.approx(
        100 * _least(need) / 5600e-6)
    for name in NEW_READERS:
        if name.endswith("_roofline"):
            assert 0 < read(name) < 100, name


def test_no_lfm2_share_passes_100_at_full_hit(monkeypatch, tmp_path):
    """Every expert hit and every slot live with a full ring, at the times
    a chip at its memory peak would need for exactly that work: the shares
    read 100 and not more (what is counted is what the traffic made
    live)."""
    model = harness.find_cell(BENCH, CELL).config["model"]
    load = {"held": 64 * 4.0 * 4, "experts_hit": 256.0}
    live = 64 * 8192.0
    full = [("decode", 0.0, 1.0, {
        "active": 64, "live_state_bytes": 64 * WINDOWS,
        "moe": {"held": 64 * 4 * 4, "max_expert": 40, "experts_hit": 256}}),
        ("prefill_call", 8.0, 8.5, {"iteration": 3, "size": 1024})]
    run = _traced(monkeypatch, tmp_path, spans=full)
    run.values.update(decode_rows=64.0, decode_live_positions=live)
    for name, need, measured in (
            ("lfm2_moe_experts_roofline",
             lfm2_sizes.experts_need(model, load), 5000e-6),
            ("lfm2_decode_attn_roofline",
             lfm2_sizes.attn_need(model, live), 110e-6),
            ("lfm2_prefill_moe_experts_roofline",
             lfm2_sizes.prefill_experts_need(model, 1024), 8000e-6),
            ("lfm2_decode_step_roofline",
             lfm2_sizes.decode_need(model, load, 64.0, live), 5600e-6)):
        got = harness._reader_for(name)(run)
        assert got == pytest.approx(100 * _least(need) / measured)
        # the fixture's times are not the chip's: scale them to its least
        assert got * measured / _least(need) == pytest.approx(100.0)
    # a full hit reads no more than the experts there are
    assert lfm2_sizes.expected_experts_hit(model, 10**6) == 64.0


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_lfm2_reader_with_nothing_to_read_returns_none(
        monkeypatch, tmp_path, name):
    """A program from before the scopes and counters: nothing, and no
    exception, never 0; without a trace the device metrics return nothing
    either. And the declaration is the benchmark's entry."""
    bare = [("decode", 0.0, 1.0, {"active": 100})]
    run = _traced(monkeypatch, tmp_path, scoped=False, spans=bare)
    run.values["decode_live_positions"] = None
    assert harness._reader_for(name)(run) is None
    run = _traced(monkeypatch, tmp_path / "b")
    run.planes = None
    device = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert (harness._reader_for(name)(run) is None) == (
        device["source"] == "device_trace")
    run.spans = None
    assert harness._reader_for(name)(run) is None
    decl = harness.load_json("layer_metrics", name + ".json")
    assert (decl["unit"], decl["layer"], decl["moves"]) == (
        device["unit"], device["layer"], device["moves"])
    assert device["workloads"] == [CELL] and device["moves"] == "itl_mean_ms"
    if name.endswith("_roofline"):
        assert device["unit"] == "%" and device["better"] == "higher"


def test_the_lfm2_cell_joins_the_shared_lists_and_no_silent_one():
    cell = harness.find_cell(BENCH, CELL)
    assert cell.chips == 1
    assert "itl_mean_ms" in cell.end_to_end and "setup_s" in cell.end_to_end
    mine = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= mine
    assert {"state_resets_per_iter", "decode_moe_ms_per_step",
            "decode_moe_experts_ms_per_step", "decode_attn_ms_per_step",
            "decode_kv_write_ms_per_step", "decode_step_device_ms",
            "decode_rest_ms_per_step", "device_idle_pct.serve"} <= mine
    if "serve_tokens_per_s" not in cell.end_to_end:
        # then the three per-layer metrics that move it go with it
        assert not {"gen_lag_p95_ms", "slot_occupancy_pct",
                    "peak_hbm_gb.serve"} & mine
    # the two sampler metrics that fell silent with PR 39 are not asked of
    # it, nor a scope this family's programs do not have
    assert not {"sampler_logprobs_ms_per_iter",
                "sampler_pipeline_ms_per_iter",
                "decode_moe_latent_ms_per_step", "decode_ssm_ms_per_step",
                "decode_ssm_state_ms_per_step",
                "prefill_ssm_scan_ms_per_call"} & mine
    # every metric that all six older serve cells carry
    older = [w["name"] for w in BENCH["workloads"]
             if w["name"].startswith("serve-") and w["name"] != CELL][:6]
    shared = {m["name"] for m in BENCH["per_layer"]
              if set(older) <= set(m.get("workloads", []))}
    assert shared <= mine and len(shared) == 29
    assert all(m["moves"] in cell.end_to_end for m in cell.per_layer)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert (entry["config"], entry["traffic"]) == (
        CONFIG, "extract-rag-open-0.8knee")
    # the new entries follow one another in the issue's order
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW_READERS[0])
    assert names[at:at + len(NEW_READERS)] == list(NEW_READERS)


# -- the serving limit against the faults it is there to catch ----------------

@pytest.fixture(scope="module")
def planted_lfm2():
    """``selftest_lfm2.py --witness`` at the rehearsal's widths and 4 x 60
    positions: the reference with each fault planted, judged as a served
    token is. At the cell's own size it runs on the chip (PERF.md section
    2)."""
    import selftest_lfm2 as cellcheck
    cell = harness.find_cell(BENCH, CELL)
    model = dict(cell.config["model"], **cellcheck.TINY_MODEL)
    return (cellcheck.witness_gaps(model, harness.load_reference(cell.config),
                                   rows=4, length=60),
            cell.config["correct"]["serve"]["token_gap"])


@pytest.mark.parametrize("fault", [
    "window_dropped", "taps_reversed", "rope_before_norm", "no_qk_norm",
    "not_renormalised"])
def test_the_serving_limit_fails_a_planted_lfm2_fault(planted_lfm2, fault):
    gaps, limit = planted_lfm2
    assert not check.judge([("served_token_gap", gaps[fault], limit)], fault)


def test_the_faults_that_need_not_fail_are_read_all_the_same(planted_lfm2):
    import selftest_lfm2 as cellcheck
    gaps, _ = planted_lfm2
    assert set(cellcheck.TOO_SMALL) <= set(gaps)
    assert all(gaps[f] >= 0 for f in cellcheck.TOO_SMALL)

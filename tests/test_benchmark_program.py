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

from lib import harness, jamba_sizes, program, scopes, traffic  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    ModelConfig,
)

BENCH = harness.load_benchmark()
JAMBA_CELL = "serve-jamba2-3b-reason-chat"
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

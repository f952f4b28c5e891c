"""The `nemotron3-super-11l-ep4` configuration, its cell, its need functions
and the readers of its spans and scopes (PR 42). On the CPU, no chip:

    python3 -m pytest benchmark/tests/test_benchmark_nemotron_h.py -q

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
    harness,
    kimi_linear_sizes,
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
CELL = "serve-nemotron3-super-11l-ep4-agent-turns"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_READERS = (
    "nemotron_h_decode_step_roofline", "ssd_state_update_roofline",
    "ssd_chunk_roofline", "nemotron_h_moe_experts_roofline",
    "decode_moe_latent_ms_per_step", "decode_live_state_mb_per_step",
    "nemotron_h_moe_held_assignments_per_row",
    "nemotron_h_moe_experts_hit_per_step",
    "nemotron_h_moe_expert_load_max_over_mean")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE = 5 * 128 * 8192 * 4  # a slot's five Mamba-2 states, float32


def test_the_shipped_nemotron_h_model_block_builds_the_published_share():
    config = harness.find_cell(BENCH, CELL).config
    assert "train" not in config and set(config["correct"]) == {"serve"}
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron3-super-11l-ep4")
    assert entry == BENCH["configs"][-1]
    # the driver refuses a `why` or `source` of more than 200 characters
    assert all(1 <= len(entry[k]) <= 200 and entry[k].isprintable()
               for k in ("why", "source"))
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    want = ModelConfig(
        model="nemotron_h", vocab_size=32768, n_embd=4096, n_head=32,
        kv_heads=2, n_layer=11, block_size=8192, norm_eps=1e-5,
        hybrid_override_pattern="MEMEMEM*EME", mamba_num_heads=128,
        mamba_head_dim=64, n_groups=8, ssm_state_size=128, chunk_size=128,
        mamba_d_conv=4, num_experts=512, experts_per_token=22,
        moe_hidden=2688, moe_latent_size=1024, moe_shared_hidden=5376,
        mlp_act="relu2", routed_scaling=5.0, held_experts=(0, 128),
        compute_dtype="bfloat16", param_dtype="bfloat16")
    assert program.served_model(config) == want
    # the published config.json's own keys beside `model`: every width as
    # published, the three cuts of `reduced` alone changed
    m = config["model"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (11, 128, 32768)
    assert config["n_routed_experts"] == (
        m["held_experts"][1] - m["held_experts"][0])
    assert config["hybrid_override_pattern"][:11] == m[
        "hybrid_override_pattern"] and len(
            config["hybrid_override_pattern"]) == 88
    for key, field in (("hidden_size", "n_embd"),
                       ("num_attention_heads", "n_head"),
                       ("num_key_value_heads", "kv_heads"),
                       ("mamba_num_heads", "mamba_num_heads"),
                       ("mamba_head_dim", "mamba_head_dim"),
                       ("n_groups", "n_groups"),
                       ("ssm_state_size", "ssm_state_size"),
                       ("chunk_size", "chunk_size"),
                       ("conv_kernel", "mamba_d_conv"),
                       ("moe_intermediate_size", "moe_hidden"),
                       ("moe_latent_size", "moe_latent_size"),
                       ("moe_shared_expert_intermediate_size",
                        "moe_shared_hidden"),
                       ("num_experts_per_tok", "experts_per_token"),
                       ("routed_scaling_factor", "routed_scaling"),
                       ("norm_eps", "norm_eps")):
        assert config[key] == m[field], key
    assert config["mlp_hidden_act"] == m["mlp_act"] == "relu2"
    assert config["hidden_size"] // config["num_attention_heads"] == config[
        "head_dim"]
    # the multi-token-prediction module: published above, left out below
    assert config["num_nextn_predict_layers"] == 1
    assert "multi-token prediction" in config["left_out"]
    assert not {"num_nextn_predict_layers",
                "mtp_hybrid_override_pattern"} & set(m)
    with pytest.raises(SystemExit, match="multi-token-prediction"):
        program.check_config(dict(config, model=dict(
            m, num_nextn_predict_layers=1)))
    # the deployment the file states
    for said in ("FOUR-chip", "8 pipeline stages", "128 of the 512",
                 "quarter of the vocabulary", "4.648 G", "9.30 GB"):
        assert said in config["what"], said


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_every_published_nemotron_h_number_stands_but_the_three_cuts():
    row = next(json.loads(line) for line in CATALOG.open()
               if '"name": "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line)
    config = harness.find_cell(BENCH, CELL).config
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron3-super-11l-ep4")
    assert config["source"] == entry["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"])


def test_the_nemotron_h_mix_is_the_one_the_issue_gives():
    cell = harness.find_cell(BENCH, CELL)
    mix, model = cell.traffic, cell.config["model"]
    assert mix["arrival"]["process"] == "poisson_trace"
    assert mix["arrival"]["ramp_s"] == 30.0
    # ISSUE 42's mix, or its one fallback: (prompts, answers, mean prompt,
    # mean answer of the clipped lognormals)
    first = ({"dist": "lognormal", "median": 384, "sigma": 1.0, "min": 32,
              "max": 6144},
             {"dist": "lognormal", "median": 64, "sigma": 0.8, "min": 8,
              "max": 512}, 600, 88)
    fallback = ({"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
                 "max": 4096},
                {"dist": "lognormal", "median": 48, "sigma": 0.7, "min": 8,
                 "max": 256}, 380, 61)
    stands = next(m for m in (first, fallback)
                  if (mix["prompt_len"], mix["output_len"]) == m[:2])
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
    # the stated means, in both parts of the chosen draw (what it was
    # chosen UNDER: the mix's file), to 7%
    for part in parts:
        prompt = sum(len(r.prompt) for r in part) / len(part)
        answer = sum(r.max_new_tokens for r in part) / len(part)
        assert 0.93 * stands[2] <= prompt <= 1.07 * stands[2], prompt
        assert 0.93 * stands[3] <= answer <= 1.07 * stands[3], answer
    # the window holds a prompt that runs the scan over several chunks of
    # 1,024 with a state handed on
    assert max(len(r.prompt) for r in window) > 2048
    assert str(mix["shape_seed"]) in mix["what"]


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(_decode)/ssm/ssm_state/ssm_ssd_state_update", "ssm_state", True),
    ("jit(_decode)/ssm/ssm_state/ssm_ssd_state_update", "ssm", True),
    ("jit(_decode)/ssm/ssm_conv/mul", "ssm_state", False),
    ("jit(_decode)/ssm/dot_general", "ssm_conv", False),
    ("jit(_prefill)/ssm/ssm_scan/while/body/dot_general", "ssm_scan", True),
    ("jit(_prefill)/ssm/ssm_scan/exp", "ssm_state", False),
    ("jit(_decode)/moe/moe_latent/dot_general", "moe_latent", True),
    ("jit(_decode)/moe/moe_latent/dot_general", "moe", True),
    ("jit(_decode)/moe/moe_experts/moe_grouped_matmul", "moe_latent", False),
    ("jit(_decode)/attn/attn_full/ring_gqa_decode_fwd", "attn_full", True),
])
def test_scope_matching_finds_the_nemotron_h_scopes(path, scope, inside):
    assert scopes.in_scope(path, scope) is inside


def test_the_nemotron_h_need_functions_count_the_published_share():
    model = harness.find_cell(BENCH, CELL).config["model"]
    s, p = nemotron_h_sizes.sizes(model), nemotron_h_sizes.param_parts(model)
    assert (s["layers"], s["mamba2"], s["moe"], s["attn"], s["held"]) == (
        11, 5, 5, 1, 128)
    # ISSUE 42's table, to the fourth digit
    assert round(p["mamba2"] / 1e6, 2) == 109.64
    assert round(p["attn"] / 1e6, 2) == 35.66
    assert round(p["moe_fixed"] / 1e6, 2) == 54.53
    assert p["expert"] == 2 * 1024 * 2688 and round(
        p["expert"] / 1e6, 3) == 5.505
    assert round((p["head"] + p["embed"]) / 1e6, 1) == 268.4
    cfg = program.served_model(harness.find_cell(BENCH, CELL).config)
    import jax

    from differential_transformer_replication_tpu.models import init_model
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    count = nemotron_h_sizes.param_count(model)
    assert count == sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert round(count / 1e6) == 4648  # ISSUE 42: 4,648 M parameters
    # a slot: five states of 4.19 MB, five windows, one ring: 29.7 MB
    assert nemotron_h_sizes.state_bytes(model) == STATE == 20971520
    assert round(nemotron_h_sizes.slot_bytes(model) / 1e6, 1) == 29.7
    from differential_transformer_replication_tpu.models.decode import (
        init_cache,
    )
    pool = jax.eval_shape(lambda: init_cache(cfg, 2))
    assert sum(a.size * a.dtype.itemsize for a in
               jax.tree_util.tree_leaves(pool)) == 2 * nemotron_h_sizes.slot_bytes(model)
    # the update: 24 live rows move 1.0 GB there and back
    update = nemotron_h_sizes.update_need(model, 24)
    assert 1.00e9 < update["bytes"] < 1.03e9
    assert update["bytes"] >= 24 * 2 * STATE
    # the chunked form: about 6.5 MFLOP a token and layer at 128 a sub-chunk
    chunk = nemotron_h_sizes.chunk_need(model, [1024])
    assert 6.4e6 < chunk["flops"] / (5 * 1024) < 6.7e6
    short = nemotron_h_sizes.chunk_need(model, [32])
    assert short["flops"] / 32 < chunk["flops"] / 1024  # a sub-chunk of 32
    assert nemotron_h_sizes.chunk_need(model, [512, 512])["bytes"] > chunk[
        "bytes"]  # two calls hand the state over twice
    # the experts that got a row are read once, 11 MB each in bfloat16
    load = {"held": 660.0, "experts_hit": 410.0, "max_expert": 20.0}
    routed = nemotron_h_sizes.experts_need(model, load)
    assert routed["bytes"] == 410 * p["expert"] * 2 + 660 * 2 * 1024 * 2
    assert 4.4e9 < routed["bytes"] < 4.6e9
    step = nemotron_h_sizes.decode_need(model, load, {"active": 24.0})
    fixed = 5 * p["mamba2"] + p["attn"] + 5 * p["moe_fixed"] + p["head"]
    assert step["bytes"] == (fixed * 2 + 24 * 4096 * 2 + routed["bytes"]
                             + update["bytes"])
    assert 7.4e9 < step["bytes"] < 7.6e9  # ISSUE 42: 7.5 GB a step
    with pytest.raises(ValueError, match="jamba"):
        nemotron_h_sizes.sizes(dict(model, model="jamba"))


# -- the new readers on a hand-made trace and span record ---------------------
# Two executions of the decode program with a prefill program between
# them, microseconds (start, duration).
_CC = ', custom_call_target="tpu_custom_call"'
_F = "%fusion.{} = f32[8] fusion(f32[8] %p)"
_D, _P = "jit(_decode)/", "jit(_prefill)/"
_DECODE_OPS = [
    (_F.format(1), 0, 40, _D + "ssm/ssm_conv/mul"),
    (_F.format(2), 40, 10, _D + "ssm/ssm_state/sort"),
    (f"%ssm_ssd_state_update.3 = f32[8] custom-call(f32[8] %p){_CC}", 50, 150,
     _D + "ssm/ssm_state/ssm_ssd_state_update"),
    (_F.format(4), 200, 20, _D + "moe/moe_latent/dot_general"),
    (f"%moe_grouped_matmul.5 = bf16[8] custom-call(bf16[8] %p){_CC}", 220,
     500, _D + "moe/moe_experts/moe_grouped_matmul"),
    (_F.format(6), 720, 30, _D + "moe/moe_latent/dot_general"),
    (_F.format(7), 750, 150, _D + "moe/moe_shared/dot_general"),
]
_OPS = (_DECODE_OPS
        + [("%while.8 = f32[8] while(f32[8] %p)", 1000, 300,
            _P + "ssm/ssm_scan/while"),
           (_F.format(9), 1010, 100,
            _P + "ssm/ssm_scan/while/body/dot_general"),
           (_F.format(10), 1300, 100, _P + "ssm/ssm_scan/dot_general"),
           (_F.format(11), 1400, 50, _P + "ssm/ssm_conv/mul")]
        + [(n, a + 2000, d, p) for n, a, d, p in _DECODE_OPS])
_MODS = [("jit__decode(1)", 0, 900), ("jit__prefill(2)", 1000, 500),
         ("jit__decode(1)", 2000, 900)]
_SPANS = [
    ("decode", 0.0, 1.0, {"active": 20, "live_state_bytes": 20 * STATE,
                          "moe": {"held": 540, "max_expert": 14,
                                  "experts_hit": 360}}),
    ("decode", 1.0, 2.0, {"active": 28, "live_state_bytes": 28 * STATE,
                          "moe": {"held": 780, "max_expert": 22,
                                  "experts_hit": 460}}),
    ("decode", 9.0, 11.0, {"active": 7, "live_state_bytes": 7 * STATE,
                           "moe": {"held": 1, "max_expert": 1,
                                   "experts_hit": 1}}),  # ends past the window
    ("prefill_call", 8.0, 8.5, {"iteration": 3, "size": 700}),
    ("prefill_call", 3.0, 3.5, {"iteration": 1, "size": 90}),  # not traced
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
                               "trace_steps": 2, "decode_rows": 24.0})


def _least(need):
    return max(need["flops"] / 197e12, need["bytes"] / 819e9)


def test_every_new_nemotron_h_reader_on_the_trace_fixture(monkeypatch,
                                                          tmp_path):
    run = _traced(monkeypatch, tmp_path)
    read = lambda name: harness._reader_for(name)(run)  # noqa: E731
    model = run.cell.config["model"]
    state = nemotron_h_sizes.state_load(run)
    assert (state["bytes"], state["active"], state["steps"]) == (
        24.0 * STATE, 24.0, 2)
    assert read("decode_live_state_mb_per_step") == 24 * STATE / 1e6
    # 660 assignments a step on 24 rows x 5 expert layers: 5.5 a row
    assert read("nemotron_h_moe_held_assignments_per_row") == 660 / (24 * 5)
    assert read("nemotron_h_moe_experts_hit_per_step") == 410.0
    # the fullest expert's 18 rows (summed over the layers) over the mean
    # expert's 660 / 128
    assert read("nemotron_h_moe_expert_load_max_over_mean") == pytest.approx(
        18.0 * 128 / 660.0)
    # device time under the scopes, an execution of each program
    assert read("decode_moe_latent_ms_per_step") == pytest.approx(0.050)
    assert scopes.scope_ms(run, "ssm_state", "jit__decode") == (
        pytest.approx(0.160))
    assert scopes.scope_ms(run, "ssm_scan", "jit__prefill") == (
        pytest.approx(0.400))
    # the accepted scope readers the cell joins read the same trace
    assert read("decode_ssm_state_ms_per_step") == pytest.approx(0.160)
    assert read("decode_ssm_ms_per_step") == pytest.approx(0.200)
    assert read("prefill_ssm_scan_ms_per_call") == pytest.approx(0.400)
    assert read("decode_moe_experts_ms_per_step") == pytest.approx(0.500)
    assert read("decode_moe_ms_per_step") == pytest.approx(0.700)
    need = nemotron_h_sizes.update_need(model, 24.0)
    assert read("ssd_state_update_roofline") == pytest.approx(
        100 * _least(need) / 160e-6)
    # the one traced prefill call held 700 tokens (the one of 90 ended
    # before the traced part of the window)
    assert nemotron_h_sizes.traced_prefill_calls(run) == [700]
    need = nemotron_h_sizes.chunk_need(model, [700])
    assert read("ssd_chunk_roofline") == pytest.approx(
        100 * _least(need) / 400e-6)
    load = kimi_linear_sizes.expert_load(run)
    assert (load["held"], load["experts_hit"]) == (660.0, 410.0)
    need = nemotron_h_sizes.experts_need(model, load)
    assert read("nemotron_h_moe_experts_roofline") == pytest.approx(
        100 * _least(need) / 500e-6)
    need = nemotron_h_sizes.decode_need(model, load, state)
    assert read("nemotron_h_decode_step_roofline") == pytest.approx(
        100 * _least(need) / 900e-6)


def test_no_share_passes_100_at_full_hit(monkeypatch, tmp_path):
    """Every held expert hit and every slot live, at the times a chip at
    its memory peak would need for exactly that work: the shares read 100
    and not more (what is counted is what the traffic made live)."""
    model = harness.find_cell(BENCH, CELL).config["model"]
    load = {"held": 64 * 22.0 * 5, "experts_hit": 640.0}
    state = {"active": 64.0}
    full = [("decode", 0.0, 1.0, {
        "active": 64, "live_state_bytes": 64 * STATE,
        "moe": {"held": 64 * 22 * 5, "max_expert": 64 * 5,
                "experts_hit": 640}})]
    run = _traced(monkeypatch, tmp_path, spans=full)
    run.values["decode_rows"] = 64.0
    for name, need, measured in (
            ("ssd_state_update_roofline",
             nemotron_h_sizes.update_need(model, 64.0), 160e-6),
            ("nemotron_h_moe_experts_roofline",
             nemotron_h_sizes.experts_need(model, load), 500e-6),
            ("nemotron_h_decode_step_roofline",
             nemotron_h_sizes.decode_need(model, load, state), 900e-6)):
        got = harness._reader_for(name)(run)
        assert got == pytest.approx(100 * _least(need) / measured)
        # the fixture's times are far under the chip's least: scale them
        assert got * measured / _least(need) == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_nemotron_h_reader_with_nothing_to_read_returns_none(
        monkeypatch, tmp_path, name):
    """A program from before the scopes and counters (or another family's):
    nothing, and no exception, never 0; without a trace the device metrics
    return nothing either. And the declaration is the benchmark's entry."""
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


def test_the_nemotron_h_cell_joins_the_shared_lists_and_no_silent_one():
    cell = harness.find_cell(BENCH, CELL)
    assert cell.chips == 1
    assert "itl_mean_ms" in cell.end_to_end and "setup_s" in cell.end_to_end
    mine = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= mine
    assert {"decode_ssm_ms_per_step", "decode_ssm_state_ms_per_step",
            "prefill_ssm_scan_ms_per_call", "state_resets_per_iter",
            "decode_moe_ms_per_step", "decode_moe_experts_ms_per_step",
            "decode_step_device_ms", "device_idle_pct.serve"} <= mine
    if "serve_tokens_per_s" not in cell.end_to_end:
        # then the three per-layer metrics that move it go with it
        assert not {"gen_lag_p95_ms", "slot_occupancy_pct",
                    "peak_hbm_gb.serve"} & mine
    # the two sampler metrics that fell silent with PR 39 are not asked of it
    assert not {"sampler_logprobs_ms_per_iter",
                "sampler_pipeline_ms_per_iter"} & mine
    # every metric the five older serve cells all carry
    dsv2 = {m["name"] for m in harness.find_cell(
        BENCH, "serve-deepseek-v2-5l-ep8-code-chat").per_layer}
    shared = {m["name"] for m in BENCH["per_layer"]
              if len(m.get("workloads", [])) >= 6}
    assert shared <= mine and shared <= dsv2 and len(shared) == 29
    assert all(m["moves"] in cell.end_to_end for m in cell.per_layer)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == BENCH["workloads"][-1] and len(entry["why"]) <= 200
    assert entry["traffic"] == "agent-turns-open-0.8knee"
    # new entries stand at the end of their lists, in the issue's order
    assert [m["name"] for m in BENCH["per_layer"][-len(NEW_READERS):]] == list(
        NEW_READERS)


# -- the serving limit against the faults it is there to catch ----------------

@pytest.fixture(scope="module")
def planted_nemotron_h():
    """``selftest_nemotron_h.py --witness`` at the rehearsal's widths and 4
    x 60 positions (sub-chunks of 8): the reference with each fault
    planted, judged as a served token is. At the cell's own size it runs
    on the chip (PERF.md section 2)."""
    import selftest_nemotron_h as cellcheck
    cell = harness.find_cell(BENCH, CELL)
    model = dict(cell.config["model"], **cellcheck.TINY_MODEL)
    return (cellcheck.witness_gaps(model, harness.load_reference(cell.config),
                                   rows=4, length=60),
            cell.config["correct"]["serve"]["token_gap"])


@pytest.mark.parametrize("fault", [
    "group0_bc", "norm_all_channels", "relu_no_square",
    "weights_not_renormalised", "held_expert_zeroed", "held_eighth_zeroed",
    "chunk_state_dropped"])
def test_the_serving_limit_fails_a_planted_nemotron_h_fault(
        planted_nemotron_h, fault):
    gaps, limit = planted_nemotron_h
    assert not check.judge([("served_token_gap", gaps[fault], limit)], fault)

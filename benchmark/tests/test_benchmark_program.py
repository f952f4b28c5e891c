"""``benchmark/lib/program.py``: how a configuration file becomes the
program's own ``ModelConfig`` and ``TrainConfig``. On the CPU, no chip:

    python3 -m pytest benchmark/tests -q

One parametrised test a rule: the shipped files build what an explicit
construction builds; a ``model`` key the program declares arrives, whatever
the key; one it does not declare ends the run, naming the key and the file;
a configuration states the paths it has; ``lib/cost.py`` counts the
families it names and refuses another.
"""

import copy
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from lib import cost, harness, program  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    MeshConfig,
    ModelConfig,
    TrainConfig,
)

BENCH = harness.load_benchmark()
RECIPE = dict(vocab_size=12000, n_embd=768, n_head=4, n_layer=8,
              block_size=512, dropout=0.0, compute_dtype="bfloat16",
              param_dtype="float32", attention_impl="pallas",
              ffn_impl="pallas")
OPTIMIZER = dict(grad_acc_steps=1, learning_rate=0.00032, min_lr=6e-05,
                 weight_decay=0.1, beta1=0.9, beta2=0.95, warmup_iters=1000,
                 max_iters=40000, grad_clip=1.0)


def config_of(cell: str) -> dict:
    return copy.deepcopy(harness.find_cell(BENCH, cell).config)


@pytest.mark.parametrize("cell, family, rows, chips, served_heads", [
    ("train-diff-recipe", "diff", 64, 1, 4),
    ("train-control-recipe", "control", 64, 1, 8),
    ("train-diff-recipe-dp4", "diff", 256, 4, 4),
    ("serve-diff-recipe-chat", "diff", 1, 1, 4),
])
def test_shipped_files_build_the_configs_written_out_here(
        cell, family, rows, chips, served_heads):
    config = config_of(cell)
    model = ModelConfig(model=family, **RECIPE)
    assert program.model_config(config) == model
    assert program.train_config(config, rows, chips) == TrainConfig(
        model=model, mesh=MeshConfig(data=chips), vocab_size=12000,
        control_head_multiplier=2, micro_batch_size=rows,
        sampler="replacement", **OPTIMIZER)
    assert program.served_model(config) == ModelConfig(
        model=family, **dict(RECIPE, n_head=served_heads))


@pytest.mark.parametrize("key, value", [
    ("kv_cache_dtype", "int8"), ("n_terms", 3), ("remat", True),
    ("loss_chunk", 128), ("decode_attention_impl", "pallas"),
])
def test_a_model_field_the_program_declares_arrives(key, value):
    config = config_of("train-diff-recipe")
    assert getattr(program.model_config(config), key) != value
    config["model"][key] = value
    for built in (program.model_config(config),
                  program.train_config(config, 64, 1).model,
                  program.served_model(config)):
        assert getattr(built, key) == value


@pytest.mark.parametrize("block, key, build", [
    ("model", "n_kv_head", program.served_model),
    ("model", "n_kv_head", lambda c: program.train_config(c, 64, 1)),
    ("model", "learning_rate", program.check_config),  # a field, of the other block
    ("train", "lr", lambda c: program.train_config(c, 64, 1)),
    ("train", "lr", program.check_config),
    ("train", "micro_batch_size", program.check_config),  # the cell's to set
])
def test_a_key_the_program_does_not_declare_exits_naming_key_and_file(
        block, key, build):
    config = config_of("train-diff-recipe")
    config[block][key] = 2
    with pytest.raises(SystemExit) as e:
        build(config)
    said = str(e.value)
    assert repr(key) in said and f"`{block}`" in said
    assert "benchmark/configs/diff-recipe.json" in said


def _drive(kind: str, config: dict):
    cell = harness.find_cell(BENCH, {"train_steps": "train-diff-recipe",
                                     "open_loop": "serve-diff-recipe-chat"}[kind])
    cell.config = config
    args = types.SimpleNamespace(seed=3, seconds=1.0, trace=0)
    return harness.driver(kind).run(cell, harness.Env([], None, True), args, 0.0)


@pytest.mark.parametrize("dropped, kind, named", [
    (("train",), None, None),  # it serves
    (("train",), "train_steps", "`train`"),
    (("correct.train",), "train_steps", "`correct.train`"),
    (("correct.serve",), "open_loop", "`correct.serve`"),
    (("correct",), "open_loop", "`correct.serve`"),
])
def test_a_configuration_states_the_paths_it_has(dropped, kind, named):
    config = config_of("serve-diff-recipe-chat")
    for block in dropped:
        *outer, last = block.split(".")
        node = config
        for k in outer:
            node = node[k]
        del node[last]
    program.check_config(config)  # an absent block is no fault of the file
    assert program.served_model(config) == ModelConfig(model="diff", **RECIPE)
    if kind is None:
        return
    with pytest.raises(SystemExit) as e:
        _drive(kind, config)
    assert named in str(e.value) and "diff-recipe.json" in str(e.value)
    assert f"the {kind} driver" in str(e.value)


@pytest.mark.parametrize("family, sizes", [
    ("diff", (2, 4, 96, 192)), ("control", (1, 8, 96, 96)),
    ("ndiff", None), ("some_other_family", None),
])
def test_cost_counts_the_families_it_names_and_refuses_another(family, sizes):
    model = dict(config_of("train-diff-recipe")["model"], model=family)
    if sizes is not None:
        assert cost._attn_sizes(model) == sizes
        return
    for count in (cost._attn_sizes, cost.non_embedding_params,
                  lambda m: cost.decode_step(m, {}),
                  lambda m: cost.train_step_6nd(m, {"rows_per_chip": 1,
                                                    "seq_len": 1})):
        with pytest.raises(ValueError, match=family):
            count(model)

"""The system under test, as the benchmark takes it: the only module of the
benchmark that imports the program. It builds the program's own objects
(configs, the jitted train step, the serving engine under its supervisor)
from a configuration file and a traffic file, and hands them weights made
by the benchmark. Nothing here measures or judges.
"""

from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def setup_compile_cache() -> None:
    """The program's own placement (``<checkout>/.jax_cache``, or where
    ``JAX_COMPILATION_CACHE_DIR`` says), plus every program cached however
    fast it compiled: a run is a new process, and the sub-second programs
    JAX leaves out by default were 11 s of a warm start (PR 21)."""
    import jax
    from differential_transformer_replication_tpu.utils.device import (
        setup_compile_cache as place,
    )

    place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def config_file(config: dict) -> str:
    """The configuration's file, for a message (``harness.find_cell`` puts
    BENCHMARK.json's ``file`` into the dict it loads)."""
    return config.get("file") or f"benchmark/configs/{config.get('name')}.json"


def _declared(cls, block: dict, config: dict, name: str):
    """``cls(**block)``, the configuration's ``name`` block whole. A key
    that is no field the program declares ends the run here, naming the
    key and the file: dropped, the reference (which reads the raw block)
    and the program would run different models without a word."""
    fields = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(block) - fields)
    if unknown:
        raise SystemExit(
            f"benchmark: {config_file(config)}: `{name}` key "
            f"{', '.join(map(repr, unknown))} is no field of the program's "
            f"{cls.__name__} (config.py)")
    try:
        return cls(**block)
    except ValueError as e:  # the dataclass's own check of a value
        raise SystemExit(f"benchmark: {config_file(config)}: `{name}`: {e}")


def _trainer(config: dict, **fields):
    """The program's ``TrainConfig`` around the configuration's ``model``
    block. One key of that block is no model field but the trainer-level
    switch ``TrainConfig.resolved_model`` applies
    (``control_head_multiplier``; 1 where the file does not state it), and
    the trainer's vocabulary is the model block's."""
    from differential_transformer_replication_tpu.config import (
        ModelConfig,
        TrainConfig,
    )

    block = dict(config["model"])
    multiplier = block.pop("control_head_multiplier", 1)
    model = _declared(ModelConfig, block, config, "model")
    return _declared(TrainConfig, dict(
        fields, model=model, vocab_size=model.vocab_size,
        control_head_multiplier=multiplier), config, "train")


def model_config(config: dict):
    """Every key of the ``model`` block, as the program's ``ModelConfig``
    (before the trainer-level switches: see :func:`served_model`)."""
    return _trainer(config).model


def train_config(config: dict, rows: int, chips: int):
    """``rows`` is the global batch of one step (the trainer's
    ``micro_batch_size`` before the data-parallel split). Every key of the
    ``train`` block reaches ``TrainConfig``; what the cell itself sets
    (the batch, the mesh, the sampler) a configuration may not state."""
    from differential_transformer_replication_tpu.config import MeshConfig

    fixed = dict(mesh=MeshConfig(data=chips), micro_batch_size=rows,
                 sampler="replacement")
    clash = sorted(set(config["train"]) & (set(fixed) | {
        "model", "vocab_size", "control_head_multiplier"}))
    if clash:
        raise SystemExit(
            f"benchmark: {config_file(config)}: `train` key "
            f"{', '.join(map(repr, clash))} is set by the cell or by the "
            "`model` block, not by the `train` block")
    return _trainer(config, **fixed, **config["train"])


def served_model(config: dict):
    """The model configuration a server gets from a checkpoint of this
    recipe (the trainer's head doubling for `control` applied). It needs
    no ``train`` block: a configuration that can only be served has none."""
    return _trainer(config).resolved_model()


def check_config(config: dict) -> None:
    """Both blocks against the program's declarations, before anything is
    built: every entry point passes here (``harness.find_cell``)."""
    served_model(config)
    if "train" in config:
        train_config(config, 1, 1)


def check_layout(params, config: dict) -> None:
    """The benchmark's weights must be the tree the program initialises:
    a program whose checkpoint layout moved needs a reference that moved
    with it, not a silent reshape."""
    import jax
    from differential_transformer_replication_tpu.models import init_model

    want = jax.eval_shape(lambda k: init_model(k, served_model(config)),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise SystemExit("benchmark/reference.py:param_spec and the "
                         "program's init_model disagree on the weight tree")


def make_mesh(tcfg):
    from differential_transformer_replication_tpu.parallel import create_mesh

    return create_mesh(tcfg.mesh)


def train_state(params, tcfg, mesh=None):
    """The trainer's state around the given weights (its optimizer's own
    init, its guard scalars), placed as the trainer places it."""
    import jax
    import jax.numpy as jnp
    from differential_transformer_replication_tpu.train.anomaly import (
        init_guard_state,
    )
    from differential_transformer_replication_tpu.train.optim import (
        make_optimizer,
    )

    tx, _ = make_optimizer(tcfg)

    def build(p):
        state = {"params": p, "opt_state": tx.init(p),
                 "step": jnp.zeros((), jnp.int32)}
        if tcfg.anomaly_guard:
            state["guard"] = init_guard_state()
        return state

    if mesh is None:
        return jax.jit(build, donate_argnums=(0,))(params)
    from jax.sharding import NamedSharding, PartitionSpec

    repl = NamedSharding(mesh, PartitionSpec())
    return jax.jit(build, donate_argnums=(0,), out_shardings=repl)(params)


def train_step(tcfg, mesh, state):
    """What ``train()`` calls every iteration: the donated single-device
    step, or with a mesh the step ``--data-parallel N`` reaches."""
    if mesh is None:
        from differential_transformer_replication_tpu.train.step import (
            make_train_step,
        )

        return make_train_step(tcfg)
    from differential_transformer_replication_tpu.parallel import (
        make_sharded_train_step,
    )

    return make_sharded_train_step(tcfg, mesh, state)


def adam_first_moment(state):
    """The optimizer's first moment, a tree like the parameters."""
    for part in state["opt_state"]:
        for sub in (part if isinstance(part, tuple) else (part,)):
            if hasattr(sub, "mu"):
                return sub.mu
    raise SystemExit("no Adam first moment in the program's optimizer state")


def token_windows(tokens, block_size: int):
    from differential_transformer_replication_tpu.data.sampler import (
        TokenWindows,
    )

    return TokenWindows(tokens, block_size)


def serving_engine(params, config: dict, engine: dict, tracer=None):
    """``(engine, runner)``: the engine over the given weights under the
    server's own supervisor loop (HTTP is left out)."""
    from differential_transformer_replication_tpu.config import ServingConfig
    from differential_transformer_replication_tpu.serving.engine import (
        ServingEngine,
    )
    from differential_transformer_replication_tpu.serving.server import (
        EngineRunner,
    )

    eng = ServingEngine(params, served_model(config),
                        ServingConfig(**engine), tracer=tracer)
    return eng, EngineRunner(eng)


def sampling_params(**kw):
    from differential_transformer_replication_tpu.serving.request import (
        SamplingParams,
    )

    return SamplingParams(**kw)

"""The system under test, as the benchmark takes it: the only module of the
benchmark that imports the program. It builds the program's own objects
(configs, the jitted train step, the serving engine under its supervisor)
from a configuration file and a traffic file, and hands them weights made
by the benchmark. Nothing here measures or judges.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_MODEL_KEYS = ("model", "vocab_size", "n_embd", "n_head", "n_layer",
               "block_size", "dropout", "compute_dtype", "param_dtype",
               "attention_impl", "ffn_impl")
_TRAIN_KEYS = ("learning_rate", "min_lr", "weight_decay", "beta1", "beta2",
               "warmup_iters", "max_iters", "grad_clip", "grad_acc_steps")


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


def model_config(config: dict, **overrides):
    from differential_transformer_replication_tpu.config import ModelConfig

    fields = {k: config["model"][k] for k in _MODEL_KEYS if k in config["model"]}
    fields.update(overrides)
    return ModelConfig(**fields)


def train_config(config: dict, rows: int, chips: int):
    """``rows`` is the global batch of one step (the trainer's
    ``micro_batch_size`` before the data-parallel split)."""
    from differential_transformer_replication_tpu.config import (
        MeshConfig,
        TrainConfig,
    )

    train = config["train"]
    return TrainConfig(
        model=model_config(config),
        mesh=MeshConfig(data=chips),
        vocab_size=config["model"]["vocab_size"],
        control_head_multiplier=config["model"].get(
            "control_head_multiplier", 1),
        micro_batch_size=rows,
        sampler="replacement",
        **{k: train[k] for k in _TRAIN_KEYS if k in train},
    )


def served_model(config: dict):
    """The model configuration a server gets from a checkpoint of this
    recipe (the trainer's head doubling for `control` applied)."""
    return train_config(config, 1, 1).resolved_model()


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

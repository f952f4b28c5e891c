"""Model-select switch.

The reference selects models by commenting code blocks in and out
(train.py:205-230); here it is a first-class dispatch on
``ModelConfig.model`` covering the same three families, and ``jamba``
(models/jamba.py), ``kimi_linear`` (models/kimi_linear.py), ``afmoe``
(models/afmoe.py), ``deepseek_v2`` (models/deepseek_v2.py),
``nemotron_h`` (models/nemotron_h.py) and ``lfm2`` (models/lfm2.py), whose
layers are of several kinds.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models import (
    afmoe,
    control,
    deepseek_v2,
    diff,
    jamba,
    kimi_linear,
    lfm2,
    ndiff,
    nemotron_h,
)

_MODULES = {"control": control, "diff": diff, "ndiff": ndiff,
            "jamba": jamba, "kimi_linear": kimi_linear, "afmoe": afmoe,
            "deepseek_v2": deepseek_v2, "nemotron_h": nemotron_h,
            "lfm2": lfm2}


def init_model(key: jax.Array, cfg: ModelConfig) -> dict:
    return _MODULES[cfg.model].init(key, cfg)


def model_forward(
    params: dict,
    idx: jnp.ndarray,
    cfg: ModelConfig,
    targets: Optional[jnp.ndarray] = None,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """``mesh`` (jax.sharding.Mesh, optional): when it carries a >1
    ``sequence`` axis, attention runs ring-sharded over it
    (parallel/ring.py); otherwise it is ignored."""
    return _MODULES[cfg.model].forward(
        params, idx, cfg, targets=targets, rng=rng, mesh=mesh
    )


def param_count(params: dict) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def model_module(cfg: ModelConfig):
    """The family's module, exposing the split forward pieces each family
    defines with a uniform signature — ``embed(params, idx, cfg)`` and
    ``block_forward(x, blk, layer_idx, cfg, cos, sin, mask, rng, mesh)`` —
    used by the pipeline-parallel schedule (parallel/pipeline.py), which
    must place embed / blocks / lm-head on different stages."""
    return _MODULES[cfg.model]

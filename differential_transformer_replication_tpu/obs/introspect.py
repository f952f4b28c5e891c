"""Paper-level introspection: per-layer lambda + per-group norms.

The Differential Transformer's central learnable quantity is the
per-layer lambda that weights the subtracted attention map (Ye et al.,
2024); the paper's lambda-evolution figure shows it drifting away from
the ``0.8 - 0.6*exp(-0.3*(l-1))`` init schedule during training. The
reference repo never logs it — this module closes that gap with a
jitted-cheap summary op the trainer calls every eval interval, so the
figure can be reproduced from any run's ``metrics.jsonl``
(``tools/lambda_report.py`` renders it).

``make_param_summary(cfg)`` returns a jitted ``params -> small pytree``
op touching only the lambda vectors (a few KB) and one reduction per
layer group for the norms — microseconds of device work, one compile
per param layout (it never retraces across steps: params keep their
shapes for the whole run).

Family shapes (the acceptance contract):
  - control: no lambdas — ``lambdas`` is None, only norms are logged,
  - diff:    ``lambdas`` is (n_layer,) — one effective lambda/layer,
  - ndiff:   ``lambdas`` is (n_layer, n_terms) — one per term per layer.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.ops.lambdas import (
    effective_diff_lambda,
    effective_ndiff_lambdas,
    lambda_init_schedule,
)


def _layer_lambdas(params: dict, cfg: ModelConfig) -> Optional[jnp.ndarray]:
    if cfg.model in ("control", "jamba"):  # one softmax: no lambdas
        return None
    blocks = params["blocks"]
    if cfg.model == "diff":
        return jnp.stack([
            effective_diff_lambda(blk["attn"], li)
            for li, blk in enumerate(blocks, 1)  # 1-based (ops/lambdas.py)
        ])
    return jnp.stack([
        effective_ndiff_lambdas(blk["attn"], li)
        for li, blk in enumerate(blocks, 1)
    ])


def serving_lambda_summary(params: dict, cfg: ModelConfig) -> dict:
    """Host-side per-layer effective-lambda view for the SERVING
    telemetry path (serving/engine.py mirrors it into
    ``serving_lambda_mean{layer=}`` and ``{"record": "quality"}``
    rows): the same ``lambda_l<k>`` / ``lambda_l<k>_t<j>`` key schema
    as :func:`lambda_record`, so ``tools/lambda_report.py --serving``
    renders live-fleet rows beside training ones. ``lambda_l<k>`` is
    the term mean for ndiff (the gauge's value); per-term detail rides
    the ``_t<j>`` keys. Empty dict for the control family.

    Unjitted on purpose — it runs once at engine build and after a
    params rebind (the ``quality_drift`` fault), never per step."""
    import numpy as np

    lams = _layer_lambdas(params, cfg)
    if lams is None:
        return {}
    lams = np.asarray(lams)
    out = {}
    for li in range(lams.shape[0]):
        if lams.ndim == 1:  # diff: one effective lambda per layer
            out[f"lambda_l{li + 1}"] = float(lams[li])
        else:  # ndiff: per-term lambdas + their mean
            out[f"lambda_l{li + 1}"] = float(lams[li].mean())
            for tj in range(lams.shape[1]):
                out[f"lambda_l{li + 1}_t{tj}"] = float(lams[li, tj])
    return out


def group_norms(params: dict) -> dict:
    """Global L2 norm per layer group: embeddings, each block, the final
    norm + lm head — the standard per-depth training-health view."""
    embed = {
        k: v for k, v in params.items()
        if k in ("tok_emb", "pos_emb")
    }
    head = {k: v for k, v in params.items() if k in ("ln_f", "lm_head")}
    return {
        "embed": optax.global_norm(embed),
        "blocks": jnp.stack(
            [optax.global_norm(blk) for blk in params["blocks"]]
        ),
        "head": optax.global_norm(head),
    }


def make_param_summary(cfg: ModelConfig):
    """Jitted ``summary(params) -> dict`` with ``lambdas`` (see module
    docstring; absent for control) and ``param_norms`` (embed / (L,)
    blocks / head). Call on the live train state's params — sharded
    arrays are fine, the op compiles against their shardings."""

    @jax.jit
    def summary(params: dict) -> dict:
        out = {"param_norms": group_norms(params)}
        lams = _layer_lambdas(params, cfg)
        if lams is not None:
            out["lambdas"] = lams
        return out

    return summary


def lambda_record(summary_out: dict, cfg: ModelConfig,
                  grad_norms=None) -> dict:
    """Convert a fetched (host-side) summary into flat JSON-friendly
    fields for one ``metrics.jsonl`` record. Keys:

      - diff:  ``lambda_l<k>`` (1-based layer) -> float,
      - ndiff: ``lambda_l<k>_t<j>`` (0-based term, matching the
        reference's term indexing) -> float,
      - both + control: ``param_norm_embed`` / ``param_norm_l<k>`` /
        ``param_norm_head``; ``lambda_init_l<k>`` (the schedule, so the
        drift is readable without recomputing it),
      - optional ``grad_norm_*`` mirrors from the train step's
        per-group gradient norms.
    """
    import numpy as np

    rec = {}
    lams = summary_out.get("lambdas")
    if lams is not None:
        lams = np.asarray(lams)
        for li in range(lams.shape[0]):
            rec[f"lambda_init_l{li + 1}"] = round(
                float(lambda_init_schedule(li + 1)), 6
            )
            if lams.ndim == 1:  # diff: one per layer
                rec[f"lambda_l{li + 1}"] = round(float(lams[li]), 6)
            else:  # ndiff: one per term per layer
                for tj in range(lams.shape[1]):
                    rec[f"lambda_l{li + 1}_t{tj}"] = round(
                        float(lams[li, tj]), 6
                    )
    norms = summary_out["param_norms"]
    rec["param_norm_embed"] = round(float(norms["embed"]), 4)
    for li, v in enumerate(np.asarray(norms["blocks"]), 1):
        rec[f"param_norm_l{li}"] = round(float(v), 4)
    rec["param_norm_head"] = round(float(norms["head"]), 4)
    if grad_norms is not None:
        g = np.asarray(grad_norms)
        rec["grad_norm_embed"] = round(float(g[0]), 6)
        for li in range(1, g.shape[0] - 1):
            rec[f"grad_norm_l{li}"] = round(float(g[li]), 6)
        rec["grad_norm_head"] = round(float(g[-1]), 6)
    return rec

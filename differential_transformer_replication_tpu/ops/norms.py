"""Normalization layers.

Both the blocks' pre-LN (``nn.LayerNorm``, control.py:105-106) and the
differential attention's ``GroupLayerNorm`` (diff_transformer.py:5-20,
Ndiff_transformer.py:24-38) reduce over the ENTIRE last dimension with
biased variance and ``eps`` inside the square root.

Parity note (SURVEY.md section 2.1): despite its name and docstring, the
reference's GroupLayerNorm is NOT a per-head group norm — it computes
mean/var over the full concatenated ``num_heads * 2*head_size`` dimension
(diff_transformer.py:17-18). We replicate that behavior, not the docstring.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """LayerNorm over the last axis: biased variance, ``(var + eps).sqrt()``
    denominator — the exact formula at diff_transformer.py:17-19, which is
    also what ``nn.LayerNorm`` computes."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mean) / jnp.sqrt(var + eps)
    return (normed * weight + bias).astype(x.dtype)


def group_layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """The reference's GroupLayerNorm: a full-width LayerNorm over the
    concatenated head outputs (diff_transformer.py:15-20). Kept as a named
    alias so call sites document which reference module they replicate."""
    return layer_norm(x, weight, bias, eps=eps)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm over the last axis: ``x / sqrt(mean(x^2) + eps) * weight``,
    no mean subtracted and no bias (the ``jamba`` family's norm, also on
    the Mamba mixer's ``dt``, ``B`` and ``C``). Computed in float32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * weight).astype(x.dtype)

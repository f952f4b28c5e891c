"""StandardTransformer: the vanilla-attention control model.

Functional JAX re-design of control.py:113-171 — decoder-only LM with
RoPE as the only position encoding (no position table, control.py:118-119,
143-144), pre-LN residual blocks, SwiGLU FFN, untied lm_head.

All heads are computed in one merged einsum instead of the reference's
per-head Python loop (control.py:76).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models import common
from differential_transformer_replication_tpu.ops import (
    apply_rope,
    causal_mask,
    rope_cos_sin,
    vanilla_attention,
)
from differential_transformer_replication_tpu.ops.streams import vanilla_coeffs


# RoPE is this family's position encoding (control.py:47-48); consumers
# that precompute the tables (parallel/pipeline.py) key on this flag.
USES_ROPE = True


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    H, d, E = cfg.n_head, cfg.head_size, cfg.n_embd
    keys = jax.random.split(key, cfg.n_layer + 3)
    blocks = []
    for li in range(cfg.n_layer):
        kq, kk, kv, ko, kf = jax.random.split(keys[li], 5)
        blocks.append(
            {
                "ln1": common.layer_norm_params(E),
                "attn": {
                    # merged per-head K/Q/V projections, no bias
                    # (control.py:28-30)
                    "wq": common.normal_init(kq, (E, H, d)),
                    "wk": common.normal_init(kk, (E, H, d)),
                    "wv": common.normal_init(kv, (E, H, d)),
                    # out-proj Linear(head_size*num_heads, n_embd) with bias
                    # (control.py:72)
                    "out": common.linear_params(ko, H * d, E),
                },
                "ln2": common.layer_norm_params(E),
                "ffn": common.ffn_params(kf, E),
            }
        )
    return {
        "tok_emb": common.normal_init(keys[-3], (cfg.vocab_size, E)),
        "blocks": blocks,
        "ln_f": common.layer_norm_params(E),
        "lm_head": common.linear_params(keys[-1], E, cfg.vocab_size),
    }


def _attn(
    x: jnp.ndarray,
    p: dict,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    mask: jnp.ndarray,
    dropout_rate: float,
    rng: Optional[jax.Array],
    impl: str = "xla",
    mesh=None,
    seq_impl: str = "ring",
) -> jnp.ndarray:
    B, T, E = x.shape
    r_att, r_out = common.split_rng(rng, 2)
    q = jnp.einsum("bte,ehd->bthd", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bte,ehd->bthd", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bte,ehd->bthd", x, p["wv"].astype(x.dtype))
    q = apply_rope(q, cos, sin)  # control.py:47-48
    k = apply_rope(k, cos, sin)
    coeffs = vanilla_coeffs(q.shape[2])
    out = common.dispatch_attention(
        q[None], k[None], v, coeffs,
        # the dense XLA reference op (control.py:52-62)
        lambda: vanilla_attention(
            q, k, v, mask=mask, dropout_rate=dropout_rate, rng=r_att
        ),
        impl=impl, mesh=mesh, dropout_rate=dropout_rate, rng=r_att,
        seq_impl=seq_impl,
        # kernel-native-layout fast path; it rotates for itself, halves
        # of re-ordered projections (the q, k above are the dense path's)
        flash_fn=common.flash_bh_fn(
            x, p["wq"][None], p["wk"][None], p["wv"], coeffs,
            dropout_rate=dropout_rate, rng=r_att, cos=cos, sin=sin,
        ),
    )
    out = out.reshape(B, T, -1)  # concat heads (control.py:76)
    out = common.linear(out, p["out"])
    return common.dropout(out, dropout_rate, r_out)  # control.py:77


def embed(params: dict, idx: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Token embedding only — RoPE is the position encoding
    (control.py:144, no position table)."""
    with jax.named_scope("embed"):
        return params["tok_emb"][idx].astype(jnp.dtype(cfg.compute_dtype))


def block_forward(
    x: jnp.ndarray,
    blk: dict,
    layer_idx,
    cfg: ModelConfig,
    cos: Optional[jnp.ndarray],
    sin: Optional[jnp.ndarray],
    mask: jnp.ndarray,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> jnp.ndarray:
    """One pre-LN residual block (control.py:92-111). ``layer_idx`` is part
    of the uniform per-family signature (models/registry.py); the control
    model has no per-layer schedule, so it is unused here."""
    del layer_idx
    r_attn, r_ffn = common.split_rng(rng, 2)
    with jax.named_scope("attn_norm"):
        h = common.apply_pre_norm(x, blk["ln1"], cfg, mesh)
    with jax.named_scope("attn"):
        a = _attn(
            h, blk["attn"], cos, sin, mask, cfg.dropout, r_attn,
            cfg.attention_impl, mesh, cfg.sequence_impl,
        )
    # residual add + ln2 + SwiGLU + down-proj + residual, ffn_impl-
    # dispatched (fused kernels when "pallas"; models/common.py)
    return common.apply_block_ffn(x, a, blk, cfg, r_ffn, mesh)


def forward(
    params: dict,
    idx: jnp.ndarray,
    cfg: ModelConfig,
    targets: Optional[jnp.ndarray] = None,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """(B, T) int tokens -> (logits (B, T, V), loss or None)."""
    B, T = idx.shape
    x = embed(params, idx, cfg)
    cos, sin = rope_cos_sin(cfg.head_size, T)
    mask = causal_mask(T)
    rngs = common.split_rng(rng, cfg.n_layer)
    for li, (blk, r) in enumerate(zip(params["blocks"], rngs), 1):
        fn = block_forward
        if cfg.remat:  # recompute this block's activations in the backward
            fn = common.remat_block(fn, cfg)  # cfg.remat_policy-aware
        x = fn(x, blk, li, cfg, cos, sin, mask, r, mesh)
    return common.tail_and_loss(x, params, cfg, targets, mesh)

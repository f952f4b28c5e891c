"""DiffTransformer: the 2-term differential attention model.

Functional JAX re-design of diff_transformer.py:128-185. Distinctive
reference behaviors preserved:
  - learned ABSOLUTE position embeddings — the only variant with a
    position table; no RoPE (diff_transformer.py:133-134, 157-159),
  - head_size = n_embd // (2 * n_head) with doubled values
    (diff_transformer.py:111, 30),
  - per-layer dynamic lambda_init with 1-BASED layer indices
    (diff_transformer.py:43, 161), computed purely from the static layer
    index instead of the reference's in-place buffer write,
  - full-width GroupLayerNorm over the head concat, then the CONSTANT 0.2
    output scale (diff_transformer.py:90-91; SURVEY.md section 2.1 quirks).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models import common
from differential_transformer_replication_tpu.ops import (
    causal_mask,
    diff_attention,
    diff_lambda,
    lambda_init_schedule,
)
from differential_transformer_replication_tpu.ops.lambdas import OUTPUT_SCALE
from differential_transformer_replication_tpu.ops.streams import diff_coeffs


# learned absolute positions, no RoPE (diff_transformer.py:133-134);
# consumers that precompute RoPE tables (parallel/pipeline.py) key on this.
USES_ROPE = False


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
                    # the two Q/K streams stacked on a leading axis
                    # (query1/query2, key1/key2: diff_transformer.py:26-29)
                    "wq": common.normal_init(kq, (2, E, H, d)),
                    "wk": common.normal_init(kk, (2, E, H, d)),
                    # doubled value projection (diff_transformer.py:30)
                    "wv": common.normal_init(kv, (E, H, 2 * d)),
                    # lambda vectors, zero-init (diff_transformer.py:35-38)
                    "lambda_q": jnp.zeros((2, H, d), jnp.float32),
                    "lambda_k": jnp.zeros((2, H, d), jnp.float32),
                    "gn": common.layer_norm_params(H * 2 * d),
                    # out-proj Linear(2*head_size*num_heads, n_embd), bias
                    # (diff_transformer.py:84)
                    "out": common.linear_params(ko, H * 2 * d, E),
                },
                "ln2": common.layer_norm_params(E),
                "ffn": common.ffn_params(kf, E),
            }
        )
    return {
        "tok_emb": common.normal_init(keys[-3], (cfg.vocab_size, E)),
        # learned absolute positions (diff_transformer.py:134)
        "pos_emb": common.normal_init(keys[-2], (cfg.block_size, E)),
        "blocks": blocks,
        "ln_f": common.layer_norm_params(E),
        "lm_head": common.linear_params(keys[-1], E, cfg.vocab_size),
    }


def _attn(
    x: jnp.ndarray,
    p: dict,
    layer_idx: int,
    mask: jnp.ndarray,
    dropout_rate: float,
    rng: Optional[jax.Array],
    impl: str = "xla",
    mesh=None,
    seq_impl: str = "ring",
    cfg=None,
) -> jnp.ndarray:
    B, T, E = x.shape
    r_att, r_out = common.split_rng(rng, 2)
    qs = jnp.einsum("bte,sehd->sbthd", x, p["wq"].astype(x.dtype))
    ks = jnp.einsum("bte,sehd->sbthd", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bte,ehd->bthd", x, p["wv"].astype(x.dtype))
    lam = diff_lambda(
        p["lambda_q"][0], p["lambda_k"][0],
        p["lambda_q"][1], p["lambda_k"][1],
        lambda_init_schedule(layer_idx),
    )  # (H,) fp32

    coeffs = diff_coeffs(lam)
    out = common.dispatch_attention(
        qs, ks, v, coeffs,
        # the dense XLA reference op (att1 - lam*att2, diff_transformer.py:70)
        lambda: diff_attention(
            qs[0], ks[0], qs[1], ks[1], v, lam,
            mask=mask, dropout_rate=dropout_rate, rng=r_att,
        ),
        impl=impl, mesh=mesh, dropout_rate=dropout_rate, rng=r_att,
        seq_impl=seq_impl,
        # kernel-native-layout fast path (the stacked projections above
        # are dead code on that branch and DCE'd)
        flash_fn=common.flash_bh_fn(
            x, p["wq"], p["wk"], p["wv"], coeffs,
            dropout_rate=dropout_rate, rng=r_att,
        ),
    )
    out = out.reshape(B, T, -1)  # concat heads (diff_transformer.py:89)
    out = common.apply_group_norm(out, p["gn"], cfg, mesh)  # :90
    out = out * OUTPUT_SCALE  # constant 0.2, :91
    out = common.linear(out, p["out"])
    return common.dropout(out, dropout_rate, r_out)


def embed(params: dict, idx: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Token embedding PLUS the learned absolute position table — the only
    family with one (diff_transformer.py:133-134, 157-159)."""
    T = idx.shape[-1]
    if T > cfg.block_size:
        # The reference raises (nn.Embedding index error) past block_size;
        # a JAX gather would silently clamp, so fail loudly instead.
        raise ValueError(f"sequence length {T} exceeds block_size {cfg.block_size}")
    with jax.named_scope("embed"):
        tok = params["tok_emb"][idx]
        pos = params["pos_emb"][jnp.arange(T)]  # diff_transformer.py:158
        return (tok + pos).astype(jnp.dtype(cfg.compute_dtype))


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
    """One pre-LN residual block (diff_transformer.py:107-126).
    ``layer_idx`` is 1-based (diff_transformer.py:161) and may be a static
    int or a traced integer (the pipeline-parallel layer scan). ``cos``/
    ``sin`` are part of the uniform per-family signature; this family has
    no RoPE."""
    del cos, sin
    r_attn, r_ffn = common.split_rng(rng, 2)
    with jax.named_scope("attn_norm"):
        h = common.apply_pre_norm(x, blk["ln1"], cfg, mesh)
    with jax.named_scope("attn"):
        a = _attn(
            h, blk["attn"], layer_idx, mask, cfg.dropout, r_attn,
            cfg.attention_impl, mesh, cfg.sequence_impl, cfg,
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
    mask = causal_mask(T)
    rngs = common.split_rng(rng, cfg.n_layer)
    for li, (blk, r) in enumerate(zip(params["blocks"], rngs), 1):  # 1-based, :161
        fn = block_forward
        if cfg.remat:  # recompute this block's activations in the backward
            fn = common.remat_block(fn, cfg)  # cfg.remat_policy-aware
        x = fn(x, blk, li, cfg, None, None, mask, r, mesh)
    return common.tail_and_loss(x, params, cfg, targets, mesh)

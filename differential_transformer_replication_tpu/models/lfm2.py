"""The ``lfm2`` family: gated short convolutions beside a few rotary
grouped-query attention layers, and routed experts without a shared one.

A decoder-only stack (Liquid AI's LFM2 mixture-of-experts models,
``model_type: lfm2_moe``, as ``LiquidAI/LFM2-24B-A2B``'s published
``config.json`` sizes it) whose ``layer_types`` name every layer ``"conv"``
or ``"full_attention"`` (``ModelConfig.layer_kinds``: ``"shortconv"``,
``"full"``); the first ``first_dense_layers`` layers' feed-forward part is
a dense SwiGLU, the others' a layer of routed experts. RMSNorm with a
learned scale, two a block, no bias anywhere, the head tied to the token
table behind a final norm.

  layer:  h = x + Op(RMSNorm(x));  y = h + FF(RMSNorm(h))
  conv:   [B ; C ; u] = h W_in  (three parts of n_embd);  z = B * u
          c_t = sum_k w_k * z_{t + k - (K-1)}   (depthwise, causal, K =
          conv_taps, zeros before a sequence's start, no bias, no activation)
          Op = (C * c) W_out
  full_attention:  q (H heads of d), k, v (kv_heads, each shared by H /
          kv_heads query heads);  q_h = RMSNorm_d(q_h), k_h = RMSNorm_d(k_h)
          (one scale for q, one for k), THEN both rotated at their absolute
          position (rope_theta, dimension i with i + d/2);
          causal softmax(q k^T / sqrt(d)) v over every earlier position, W_o
  dense:  W_out(silu(W_gate h) * W_xform h)
  experts: ``kimi_linear.moe_mlp`` without a shared expert:
          s = sigmoid(h W_r);  the experts_per_token largest of s + b;
          w_i = routed_scaling s_i / (sum_chosen s + router_eps)
          y = sum_{i chosen and HELD} w_i E_i(h)

What a sequence carries from token to token in a conv layer is its last
``conv_taps - 1`` gated inputs ``z`` and nothing else: the WHOLE cache of
that layer (8 KB a slot at the published sizes), overwritten every token
like a recurrent state, so models/decode.py keeps it under a recurrent kind
of its own (``"shortconv"``) whose only leaf is the window. An attention
layer keeps a K/V ring, read in live blocks (``"full"``, in this family's
flavour: :func:`ring_qkv`). ``held_experts`` is an expert-parallel share as
in ``kimi_linear``; the published deployment holds all. Served, not trained.

The parameter tree (weights stored ``(in, out)``, every leaf in
``param_dtype``):

  tok_emb (V, E)
  blocks[l]: ln1{w}  ln2{w}
    conv:      conv{in_proj (E, 3 E)  conv_w (K, E)  out_proj (E, E)}
    attention: attn{wq (E, H, d)  wk, wv (E, KV, d)  q_norm (d)  k_norm (d)
               out{w (H d, E)}}
    dense:     ffn{gate{w (E, F)} xform{w (E, F)} out{w (F, E)}}
    experts:   moe{router{w (E, N) b (N)}  experts{gate_up (G, E, 2 Fm)
               down (G, Fm, E)}}
  ln_f{w}  [lm_head{w (E, V)} unless tie_embeddings]
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models import common
from differential_transformer_replication_tpu.models.jamba import (
    attend,
    embed,
    ffn,
    lm_head,
    norm,
    qkv,
)
from differential_transformer_replication_tpu.models.kimi_linear import moe
from differential_transformer_replication_tpu.ops.norms import rms_norm
from differential_transformer_replication_tpu.ops.rope import apply_rope_half
from differential_transformer_replication_tpu.ops.ssm import causal_conv

USES_ROPE = False  # no table: the attention layers rotate from the positions


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    E, H, KV, d = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_size
    lo, hi = cfg.held_expert_range
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, cfg.n_layer + 2)
    w = lambda k, *shape: common.normal_init(k, shape).astype(dtype)  # noqa: E731
    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    blocks = []
    for kind, mlp_kind, lk in zip(cfg.layer_kinds(), cfg.mlp_kinds(), keys):
        ks = jax.random.split(lk, 8)
        blk = {"ln1": {"w": ones(E)}, "ln2": {"w": ones(E)}}
        if kind == "shortconv":
            blk["conv"] = {
                "in_proj": w(ks[0], E, 3 * E),
                "conv_w": (jax.random.uniform(
                    ks[1], (cfg.conv_taps, E), minval=-1.0)
                    / math.sqrt(cfg.conv_taps)).astype(dtype),
                "out_proj": w(ks[2], E, E),
            }
        else:
            blk["attn"] = {
                "wq": w(ks[0], E, H, d), "wk": w(ks[1], E, KV, d),
                "wv": w(ks[2], E, KV, d), "q_norm": ones(d),
                "k_norm": ones(d), "out": {"w": w(ks[3], H * d, E)},
            }
        if mlp_kind == "dense":
            F = cfg.ffn_width
            blk["ffn"] = {"gate": {"w": w(ks[4], E, F)},
                          "xform": {"w": w(ks[5], E, F)},
                          "out": {"w": w(ks[6], F, E)}}
        else:
            Fm, N = cfg.moe_hidden, cfg.num_experts
            blk["moe"] = {
                "router": {"w": w(ks[4], E, N), "b": jnp.zeros((N,), dtype)},
                "experts": {"gate_up": w(ks[5], hi - lo, E, 2 * Fm),
                            "down": w(ks[6], hi - lo, Fm, E)},
            }
        blocks.append(blk)
    params = {"tok_emb": w(keys[-2], cfg.vocab_size, E), "blocks": blocks,
              "ln_f": {"w": ones(E)}}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": w(keys[-1], E, cfg.vocab_size)}
    return params


# -- the gated short convolution -----------------------------------------------


def conv_chunk(h: jnp.ndarray, p: dict, cfg: ModelConfig, conv: jnp.ndarray,
               valid=None):
    """The mixer over a chunk ``h`` (B, L, E) of normed inputs that
    continues sequences whose last ``conv_taps - 1`` gated inputs are
    ``conv`` (B, K-1, E; zeros are a sequence's start): ``(out (B, L, E),
    the window after the chunk)``. With ``valid`` (a runtime scalar) the
    tokens from ``valid`` on are padding: the window returned is the one
    after ``valid`` tokens, and ``out`` past it is not a sequence's. The
    window holds ``z`` as the chunk computed it, rounded to its dtype."""
    del cfg
    gate_in, gate_out, u = jnp.split(h @ p["in_proj"].astype(h.dtype), 3,
                                     axis=-1)
    with jax.named_scope("conv_taps"):
        c, conv = causal_conv(gate_in * u, p["conv_w"], None, conv, valid)
        c = c.astype(h.dtype)
    return (gate_out * c) @ p["out_proj"].astype(h.dtype), conv


def conv_step(h: jnp.ndarray, p: dict, cfg: ModelConfig, conv: jnp.ndarray,
              active: jnp.ndarray):
    """One token a slot of the decode pool: ``h`` (S, E); ``conv`` (S,
    K-1, E) is the pool's leaf, and a slot that is not ``active`` keeps
    every bit of it."""
    out, moved = conv_chunk(h[:, None], p, cfg, conv)
    with jax.named_scope("conv_taps"):
        conv = jnp.where(active[:, None, None], moved, conv)
    return out[:, 0], conv


def zero_window(cfg: ModelConfig, batch: int, compute_dtype=None):
    """``(conv,)`` of ``batch`` sequences at their start: all a conv layer
    keeps."""
    return (jnp.zeros((batch, cfg.conv_taps - 1, cfg.n_embd),
                      jnp.dtype(compute_dtype or cfg.compute_dtype)),)


# -- the attention mixer -------------------------------------------------------


def normed_rotated_qkv(h: jnp.ndarray, p: dict, cfg: ModelConfig,
                       pos: jnp.ndarray):
    """``h`` (.., E) -> q (.., H, d), k and v (.., KV, d), each head of q
    and k RMS-normed and THEN rotated at ``pos`` (which broadcasts against
    ``h``'s leading axes): the published order. A rotation keeps a head's
    length, so the other order differs by where the learned scales fall:
    on the rotated pairs, or on the values that are then rotated."""
    q, k, v = qkv(h, p)
    f32, eps = jnp.float32, cfg.resolved_norm_eps
    at = jnp.asarray(pos)[..., None]  # over the head axis
    q = apply_rope_half(rms_norm(q, p["q_norm"].astype(f32), eps), at,
                        cfg.rope_theta)
    k = apply_rope_half(rms_norm(k, p["k_norm"].astype(f32), eps), at,
                        cfg.rope_theta)
    return q, k, v


def ring_qkv(h: jnp.ndarray, p: dict, cfg: ModelConfig, pos, kind: str):
    """:func:`normed_rotated_qkv` for the ring chunk and step of
    models/decode.py: ``pos`` is a chunk's first position (``h`` (B, L,
    E)) or a position a row (``h`` (B, E)). The ``"full"`` layers of THIS
    family rotate (afmoe's and nemotron_h's carry no position); what
    follows the ring's read is the plain output projection,
    ``jamba.ring_out``."""
    del kind
    at = pos + jnp.arange(h.shape[1]) if h.ndim == 3 else pos
    return (*normed_rotated_qkv(h, p, cfg, at), None)


# -- the model -----------------------------------------------------------------


def block_forward(
    x: jnp.ndarray,
    blk: dict,
    layer_idx,
    cfg: ModelConfig,
    cos=None,
    sin=None,
    mask=None,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> jnp.ndarray:
    """One residual block over whole sequences ``x`` (B, T, E), in the
    uniform per-family signature (models/registry.py). The layer's kinds
    are read off its leaves."""
    del layer_idx, cos, sin, mask, rng, mesh
    T = x.shape[1]
    if "conv" in blk:  # graftlint: disable=GL104 (a dict's keys are static)
        with jax.named_scope("conv"):
            h = norm(x, blk["ln1"], cfg)
            a, _ = conv_chunk(h, blk["conv"], cfg,
                              *zero_window(cfg, x.shape[0], x.dtype))
    else:
        with jax.named_scope("attn_norm"):
            h = norm(x, blk["ln1"], cfg)
        with jax.named_scope("attn"):
            q, k, v = normed_rotated_qkv(h, blk["attn"], cfg, jnp.arange(T))
            with jax.named_scope("attn_full"):
                o = attend(q, k.swapaxes(1, 2), v.swapaxes(1, 2),
                           jnp.tril(jnp.ones((T, T), bool)))
            a = o @ blk["attn"]["out"]["w"].astype(o.dtype)
    if "moe" in blk:  # graftlint: disable=GL104 (a dict's keys are static)
        return moe(x + a, blk, cfg)[0]
    return ffn(x + a, blk, cfg)


def forward(
    params: dict,
    idx: jnp.ndarray,
    cfg: ModelConfig,
    targets: Optional[jnp.ndarray] = None,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> Tuple[Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """(B, T) int tokens -> (logits (B, T, V), None)."""
    del rng
    if targets is not None:
        raise ValueError(
            "the lfm2 family is served, not trained: no loss is defined "
            "for it (the grouped expert product has no tested backward "
            "pass)"
        )
    x = embed(params, idx, cfg)
    for li, blk in enumerate(params["blocks"], 1):
        x = block_forward(x, blk, li, cfg, None, None, None, None, mesh)
    with jax.named_scope("lm_head"):
        return lm_head(params, x, cfg), None

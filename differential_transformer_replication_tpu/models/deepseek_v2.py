"""The ``deepseek_v2`` family: multi-head latent attention in every layer,
with a low-rank query and a decoupled rotary key under YaRN scaling, and
routed experts limited to a few groups a token, beside shared experts, in
every layer but the first.

A decoder-only stack (DeepSeek-V2, arXiv:2405.04434, as
``deepseek-ai/DeepSeek-V2``'s published ``config.json`` sizes it). RMSNorm
everywhere, no bias in any projection, an untied head.

  layer:   x = x + MLA(RMSNorm(x));  x = x + MLP_l(RMSNorm(x))
  MLA:     c_q = RMSNorm(x W_qa)                      (q_lora_rank)
           [q_nope_h ; q_pe_h] = (c_q W_qb)_h;  q_pe_h = RoPE_t(q_pe_h)
           [c ; k_pe] = x W_kva;  c = RMSNorm(c);  k_pe = RoPE_t(k_pe)
           the cache holds [c ; k_pe] a position, one for all heads
           [k_nope_h ; v_h] = (c W_kvb)_h
           p = softmax_float32((q_nope_h . k_nope_h + q_pe_h . k_pe) s), causal
           s = (nope + rope)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
           out = concat_h(sum p v_h) W_o
  RoPE:    the pairs (2i, 2i + 1) of the rope dimensions turn by t f_i, f
           blended by YaRN between theta^(-2i/d) and that over ``factor``
           (ops/rope.py:yarn_frequencies)
  dense:   W_out(silu(W_gate h) * W_xform h)           (the first layers)
  experts: p = softmax_float32(h W_r); a group's score is its best expert's;
           the topk_group best groups, the experts_per_token best experts
           inside them; w_i = routed_scaling p_i (not renormalised)
           y = sum_{i chosen and HELD} w_i E_i(h) + E_shared(h)
           E_shared ONE MLP of n_shared_experts * moe_hidden, unscaled

The mixer is ``models/kimi_linear.py``'s MLA mixer (what differs follows
from the leaves and the configuration there), the experts its
``moe_mlp``, the blocks ``models/jamba.py``'s. ``held_experts`` is an
expert-parallel share, whole routing groups (a group is what one device of
the stage holds): the router ranks all ``num_experts``, the terms of the
absent experts are left out. A sequence's cache is a ring of latents a
layer, ``rank + rope`` values a position (models/decode.py), read live.
The family is served, not trained.

The parameter tree (weights stored ``(in, out)``, every leaf in
``param_dtype``; the rope columns of ``wq_b`` and ``wkv_a`` in the
published order, dimension 2i beside 2i + 1):

  tok_emb (V, E)
  blocks[l]: ln1{w}  ln2{w}
    mla{wq_a (E, q_rank)  q_norm (q_rank)  wq_b (q_rank, H, nope + rope)
        wkv_a (E, rank + rope)  kv_norm (rank)  wkv_b (rank, H, nope + v)
        out{w (H v, E)}}
    dense:   ffn{gate{w (E, F)} xform{w (E, F)} out{w (F, E)}}
    experts: moe{router{w (E, N)}  experts{gate_up (G, E, 2 Fm)
             down (G, Fm, E)}  shared{gate{w} xform{w} out{w}} (n_shared Fm)}
  ln_f{w}  lm_head{w (E, V)}
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models import common
from differential_transformer_replication_tpu.models.jamba import (
    embed,
    ffn,
    lm_head,
    norm,
)
from differential_transformer_replication_tpu.models.kimi_linear import (
    mla_chunk_attend,
    mla_latent,
    moe,
)

USES_ROPE = False  # no table: the rotary parts turn from the positions


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    E, H, qr = cfg.n_embd, cfg.n_head, cfg.q_lora_rank
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, vd = cfg.qk_rope_head_dim, cfg.v_head_dim
    lo, hi = cfg.held_expert_range
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, cfg.n_layer + 2)
    w = lambda k, *shape: common.normal_init(k, shape).astype(dtype)  # noqa: E731
    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    mlp = lambda ks, F: {"gate": {"w": w(ks[0], E, F)},  # noqa: E731
                         "xform": {"w": w(ks[1], E, F)},
                         "out": {"w": w(ks[2], F, E)}}
    blocks = []
    for mlp_kind, lk in zip(cfg.mlp_kinds(), keys):
        ks = jax.random.split(lk, 12)
        blk = {
            "ln1": {"w": ones(E)}, "ln2": {"w": ones(E)},
            "mla": {
                "wq_a": w(ks[0], E, qr), "q_norm": ones(qr),
                "wq_b": w(ks[1], qr, H, nope + rope),
                "wkv_a": w(ks[2], E, rank + rope), "kv_norm": ones(rank),
                "wkv_b": w(ks[3], rank, H, nope + vd),
                "out": {"w": w(ks[4], H * vd, E)},
            },
        }
        if mlp_kind == "dense":
            blk["ffn"] = mlp(ks[5:8], cfg.ffn_width)
        else:
            Fm, N = cfg.moe_hidden, cfg.num_experts
            blk["moe"] = {
                "router": {"w": w(ks[5], E, N)},
                "experts": {"gate_up": w(ks[6], hi - lo, E, 2 * Fm),
                            "down": w(ks[7], hi - lo, Fm, E)},
                "shared": mlp(ks[8:11], cfg.n_shared_experts * Fm),
            }
        blocks.append(blk)
    return {"tok_emb": w(keys[-2], cfg.vocab_size, E), "blocks": blocks,
            "ln_f": {"w": ones(E)},
            "lm_head": {"w": w(keys[-1], E, cfg.vocab_size)}}


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
    uniform per-family signature (models/registry.py): the sequence's own
    latents are the ring, read as a prefill chunk at position 0 reads
    it."""
    del layer_idx, cos, sin, mask, rng, mesh
    with jax.named_scope("mla"):
        h = norm(x, blk["ln1"], cfg)
        latent = mla_latent(h, blk["mla"], cfg, jnp.arange(x.shape[1]))
        a = mla_chunk_attend(h, blk["mla"], cfg, latent, 0)
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
            "the deepseek_v2 family is served, not trained: no loss is "
            "defined for it (the grouped expert product has no tested "
            "backward pass)"
        )
    x = embed(params, idx, cfg)
    for li, blk in enumerate(params["blocks"], 1):
        x = block_forward(x, blk, li, cfg, None, None, None, None, mesh)
    with jax.named_scope("lm_head"):
        return lm_head(params, x, cfg), None

"""The ``kimi_linear`` family: KDA mixers beside a few MLA layers, and
routed experts in every layer but the first.

A decoder-only stack (Moonshot's Kimi-Linear, arXiv:2510.26692, as its
published ``config.json`` sizes it) whose layers differ in two ways
(``ModelConfig.layer_kinds``, ``mlp_kinds``): a layer mixes tokens by KDA,
a gated delta rule with a recurrent state a head, or by MLA, attention
over a latent cache; and its MLP is dense (the first
``first_dense_layers``) or a layer of routed experts with one shared
expert. RMSNorm everywhere, no bias in any projection, no position
information of any kind, an untied head.

  layer:   x = x + mixer(RMSNorm(x));  x = x + mlp(RMSNorm(x))
  kda:     [q, k, v] = silu(conv(x W_qkv))     (causal, depthwise, a window
           of kda_conv); q, k L2-normalised a head, q scaled by d^-1/2
           g = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)   (a channel)
           beta = sigmoid(x W_b)                                (a head)
           S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
           o_t = S_t^T q_t;  out = (RMSNorm_d(o) * sigmoid(x W_ga W_gb)) W_o
  mla:     q_h = (x W_q)_h;  [c ; k_r] = x W_kva,  c = RMSNorm(c): the cache
           [k_h ; v_h] = (c W_kvb)_h,  key [k_h ; k_r] (not rotated)
           causal softmax(q_h . key / sqrt(nope + rope)) v_h,  W_o
  dense:   W_out(silu(W_gate h) * W_xform h)
  experts: s = sigmoid(h W_r);  the experts_per_token largest of s + b;
           w_i = routed_scaling s_i / sum_chosen s
           y = sum_{i chosen and HELD} w_i E_i(h) + E_shared(h)

``held_experts`` is an expert-parallel share: the layer's parameters hold
the experts ``[lo, hi)`` alone, the router ranks all ``num_experts``, and
the terms of the absent experts are left out (ops/moe.py). A sequence's
state is a KDA layer's ``S`` (H, d, d) float32 with the last ``kda_conv -
1`` inputs of the convolution, and an MLA layer's latents: what
models/decode.py keeps a slot. The family is served, not trained.

The parameter tree (weights stored ``(in, out)``, every leaf in
``param_dtype``; Hd = n_head * kda_head_dim, r = kda_head_dim):

  tok_emb (V, E)
  blocks[l]: ln1{w}  ln2{w}
    kda layer: kda{qkv (E, 3 Hd)  conv_w (K, 3 Hd)  f_a (E, r)  f_b (r, Hd)
               dt_bias (Hd)  A_log (H)  b (E, H)  g_a (E, r)  g_b (r, Hd)
               o_norm (d)  out (Hd, E)}
    mla layer: mla{wq (E, H, nope + rope)  wkv_a (E, rank + rope)
               kv_norm (rank)  wkv_b (rank, H, nope + v)  out{w (H v, E)}}
    dense:     ffn{gate{w (E, F)} xform{w (E, F)} out{w (F, E)}}
    experts:   moe{router{w (E, N) b (N)}  experts{gate_up (G, E, 2 Fm)
               down (G, Fm, E)}  shared{gate{w} xform{w} out{w}}}
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
    gated_mlp,
    lm_head,
    norm,
)
from differential_transformer_replication_tpu.ops import kda as kda_ops
from differential_transformer_replication_tpu.ops import moe as moe_ops
from differential_transformer_replication_tpu.ops.mla import (
    absorb_queries,
    attend_latent,
    chunk_attention,
    latent_decode_attention,
)
from differential_transformer_replication_tpu.ops.norms import rms_norm
from differential_transformer_replication_tpu.ops.rope import (
    apply_rope_pairs_at,
    yarn_frequencies,
    yarn_mscale,
)
from differential_transformer_replication_tpu.ops.ssm import causal_conv

USES_ROPE = False  # embed, ffn, lm_head and norm are jamba's: the same blocks


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    E, H, d, K = cfg.n_embd, cfg.n_head, cfg.kda_head_dim, cfg.kda_conv
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
    for kind, mlp_kind, lk in zip(cfg.layer_kinds(), cfg.mlp_kinds(), keys):
        ks = jax.random.split(lk, 16)
        blk = {"ln1": {"w": ones(E)}, "ln2": {"w": ones(E)}}
        if kind == "kda":
            blk["kda"] = {
                "qkv": w(ks[0], E, 3 * H * d),
                "conv_w": (jax.random.uniform(ks[1], (K, 3 * H * d),
                                              minval=-1.0) / K ** 0.5
                           ).astype(dtype),
                "f_a": w(ks[2], E, d), "f_b": w(ks[3], d, H * d),
                "dt_bias": jnp.zeros((H * d,), dtype),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (H,), minval=1.0, maxval=16.0)).astype(dtype),
                "b": w(ks[5], E, H),
                "g_a": w(ks[6], E, d), "g_b": w(ks[7], d, H * d),
                "o_norm": ones(d), "out": w(ks[8], H * d, E),
            }
        else:
            blk["mla"] = {
                "wq": w(ks[0], E, H, nope + rope),
                "wkv_a": w(ks[1], E, rank + rope), "kv_norm": ones(rank),
                "wkv_b": w(ks[2], rank, H, nope + vd),
                "out": {"w": w(ks[3], H * vd, E)},
            }
        if mlp_kind == "dense":
            blk["ffn"] = mlp(ks[9:12], cfg.ffn_width)
        else:
            Fm, N = cfg.moe_hidden, cfg.num_experts
            blk["moe"] = {
                "router": {"w": w(ks[9], E, N), "b": jnp.zeros((N,), dtype)},
                "experts": {"gate_up": w(ks[10], hi - lo, E, 2 * Fm),
                            "down": w(ks[11], hi - lo, Fm, E)},
                "shared": mlp(ks[12:15], Fm),
            }
        blocks.append(blk)
    return {"tok_emb": w(keys[-2], cfg.vocab_size, E), "blocks": blocks,
            "ln_f": {"w": ones(E)},
            "lm_head": {"w": w(keys[-1], E, cfg.vocab_size)}}


# -- the KDA mixer -------------------------------------------------------------


def _l2_norm(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_inputs(h: jnp.ndarray, p: dict, cfg: ModelConfig,
                conv: jnp.ndarray, valid=None):
    """From the normed inputs ``h`` (B, L, E) and the convolution's
    window ``conv`` (B, K-1, 3 Hd): ``q``, ``k``, ``v``, the log-decay
    ``g`` (B, L, H, d) and ``beta`` (B, L, H), all float32, and the
    window after the chunk."""
    H, d = cfg.n_head, cfg.kda_head_dim
    f32 = jnp.float32
    with jax.named_scope("kda_conv"):
        c, conv = causal_conv(h @ p["qkv"].astype(h.dtype), p["conv_w"],
                              jnp.zeros((), f32), conv, valid)
        q, k, v = (a.reshape(a.shape[:2] + (H, d))
                   for a in jnp.split(jax.nn.silu(c), 3, axis=-1))
    q, k = _l2_norm(q) * d ** -0.5, _l2_norm(k)
    low = h @ p["f_a"].astype(h.dtype)
    g = jax.nn.softplus(
        jnp.dot(low, p["f_b"].astype(h.dtype), preferred_element_type=f32)
        + p["dt_bias"].astype(f32)).reshape(h.shape[:2] + (H, d))
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * g
    beta = jax.nn.sigmoid(jnp.dot(h, p["b"].astype(h.dtype),
                                  preferred_element_type=f32))
    return q, k, v, g, beta, conv


def _kda_out(o: jnp.ndarray, h: jnp.ndarray, p: dict,
             cfg: ModelConfig) -> jnp.ndarray:
    """``o`` (.., H, d) float32 -> the mixer's output (.., E): a head's
    RMSNorm, the sigmoid gate from ``h``, the output projection."""
    f32 = jnp.float32
    gate = jax.nn.sigmoid(jnp.dot(
        h @ p["g_a"].astype(h.dtype), p["g_b"].astype(h.dtype),
        preferred_element_type=f32))
    o = rms_norm(o, p["o_norm"].astype(f32), cfg.resolved_norm_eps)
    o = o.reshape(gate.shape) * gate
    return o.astype(h.dtype) @ p["out"].astype(h.dtype)


def kda_chunk(h: jnp.ndarray, p: dict, cfg: ModelConfig, conv: jnp.ndarray,
              state: jnp.ndarray, valid=None):
    """The mixer over a chunk ``h`` (B, L, E) of normed inputs that
    continues sequences in ``conv`` (B, K-1, 3 Hd), ``state`` (B, H, d,
    d): returns ``(out (B, L, E), conv, state)`` after the chunk. Zeros
    are a sequence's start; with ``valid`` the steps from ``valid`` on are
    padding and leave both where step ``valid`` put them."""
    q, k, v, g, beta, conv = _kda_inputs(h, p, cfg, conv, valid)
    with jax.named_scope("kda_chunk"):
        o, last = kda_ops.chunk_fwd(q, k, v, g, beta, state, valid)
    return _kda_out(o, h, p, cfg), conv, last.astype(state.dtype)


def kda_step(h: jnp.ndarray, p: dict, cfg: ModelConfig, conv: jnp.ndarray,
             state: jnp.ndarray, active: jnp.ndarray):
    """One token a slot of the decode pool: ``h`` (S, E); ``conv`` (S,
    K-1, 3 Hd) and ``state`` (S, H, d, d) are the pool's leaves, and a
    slot that is not ``active`` keeps every bit of both."""
    q, k, v, g, beta, moved = _kda_inputs(h[:, None], p, cfg, conv)
    conv = jnp.where(active[:, None, None], moved, conv)
    with jax.named_scope("kda_state"):
        o, state = kda_ops.state_update(
            state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], active)
    return _kda_out(o, h, p, cfg), conv, state


def kda_zero_state(cfg: ModelConfig, batch: int, compute_dtype=None):
    """``(conv, state)`` of ``batch`` sequences at their start."""
    H, d = cfg.n_head, cfg.kda_head_dim
    dt = jnp.dtype(compute_dtype or cfg.compute_dtype)
    return (jnp.zeros((batch, cfg.kda_conv - 1, 3 * H * d), dt),
            jnp.zeros((batch, H, d, d), jnp.float32))


# -- the MLA mixer -------------------------------------------------------------
# ONE mixer for this family and ``deepseek_v2`` (models/deepseek_v2.py).
# What differs follows from the layer's leaves and the configuration: a
# low-rank query where the leaves hold ``wq_a`` (else the full-rank
# ``wq``); the rotary part turned where the caller gives positions
# (``cfg.mla_rotary``: this family's MLA carries none); the softmax scale
# (:func:`mla_scale`). A ring of latents (a layer of kind ``"latent"``) has
# ONE chunk read and ONE decode read, whichever family keeps it (ops/mla.py;
# models/decode.py calls them): a chunk's blocks in the widened form
# (:func:`mla_chunk_attend`) and a step's live blocks in the absorbed form
# (:func:`mla_step_attend`). :func:`mla_attend`, the latents whole under a
# mask, is this family's full forward's and the tests' reference.


def _rotate(x: jnp.ndarray, pos, cfg: ModelConfig) -> jnp.ndarray:
    """The rotary part ``x`` (.., rope) at ``pos`` (broadcasting against
    ``x``'s leading axes), under the configuration's YaRN block."""
    freqs, mult = yarn_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta,
                                   cfg.yarn)
    return apply_rope_pairs_at(x, pos, freqs, mult)


def mla_scale(cfg: ModelConfig) -> Optional[float]:
    """What the scores are multiplied by: ``(nope + rope) ** -0.5 m^2``
    with YaRN's ``m = 0.1 mscale_all_dim ln(factor) + 1``; None without
    such a block (the reads then divide by ``sqrt(nope + rope)``)."""
    block = cfg.yarn
    if not block or not block["mscale_all_dim"]:
        return None
    m = yarn_mscale(block["factor"], block["mscale_all_dim"])
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def mla_queries(h: jnp.ndarray, p: dict, cfg: ModelConfig,
                pos=None) -> jnp.ndarray:
    """``h`` (.., E) -> the heads' queries (.., H, nope + rope): ``x W_q``,
    or through the low-rank ``RMSNorm(x W_qa) W_qb``; with ``pos``
    (broadcasting against ``h``'s leading axes) the last ``rope``
    dimensions of every head are turned there."""
    if "wq_a" in p:  # graftlint: disable=GL104 (a dict's keys are static)
        c = rms_norm(h @ p["wq_a"].astype(h.dtype),
                     p["q_norm"].astype(jnp.float32), cfg.resolved_norm_eps)
        q = jnp.einsum("...r,rhd->...hd", c, p["wq_b"].astype(h.dtype))
    else:
        q = jnp.einsum("...e,ehd->...hd", h, p["wq"].astype(h.dtype))
    if pos is None:
        return q
    nope = cfg.qk_nope_head_dim
    turned = _rotate(q[..., nope:], jnp.asarray(pos)[..., None], cfg)
    return jnp.concatenate([q[..., :nope], turned], axis=-1)


def mla_latent(h: jnp.ndarray, p: dict, cfg: ModelConfig,
               pos=None) -> jnp.ndarray:
    """``h`` (.., E) -> what the cache holds a position, (.., rank +
    rope): the normed latent ``c`` beside the shared key part ``k_r``,
    turned at ``pos`` where positions are given."""
    rank = cfg.kv_lora_rank
    kv = h @ p["wkv_a"].astype(h.dtype)
    c = rms_norm(kv[..., :rank], p["kv_norm"].astype(jnp.float32),
                 cfg.resolved_norm_eps)
    k_r = kv[..., rank:] if pos is None else _rotate(kv[..., rank:], pos, cfg)
    return jnp.concatenate([c.astype(h.dtype), k_r], axis=-1)


def mla_attend(h: jnp.ndarray, p: dict, cfg: ModelConfig,
               latent: jnp.ndarray, visible: jnp.ndarray) -> jnp.ndarray:
    """The queries of ``h`` (B, L, E) over ``latent`` (B, M, rank + rope)
    where ``visible`` says so, the latents whole in the absorbed form,
    through the output projection, with no position (this family's full
    forward over a whole sequence, and what the tests hold the two serving
    reads against; neither serving program calls it)."""
    q = mla_queries(h, p, cfg)
    heads = attend_latent(q, latent.astype(h.dtype), p["wkv_b"], visible,
                          mla_scale(cfg))
    return heads @ p["out"]["w"].astype(h.dtype)


def _scale(cfg: ModelConfig) -> float:
    return mla_scale(cfg) or (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_chunk_attend(h: jnp.ndarray, p: dict, cfg: ModelConfig,
                     latent: jnp.ndarray, pos) -> jnp.ndarray:
    """A chunk ``h`` (B, L, E) whose first token stands at ``pos``, over
    the ring ``latent`` (B, M, rank + rope) that holds the chunk's own
    latents: the WIDENED form, the ring in blocks as far as it is written
    (``ops/mla.py:chunk_attention``)."""
    at = pos + jnp.arange(h.shape[1]) if cfg.mla_rotary else None
    with jax.named_scope("mla_q"):
        q = mla_queries(h, p, cfg, at)
    with jax.named_scope("mla_attend"):
        heads = chunk_attention(q, latent, p["wkv_b"], pos, _scale(cfg))
    with jax.named_scope("mla_out"):
        return heads @ p["out"]["w"].astype(h.dtype)


def mla_step_attend(h: jnp.ndarray, p: dict, cfg: ModelConfig,
                    latent: jnp.ndarray, pos: jnp.ndarray,
                    live: jnp.ndarray) -> jnp.ndarray:
    """One token a slot, ``h`` (B, E) at positions ``pos`` (B,), over the
    pool's rings ``latent`` (B, 1, M, rank + rope) that hold the tokens'
    own latents: the ABSORBED form over each row's live blocks
    (``ops/mla.py:latent_decode_attention``); ``W_kvb`` is multiplied into
    the queries before the read and into the mixed latents after it."""
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    w = p["wkv_b"].astype(h.dtype)
    with jax.named_scope("mla_q"):
        q = mla_queries(h, p, cfg, pos if cfg.mla_rotary else None)
        qq = absorb_queries(q, w, cfg.qk_rope_head_dim)
    with jax.named_scope("mla_attend"):
        mixed = latent_decode_attention(qq, latent, pos, live, rank,
                                        _scale(cfg))
    with jax.named_scope("mla_out"):
        heads = jnp.einsum("bhr,rhv->bhv", mixed, w[..., nope:])
        return heads.reshape(h.shape[0], -1) @ p["out"]["w"].astype(h.dtype)


# -- the experts ---------------------------------------------------------------


def relu2_mlp(h: jnp.ndarray, p: dict) -> jnp.ndarray:
    """``W_down relu(W_up h)^2``, no gate and no bias."""
    u = jax.nn.relu(h @ p["up"]["w"].astype(h.dtype))
    return (u * u) @ p["down"]["w"].astype(h.dtype)


def moe_mlp(h: jnp.ndarray, p: dict, cfg: ModelConfig,
            live: Optional[jnp.ndarray] = None):
    """A layer of experts on the normed rows ``h`` (.., E), ``p`` the
    layer's ``moe`` leaves: ``(y (.., E), load (G,) int32)``, the held
    experts' weighted sum beside the shared expert's output, and the
    assignments that fell on each held expert from the rows that are
    ``live`` (.., bool; None = all). The ``afmoe`` family's layer is this
    one too (models/afmoe.py), and ``deepseek_v2``'s: a router without a
    correction bias among its leaves is the softmax router limited to
    groups (``ops/moe.py:route_grouped``), and the load then comes as
    ``(load, reached)``, ``reached`` () int32 the live rows that kept a
    group this share holds. The ``nemotron_h`` family's is this one as
    well: where the leaves hold ``latent_in`` and ``latent_out`` the
    routed experts read ``h W_in`` and their weighted sum leaves through
    ``W_out`` (the router and the shared expert read ``h``), and a shared
    expert without a ``gate`` leaf is the ungated ``W_down relu(W_up
    h)^2`` (:func:`relu2_mlp`), as the routed ones are where their leaves
    hold ``up`` (``ops/moe.py:experts``). The ``lfm2`` family's layer has
    NO shared expert: where the leaves hold no ``shared`` nothing is added
    to the routed experts' sum (and its router adds ``cfg.router_eps`` to
    the chosen scores' sum, 0 for the others)."""
    with jax.named_scope("moe"):
        rows = h.reshape(-1, h.shape[-1])
        flat = None if live is None else live.reshape(-1)
        lo, hi = cfg.held_expert_range
        with jax.named_scope("moe_router"):
            if "b" in p["router"]:  # graftlint: disable=GL104 (static keys)
                kept = None
                chosen, weights = moe_ops.route(
                    rows, p["router"]["w"], p["router"]["b"],
                    cfg.experts_per_token, cfg.routed_scaling,
                    cfg.router_eps)
            else:
                chosen, weights, kept = moe_ops.route_grouped(
                    rows, p["router"]["w"], cfg.experts_per_token,
                    cfg.routed_scaling, cfg.n_group, cfg.topk_group)
        into = rows
        if "latent_in" in p:  # graftlint: disable=GL104 (static keys)
            with jax.named_scope("moe_latent"):
                into = rows @ p["latent_in"].astype(rows.dtype)
        with jax.named_scope("moe_experts"):
            y, load = moe_ops.experts(into, chosen, weights, p["experts"],
                                      lo, flat)
        if "latent_out" in p:  # graftlint: disable=GL104 (static keys)
            with jax.named_scope("moe_latent"):
                y = y @ p["latent_out"].astype(rows.dtype)
        if "shared" in p:  # graftlint: disable=GL104 (static keys)
            with jax.named_scope("moe_shared"):
                shared = gated_mlp if "gate" in p["shared"] else relu2_mlp
                y = y + shared(rows, p["shared"])
        if kept is not None:
            size = cfg.expert_group_size
            mine = jnp.any(kept[:, lo // size:hi // size], axis=-1)
            load = (load, jnp.sum(mine if flat is None else mine & flat,
                                  dtype=jnp.int32))
        return y.reshape(h.shape), load


def moe(x: jnp.ndarray, blk: dict, cfg: ModelConfig,
        live: Optional[jnp.ndarray] = None):
    """The block's second half on the residual ``x`` (.., E) with a
    layer of experts: returns ``(x + y, load)`` (:func:`moe_mlp`)."""
    with jax.named_scope("ffn_norm"):
        h = norm(x, blk["ln2"], cfg)
    y, load = moe_mlp(h, blk["moe"], cfg, live)
    with jax.named_scope("moe"):
        return x + y, load


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
    if "kda" in blk:  # graftlint: disable=GL104 (a dict's keys are static)
        with jax.named_scope("kda"):
            h = norm(x, blk["ln1"], cfg)
            a, _, _ = kda_chunk(h, blk["kda"], cfg,
                                *kda_zero_state(cfg, x.shape[0], x.dtype))
    else:
        with jax.named_scope("mla"):
            h = norm(x, blk["ln1"], cfg)
            T = x.shape[1]
            with jax.named_scope("mla_attend"):
                a = mla_attend(h, blk["mla"], cfg,
                               mla_latent(h, blk["mla"], cfg),
                               jnp.tril(jnp.ones((T, T), bool)))
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
            "the kimi_linear family is served, not trained: no loss is "
            "defined for it (the grouped expert product and the chunked "
            "delta rule have no tested backward pass)"
        )
    x = embed(params, idx, cfg)
    for li, blk in enumerate(params["blocks"], 1):
        x = block_forward(x, blk, li, cfg, None, None, None, None, mesh)
    with jax.named_scope("lm_head"):
        return lm_head(params, x, cfg), None

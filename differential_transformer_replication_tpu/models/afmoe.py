"""The ``afmoe`` family: sliding-window and full attention layers in one
stack, a gated attention output, sandwich norms, and routed experts in
every layer but the first few.

A decoder-only stack (Arcee's Trinity, ``model_type: afmoe``, as
``arcee-ai/Trinity-Large-Preview``'s published ``config.json`` sizes it)
whose layers differ in two ways (``ModelConfig.layer_kinds``,
``mlp_kinds``): a layer's attention is ``"window"`` (rotary positions, the
last ``sliding_window`` positions visible) or ``"full"`` (no position
information of any kind, every earlier position visible), by the published
``layer_types`` list; and its MLP is dense (the first
``first_dense_layers``) or a layer of routed experts with one shared
expert. RMSNorm with a learned scale everywhere, FOUR a block (before and
after each half), no bias in any projection, the embedding scaled by
``sqrt(n_embd)``, an untied head.

  x = E[token] sqrt(n_embd)
  layer:     x = x + N2(attn(N1(x)));  x = x + N4(mlp(N3(x)))
  attention: q (H heads of d), k, v (kv_heads, each shared by H / kv_heads
             query heads), g = h W_g (H d)
             q_h = RMSNorm_d(q_h), k_h = RMSNorm_d(k_h)   (one scale for q,
             one for k, shared by the heads)
             window: q, k rotated at their absolute position (rope_theta,
                     dimension i with i + d/2); query i sees key j iff
                     j <= i and i - j < sliding_window
             full:   nothing rotated; query i sees every j <= i
             o = softmax(q k^T / sqrt(d)) v;  y = W_o [o * sigmoid(g)]
  dense:     W_out(silu(W_gate h) * W_xform h)
  experts:   kimi_linear's layer to the letter (``kimi_linear.moe_mlp``):
             s = sigmoid(h W_r);  the experts_per_token largest of s + b;
             w_i = routed_scaling s_i / sum_chosen s
             y = sum_{i chosen and HELD} w_i E_i(h) + E_shared(h)

``held_experts`` is an expert-parallel share, as in ``kimi_linear``. A
sequence's cache is a K/V ring a layer, ``block_size`` long in a full
layer and ``sliding_ring`` long in a sliding one (models/decode.py). The
family is served, not trained.

The parameter tree (weights stored ``(in, out)``, every leaf in
``param_dtype``):

  tok_emb (V, E)
  blocks[l]: ln1{w}  ln1_post{w}  ln2{w}  ln2_post{w}
    attn{wq (E, H, d)  wk, wv (E, KV, d)  wg (E, H d)  q_norm (d)
         k_norm (d)  out{w (H d, E)}}
    dense:   ffn{gate{w (E, F)} xform{w (E, F)} out{w (F, E)}}
    experts: moe{router{w (E, N) b (N)}  experts{gate_up (G, E, 2 Fm)
             down (G, Fm, E)}  shared{gate{w} xform{w} out{w}}}
  ln_f{w}  lm_head{w (E, V)}
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
    gated_mlp,
    lm_head,
    norm,
    qkv,
)
from differential_transformer_replication_tpu.models.kimi_linear import moe_mlp
from differential_transformer_replication_tpu.ops.norms import rms_norm
from differential_transformer_replication_tpu.ops.rope import apply_rope_half

USES_ROPE = False  # no table: the sliding layers rotate from the positions


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    E, H, KV, d = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_size
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
            "ln1": {"w": ones(E)}, "ln1_post": {"w": ones(E)},
            "ln2": {"w": ones(E)}, "ln2_post": {"w": ones(E)},
            "attn": {
                "wq": w(ks[0], E, H, d), "wk": w(ks[1], E, KV, d),
                "wv": w(ks[2], E, KV, d), "wg": w(ks[3], E, H * d),
                "q_norm": ones(d), "k_norm": ones(d),
                "out": {"w": w(ks[4], H * d, E)},
            },
        }
        if mlp_kind == "dense":
            blk["ffn"] = mlp(ks[5:8], cfg.ffn_width)
        else:
            Fm, N = cfg.moe_hidden, cfg.num_experts
            blk["moe"] = {
                "router": {"w": w(ks[5], E, N), "b": jnp.zeros((N,), dtype)},
                "experts": {"gate_up": w(ks[6], hi - lo, E, 2 * Fm),
                            "down": w(ks[7], hi - lo, Fm, E)},
                "shared": mlp(ks[8:11], Fm),
            }
        blocks.append(blk)
    return {"tok_emb": w(keys[-2], cfg.vocab_size, E), "blocks": blocks,
            "ln_f": {"w": ones(E)},
            "lm_head": {"w": w(keys[-1], E, cfg.vocab_size)}}


def embed(params: dict, idx: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The token table scaled by ``sqrt(n_embd)`` (``mup_enabled``); the
    positions enter in the sliding layers alone."""
    with jax.named_scope("embed"):
        x = params["tok_emb"][idx].astype(jnp.dtype(cfg.compute_dtype))
        return x * jnp.asarray(math.sqrt(cfg.n_embd), x.dtype)


# -- the attention mixer -------------------------------------------------------


def qkvg(h: jnp.ndarray, p: dict, cfg: ModelConfig, pos: jnp.ndarray,
         kind: str):
    """``h`` (.., E) -> q (.., H, d), k and v (.., KV, d), each head of q
    and k RMS-normed and, in a ``"window"`` layer, rotated at ``pos``
    (which broadcasts against ``h``'s leading axes), and the output gate's
    input g (.., H d). That the ``"full"`` layers carry no position is THIS
    family's choice, not the kind's: the lfm2 family's full layers norm a
    head and then rotate it (``models/lfm2.py:normed_rotated_qkv``)."""
    q, k, v = qkv(h, p)
    f32, eps = jnp.float32, cfg.resolved_norm_eps
    q = rms_norm(q, p["q_norm"].astype(f32), eps)
    k = rms_norm(k, p["k_norm"].astype(f32), eps)
    if kind == "window":
        at = jnp.asarray(pos)[..., None]  # over the head axis
        q = apply_rope_half(q, at, cfg.rope_theta)
        k = apply_rope_half(k, at, cfg.rope_theta)
    return q, k, v, h @ p["wg"].astype(h.dtype)


def gate_out(o: jnp.ndarray, g: jnp.ndarray, p: dict) -> jnp.ndarray:
    """The joined heads ``o`` (.., H d) gated elementwise by
    ``sigmoid(g)``, through the output projection."""
    with jax.named_scope("attn_gate"):
        o = o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(o.dtype)
    return o @ p["out"]["w"].astype(o.dtype)


def ring_qkv(h: jnp.ndarray, p: dict, cfg: ModelConfig, pos, kind: str):
    """:func:`qkvg` for the ring chunk and step of models/decode.py:
    ``pos`` is a chunk's first position (``h`` (B, L, E)) or a position a
    row (``h`` (B, E))."""
    at = pos + jnp.arange(h.shape[1]) if h.ndim == 3 else pos
    return qkvg(h, p, cfg, at, kind)


def ring_out(o: jnp.ndarray, g: jnp.ndarray, blk: dict,
             cfg: ModelConfig) -> jnp.ndarray:
    """The ring's read ``o`` gated, projected and normed again."""
    with jax.named_scope("attn"):
        a = gate_out(o, g, blk["attn"])
    with jax.named_scope("attn_norm"):
        return norm(a, blk["ln1_post"], cfg)


def band(T: int, window: int) -> jnp.ndarray:
    """(T, T) bool: query i sees key j iff ``j <= i`` and ``i - j <
    window``."""
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    return (j <= i) & (i - j < window)


# -- the MLP half ----------------------------------------------------------------


def mlp(x: jnp.ndarray, blk: dict, cfg: ModelConfig,
        live: Optional[jnp.ndarray] = None):
    """The block's second half on the residual ``x`` (.., E), either kind
    between its two norms: ``(x + N4(mlp(N3(x))), the held experts' load
    (G,) int32 or None)``."""
    with jax.named_scope("ffn_norm"):
        h = norm(x, blk["ln2"], cfg)
    if "moe" in blk:  # graftlint: disable=GL104 (a dict's keys are static)
        y, load = moe_mlp(h, blk["moe"], cfg, live)
    else:
        with jax.named_scope("ffn"):
            y, load = gated_mlp(h, blk["ffn"]), None
    with jax.named_scope("ffn_norm"):
        return x + norm(y, blk["ln2_post"], cfg), load


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
    uniform per-family signature (models/registry.py); ``layer_idx``
    (from 1) picks the layer's attention kind."""
    del cos, sin, mask, rng, mesh
    kind = cfg.layer_kinds()[layer_idx - 1]
    T = x.shape[1]
    with jax.named_scope("attn_norm"):
        h = norm(x, blk["ln1"], cfg)
    with jax.named_scope("attn"):
        q, k, v, g = qkvg(h, blk["attn"], cfg, jnp.arange(T), kind)
        with jax.named_scope("attn_" + kind):
            o = attend(q, k.swapaxes(1, 2), v.swapaxes(1, 2),
                       band(T, cfg.ring_window(kind) if kind == "window"
                            else T))
        a = gate_out(o, g, blk["attn"])
    with jax.named_scope("attn_norm"):
        x = x + norm(a, blk["ln1_post"], cfg)
    return mlp(x, blk, cfg)[0]


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
            "the afmoe family is served, not trained: no loss is defined "
            "for it (the grouped expert product has no tested backward "
            "pass)"
        )
    x = embed(params, idx, cfg)
    for li, blk in enumerate(params["blocks"], 1):
        x = block_forward(x, blk, li, cfg, None, None, None, None, mesh)
    with jax.named_scope("lm_head"):
        return lm_head(params, x, cfg), None

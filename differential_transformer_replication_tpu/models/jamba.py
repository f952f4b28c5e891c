"""The ``jamba`` family: Mamba-1 mixers beside a few attention layers.

A decoder-only stack with layers of two kinds (AI21's Jamba, as
``transformers``' ``JambaConfig`` describes it with ``num_experts`` 1): layer
``i`` mixes tokens by grouped-query attention if ``i % attn_layer_period ==
attn_layer_offset`` (``ModelConfig.layer_kinds``), else by a Mamba block; every
layer's feed-forward is the dense gated MLP. RMSNorm everywhere, no bias in
any projection but the Mamba ``dt`` projection and convolution, no position
information of any kind, and the head may reuse the token table.

  layer:      x = x + mixer(RMSNorm(x));  x = x + W_down(silu(W_gate h) * W_up h)
  attention:  q (H heads), k, v (kv_heads, each shared by H / kv_heads query
              heads), causal softmax(q k^T / sqrt(d)) v, W_o
  mamba:      [u, z] = x W_in;  u = silu(conv(u) + b_conv)   (causal, depthwise)
              [r, B, C] = u W_x;  r, B, C = RMSNorm_dt(r), RMSNorm_B(B), RMSNorm_C(C)
              delta = softplus(r W_dt + b_dt);  A = -exp(A_log)
              h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) B_t;  y_t = h_t . C_t + D u_t
              out = (y * silu(z)) W_out

The RMSNorms on ``dt``, ``B`` and ``C`` are Jamba's own step; plain Mamba
has none. A sequence's state is ``h`` (N, d_inner) and the last
``mamba_d_conv - 1`` inputs of the convolution, a Mamba layer: what
models/decode.py keeps a slot in place of a K/V ring. The recurrence runs in
float32 whatever ``compute_dtype`` (ops/ssm.py).

The parameter tree (weights stored ``(in, out)``, every leaf in
``param_dtype``):

  tok_emb (V, E)
  blocks[l]: ln1{w}  ln2{w}  ffn{gate{w (E, F)} xform{w (E, F)} out{w (F, E)}}
    attention layer: attn{wq (E, H, d)  wk, wv (E, KV, d)  out{w (H d, E)}}
    mamba layer:     mamba{in_proj (E, 2 Di)  conv_w (K, Di)  conv_b (Di)
                     x_proj (Di, R + 2 N)  dt_norm (R)  b_norm, c_norm (N)
                     dt_proj{w (R, Di), b (Di)}  A_log (Di, N)  D (Di)
                     out_proj (Di, E)}
  ln_f{w}  [lm_head{w (E, V)} unless tie_embeddings]
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models import common
from differential_transformer_replication_tpu.ops.losses import (
    fused_linear_cross_entropy,
)
from differential_transformer_replication_tpu.ops.norms import rms_norm
from differential_transformer_replication_tpu.ops.ssm import (
    causal_conv,
    selective_scan,
    state_update,
)
from differential_transformer_replication_tpu.ops.streams import NEG_INF

USES_ROPE = False


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    E, H, KV, d = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_size
    F, Di, N = cfg.ffn_width, cfg.d_inner, cfg.mamba_d_state
    K, R = cfg.mamba_d_conv, cfg.dt_rank
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, cfg.n_layer + 2)
    w = lambda k, *shape: common.normal_init(k, shape).astype(dtype)  # noqa: E731
    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    blocks = []
    for kind, lk in zip(cfg.layer_kinds(), keys):
        ks = jax.random.split(lk, 9)
        blk = {
            "ln1": {"w": ones(E)},
            "ln2": {"w": ones(E)},
            "ffn": {"gate": {"w": w(ks[0], E, F)},
                    "xform": {"w": w(ks[1], E, F)},
                    "out": {"w": w(ks[2], F, E)}},
        }
        if kind == "attention":
            blk["attn"] = {
                "wq": w(ks[3], E, H, d), "wk": w(ks[4], E, KV, d),
                "wv": w(ks[5], E, KV, d), "out": {"w": w(ks[6], H * d, E)},
            }
        else:
            # Mamba's own start: A = -(1..N) a channel, D = 1, and a dt
            # bias whose softplus is log-uniform in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(ks[7], (Di,)) * (
                math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            blk["mamba"] = {
                "in_proj": w(ks[3], E, 2 * Di),
                "conv_w": (jax.random.uniform(ks[4], (K, Di), minval=-1.0)
                           / math.sqrt(K)).astype(dtype),
                "conv_b": jnp.zeros((Di,), dtype),
                "x_proj": w(ks[5], Di, R + 2 * N),
                "dt_norm": ones(R), "b_norm": ones(N), "c_norm": ones(N),
                "dt_proj": {
                    "w": (jax.random.uniform(ks[6], (R, Di), minval=-1.0)
                          / math.sqrt(R)).astype(dtype),
                    "b": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                },
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                    (Di, N)).astype(dtype),
                "D": ones(Di),
                "out_proj": w(ks[8], Di, E),
            }
        blocks.append(blk)
    params = {"tok_emb": w(keys[-2], cfg.vocab_size, E), "blocks": blocks,
              "ln_f": {"w": ones(E)}}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": w(keys[-1], E, cfg.vocab_size)}
    return params


def norm(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    return rms_norm(x, p["w"].astype(jnp.float32), cfg.resolved_norm_eps)


def ffn(x: jnp.ndarray, blk: dict, cfg: ModelConfig) -> jnp.ndarray:
    """The block's second half on the residual ``x``: RMSNorm, the gated
    MLP (no bias), the residual add."""
    with jax.named_scope("ffn_norm"):
        h = norm(x, blk["ln2"], cfg)
    with jax.named_scope("ffn"):
        return x + gated_mlp(h, blk["ffn"])


def gated_mlp(h: jnp.ndarray, p: dict) -> jnp.ndarray:
    """``W_out(silu(W_gate h) * W_xform h)``, no bias."""
    g = jax.nn.silu(h @ p["gate"]["w"].astype(h.dtype))
    return (g * (h @ p["xform"]["w"].astype(h.dtype))) @ p["out"][
        "w"].astype(h.dtype)


# -- the Mamba mixer -----------------------------------------------------------


def _mixer_inputs(u: jnp.ndarray, p: dict, cfg: ModelConfig):
    """From the convolved, activated ``u`` (.., Di): ``delta`` (.., Di)
    float32, ``B`` and ``C`` (.., N) float32 (each RMS-normed with its own
    scale) and ``A`` (Di, N) float32."""
    R, N = cfg.dt_rank, cfg.mamba_d_state
    eps = cfg.resolved_norm_eps
    f32 = jnp.float32
    rbc = u @ p["x_proj"].astype(u.dtype)
    r = rms_norm(rbc[..., :R], p["dt_norm"].astype(f32), eps)
    Bm = rms_norm(rbc[..., R:R + N].astype(f32), p["b_norm"].astype(f32), eps)
    Cm = rms_norm(rbc[..., R + N:].astype(f32), p["c_norm"].astype(f32), eps)
    delta = jax.nn.softplus(
        jnp.dot(r, p["dt_proj"]["w"].astype(r.dtype),
                preferred_element_type=f32)
        + p["dt_proj"]["b"].astype(f32))
    return delta, Bm, Cm, -jnp.exp(p["A_log"].astype(f32))


def _gate_out(y: jnp.ndarray, z: jnp.ndarray, p: dict) -> jnp.ndarray:
    g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return g @ p["out_proj"].astype(z.dtype)


def mixer_chunk(h: jnp.ndarray, p: dict, cfg: ModelConfig,
                conv: jnp.ndarray, ssm: jnp.ndarray, valid=None):
    """The mixer over a chunk ``h`` (B, L, E) of normed inputs that
    continues sequences in the state ``conv`` (B, K-1, Di), ``ssm``
    (B, N, Di): returns ``(out (B, L, E), conv, ssm)`` after the chunk.
    Zeros are a sequence's start. With ``valid`` (a runtime scalar) the
    steps from ``valid`` on are padding: their ``delta`` is zero, which
    leaves the recurrence where step ``valid`` put it (``exp(0 A) = 1``,
    ``0 u B = 0``), and the states returned are those after ``valid``
    steps; ``out`` past it is not a sequence's."""
    Di = cfg.d_inner
    uz = h @ p["in_proj"].astype(h.dtype)
    u, z = uz[..., :Di], uz[..., Di:]
    with jax.named_scope("ssm_conv"):
        c, conv = causal_conv(u, p["conv_w"], p["conv_b"], conv, valid)
        u = jax.nn.silu(c).astype(h.dtype)
    delta, Bm, Cm, A = _mixer_inputs(u, p, cfg)
    if valid is not None:
        real = jnp.arange(h.shape[1])[None, :, None] < valid
        delta = jnp.where(real, delta, jnp.zeros((), delta.dtype))
    with jax.named_scope("ssm_scan"):
        y, last = selective_scan(u, delta, A, Bm, Cm, p["D"], ssm,
                                 cfg.ssm_impl)
    return _gate_out(y, z, p), conv, last.astype(ssm.dtype)


def mixer_step(h: jnp.ndarray, p: dict, cfg: ModelConfig,
               conv: jnp.ndarray, ssm: jnp.ndarray, active: jnp.ndarray):
    """One token a slot of the decode pool: ``h`` (S, E); ``conv``
    (S, K-1, Di) and ``ssm`` (S, N, Di) are the pool's leaves, and a slot
    that is not ``active`` keeps every bit of both."""
    Di = cfg.d_inner
    uz = h @ p["in_proj"].astype(h.dtype)
    u, z = uz[..., :Di], uz[..., Di:]
    with jax.named_scope("ssm_conv"):
        c, moved = causal_conv(u[:, None], p["conv_w"], p["conv_b"], conv)
        u = jax.nn.silu(c[:, 0]).astype(h.dtype)
        conv = jnp.where(active[:, None, None], moved, conv)
    delta, Bm, Cm, A = _mixer_inputs(u, p, cfg)
    with jax.named_scope("ssm_state"):
        y, ssm = state_update(ssm, u, delta, A, Bm, Cm, p["D"], active,
                              cfg.ssm_impl)
    return _gate_out(y, z, p), conv, ssm


def zero_state(cfg: ModelConfig, batch: int, compute_dtype=None):
    """``(conv, ssm)`` of ``batch`` sequences at their start."""
    dt = jnp.dtype(compute_dtype or cfg.compute_dtype)
    return (jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.d_inner), dt),
            jnp.zeros((batch, cfg.mamba_d_state, cfg.d_inner),
                      jnp.dtype(cfg.ssm_state_dtype)))


# -- grouped-query attention ---------------------------------------------------


def qkv(h: jnp.ndarray, p: dict):
    """``h`` (.., E) -> q (.., H, d), k and v (.., KV, d)."""
    return tuple(jnp.einsum("...e,ehd->...hd", h, p[n].astype(h.dtype))
                 for n in ("wq", "wk", "wv"))


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           visible: jnp.ndarray) -> jnp.ndarray:
    """``q`` (B, L, H, d) over keys and values (B, KV, M, d) where
    ``visible`` (L, M) or (B, L, M) says so; every K/V head serves
    H / KV query heads. Returns (B, L, H * d); the softmax is float32."""
    B, L, H, d = q.shape
    KV = k.shape[1]
    qg = q.reshape(B, L, KV, H // KV, d)
    scores = jnp.einsum("blkgd,bkmd->bkglm", qg, k).astype(
        jnp.float32) / math.sqrt(d)
    vis = visible if visible.ndim == 3 else visible[None]
    probs = jax.nn.softmax(
        jnp.where(vis[:, None, None], scores, NEG_INF), axis=-1)
    out = jnp.einsum("bkglm,bkmd->blkgd", probs.astype(q.dtype), v)
    return out.reshape(B, L, H * d)


def ring_qkv(h: jnp.ndarray, p: dict, cfg: ModelConfig, pos, kind: str):
    """What the ring chunk and step of models/decode.py take from a
    family: q, k, v and what its ``ring_out`` needs besides (here nothing
    is normed or rotated, whatever the position and the kind)."""
    del cfg, pos, kind
    return (*qkv(h, p), None)


def ring_out(o: jnp.ndarray, _, blk: dict, cfg: ModelConfig) -> jnp.ndarray:
    """The joined heads ``o`` through the output projection."""
    with jax.named_scope("attn"):
        return o @ blk["attn"]["out"]["w"].astype(o.dtype)


def _attn_full(h: jnp.ndarray, p: dict) -> jnp.ndarray:
    T = h.shape[1]
    q, k, v = qkv(h, p)
    causal = jnp.tril(jnp.ones((T, T), bool))
    out = attend(q, k.swapaxes(1, 2), v.swapaxes(1, 2), causal)
    return out @ p["out"]["w"].astype(h.dtype)


# -- the model -----------------------------------------------------------------


def embed(params: dict, idx: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The token table alone: the family has no position information."""
    with jax.named_scope("embed"):
        return params["tok_emb"][idx].astype(jnp.dtype(cfg.compute_dtype))


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
    uniform per-family signature (models/registry.py). The layer's kind is
    read off its leaves; ``layer_idx``, the tables, ``mask``, ``rng`` and
    ``mesh`` go unused (no schedule, no positions, no dropout)."""
    del layer_idx, cos, sin, mask, rng, mesh
    if "mamba" in blk:  # graftlint: disable=GL104 (a dict's keys are static)
        with jax.named_scope("ssm"):
            h = norm(x, blk["ln1"], cfg)
            a, _, _ = mixer_chunk(h, blk["mamba"], cfg,
                                  *zero_state(cfg, x.shape[0], x.dtype))
    else:
        with jax.named_scope("attn_norm"):
            h = norm(x, blk["ln1"], cfg)
        with jax.named_scope("attn"):
            a = _attn_full(h, blk["attn"])
    return ffn(x + a, blk, cfg)


def head_weight(params: dict, cfg: ModelConfig) -> jnp.ndarray:
    """The head's (E, V) matrix: its own leaf, or the token table's
    transpose where the embeddings are tied."""
    if cfg.tie_embeddings:
        return params["tok_emb"].T
    return params["lm_head"]["w"]


def lm_head(params: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Final RMSNorm, then float32 logits: the product's operands are in
    the compute dtype, its sums and its result are not rounded to it (a
    bfloat16 logit of 4 is 0.03 coarse, a tenth of what decides a greedy
    token among 65,536)."""
    x = norm(x, params["ln_f"], cfg)
    if cfg.tie_embeddings:
        return jnp.einsum("...e,ve->...v", x,
                          params["tok_emb"].astype(x.dtype),
                          preferred_element_type=jnp.float32)
    return jnp.dot(x, params["lm_head"]["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


def forward(
    params: dict,
    idx: jnp.ndarray,
    cfg: ModelConfig,
    targets: Optional[jnp.ndarray] = None,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> Tuple[Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """(B, T) int tokens -> (logits (B, T, V), loss or None); with
    ``loss_chunk`` and targets ``(None, loss)``, as the other families."""
    del rng
    if targets is not None and cfg.ssm_impl == "pallas":
        raise ValueError(
            "ssm_impl='pallas' is forward only (ops/ssm.py: ssm_scan_fwd has "
            "no backward kernel); train the jamba family with ssm_impl='xla'"
        )
    x = embed(params, idx, cfg)
    for li, blk in enumerate(params["blocks"], 1):
        fn = block_forward
        if cfg.remat:
            fn = common.remat_block(fn, cfg)
        x = fn(x, blk, li, cfg, None, None, None, None, mesh)
    if targets is None:
        with jax.named_scope("lm_head"):
            return lm_head(params, x, cfg), None
    with jax.named_scope("lm_head_loss"):
        if cfg.loss_chunk:
            return None, fused_linear_cross_entropy(
                norm(x, params["ln_f"], cfg), head_weight(params, cfg),
                None, targets, cfg.loss_chunk)
        logits = lm_head(params, x, cfg)
        return logits, common.cross_entropy_loss(logits, targets)

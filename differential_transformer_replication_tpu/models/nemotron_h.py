"""The ``nemotron_h`` family: Mamba-2 mixers, a few attention layers and
expert feed-forward layers, each layer ONE of the three.

A decoder-only stack (NVIDIA's Nemotron-H / Nemotron-3, ``model_type:
nemotron_h``, as the published ``config.json`` sizes it) whose
``hybrid_override_pattern`` names every layer ``M`` (a Mamba-2 mixer), ``*``
(grouped-query attention) or ``E`` (routed experts beside a shared one): a
layer is one norm, one of the three and the residual add, so a mixer layer
has no feed-forward part and an ``E`` layer no mixer and no cache. RMSNorm
everywhere, no bias but the convolution's, no position information of any
kind, an untied head.

  layer:  x = x + F(RMSNorm(x)),  F by the pattern
  M:      [z ; xBC ; dt] = h W_in;  xBC = silu(conv(xBC) + b)  (causal, depthwise,
          over x, B and C together);  [x ; B ; C] = xBC  (B, C: n_groups x N)
          dt = softplus(dt + dt_bias);  A_p = -exp(A_log_p)  (a scalar a head p)
          H_t,p = exp(dt_t,p A_p) H_t-1,p + dt_t,p x_t,p (x) B_t,g(p)
          y_t,p = H_t,p C_t,g(p) + D_p x_t,p                 (ops/ssd.py)
          y = y * silu(z);  y = y / rms(y over a group's channels) * w;  y W_out
  *:      q (H heads), k, v (kv_heads), causal softmax(q k^T / sqrt(d)) v, W_o
          (``models/jamba.py``'s attention: no rotation)
  E:      s = sigmoid(h W_r);  the experts_per_token largest of s + b;
          w_i = routed_scaling s_i / sum_chosen s;  u = h W_in_latent
          E_i(u) = W_down,i relu(W_up,i u)^2                 (ungated, in the latent)
          y = (sum_{i chosen and HELD} w_i E_i(u)) W_out_latent + E_shared(h)
          E_shared(h) = W_down relu(W_up h)^2                (on the hidden state)

The expert layer is ``models/kimi_linear.py:moe_mlp`` (the latent
projections, the ungated activation and the shared expert's form follow
from the leaves, which ``cfg.mlp_act`` names); ``held_experts`` is an
expert-parallel share as there. A sequence's state is a Mamba-2 layer's
``H`` (N, heads x P) float32 with the last ``mamba_d_conv - 1`` inputs of
the convolution, and an attention layer's K/V ring: what models/decode.py
keeps a slot. The published multi-token-prediction module is left out
(``config.py`` refuses its keys under that reason). Served, not trained.

The parameter tree (weights stored ``(in, out)``, every leaf in
``param_dtype``; Di = heads x P, Dc = Di + 2 n_groups N, Lz the latent):

  tok_emb (V, E)
  blocks[l]:
    M: ln1{w}  mamba2{in_proj (E, Di + Dc + heads)  conv_w (K, Dc)  conv_b (Dc)
       dt_bias, A_log, D (heads)  norm (Di)  out_proj (Di, E)}
    *: ln1{w}  attn{wq (E, H, d)  wk, wv (E, KV, d)  out{w (H d, E)}}
    E: ln2{w}  moe{router{w (E, N) b (N)}  latent_in (E, Lz)  latent_out (Lz, E)
       experts{up (G, Lz, Fm)  down (G, Fm, Lz)}  shared{up{w (E, Fs)} down{w (Fs, E)}}}
       (``mlp_act: relu2``, the published activation: no gate, so ``up`` and
       not the other families' ``gate_up``)
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
    _attn_full,
    embed,
    lm_head,
    norm,
)
from differential_transformer_replication_tpu.models.kimi_linear import moe
from differential_transformer_replication_tpu.ops import ssd
from differential_transformer_replication_tpu.ops.ssm import causal_conv

USES_ROPE = False  # embed, lm_head, norm and the attention are jamba's


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    E, H, KV, d = cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_size
    Hm, Di, Dc = cfg.mamba_num_heads, cfg.ssd_inner, cfg.ssd_conv_channels
    K, Lz = cfg.mamba_d_conv, cfg.moe_latent_size or cfg.n_embd
    lo, hi = cfg.held_expert_range
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, cfg.n_layer + 2)
    w = lambda k, *shape: common.normal_init(k, shape).astype(dtype)  # noqa: E731
    ones = lambda n: jnp.ones((n,), dtype)  # noqa: E731
    blocks = []
    for letter, lk in zip(cfg.hybrid_override_pattern, keys):
        ks = jax.random.split(lk, 8)
        if letter == "M":
            # Mamba-2's own start: A = -(1..16) a head, D = 1, and a dt
            # bias whose softplus is log-uniform in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(ks[3], (Hm,)) * (
                math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            blk = {"ln1": {"w": ones(E)}, "mamba2": {
                "in_proj": w(ks[0], E, Di + Dc + Hm),
                "conv_w": (jax.random.uniform(ks[1], (K, Dc), minval=-1.0)
                           / math.sqrt(K)).astype(dtype),
                "conv_b": jnp.zeros((Dc,), dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (Hm,), minval=1.0, maxval=16.0)).astype(dtype),
                "D": ones(Hm), "norm": ones(Di),
                "out_proj": w(ks[2], Di, E),
            }}
        elif letter == "*":
            blk = {"ln1": {"w": ones(E)}, "attn": {
                "wq": w(ks[0], E, H, d), "wk": w(ks[1], E, KV, d),
                "wv": w(ks[2], E, KV, d), "out": {"w": w(ks[3], H * d, E)},
            }}
        else:
            Fm, Fs, N = cfg.moe_hidden, cfg.moe_shared_hidden, cfg.num_experts
            blk = {"ln2": {"w": ones(E)}, "moe": {
                "router": {"w": w(ks[0], E, N), "b": jnp.zeros((N,), dtype)},
                "experts": {"up": w(ks[1], hi - lo, Lz, Fm),
                            "down": w(ks[2], hi - lo, Fm, Lz)},
                "shared": {"up": {"w": w(ks[3], E, Fs)},
                           "down": {"w": w(ks[4], Fs, E)}},
            }}
            if cfg.moe_latent_size:
                blk["moe"]["latent_in"] = w(ks[5], E, Lz)
                blk["moe"]["latent_out"] = w(ks[6], Lz, E)
        blocks.append(blk)
    return {"tok_emb": w(keys[-2], cfg.vocab_size, E), "blocks": blocks,
            "ln_f": {"w": ones(E)},
            "lm_head": {"w": w(keys[-1], E, cfg.vocab_size)}}


# -- the Mamba-2 mixer ---------------------------------------------------------


def _mixer_inputs(h: jnp.ndarray, p: dict, cfg: ModelConfig,
                  conv: jnp.ndarray, valid=None):
    """From the normed inputs ``h`` (B, L, E) and the convolution's window
    ``conv`` (B, K-1, Dc): the gate ``z`` (B, L, Di), ``x`` (B, L, heads,
    P), ``B`` and ``C`` (B, L, n_groups, N) in h's dtype, ``dt`` (B, L,
    heads) and the heads' decay ``A = -exp(A_log)`` float32, and the window
    after the chunk."""
    Hm, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N = cfg.n_groups, cfg.ssm_state_size
    Di, Dc = cfg.ssd_inner, cfg.ssd_conv_channels
    f32 = jnp.float32
    zxd = h @ p["in_proj"].astype(h.dtype)
    z, xbc, dt = zxd[..., :Di], zxd[..., Di:Di + Dc], zxd[..., Di + Dc:]
    with jax.named_scope("ssm_conv"):
        c, conv = causal_conv(xbc, p["conv_w"], p["conv_b"], conv, valid)
        xbc = jax.nn.silu(c).astype(h.dtype)
    lead = h.shape[:2]
    x = xbc[..., :Di].reshape(lead + (Hm, P))
    Bm = xbc[..., Di:Di + G * N].reshape(lead + (G, N))
    Cm = xbc[..., Di + G * N:].reshape(lead + (G, N))
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    return z, x, Bm, Cm, dt, -jnp.exp(p["A_log"].astype(f32)), conv


def _gate_norm_out(y: jnp.ndarray, z: jnp.ndarray, p: dict,
                   cfg: ModelConfig) -> jnp.ndarray:
    """``y`` (.., Di) float32 gated by ``silu(z)``, THEN RMS-normed over
    each group's channels, through the output projection."""
    G = cfg.n_groups
    g = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(g.shape[:-1] + (G, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + cfg.resolved_norm_eps)
    g = grouped.reshape(g.shape) * p["norm"].astype(jnp.float32)
    return g.astype(z.dtype) @ p["out_proj"].astype(z.dtype)


def mixer_chunk(h: jnp.ndarray, p: dict, cfg: ModelConfig,
                conv: jnp.ndarray, ssm: jnp.ndarray, valid=None):
    """The mixer over a chunk ``h`` (B, L, E) of normed inputs that
    continues sequences in the state ``conv`` (B, K-1, Dc), ``ssm`` (B, N,
    Di): returns ``(out (B, L, E), conv, ssm)`` after the chunk. Zeros are
    a sequence's start. With ``valid`` (a runtime scalar) the steps from
    ``valid`` on are padding: their ``dt`` is zero, which leaves the
    recurrence where step ``valid`` put it, and the states returned are
    those after ``valid`` steps; ``out`` past it is not a sequence's."""
    z, x, Bm, Cm, dt, A, conv = _mixer_inputs(h, p, cfg, conv, valid)
    if valid is not None:
        real = jnp.arange(h.shape[1])[None, :, None] < valid
        dt = jnp.where(real, dt, jnp.zeros((), dt.dtype))
    with jax.named_scope("ssm_scan"):
        y, last = ssd.chunk_scan(x, dt, A, Bm, Cm, p["D"], ssm,
                                 cfg.chunk_size)
    y = y.reshape(h.shape[:2] + (cfg.ssd_inner,))
    return _gate_norm_out(y, z, p, cfg), conv, last.astype(ssm.dtype)


def mixer_step(h: jnp.ndarray, p: dict, cfg: ModelConfig,
               conv: jnp.ndarray, ssm: jnp.ndarray, active: jnp.ndarray):
    """One token a slot of the decode pool: ``h`` (S, E); ``conv`` (S,
    K-1, Dc) and ``ssm`` (S, N, Di) are the pool's leaves, and a slot that
    is not ``active`` keeps every bit of both (its state is not read)."""
    z, x, Bm, Cm, dt, A, moved = _mixer_inputs(h[:, None], p, cfg, conv)
    with jax.named_scope("ssm_conv"):
        conv = jnp.where(active[:, None, None], moved, conv)
    with jax.named_scope("ssm_state"):
        y, ssm = ssd.state_update(
            ssm, x[:, 0].reshape(h.shape[0], -1), dt[:, 0], A, Bm[:, 0],
            Cm[:, 0], p["D"], active)
    return _gate_norm_out(y, z[:, 0], p, cfg), conv, ssm


def zero_state(cfg: ModelConfig, batch: int, compute_dtype=None):
    """``(conv, ssm)`` of ``batch`` sequences at their start."""
    dt = jnp.dtype(compute_dtype or cfg.compute_dtype)
    return (jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.ssd_conv_channels),
                      dt),
            jnp.zeros((batch, cfg.ssm_state_size, cfg.ssd_inner),
                      jnp.dtype(cfg.ssm_state_dtype)))


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
    """One residual layer over whole sequences ``x`` (B, T, E), in the
    uniform per-family signature (models/registry.py). Which of the three
    it is, is read off its leaves."""
    del layer_idx, cos, sin, mask, rng, mesh
    if "moe" in blk:  # graftlint: disable=GL104 (a dict's keys are static)
        return moe(x, blk, cfg)[0]
    if "mamba2" in blk:  # graftlint: disable=GL104
        with jax.named_scope("ssm"):
            h = norm(x, blk["ln1"], cfg)
            a, _, _ = mixer_chunk(h, blk["mamba2"], cfg,
                                  *zero_state(cfg, x.shape[0], x.dtype))
        return x + a
    with jax.named_scope("attn_norm"):
        h = norm(x, blk["ln1"], cfg)
    with jax.named_scope("attn"):
        return x + _attn_full(h, blk["attn"])


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
            "the nemotron_h family is served, not trained: no loss is "
            "defined for it (the grouped expert product and the chunked "
            "state-space scan have no tested backward pass)"
        )
    x = embed(params, idx, cfg)
    for li, blk in enumerate(params["blocks"], 1):
        x = block_forward(x, blk, li, cfg, None, None, None, None, mesh)
    with jax.named_scope("lm_head"):
        return lm_head(params, x, cfg), None

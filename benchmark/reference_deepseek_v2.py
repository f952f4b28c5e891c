"""The plain reference of the `deepseek_v2` language model (DeepSeek-V2,
arXiv:2405.04434, as ``deepseek-ai/DeepSeek-V2``'s ``config.json`` sizes
it), written from the published equations in straightforward ``jax.numpy``:
float32 arithmetic, every matrix product under ``precision="highest"``,
attention in the WIDENED form (keys and values a head made from the
latents, full causal maps over the whole sequence, a head and a piece of
queries at a time so that a map fits), the rotation on the published pairs
``(2i, 2i + 1)``, the experts a plain loop over the held ones with every
token offered to each; no kernels, no cache, no absorbed products, no
grouping. It imports nothing of the program and takes nothing the program
has made: the weights come from :func:`make_params` (this file, from the
seed), and the program is handed the same tree.

  layer l (from 1): x = x + MLA(RMSNorm(x));  x = x + MLP_l(RMSNorm(x))
  MLA:   c_q = RMSNorm(x W_qa);  [q_nope_h ; q_pe_h] = (c_q W_qb)_h
         [c ; k_pe] = x W_kva;  c = RMSNorm(c)
         q_pe_h = RoPE_t(q_pe_h),  k_pe = RoPE_t(k_pe)  (one k_pe for all heads)
         [k_nope_h ; v_h] = (c W_kvb)_h
         p = softmax((q_nope_h . k_nope_h + q_pe_h . k_pe) s), causal
         s = (nope + rope)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
         y = concat_h(sum p v_h) W_o
  RoPE:  the pair (x_2i, x_2i+1) turns by the angle t f_i, i = 0 .. rope/2 - 1
         f_i = (1 - g_i) / (factor theta^(2i/rope)) + g_i / theta^(2i/rope)
         g_i = 1 - clip((i - low) / (high - low), 0, 1)
         low = floor(d(beta_fast)), high = ceil(d(beta_slow)),
         d(r) = rope ln(original_max / (2 pi r)) / (2 ln theta)
         cos and sin times (0.1 mscale ln(factor) + 1) / m
  dense MLP (l <= first_dense_layers): W_out(silu(W_gate h) * W_xform h)
  experts: p = softmax(h W_r) over all N;  group g = experts g N/n_group ..
         a group's score = its largest p; the topk_group best groups kept;
         the experts_per_token largest p inside them (the others at 0)
         w_i = routed_scaling p_i   (NOT renormalised)
         y = sum_{i chosen, i HELD} w_i E_i(h) + E_shared(h),  E a SwiGLU;
         E_shared ONE SwiGLU of n_shared_experts * moe_hidden, unscaled
  head:  RMSNorm, logits = x W_head (untied)

``held_experts`` ``[lo, hi)`` is an expert-parallel share: the tree holds
those experts only, the router ranks all ``num_experts``, and what the
absent experts would add is left out, here as in the program. Expert
``e``'s weights are drawn from a key of their own, so the shares of one
seed are slices of one uncut model.

The parameter tree's names and shapes are the checkpoint layout the program
reads (``models/deepseek_v2.py``; weights stored ``(in, out)``), every leaf
in the configuration's ``param_dtype``. 3.15 G parameters are 12.6 GB in
float32, so the weights stay in the stated dtype and are widened where they
are used, an expert at a time (the values are the ones the program reads;
the arithmetic is float32), and the sequences go through one at a time.

``quant`` is the lower-precision control of the benchmark's `correct`
(PERF.md section 2): every matrix product, the router's and attention's
two included, takes its operands rounded to float8 (e4m3, one scale a
tensor). The configuration states bfloat16 compute, so float8 is the step
below. ``fault`` plants one wrong piece of the mathematics, for the
witness of the serving limit (``selftest_deepseek_v2.py --witness``): a
router that renormalises its weights, a shared key part that is not
rotated, ``m^2`` left out of the softmax scale, one held expert's output
zeroed.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: ``fault=`` of :func:`hidden` and :func:`make_token_gaps`; None is sound
FAULTS = (None, "router_renormalised", "key_not_rotated", "no_mscale",
          "held_expert_zeroed")

#: what a routed expert's down projection is scaled by, beside the rule's
#: ``fan_in ** -0.5`` (:func:`param_spec` says why, PERF.md section 2 has
#: the readings it was set from)
ROUTED_DOWN_SCALE = 1.0 / 3.0


# -- sizes -----------------------------------------------------------------


def sizes(model: dict) -> dict:
    """Every size from a configuration file's ``model`` group, the
    defaults being the program's (``config.py:ModelConfig``)."""
    if model["model"] != "deepseek_v2":
        raise ValueError(f"no reference for model kind {model['model']!r}")
    E, N = model["n_embd"], model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    yarn = dict(model.get("rope_scaling") or {})
    yarn.pop("type", None)
    return {
        "E": E, "H": model["n_head"], "V": model["vocab_size"],
        "qr": model["q_lora_rank"],
        "rank": model.get("kv_lora_rank", 512),
        "nope": model.get("qk_nope_head_dim", 128),
        "rope": model.get("qk_rope_head_dim", 64),
        "vd": model.get("v_head_dim", 128),
        "theta": float(model.get("rope_theta", 10000.0)),
        "yarn": tuple(sorted(yarn.items())),
        "F": model.get("ffn_hidden") or 4 * E,
        "N": N, "top": model.get("experts_per_token", 8),
        "groups": model.get("n_group", 1),
        "top_groups": model.get("topk_group", 1),
        "shared": model.get("n_shared_experts", 1),
        "Fm": model.get("moe_hidden", 1024),
        "scaling": model.get("routed_scaling", 1.0),
        "lo": lo, "hi": hi or N,
        "eps": model.get("norm_eps") or 1e-6,
        "dtype": model.get("param_dtype", "float32"),
    }


def mlp_kinds(model: dict) -> list:
    """``"dense"`` for the first ``first_dense_layers``, ``"moe"``
    after."""
    dense = model.get("first_dense_layers", 1)
    return ["dense" if l <= dense else "moe"
            for l in range(1, model["n_layer"] + 1)]


def param_spec(model: dict) -> dict:
    """The tree of ``(shape, mean, std)`` that :func:`make_params` fills;
    an expert leaf carries a fourth item, the range of experts it holds.
    Every leaf is random, by the rule of ``reference_kimi_linear.py``: a
    projection's entries have a standard deviation of ``fan_in ** -0.5``
    of the width it reads, so that at any width a layer's output outweighs
    the token's own embedding in the residual stream; norm scales N(1,
    0.02), the two inner norms (query rank, latent) N(1, 0.1). The router
    has no bias (the published one has none). A routed expert's down
    projection is ``ROUTED_DOWN_SCALE`` of the rule's: with random weights
    a token's 6th and 7th experts, and its 3rd and 4th groups, score alike
    (a trained router's are peaked), so a score that bfloat16 activations
    round the other way swaps whole terms of weight 16 p, about 0.7 each,
    and at the full scale those swaps, not the arithmetic, would set the
    served-token gap."""
    s = sizes(model)
    E, H, V = s["E"], s["H"], s["V"]
    qr, rank, nope, rope, vd = s["qr"], s["rank"], s["nope"], s["rope"], s["vd"]
    w = lambda *shape, fan=E: (shape, 0.0, fan ** -0.5)  # noqa: E731
    scale = lambda n, std=0.02: {"w": ((n,), 1.0, std)}  # noqa: E731
    mlp = lambda F: {"gate": {"w": w(E, F)}, "xform": {"w": w(E, F)},  # noqa: E731
                     "out": {"w": w(F, E, fan=F)}}
    mla = {
        "wq_a": w(E, qr), "q_norm": ((qr,), 1.0, 0.1),
        "wq_b": w(qr, H, nope + rope, fan=qr),
        "wkv_a": w(E, rank + rope), "kv_norm": ((rank,), 1.0, 0.1),
        "wkv_b": w(rank, H, nope + vd, fan=rank),
        "out": {"w": w(H * vd, E, fan=H * vd)},
    }
    held = (s["lo"], s["hi"])
    G, Fm = s["hi"] - s["lo"], s["Fm"]
    moe = {
        "router": {"w": w(E, s["N"])},
        "experts": {"gate_up": ((G, E, 2 * Fm), 0.0, E ** -0.5, held),
                    "down": ((G, Fm, E), 0.0,
                             Fm ** -0.5 * ROUTED_DOWN_SCALE, held)},
        "shared": mlp(s["shared"] * Fm),
    }
    blocks = [dict({"ln1": scale(E), "ln2": scale(E), "mla": mla},
                   **({"ffn": mlp(s["F"])} if kind == "dense"
                      else {"moe": moe}))
              for kind in mlp_kinds(model)]
    return {"tok_emb": w(V, E), "blocks": blocks, "ln_f": scale(E),
            "lm_head": {"w": w(E, V)}}


def _is_leaf_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) in (3, 4) and isinstance(x[0], tuple)


def make_params(seed: int, model: dict, sharding=None):
    """Weights from the seed in the configuration's ``param_dtype``, made
    on the device a leaf at a time (every leaf its own ``fold_in`` of the
    seed's key, every expert of an expert leaf its own ``fold_in`` of the
    leaf's; drawn in float32, then rounded once)."""
    dtype = jnp.dtype(sizes(model)["dtype"])
    leaves, treedef = jax.tree_util.tree_flatten(
        param_spec(model), is_leaf=_is_leaf_spec)
    key = jax.random.key(seed % (2**31))

    @partial(jax.jit, static_argnums=(1, 2, 3, 4), out_shardings=sharding)
    def draw(k, shape, mean, std, held=None):
        normal = lambda kk, sh: (  # noqa: E731
            mean + std * jax.random.normal(kk, sh, jnp.float32)).astype(dtype)
        if held is None:
            return normal(k, shape)
        return jax.vmap(lambda e: normal(jax.random.fold_in(k, e), shape[1:])
                        )(jnp.arange(*held))

    return jax.tree_util.tree_unflatten(treedef, [
        draw(jax.random.fold_in(key, i), *leaf)
        for i, leaf in enumerate(leaves)])


# -- the lower-precision control -------------------------------------------


def _fake_quant(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax  # e4m3's largest finite value
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _fake_quant(a.astype(jnp.float32), quant),
                      _fake_quant(b.astype(jnp.float32), quant),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


# -- forward: one sequence (T, E) at a time ----------------------------------

_QUERY_ROWS = 1024  # queries whose score maps exist at once, a head


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * _f32(w)


def _swiglu(h, p, quant):
    gated = jax.nn.silu(_mm("te,ef->tf", h, p["gate"]["w"], quant)) * _mm(
        "te,ef->tf", h, p["xform"]["w"], quant)
    return _mm("tf,fe->te", gated, p["out"]["w"], quant)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(rope: int, theta: float, yarn: dict):
    """``(f (rope/2,), the multiplier of cos and sin)`` by the docstring's
    closed forms; the plain ``theta ** (-2i/rope)`` and 1 without a YaRN
    block."""
    i = jnp.arange(rope // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / rope)
    if not yarn:
        return plain, 1.0
    at = lambda r: rope * math.log(  # noqa: E731
        yarn["original_max_position_embeddings"] / (2 * math.pi * r)) / (
        2 * math.log(theta))
    low = max(math.floor(at(yarn["beta_fast"])), 0)
    high = min(math.ceil(at(yarn["beta_slow"])), rope - 1)
    g = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    f = (1.0 - g) * plain / yarn["factor"] + g * plain
    return f, (yarn_mscale(yarn["factor"], yarn["mscale"])
               / yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]))


def _rotate(x, s):
    """``x`` (.., T, rope) at positions 0 .. T-1: the pair ``(x_2i,
    x_2i+1)`` turns by ``t f_i``."""
    T = x.shape[-2]
    f, mult = yarn_frequencies(s["rope"], s["theta"], dict(s["yarn"]))
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * f  # (T, rope/2)
    cos, sin = jnp.cos(angle) * mult, jnp.sin(angle) * mult
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def softmax_scale(s: dict, fault=None) -> float:
    base = (s["nope"] + s["rope"]) ** -0.5
    yarn = dict(s["yarn"])
    if not yarn or not yarn["mscale_all_dim"] or fault == "no_mscale":
        return base
    return base * yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2


def _mla(h, p, s, quant, fault):
    H, rank, nope, rope, vd = s["H"], s["rank"], s["nope"], s["rope"], s["vd"]
    T = h.shape[0]
    c_q = _rms_norm(_mm("te,er->tr", h, p["wq_a"], quant), p["q_norm"],
                    s["eps"])
    q = _mm("tr,rhd->htd", c_q, p["wq_b"], quant)  # (H, T, nope + rope)
    kv = _mm("te,er->tr", h, p["wkv_a"], quant)
    c = _rms_norm(kv[:, :rank], p["kv_norm"], s["eps"])
    k_pe = kv[:, rank:]
    if fault != "key_not_rotated":
        k_pe = _rotate(k_pe, s)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], s)], axis=-1)
    wide = _mm("tr,rhd->htd", c, p["wkv_b"], quant)  # (H, T, nope + vd)
    keys = jnp.concatenate(
        [wide[..., :nope], jnp.broadcast_to(k_pe[None], (H, T, rope))],
        axis=-1)
    scale = softmax_scale(s, fault)
    rows = _QUERY_ROWS if T % _QUERY_ROWS == 0 else T
    j = jnp.arange(T)[None, :]

    def head(xs):  # one head at a time: a batch of (T, T) maps would not fit
        q_h, k_h, v_h = xs

        def piece(xs):
            q_b, i0 = xs  # (rows, nope + rope)
            keep = j <= i0 + jnp.arange(rows)[:, None]
            scores = _mm("td,sd->ts", q_b, k_h, quant) * scale
            maps = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
            return _mm("ts,sd->td", maps, v_h, quant)

        o = jax.lax.map(piece, (q_h.reshape(T // rows, rows, -1),
                                jnp.arange(0, T, rows)))
        return o.reshape(T, vd)

    o = jax.lax.map(head, (q, keys, wide[..., nope:]))  # (H, T, vd)
    return _mm("ti,io->to", o.swapaxes(0, 1).reshape(T, H * vd),
               p["out"]["w"], quant)


def route(h, w_r, s, quant=None, fault=None):
    """``(T, N)``: the weight a token gives an expert, 0 where it did not
    choose it."""
    T, N, G = h.shape[0], s["N"], s["groups"]
    probs = jax.nn.softmax(_mm("te,en->tn", h, w_r, quant), axis=-1)
    best = jnp.max(probs.reshape(T, G, N // G), axis=-1)
    _, groups = jax.lax.top_k(best, s["top_groups"])
    kept = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], groups].set(True)
    inside = jnp.where(jnp.repeat(kept, N // G, axis=-1), probs, 0.0)
    picked, chosen = jax.lax.top_k(inside, s["top"])
    if fault == "router_renormalised":
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(T)[:, None], chosen].set(picked * s["scaling"])


def _moe(h, p, s, quant, fault):
    """Router over all N experts, then every HELD expert in turn over
    every token, weighted by what the router gave it there (0 for a token
    that did not choose it)."""
    dense = route(h, p["router"]["w"], s, quant, fault)
    Fm = s["Fm"]
    held = s["lo"] + jnp.arange(s["hi"] - s["lo"])
    zeroed = s["lo"] if fault == "held_expert_zeroed" else -1

    def expert(y, xs):
        e, gate_up, down = xs
        gu = _mm("te,ef->tf", h, gate_up, quant)
        out = _mm("tf,fe->te", jax.nn.silu(gu[:, :Fm]) * gu[:, Fm:], down,
                  quant)
        return y + jnp.where(e == zeroed, 0.0, dense[:, e])[:, None] * out, None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (held, p["experts"]["gate_up"], p["experts"]["down"]))
    return y + _swiglu(h, p["shared"], quant)


@lru_cache(maxsize=None)
def _layer_fn(kind: str, frozen_sizes: tuple, quant, fault):
    """One block over one sequence (T, E), jitted once a kind of MLP: the
    weights arrive in the stored dtype and are widened where used."""
    s = dict(frozen_sizes)

    @jax.jit
    def layer(x, blk):
        h = _rms_norm(x, blk["ln1"]["w"], s["eps"])
        x = x + _mla(h, blk["mla"], s, quant, fault)
        h = _rms_norm(x, blk["ln2"]["w"], s["eps"])
        return x + (_swiglu(h, blk["ffn"], quant) if kind == "dense"
                    else _moe(h, blk["moe"], s, quant, fault))

    return layer


def _frozen(model: dict) -> tuple:
    return tuple(sorted(sizes(model).items()))


def hidden(params, idx, model: dict, quant=None, fault=None):
    """(B, T) token ids -> the last layer's output (B, T, E), float32,
    before the final norm; a sequence at a time."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    rows = []
    for ids in idx:
        x = _f32(params["tok_emb"][ids])
        for kind, blk in zip(mlp_kinds(model), params["blocks"]):
            x = _layer_fn(kind, _frozen(model), quant, fault)(x, blk)
        rows.append(x)
    return jnp.stack(rows)


def _head(params, x, s, quant):
    x = _rms_norm(x, params["ln_f"]["w"], s["eps"])
    return _mm("te,ev->tv", x, params["lm_head"]["w"], quant)


@lru_cache(maxsize=None)
def _head_fn(frozen_sizes: tuple, quant):
    s = dict(frozen_sizes)
    return jax.jit(lambda p, xb: _head(p, xb, s, quant))


def _head_leaves(params):
    return {k: v for k, v in params.items() if k not in ("blocks", "tok_emb")}


def forward(params, idx, model: dict, quant=None, fault=None):
    """(B, T) token ids -> float32 logits (B, T, V). For sequences whose
    logits fit at once; :func:`make_token_gaps` goes a piece at a time."""
    head = _head_fn(_frozen(model), quant)
    x = hidden(params, idx, model, quant, fault)
    return jnp.stack([head(_head_leaves(params), xb) for xb in x])


# -- serving: how far below the reference's best a served token lies --------

_HEAD_ROWS = 1024  # positions whose logits exist at once: 52 MB at V = 12,800


def make_token_gaps(model: dict, quant=None, fault=None):
    """``gaps(params, seqs, served) -> (B, T)``: at every position, the
    reference's best logit minus its logit of ``served[b, t]``, the token
    that followed position t. With ``quant`` (or a planted ``fault``) the
    token judged is the one the lower precision (the faulty model) puts
    first at that position instead: the control need not decode. The
    logits exist ``_HEAD_ROWS`` positions at a time."""
    s = sizes(model)

    @jax.jit
    def row_gaps(head, xb, served_b):
        logits = _head(head, xb, s, None)
        got = jnp.take_along_axis(logits, served_b[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    @jax.jit
    def row_best(head, xb):
        return jnp.argmax(_head(head, xb, s, quant), axis=-1)

    def pieces(fn, head, xb, *more):
        T = xb.shape[0]
        return jnp.concatenate([
            fn(head, xb[t:t + _HEAD_ROWS], *(m[t:t + _HEAD_ROWS] for m in more))
            for t in range(0, T, _HEAD_ROWS)])

    def gaps(params, seqs, served):
        head = _head_leaves(params)
        x = hidden(params, seqs, model)
        if quant is not None or fault is not None:
            xq = hidden(params, seqs, model, quant, fault)
            served = jnp.stack([pieces(row_best, head, xb) for xb in xq])
        return jnp.stack([pieces(row_gaps, head, xb, sb)
                          for xb, sb in zip(x, served)])

    return gaps

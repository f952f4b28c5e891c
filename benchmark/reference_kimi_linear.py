"""The plain reference of the `kimi_linear` language model (Moonshot's
Kimi-Linear, arXiv:2510.26692, as ``moonshotai/Kimi-Linear-48B-A3B-Instruct``'s
``config.json`` sizes it), written from the paper's equations in
straightforward ``jax.numpy``: float32 arithmetic, every matrix product
under ``precision="highest"``, the delta rule a ``lax.scan`` over time one
token a step, attention over per-head keys and values with full (T, T)
maps, the experts a plain loop over the held ones with every token offered
to each, no kernels, no cache, no grouping. It imports nothing of the
program and takes nothing the program has made: the weights come from
:func:`make_params` (this file, from the seed), and the program is handed
the same tree.

  layer l (from 1): x = x + mixer_l(RMSNorm(x));  x = x + mlp_l(RMSNorm(x))
  KDA (l in kda_layers; H heads of d):
      [q, k, v] = silu(conv(x W_qkv))  (causal, depthwise, kda_conv taps)
      q, k L2-normalised a head (x / sqrt(sum x^2 + 1e-6)); q scaled by d^-1/2
      g_t = -exp(A_log_h) softplus(x_t W_fa W_fb + dt_bias)   in R^{H x d}
      beta_t = sigmoid(x_t W_b)                               in R^H
      S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t;  y_t = [RMSNorm_d(o_t) * sigmoid(x_t W_ga W_gb)] W_o
  MLA (l in full_attn_layers; no position encoding, nothing is rotated):
      q_h = (x W_q)_h;  [c ; k_r] = x W_kva;  c = RMSNorm(c)
      [k_h ; v_h] = (c W_kvb)_h;  key_h = [k_h ; k_r]
      causal softmax(q_h . key_h / sqrt(nope + rope)) v_h, heads joined, W_o
  dense MLP (l <= first_dense_layers): W_out(silu(W_gate h) * W_xform h)
  experts: s = sigmoid(h W_r);  the experts_per_token largest of s + b
      w_i = routed_scaling s_i / sum_chosen s
      y = sum_{i chosen, i HELD} w_i E_i(h) + E_shared(h),  E a SwiGLU
  head:  RMSNorm, logits = x W_head (untied)

``held_experts`` ``[lo, hi)`` is an expert-parallel share: the tree holds
those experts only, the router ranks all ``num_experts``, and what the
absent experts would add is left out, here as in the program. Expert
``e``'s weights are drawn from a key of their own, so the shares of one
seed are slices of one uncut model.

The parameter tree's names and shapes are the checkpoint layout the program
reads (``models/kimi_linear.py``; weights stored ``(in, out)``), every leaf
in the configuration's ``param_dtype``. 4.66 G parameters are 18.6 GB in
float32, so the weights stay in the stated dtype and are widened where they
are used, an expert at a time (the values are the ones the program reads;
the arithmetic is float32), and the sequences go through one at a time.

``quant`` is the lower-precision control of the benchmark's `correct`
(PERF.md section 2): every matrix product, the router's, attention's two
and the delta rule's two reads of the state included, takes its operands
rounded to float8 (e4m3, one scale a tensor). The configuration states
bfloat16 compute, so float8 is the step below. ``quant="router_bf16"`` is
no control but a witness: every product exact but the router's, rounded as
a bfloat16 program rounds it (what that alone does to the served tokens:
``selftest_kimi_linear.py --witness``).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# -- sizes -----------------------------------------------------------------


def sizes(model: dict) -> dict:
    """Every size from a configuration file's ``model`` group, the
    defaults being the program's (``config.py:ModelConfig``)."""
    if model["model"] != "kimi_linear":
        raise ValueError(f"no reference for model kind {model['model']!r}")
    E, N = model["n_embd"], model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    return {
        "E": E, "H": model["n_head"], "V": model["vocab_size"],
        "d": model.get("kda_head_dim", 128), "K": model.get("kda_conv", 4),
        "rank": model.get("kv_lora_rank", 512),
        "nope": model.get("qk_nope_head_dim", 128),
        "rope": model.get("qk_rope_head_dim", 64),
        "vd": model.get("v_head_dim", 128),
        "F": model.get("ffn_hidden") or 4 * E,
        "N": N, "top": model.get("experts_per_token", 8),
        "Fm": model.get("moe_hidden", 1024),
        "scaling": model.get("routed_scaling", 1.0),
        "lo": lo, "hi": hi or N,
        "eps": model.get("norm_eps") or 1e-6,
        "dtype": model.get("param_dtype", "float32"),
    }


def layer_kinds(model: dict) -> list:
    """``(mixer, mlp)`` for every layer: ``"kda"`` or ``"mla"`` by the
    published lists (numbered from 1), ``"dense"`` for the first
    ``first_dense_layers`` and ``"moe"`` after."""
    dense = model.get("first_dense_layers", 1)
    return [("kda" if l in model["kda_layers"] else "mla",
             "dense" if l <= dense else "moe")
            for l in range(1, model["n_layer"] + 1)]


def param_spec(model: dict) -> dict:
    """The tree of ``(shape, mean, std)`` that :func:`make_params` fills;
    an expert leaf carries a fourth item, the range of experts it holds.
    Every leaf is random. A projection's entries have a standard deviation
    of ``fan_in ** -0.5`` of the width it reads (``n_embd`` for most:
    0.0208 at the published 2304), so that at any width a layer's output
    outweighs the token's own embedding in the residual stream. The
    recurrence is exercised on both sides: ``exp(A_log)`` has a median of
    1.6 (0.33 to 8 over two sigma) and ``softplus(dt_bias + .)`` of 0.08
    (0.004 to 1), so a channel's decay ``exp(g)`` runs from 0.999 (a state
    that remembers for a thousand tokens) to 3e-4 (one that forgets at
    once). The router's correction bias is N(0, 0.02): it moves the
    ranking of experts whose scores lie within a few hundredths, not the
    weights (the published bias exists to even the experts' load out; at
    N(0, 0.1) it made a few experts six to ten times as busy as the mean,
    and how many of those fell in the held half differed by 8% a seed).
    A routed expert's down projection is a third of the rule's: with
    random weights a token's 8th and 9th experts score alike and give
    unrelated outputs at a weight of 0.3 each, so a score that bfloat16
    rounds the other way swaps a tenth of the layer's output, which a
    trained router's peaked weights would not; at the full scale those
    swaps alone moved the logits by 0.09 rms and the served-token gap read
    0.41-0.64 beside the float8 control's 0.92-1.25 (my chip runs,
    PR 32)."""
    s = sizes(model)
    E, H, d, K, V = s["E"], s["H"], s["d"], s["K"], s["V"]
    rank, nope, rope, vd = s["rank"], s["nope"], s["rope"], s["vd"]
    w = lambda *shape, fan=E: (shape, 0.0, fan ** -0.5)  # noqa: E731
    scale = lambda n, std=0.02: {"w": ((n,), 1.0, std)}  # noqa: E731
    mlp = lambda F: {"gate": {"w": w(E, F)}, "xform": {"w": w(E, F)},  # noqa: E731
                     "out": {"w": w(F, E)}}
    kda = {
        "qkv": w(E, 3 * H * d), "conv_w": ((K, 3 * H * d), 0.0, 0.3),
        "f_a": w(E, d), "f_b": w(d, H * d, fan=d),
        "dt_bias": ((H * d,), -2.5, 1.5), "A_log": ((H,), 0.5, 0.8),
        "b": w(E, H), "g_a": w(E, d), "g_b": w(d, H * d, fan=d),
        "o_norm": ((d,), 1.0, 0.1), "out": w(H * d, E),
    }
    mla = {
        "wq": w(E, H, nope + rope), "wkv_a": w(E, rank + rope),
        "kv_norm": ((rank,), 1.0, 0.1),
        "wkv_b": w(rank, H, nope + vd, fan=rank),
        "out": {"w": w(H * vd, E)},
    }
    held = (s["lo"], s["hi"])
    G, Fm = s["hi"] - s["lo"], s["Fm"]
    moe = {
        "router": {"w": w(E, s["N"]), "b": ((s["N"],), 0.0, 0.02)},
        "experts": {"gate_up": ((G, E, 2 * Fm), 0.0, E ** -0.5, held),
                    "down": ((G, Fm, E), 0.0, E ** -0.5 / 3, held)},
        "shared": mlp(Fm),
    }
    blocks = [dict({"ln1": scale(E), "ln2": scale(E)},
                   **({"kda": kda} if mixer == "kda" else {"mla": mla}),
                   **({"ffn": mlp(s["F"])} if kind == "dense"
                      else {"moe": moe}))
              for mixer, kind in layer_kinds(model)]
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


ROUTER_BF16 = "router_bf16"  # only the router's product rounds: :func:`_moe`


def _fake_quant(x, quant):
    if quant is None or quant == ROUTER_BF16:
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


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * _f32(w)


def _swiglu(h, p, quant):
    gated = jax.nn.silu(_mm("te,ef->tf", h, p["gate"]["w"], quant)) * _mm(
        "te,ef->tf", h, p["xform"]["w"], quant)
    return _mm("tf,fe->te", gated, p["out"]["w"], quant)


def _kda(h, p, s, quant):
    H, d, K = s["H"], s["d"], s["K"]
    T = h.shape[0]
    qkv = _mm("te,ei->ti", h, p["qkv"], quant)
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))  # causal: zeros before t=0
    qkv = jax.nn.silu(sum(padded[k:k + T] * _f32(p["conv_w"][k])
                          for k in range(K)))
    q, k, v = (a.reshape(T, H, d) for a in jnp.split(qkv, 3, axis=-1))
    unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * d ** -0.5, unit(k)
    f = _mm("tr,ri->ti", _mm("te,er->tr", h, p["f_a"], quant), p["f_b"], quant)
    g = -jnp.exp(_f32(p["A_log"]))[:, None] * jax.nn.softplus(
        f + _f32(p["dt_bias"])).reshape(T, H, d)
    beta = jax.nn.sigmoid(_mm("te,eh->th", h, p["b"], quant))

    def step(S, xs):  # S (H, d_k, d_v): one token
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - _mm("hk,hkv->hv", k_t, S, quant))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, _mm("hk,hkv->hv", q_t, S, quant)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid(_mm(
        "tr,ri->ti", _mm("te,er->tr", h, p["g_a"], quant), p["g_b"], quant))
    o = _rms_norm(o, p["o_norm"], s["eps"]).reshape(T, H * d) * gate
    return _mm("ti,ie->te", o, p["out"], quant)


def _mla(h, p, s, quant):
    H, rank, nope, vd = s["H"], s["rank"], s["nope"], s["vd"]
    T = h.shape[0]
    q = _mm("te,ehd->htd", h, p["wq"], quant)
    kv = _mm("te,er->tr", h, p["wkv_a"], quant)
    c = _rms_norm(kv[:, :rank], p["kv_norm"], s["eps"])
    wide = _mm("tr,rhd->htd", c, p["wkv_b"], quant)
    keys = jnp.concatenate(
        [wide[..., :nope], jnp.broadcast_to(kv[None, :, rank:], (H, T, s["rope"]))],
        axis=-1)
    keep = jnp.tril(jnp.ones((T, T), bool))

    def head(xs):  # one head at a time: a batch of (T, T) maps would not fit
        q_h, k_h, v_h = xs
        scores = _mm("td,sd->ts", q_h, k_h, quant) / math.sqrt(q_h.shape[-1])
        maps = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return _mm("ts,sd->td", maps, v_h, quant)

    o = jax.lax.map(head, (q, keys, wide[..., nope:]))  # (H, T, vd)
    return _mm("ti,io->to", o.swapaxes(0, 1).reshape(T, H * vd),
               p["out"]["w"], quant)


def _moe(h, p, s, quant):
    """Router over all N experts, then every HELD expert in turn over
    every token, weighted by what the router gave it there (0 for a token
    that did not choose it)."""
    if quant == ROUTER_BF16:
        # the witness of what sets the program's gap (selftest_kimi_linear.py
        # --witness): every product exact but this one, whose operands and
        # result are rounded as a bfloat16 program rounds them
        bf = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
        scores = jax.nn.sigmoid(_f32(bf(_mm(
            "te,en->tn", bf(h), bf(p["router"]["w"]), None))))
    else:
        scores = jax.nn.sigmoid(_mm("te,en->tn", h, p["router"]["w"], quant))
    _, chosen = jax.lax.top_k(scores + _f32(p["router"]["b"]), s["top"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * s["scaling"]
    # (T, N): the weight a token gives an expert, 0 where not chosen
    dense = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(weights)
    Fm = s["Fm"]

    def expert(y, xs):
        e, gate_up, down = xs
        gu = _mm("te,ef->tf", h, gate_up, quant)
        out = _mm("tf,fe->te", jax.nn.silu(gu[:, :Fm]) * gu[:, Fm:], down,
                  quant)
        return y + dense[:, e][:, None] * out, None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (jnp.arange(s["lo"], s["hi"]), p["experts"]["gate_up"],
         p["experts"]["down"]))
    return y + _swiglu(h, p["shared"], quant)


@lru_cache(maxsize=None)
def _layer_fn(kinds: tuple, frozen_sizes: tuple, quant):
    """One block over one sequence (T, E), jitted once a pair of kinds:
    the weights arrive in the stored dtype and are widened where used."""
    s = dict(frozen_sizes)
    mixer, kind = kinds

    @jax.jit
    def layer(x, blk):
        h = _rms_norm(x, blk["ln1"]["w"], s["eps"])
        x = x + (_kda(h, blk["kda"], s, quant) if mixer == "kda"
                 else _mla(h, blk["mla"], s, quant))
        h = _rms_norm(x, blk["ln2"]["w"], s["eps"])
        return x + (_swiglu(h, blk["ffn"], quant) if kind == "dense"
                    else _moe(h, blk["moe"], s, quant))

    return layer


def _frozen(model: dict) -> tuple:
    return tuple(sorted(sizes(model).items()))


def hidden(params, idx, model: dict, quant=None):
    """(B, T) token ids -> the last layer's output (B, T, E), float32,
    before the final norm; a sequence at a time."""
    rows = []
    for ids in idx:
        x = _f32(params["tok_emb"][ids])
        for kinds, blk in zip(layer_kinds(model), params["blocks"]):
            x = _layer_fn(kinds, _frozen(model), quant)(x, blk)
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


def forward(params, idx, model: dict, quant=None):
    """(B, T) token ids -> float32 logits (B, T, V). For sequences whose
    logits fit at once; :func:`make_token_gaps` goes a piece at a time."""
    head = _head_fn(_frozen(model), quant)
    x = hidden(params, idx, model, quant)
    return jnp.stack([head(_head_leaves(params), xb) for xb in x])


# -- serving: how far below the reference's best a served token lies --------

_HEAD_ROWS = 1024  # positions whose logits exist at once: 0.67 GB at V = 163,840


def make_token_gaps(model: dict, quant=None):
    """``gaps(params, seqs, served) -> (B, T)``: at every position, the
    reference's best logit minus its logit of ``served[b, t]``, the token
    that followed position t. With ``quant`` the token judged is the one
    the lower precision puts first at that position instead (the control:
    it need not decode). The logits exist ``_HEAD_ROWS`` positions at a
    time."""
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
        if quant is not None:
            xq = hidden(params, seqs, model, quant)
            served = jnp.stack([pieces(row_best, head, xb) for xb in xq])
        return jnp.stack([pieces(row_gaps, head, xb, sb)
                          for xb, sb in zip(x, served)])

    return gaps

"""The plain reference of the `jamba` language model (AI21 Jamba, dense:
``num_experts`` 1), written from ``transformers``' description of the
architecture (``JambaConfig``, ``modeling_jamba.py``: ``JambaMambaMixer``'s
slow path, ``JambaAttention``, ``JambaMLP``, ``JambaRMSNorm``) in
straightforward ``jax.numpy``: float32 arithmetic, every matrix product
under ``precision="highest"``, full (T, T) attention maps, the recurrence a
``lax.scan`` over time one token a step, no kernels, no cache, no batching
tricks. It imports nothing of the program and takes nothing the program has
made: the weights come from :func:`make_params` (this file, from the seed),
and the program is handed the same tree.

  layer i: x = x + mixer_i(RMSNorm(x));  x = x + W_down(silu(W_gate h) * W_up h),
           h = RMSNorm(x);  mixer_i is attention iff
           i % attn_layer_period == attn_layer_offset, else Mamba
  attention: n_head query heads, kv_heads K/V heads each shared by a group of
           query heads, no bias, NO position information, causal softmax
  Mamba:   [u, z] = x W_in; u = silu(conv(u) + b_conv) (causal, depthwise, d_conv
           taps); [r, B, C] = u W_x; r, B, C RMS-normed each with its own scale
           (Jamba's step); delta = softplus(r W_dt + b_dt); A = -exp(A_log);
           h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) B_t; y_t = h_t . C_t + D u_t;
           out = (y * silu(z)) W_out
  head:    RMSNorm, then logits = x E^T (tied) or x W_head

The parameter tree's names and shapes are the checkpoint layout the program
reads (``models/jamba.py``; weights stored ``(in, out)``), every leaf in the
configuration's ``param_dtype``:

  tok_emb (V, E)
  blocks[l]: ln1{w} ln2{w} ffn{gate{w} xform{w} out{w}}
    attention layer: attn{wq (E, H, d)  wk, wv (E, KV, d)  out{w (H d, E)}}
    mamba layer:     mamba{in_proj (E, 2 Di) conv_w (K, Di) conv_b (Di)
                     x_proj (Di, R + 2 N) dt_norm (R) b_norm, c_norm (N)
                     dt_proj{w (R, Di), b (Di)} A_log (Di, N) D (Di)
                     out_proj (Di, E)}
  ln_f{w}  [lm_head{w (E, V)} unless tie_embeddings]

Departures from the published description: none in the arithmetic. The
configuration states bfloat16 weights (served), so the float32 tree would
be 12 GB and not fit beside the check: the weights stay in the stated dtype
and are widened to float32 a layer at a time (the values are the ones the
program reads; the arithmetic is float32). The published model's
``use_mamba_kernels`` fast path computes the same recurrence; the dtype the
recurrent state is KEPT in between tokens is not a property of this
reference (it keeps no state between calls).

``quant`` is the lower-precision control of the benchmark's `correct`
(PERF.md section 2): every matrix product, attention's two included, takes
its operands rounded to float8 (e4m3, one scale a tensor). The
configuration states bfloat16 compute, so float8 is the step below.
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
    if model["model"] != "jamba":
        raise ValueError(f"no reference for model kind {model['model']!r}")
    E, H = model["n_embd"], model["n_head"]
    return {
        "E": E, "H": H, "KV": model.get("kv_heads") or H, "d": E // H,
        "F": model.get("ffn_hidden") or 4 * E, "V": model["vocab_size"],
        "Di": model.get("mamba_expand", 2) * E,
        "N": model.get("mamba_d_state", 16),
        "K": model.get("mamba_d_conv", 4),
        "R": model.get("mamba_dt_rank") or -(-E // 16),
        "eps": model.get("norm_eps") or 1e-6,
        "tied": bool(model.get("tie_embeddings", False)),
        "dtype": model.get("param_dtype", "float32"),
    }


def layer_kinds(model: dict) -> list:
    """``JambaConfig.layers_block_type``."""
    period = model.get("attn_layer_period", 8)
    offset = model.get("attn_layer_offset", 4)
    return ["attention" if i % period == offset else "mamba"
            for i in range(model["n_layer"])]


def param_spec(model: dict) -> dict:
    """The tree of ``(shape, mean, std)`` that :func:`make_params` fills.
    Every leaf is random, norm scales, ``A_log``, ``D`` and the biases
    too. A projection's entries have a standard deviation of
    ``n_embd ** -0.5`` (0.0198 at the published 2560: the usual 0.02),
    so that at any width a layer's output outweighs the token's own
    embedding in the residual stream, as it does at the published width
    (at a fixed 0.02 a toy model only repeats its last token, whatever its
    mixers hold). The rest is drawn so that the recurrence is exercised on
    both sides:
    ``A = -exp(A_log)`` has a median of -2.7 (0.5 to 13 over two sigma),
    and ``delta = softplus(N(-2.5, 1.2))`` a median of 0.08 (0.005 to 1),
    so ``exp(delta A)`` runs from 0.997 (a state that remembers for
    hundreds of tokens) to 1e-5 (one that forgets at once)."""
    s = sizes(model)
    E, H, KV, d, F, V = s["E"], s["H"], s["KV"], s["d"], s["F"], s["V"]
    Di, N, K, R = s["Di"], s["N"], s["K"], s["R"]
    w = lambda *shape: (shape, 0.0, E ** -0.5)  # noqa: E731
    scale = lambda n, std=0.02: {"w": ((n,), 1.0, std)}  # noqa: E731
    ffn = {"gate": {"w": w(E, F)}, "xform": {"w": w(E, F)},
           "out": {"w": w(F, E)}}
    attn = {"wq": w(E, H, d), "wk": w(E, KV, d), "wv": w(E, KV, d),
            "out": {"w": w(H * d, E)}}
    mamba = {
        "in_proj": w(E, 2 * Di),
        "conv_w": ((K, Di), 0.0, 0.3), "conv_b": ((Di,), 0.0, 0.1),
        "x_proj": w(Di, R + 2 * N),
        "dt_norm": ((R,), 1.0, 0.1), "b_norm": ((N,), 1.0, 0.1),
        "c_norm": ((N,), 1.0, 0.1),
        "dt_proj": {"w": ((R, Di), 0.0, 0.05), "b": ((Di,), -2.5, 1.0)},
        "A_log": ((Di, N), 1.0, 0.8), "D": ((Di,), 1.0, 0.3),
        "out_proj": w(Di, E),
    }
    blocks = [dict({"ln1": scale(E), "ln2": scale(E), "ffn": ffn},
                   **({"attn": attn} if kind == "attention"
                      else {"mamba": mamba}))
              for kind in layer_kinds(model)]
    spec = {"tok_emb": w(V, E), "blocks": blocks, "ln_f": scale(E)}
    if not s["tied"]:
        spec["lm_head"] = {"w": w(E, V)}
    return spec


def _is_leaf_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def make_params(seed: int, model: dict, sharding=None):
    """Weights from the seed in the configuration's ``param_dtype``, made
    on the device a leaf at a time (every leaf its own ``fold_in`` of the
    seed's key; drawn in float32, then rounded once)."""
    dtype = jnp.dtype(sizes(model)["dtype"])
    leaves, treedef = jax.tree_util.tree_flatten(
        param_spec(model), is_leaf=_is_leaf_spec)
    key = jax.random.key(seed % (2**31))

    @partial(jax.jit, static_argnums=(1, 2, 3), out_shardings=sharding)
    def draw(k, shape, mean, std):
        return (mean + std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    return jax.tree_util.tree_unflatten(treedef, [
        draw(jax.random.fold_in(key, i), shape, mean, std)
        for i, (shape, mean, std) in enumerate(leaves)])


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
    return jnp.einsum(eq, _fake_quant(a, quant), _fake_quant(b, quant),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


# -- forward ---------------------------------------------------------------


def _wide(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention(h, p, s, quant):
    """One sequence at a time (``lax.map``): the (H, T, T) maps of a
    batch would not fit."""
    H, KV, d = s["H"], s["KV"], s["d"]

    def one(hb):  # (T, E)
        T = hb.shape[0]
        q = _mm("te,ehd->thd", hb, p["wq"], quant)
        k = _mm("te,ehd->thd", hb, p["wk"], quant)
        v = _mm("te,ehd->thd", hb, p["wv"], quant)
        k = jnp.repeat(k, H // KV, axis=1)  # query head j reads K/V head j // group
        v = jnp.repeat(v, H // KV, axis=1)
        scores = _mm("thd,shd->hts", q, k, quant) / math.sqrt(d)
        keep = jnp.tril(jnp.ones((T, T), bool))
        maps = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        o = _mm("hts,shd->thd", maps, v, quant).reshape(T, H * d)
        return _mm("ti,io->to", o, p["out"]["w"], quant)

    return jax.lax.map(one, h)


def _mamba(h, p, s, quant):
    Di, N, K, R, eps = s["Di"], s["N"], s["K"], s["R"], s["eps"]
    B, T, _ = h.shape
    uz = _mm("bte,ei->bti", h, p["in_proj"], quant)
    u, z = uz[..., :Di], uz[..., Di:]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))  # causal: zeros before t=0
    u = jax.nn.silu(p["conv_b"] + sum(
        padded[:, k:k + T] * p["conv_w"][k] for k in range(K)))
    rbc = _mm("bti,ij->btj", u, p["x_proj"], quant)
    r = _rms_norm(rbc[..., :R], p["dt_norm"], eps)
    Bm = _rms_norm(rbc[..., R:R + N], p["b_norm"], eps)
    Cm = _rms_norm(rbc[..., R + N:], p["c_norm"], eps)
    delta = jax.nn.softplus(
        _mm("btr,ri->bti", r, p["dt_proj"]["w"], quant) + p["dt_proj"]["b"])
    A = -jnp.exp(p["A_log"])  # (Di, N)

    def step(state, xs):  # state (B, Di, N): one token of every sequence
        u_t, d_t, b_t, c_t = xs
        state = (jnp.exp(d_t[..., None] * A) * state
                 + (d_t * u_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + p["D"] * u_t

    time_major = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    _, ys = jax.lax.scan(step, jnp.zeros((B, Di, N), jnp.float32),
                         tuple(map(time_major, (u, delta, Bm, Cm))))
    y = time_major(ys) * jax.nn.silu(z)
    return _mm("bti,ie->bte", y, p["out_proj"], quant)


@lru_cache(maxsize=None)
def _layer_fn(kind: str, frozen_sizes: tuple, quant):
    """One block, jitted once a kind: the weights arrive in the stored
    dtype and are widened here, a layer at a time."""
    s = dict(frozen_sizes)

    @jax.jit
    def layer(x, blk):
        blk = _wide(blk)
        h = _rms_norm(x, blk["ln1"]["w"], s["eps"])
        if kind == "attention":
            x = x + _attention(h, blk["attn"], s, quant)
        else:
            x = x + _mamba(h, blk["mamba"], s, quant)
        h = _rms_norm(x, blk["ln2"]["w"], s["eps"])
        f = blk["ffn"]
        gated = jax.nn.silu(_mm("bte,ef->btf", h, f["gate"]["w"], quant)) * _mm(
            "bte,ef->btf", h, f["xform"]["w"], quant)
        return x + _mm("btf,fe->bte", gated, f["out"]["w"], quant)

    return layer


def hidden(params, idx, model: dict, quant=None):
    """(B, T) token ids -> the last layer's output (B, T, E), float32,
    before the final norm."""
    frozen = tuple(sorted(sizes(model).items()))
    x = params["tok_emb"][idx].astype(jnp.float32)
    for kind, blk in zip(layer_kinds(model), params["blocks"]):
        x = _layer_fn(kind, frozen, quant)(x, blk)
    return x


def _head(params, x, s, quant):
    x = _rms_norm(x, params["ln_f"]["w"].astype(jnp.float32), s["eps"])
    if s["tied"]:
        return _mm("te,ve->tv", x, params["tok_emb"].astype(jnp.float32), quant)
    return _mm("te,ev->tv", x, params["lm_head"]["w"].astype(jnp.float32),
               quant)


@lru_cache(maxsize=None)
def _head_fn(frozen_sizes: tuple, quant):
    s = dict(frozen_sizes)
    return jax.jit(lambda p, xb: _head(p, xb, s, quant))


def forward(params, idx, model: dict, quant=None):
    """(B, T) token ids -> float32 logits (B, T, V). For sequences whose
    logits fit at once; :func:`make_token_gaps` goes a sequence at a time."""
    head = _head_fn(tuple(sorted(sizes(model).items())), quant)
    x = hidden(params, idx, model, quant)
    return jnp.stack([head(_head_leaves(params), xb) for xb in x])


def _head_leaves(params):
    return {k: v for k, v in params.items() if k != "blocks"}


def loss_sum(params, x, y, model: dict, quant=None):
    """Summed next-token cross entropy of (B, T) inputs and targets."""
    logits = forward(params, x, model, quant)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


# -- serving: how far below the reference's best a served token lies --------


def make_token_gaps(model: dict, quant=None):
    """``gaps(params, seqs, served) -> (B, T)``: at every position, the
    reference's best logit minus its logit of ``served[b, t]``, the token
    that followed position t. With ``quant`` the token judged is the one
    the lower precision puts first at that position instead (the control:
    it need not decode). The (T, V) logits exist a sequence at a time."""
    s = sizes(model)

    @jax.jit
    def row_gaps(head, xb, served_b):
        logits = _head(head, xb, s, None)
        got = jnp.take_along_axis(logits, served_b[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    @jax.jit
    def row_best(head, xb):
        return jnp.argmax(_head(head, xb, s, quant), axis=-1)

    def gaps(params, seqs, served):
        head = _head_leaves(params)
        x = hidden(params, seqs, model)
        if quant is not None:
            xq = hidden(params, seqs, model, quant)
            served = jnp.stack([row_best(head, xb) for xb in xq])
        return jnp.stack([row_gaps(head, xb, sb)
                          for xb, sb in zip(x, served)])

    return gaps

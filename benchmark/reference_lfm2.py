"""The plain reference of the `lfm2` language model (Liquid AI's LFM2
mixture-of-experts models, ``model_type: lfm2_moe``, as
``LiquidAI/LFM2-24B-A2B``'s published ``config.json`` sizes it), written
from the equations below in straightforward ``jax.numpy``: float32
arithmetic, every matrix product under ``precision="highest"`` (what
``jax.default_matmul_precision("highest")`` sets, stated a product), the
convolution as three shifted elementwise products over the whole sequence,
attention over per-head keys and values with a causal mask, the experts a
plain loop over the held ones with every token offered to each; no kernels,
no cache, no window carried, no ring, no grouping. It imports nothing of
the program and takes nothing the program has made: the weights come from
:func:`make_params` (this file, from the seed), and the program is handed
the same tree.

  RMSNorm(x; w) = x / sqrt(mean(x^2) + norm_eps) * w, float32
  layer l (from 1):  h = x + Op_l(RMSNorm(x; ln1))
                     y = h + FF_l(RMSNorm(h; ln2))
  Op, layer_types[l] == "conv" (a gated short convolution):
      (B, C, u) = split3(h W_in)      three parts of n_embd, in that order
      z = B * u
      c_t = sum_k w_k * z_{t + k - (K-1)}   K = conv_taps (3), depthwise,
            causal, zeros before the sequence's start, no bias, no activation
      Op = (C * c) W_out
  Op, "full_attention" (H query heads of d on KV key/value heads):
      q = h W_q, k = h W_k, v = h W_v                     (no bias)
      q_h = RMSNorm_d(q_h; q_norm), k_h = RMSNorm_d(k_h; k_norm), THEN
      both rotated at their absolute position (theta = rope_theta,
      dimension i paired with i + d/2, no scaling)
      o_h = softmax(q_h . k / sqrt(d)) v over every j <= i;  Op = W_o [o]
  FF, l <= first_dense_layers: W_out(silu(W_gate h) * W_xform h)
  FF, experts: s = sigmoid(h W_r)  (float32)
      chosen = the experts_per_token largest of s + b   (b only ranks)
      g_i = s_i / (sum_chosen s + router_eps) * routed_scaling
      FF = sum_{i chosen, i HELD} g_i E_i(h),  E a SwiGLU; NO shared expert
  head: logits = RMSNorm(x_L; ln_f) tok_emb^T       (tied), float32

``held_experts`` ``[lo, hi)`` is an expert-parallel share: the tree holds
those experts only, the router ranks all ``num_experts``, and what the
absent experts would add is left out, here as in the program. The
benchmark's configuration holds all 64. Expert ``e``'s weights are drawn
from a key of their own, so the shares of one seed are slices of one uncut
model (``tests/test_lfm2.py`` adds two shares up to it).

Departures from the published description: none is intended. What the
published ``config.json`` does not say and this file sets (the
configuration's ``assumed``): the head tied to the token table; heads of
``n_embd / n_head``; the order ``B, C, u`` of the input projection's parts
and no activation around the convolution (the family's published modelling
code as the builder knows it); the router's epsilon 1e-6; ties in the
ranking to the lower index (``top_k``'s order).

The parameter tree's names and shapes are the checkpoint layout the program
reads (``models/lfm2.py``; weights stored ``(in, out)``), every leaf in the
configuration's ``param_dtype``. 2.7 G parameters are 10.8 GB in float32,
so the weights stay in the stated dtype and are widened where they are
used, a layer (an expert) at a time (the values are the ones the program
reads; the arithmetic is float32); the sequences go through one at a time,
each CUT to the whole ``_CUT`` positions past its last token that is not 0
(the harness lays sequences out padded with zeros to the ring's length; the
model is causal, so what it gives at a position does not depend on what
follows, and the positions cut off read a gap of 0), and attention goes a
K/V head and ``_QUERY_ROWS`` queries at a time.

``quant`` is the lower-precision control of the benchmark's `correct`
(PERF.md section 2): every matrix product, the router's and attention's two
included, takes its operands rounded to float8 (e4m3, one scale a tensor).
The configuration states bfloat16 compute, so float8 is the step below.
``fault`` plants one of ``FAULTS`` in the model (``selftest_lfm2.py
--witness``): what a served-token gap has to tell from rounding.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_TYPES = {"conv": "conv", "full_attention": "full"}
#: the planted faults: the convolution's window dropped at every edge of a
#: prefill chunk of ``FAULT_CHUNK`` tokens (a chunk's first tokens see
#: zeros where the last chunk's gated inputs stood; a sequence of at most
#: one chunk is cut in the middle); the taps applied in
#: reversed order; q and k rotated BEFORE the head norm; q and k not
#: normed; the router's bias added to the weights and not only to the
#: ranking; the weights not renormalised; the first eighth of the held
#: experts zeroed; ONE held expert zeroed
FAULTS = (None, "window_dropped", "taps_reversed", "rope_before_norm",
          "no_qk_norm", "bias_in_weights", "not_renormalised",
          "eighth_of_experts_zeroed", "one_expert_zeroed")
FAULT_CHUNK = 1024
#: a routed expert's down projection against the rule's ``fan_in ** -0.5``
#: (:func:`param_spec`)
ROUTED_DOWN_SCALE = 1 / 2
#: standard deviation of the router's correction bias (:func:`param_spec`)
ROUTER_BIAS_STD = 0.02


# -- sizes -----------------------------------------------------------------


def sizes(model: dict) -> dict:
    """Every size from a configuration file's ``model`` group, the
    defaults being the program's (``config.py:ModelConfig``)."""
    if model["model"] != "lfm2":
        raise ValueError(f"no reference for model kind {model['model']!r}")
    E, N = model["n_embd"], model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    return {
        "E": E, "H": model["n_head"], "V": model["vocab_size"],
        "KV": model.get("kv_heads") or model["n_head"],
        "d": model.get("head_dim") or E // model["n_head"],
        "K": model.get("conv_taps", 3),
        "theta": model.get("rope_theta", 10000.0),
        "F": model.get("ffn_hidden") or 4 * E,
        "N": N, "top": model.get("experts_per_token", 8),
        "Fm": model.get("moe_hidden", 1024),
        "scaling": model.get("routed_scaling", 1.0),
        "router_eps": model.get("router_eps", 0.0),
        "lo": lo, "hi": hi or N,
        "eps": model.get("norm_eps") or 1e-6,
        "tied": bool(model.get("tie_embeddings", False)),
        "dtype": model.get("param_dtype", "float32"),
    }


def layer_kinds(model: dict) -> list:
    """``(mixer, feed-forward)`` for every layer: ``"conv"`` or ``"full"``
    by the published ``layer_types``, ``"dense"`` for the first
    ``first_dense_layers`` and ``"moe"`` after."""
    dense = model.get("first_dense_layers", 1)
    return [(LAYER_TYPES[t], "dense" if l <= dense else "moe")
            for l, t in enumerate(model["layer_types"], 1)]


def param_spec(model: dict) -> dict:
    """The tree of ``(shape, mean, std)`` that :func:`make_params` fills;
    an expert leaf carries a fourth item, the range of experts it holds.
    Every leaf is random. A projection's entries have a standard deviation
    of ``fan_in ** -0.5`` of the width it reads, the token table's too (the
    tied head's logits then have a standard deviation of about 1: a greedy
    token among 65,536 leads its runner-up by a few tenths). The norm
    scales are N(1, 0.02) a block and N(1, 0.5) a head (q and k: scores of
    about unit size, and scales uneven enough over a head's 64 values that
    norming after the rotation is another model than norming before it: at
    N(1, 0.3) the witness read that fault 0.37, beside 0.24 for the
    program's own rounding).
    The convolution's taps are N(0.6, 0.2) in size with a sign drawn a tap
    and channel (:func:`make_params`): no tap is negligible beside the others
    (a tap under a tenth of its neighbours would let a dropped window or a
    reversed order pass unseen), and a channel's three taps differ, so
    their order matters. The router's correction bias is
    N(0, ``ROUTER_BIAS_STD``): it moves the ranking of experts whose scores
    lie within a few hundredths, which at 4 of 64 changes the chosen set of
    about half of the rows (``tests/test_lfm2.py`` counts them), and not
    the weights; wider, a few experts take several times the mean load (PERF.md
    section 6, PR 32). A routed expert's down projection is
    ``ROUTED_DOWN_SCALE`` of the rule's, as the `kimi_linear` and
    `nemotron_h` references scale theirs: with random weights a token's 4th
    and 5th experts of 64 score alike, a score that bfloat16 activations
    round the other way swaps one expert's whole term, a quarter of the
    layer's output here (no shared expert stands beside the four), and a
    trained router's peaked weights would not (PERF.md section 2 has the
    readings the scale was set from)."""
    s = sizes(model)
    E, H, KV, d, V = s["E"], s["H"], s["KV"], s["d"], s["V"]
    w = lambda *shape, fan=E: (shape, 0.0, fan ** -0.5)  # noqa: E731
    scale = lambda n, std=0.02: {"w": ((n,), 1.0, std)}  # noqa: E731
    conv = {"in_proj": w(E, 3 * E), "conv_w": ((s["K"], E), 0.6, 0.2),
            "out_proj": w(E, E)}
    attn = {"wq": w(E, H, d), "wk": w(E, KV, d), "wv": w(E, KV, d),
            "q_norm": ((d,), 1.0, 0.5), "k_norm": ((d,), 1.0, 0.5),
            "out": {"w": w(H * d, E, fan=H * d)}}
    F = s["F"]
    ffn = {"gate": {"w": w(E, F)}, "xform": {"w": w(E, F)},
           "out": {"w": w(F, E, fan=F)}}
    held = (s["lo"], s["hi"])
    G, Fm = s["hi"] - s["lo"], s["Fm"]
    moe = {
        "router": {"w": w(E, s["N"]),
                   "b": ((s["N"],), 0.0, ROUTER_BIAS_STD)},
        "experts": {"gate_up": ((G, E, 2 * Fm), 0.0, E ** -0.5, held),
                    "down": ((G, Fm, E), 0.0,
                             Fm ** -0.5 * ROUTED_DOWN_SCALE, held)},
    }
    blocks = [dict({"ln1": scale(E), "ln2": scale(E)},
                   **({"conv": conv} if mixer == "conv" else {"attn": attn}),
                   **({"ffn": ffn} if ff == "dense" else {"moe": moe}))
              for mixer, ff in layer_kinds(model)]
    tree = {"tok_emb": w(V, E), "blocks": blocks, "ln_f": scale(E)}
    if not s["tied"]:
        tree["lm_head"] = {"w": w(E, V)}
    return tree


def _is_leaf_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) in (3, 4) and isinstance(x[0], tuple)


def make_params(seed: int, model: dict, sharding=None):
    """Weights from the seed in the configuration's ``param_dtype``, made
    on the device a leaf at a time (every leaf its own ``fold_in`` of the
    seed's key, every expert of an expert leaf its own ``fold_in`` of the
    leaf's; drawn in float32, then rounded once). The convolution's taps
    (the leaves named ``conv_w``) take a sign a tap and channel from a
    second draw."""
    dtype = jnp.dtype(sizes(model)["dtype"])
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_spec(model), is_leaf=_is_leaf_spec)
    key = jax.random.key(seed % (2**31))

    @partial(jax.jit, static_argnums=(1, 2, 3, 4, 5), out_shardings=sharding)
    def draw(k, shape, mean, std, held=None, signed=False):
        def normal(kk, sh):
            x = mean + std * jax.random.normal(kk, sh, jnp.float32)
            if signed:
                x = x * jnp.where(jax.random.bernoulli(
                    jax.random.fold_in(kk, 1), 0.5, sh), 1.0, -1.0)
            return x.astype(dtype)

        if held is None:
            return normal(k, shape)
        return jax.vmap(lambda e: normal(jax.random.fold_in(k, e), shape[1:])
                        )(jnp.arange(*held))

    def one(i, path, leaf):
        shape, mean, std, *held = leaf
        signed = getattr(path[-1], "key", None) == "conv_w"
        return draw(jax.random.fold_in(key, i), shape, mean, std,
                    held[0] if held else None, signed)

    return jax.tree_util.tree_unflatten(treedef, [
        one(i, path, leaf) for i, (path, leaf) in enumerate(paths)])


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

_QUERY_ROWS = 1024  # queries whose score maps exist at once, a K/V head
_CUT = 1024  # a padded sequence is computed in whole multiples of this


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * _f32(w)


def _swiglu(h, p, quant):
    gated = jax.nn.silu(_mm("te,ef->tf", h, p["gate"]["w"], quant)) * _mm(
        "te,ef->tf", h, p["xform"]["w"], quant)
    return _mm("tf,fe->te", gated, p["out"]["w"], quant)


def _conv(h, p, s, quant, fault):
    """The gated short convolution over one whole sequence ``h`` (T, E)."""
    E, K = s["E"], s["K"]
    T = h.shape[0]
    bcu = _mm("te,ef->tf", h, p["in_proj"], quant)
    gate_in, gate_out, u = bcu[:, :E], bcu[:, E:2 * E], bcu[:, 2 * E:]
    z = gate_in * u
    taps = _f32(p["conv_w"])
    if fault == "taps_reversed":
        taps = taps[::-1]
    padded = jnp.concatenate([jnp.zeros((K - 1, E), jnp.float32), z])
    t = jnp.arange(T)[:, None]
    # a sequence no longer than a chunk is cut in two, so that it has an edge
    chunk = FAULT_CHUNK if T > FAULT_CHUNK else max(K, T // 2)
    c = jnp.zeros_like(z)
    for k in range(K):
        back = K - 1 - k  # tap k reads z_{t - back}
        term = taps[k] * padded[k:k + T]
        if fault == "window_dropped" and back:
            # a chunk's first `back` tokens find zeros in the window
            term = jnp.where(t % chunk >= back, term, 0.0)
        c = c + term
    return _mm("te,eo->to", gate_out * c, p["out_proj"], quant)


def _rotate(x, theta):
    """``x`` (heads, T, d) rotated at positions 0 .. T-1, dimension i
    paired with i + d/2 (``rotate_half``)."""
    T, d = x.shape[-2:]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # (T, d/2)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + turned * sin


def _attention(h, p, s, quant, fault):
    H, KV, d = s["H"], s["KV"], s["d"]
    T = h.shape[0]
    q = _mm("te,ehd->htd", h, p["wq"], quant)
    k = _mm("te,ehd->htd", h, p["wk"], quant)
    v = _mm("te,ehd->htd", h, p["wv"], quant)
    if fault == "rope_before_norm":
        q, k = _rotate(q, s["theta"]), _rotate(k, s["theta"])
    if fault != "no_qk_norm":
        q = _rms_norm(q, p["q_norm"], s["eps"])
        k = _rms_norm(k, p["k_norm"], s["eps"])
    if fault != "rope_before_norm":
        q, k = _rotate(q, s["theta"]), _rotate(k, s["theta"])
    rows = _QUERY_ROWS if T % _QUERY_ROWS == 0 else T
    j = jnp.arange(T)[None, :]

    def head(xs):  # a K/V head with the H / KV query heads it serves
        q_g, k_h, v_h = xs  # (G, T, d), (T, d), (T, d)

        def piece(xs):
            q_b, i0 = xs  # (G, rows, d)
            i = i0 + jnp.arange(rows)[:, None]
            scores = _mm("gtd,sd->gts", q_b, k_h, quant) / math.sqrt(d)
            maps = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf),
                                  axis=-1)
            return _mm("gts,sd->gtd", maps, v_h, quant)

        G = q_g.shape[0]
        o = jax.lax.map(piece, (
            q_g.reshape(G, T // rows, rows, d).swapaxes(0, 1),
            jnp.arange(0, T, rows)))  # (pieces, G, rows, d)
        return o.swapaxes(0, 1).reshape(G, T, d)

    o = jax.lax.map(head, (q.reshape(KV, H // KV, T, d), k, v))  # (KV, G, T, d)
    o = o.reshape(H, T, d).swapaxes(0, 1).reshape(T, H * d)
    return _mm("ti,io->to", o, p["out"]["w"], quant)


def route(h, router, s, quant=None, fault=None):
    """(T, N): the weight a token gives an expert, 0 where not chosen."""
    scores = jax.nn.sigmoid(_mm("te,en->tn", h, router["w"], quant))
    biased = scores + _f32(router["b"])
    _, chosen = jax.lax.top_k(biased, s["top"])
    picked = jnp.take_along_axis(
        biased if fault == "bias_in_weights" else scores, chosen, axis=-1)
    weights = picked * s["scaling"]
    if fault != "not_renormalised":
        weights = weights / (jnp.sum(picked, axis=-1, keepdims=True)
                             + s["router_eps"])
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(weights)


def _moe(h, p, s, quant, fault):
    """Router over all N experts, then every HELD expert in turn over
    every token, weighted by what the router gave it there (0 for a token
    that did not choose it). No shared expert."""
    dense = route(h, p["router"], s, quant, fault)
    Fm, G = s["Fm"], s["hi"] - s["lo"]
    held = s["lo"] + jnp.arange(G)
    zeroed = {"eighth_of_experts_zeroed": max(1, G // 8),
              "one_expert_zeroed": 1}.get(fault, 0)
    alive = (jnp.arange(G) >= zeroed).astype(jnp.float32)

    def expert(y, xs):
        e, keep, gate_up, down = xs
        gu = _mm("te,ef->tf", h, gate_up, quant)
        out = _mm("tf,fe->te", jax.nn.silu(gu[:, :Fm]) * gu[:, Fm:], down,
                  quant)
        return y + keep * dense[:, e][:, None] * out, None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (held, alive, p["experts"]["gate_up"], p["experts"]["down"]))
    return y


@lru_cache(maxsize=None)
def _layer_fn(kinds: tuple, frozen_sizes: tuple, quant, fault):
    """One block over one sequence (T, E), jitted once a pair of kinds:
    the weights arrive in the stored dtype and are widened where used."""
    s = dict(frozen_sizes)
    mixer, ff = kinds
    eps = s["eps"]

    @jax.jit
    def layer(x, blk):
        h = _rms_norm(x, blk["ln1"]["w"], eps)
        x = x + (_conv(h, blk["conv"], s, quant, fault) if mixer == "conv"
                 else _attention(h, blk["attn"], s, quant, fault))
        h = _rms_norm(x, blk["ln2"]["w"], eps)
        return x + (_swiglu(h, blk["ffn"], quant) if ff == "dense"
                    else _moe(h, blk["moe"], s, quant, fault))

    return layer


def _frozen(model: dict) -> tuple:
    return tuple(sorted(sizes(model).items()))


def _cut(ids, served=None) -> int:
    """Positions of a row that are worth computing: up to the last id (of
    the row or of what was served after it) that is not 0, in whole
    ``_CUT``s; the whole row where it is shorter than one."""
    row = np.asarray(ids)
    T = row.shape[0]
    if T <= _CUT:
        return T
    live = row != 0
    if served is not None:
        live = live | (np.asarray(served) != 0)
    last = int(np.flatnonzero(live).max()) + 1 if live.any() else 1
    return min(T, -(-last // _CUT) * _CUT)


def hidden(params, idx, model: dict, quant=None, fault=None, served=None):
    """(B, T) token ids -> the last layer's output, a list of (T_b, E)
    float32 before the final norm, a sequence at a time, each cut to
    :func:`_cut`'s length."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    rows = []
    for b, ids in enumerate(idx):
        n = _cut(ids, None if served is None else served[b])
        x = _f32(params["tok_emb"][ids[:n]])
        for kinds, blk in zip(layer_kinds(model), params["blocks"]):
            x = _layer_fn(kinds, _frozen(model), quant, fault)(x, blk)
        rows.append(x)
    return rows


def _head(params, x, s, quant):
    x = _rms_norm(x, params["ln_f"]["w"], s["eps"])
    if s["tied"]:
        return _mm("te,ve->tv", x, params["tok_emb"], quant)
    return _mm("te,ev->tv", x, params["lm_head"]["w"], quant)


@lru_cache(maxsize=None)
def _head_fn(frozen_sizes: tuple, quant):
    s = dict(frozen_sizes)
    return jax.jit(lambda p, xb: _head(p, xb, s, quant))


def _head_leaves(params):
    return {k: v for k, v in params.items() if k != "blocks"}


def forward(params, idx, model: dict, quant=None, fault=None):
    """(B, T) token ids -> float32 logits (B, T, V). For sequences whose
    logits fit at once and are computed whole (T <= ``_CUT``, or no id 0
    at a row's end); :func:`make_token_gaps` goes a piece at a time."""
    head = _head_fn(_frozen(model), quant)
    T = idx.shape[1]
    rows = []
    for ids in idx:
        x = hidden(params, ids[None], model, quant, fault,
                   served=np.ones((1, T), np.int32))[0]
        rows.append(head(_head_leaves(params), x))
    return jnp.stack(rows)


# -- serving: how far below the reference's best a served token lies --------

_HEAD_ROWS = 512  # positions whose logits exist at once: 134 MB at V = 65,536


def make_token_gaps(model: dict, quant=None, fault=None):
    """``gaps(params, seqs, served) -> (B, T)``: at every position, the
    reference's best logit minus its logit of ``served[b, t]``, the token
    that followed position t (0 at the positions :func:`_cut` left out).
    With ``quant`` (or a planted ``fault``) the token judged is the one the
    lower precision (the faulty model) puts first at that position
    instead: the control need not decode. The logits exist ``_HEAD_ROWS``
    positions at a time."""
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
        T = seqs.shape[1]
        x = hidden(params, seqs, model, served=served)
        if quant is not None or fault is not None:
            xq = hidden(params, seqs, model, quant, fault, served=served)
            served = [pieces(row_best, head, xb) for xb in xq]
        out = [pieces(row_gaps, head, xb, jnp.asarray(sb)[:xb.shape[0]])
               for xb, sb in zip(x, served)]
        return jnp.stack([jnp.pad(g, (0, T - g.shape[0])) for g in out])

    return gaps

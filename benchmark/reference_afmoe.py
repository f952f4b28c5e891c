"""The plain reference of the `afmoe` language model (Arcee's Trinity, as
``arcee-ai/Trinity-Large-Preview``'s published ``config.json`` sizes it),
written from the equations below in straightforward ``jax.numpy``: float32
arithmetic, every matrix product under ``precision="highest"``, attention
over per-head keys and values with a causal mask and, on sliding layers, a
band on absolute positions, the experts a plain loop over the held ones
with every token offered to each; no kernels, no cache, no ring, no
grouping. It imports nothing of the program and takes nothing the program
has made: the weights come from :func:`make_params` (this file, from the
seed), and the program is handed the same tree.

  x = E[token] sqrt(n_embd)                      (``mup_enabled``)
  layer l (from 1), RMSNorm with a learned scale, four a block:
      x = x + N2(attn_l(N1(x)));  x = x + N4(mlp_l(N3(x)))
  attention (H query heads of d on KV key/value heads, each serving H / KV):
      q = h W_q, k = h W_k, v = h W_v, g = h W_g          (no bias)
      q_h = RMSNorm_d(q_h), k_h = RMSNorm_d(k_h)  (one scale for q, one for
      k, shared by the heads)
      layer_types[l] == "sliding_attention": q and k rotated at their
          absolute position (theta = rope_theta, dimension i paired with
          i + d/2); query i sees key j iff j <= i and i - j < sliding_window
      "full_attention": nothing is rotated; query i sees every j <= i
      o_h = softmax(q_h . k / sqrt(d)) v;  y = W_o [o * sigmoid(g)]
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
reads (``models/afmoe.py``; weights stored ``(in, out)``), every leaf in the
configuration's ``param_dtype``. 2.5 G parameters are 10 GB in float32, so
the weights stay in the stated dtype and are widened where they are used, a
layer (an expert) at a time (the values are the ones the program reads; the
arithmetic is float32), the sequences go through one at a time, and
attention goes a K/V head and ``_QUERY_ROWS`` queries at a time: a
7,424 x 7,424 float32 score map a head is 220 MB, 48 of them 10.6 GB.

``quant`` is the lower-precision control of the benchmark's `correct`
(PERF.md section 2): every matrix product, the router's and attention's two
included, takes its operands rounded to float8 (e4m3, one scale a tensor).
The configuration states bfloat16 compute, so float8 is the step below.
``fault`` plants one of the witnesses of ``selftest_afmoe.py --witness``:
``"no_window"`` (a sliding layer sees every earlier position),
``"rope_on_full"`` (the full layers rotate too), ``"wrong_held_range"``
(the held experts' weights answer for the G experts AFTER the held range:
the share taken for its neighbour's).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_TYPES = {"sliding_attention": "window", "full_attention": "full"}
FAULTS = (None, "no_window", "rope_on_full", "wrong_held_range")


# -- sizes -----------------------------------------------------------------


def sizes(model: dict) -> dict:
    """Every size from a configuration file's ``model`` group, the
    defaults being the program's (``config.py:ModelConfig``)."""
    if model["model"] != "afmoe":
        raise ValueError(f"no reference for model kind {model['model']!r}")
    E, N = model["n_embd"], model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    return {
        "E": E, "H": model["n_head"], "V": model["vocab_size"],
        "KV": model.get("kv_heads") or model["n_head"],
        "d": model.get("head_dim") or E // model["n_head"],
        "W": model.get("sliding_window", 0),
        "theta": model.get("rope_theta", 10000.0),
        "F": model.get("ffn_hidden") or 4 * E,
        "N": N, "top": model.get("experts_per_token", 8),
        "Fm": model.get("moe_hidden", 1024),
        "scaling": model.get("routed_scaling", 1.0),
        "lo": lo, "hi": hi or N,
        "eps": model.get("norm_eps") or 1e-6,
        "dtype": model.get("param_dtype", "float32"),
    }


def layer_kinds(model: dict) -> list:
    """``(attention, mlp)`` for every layer: ``"window"`` or ``"full"`` by
    the published ``layer_types``, ``"dense"`` for the first
    ``first_dense_layers`` and ``"moe"`` after."""
    dense = model.get("first_dense_layers", 1)
    return [(LAYER_TYPES[t], "dense" if l <= dense else "moe")
            for l, t in enumerate(model["layer_types"], 1)]


def param_spec(model: dict) -> dict:
    """The tree of ``(shape, mean, std)`` that :func:`make_params` fills;
    an expert leaf carries a fourth item, the range of experts it holds.
    Every leaf is random. A projection's entries have a standard deviation
    of ``fan_in ** -0.5`` of the width it reads, the token table's too: the
    scaled embedding then has unit entries, every sublayer's output leaves
    its second norm at about unit size, and the head's logits have a
    standard deviation of about 1 (a greedy token among 25,024 leads its
    runner-up by a few tenths). The norm scales are N(1, 0.02) a block and
    N(1, 0.1) a head (q and k: scores of unit size, so a query's weight
    is spread over about a third of the keys it sees, and what a sliding
    layer sees past its window would move its output by half its size).
    The router's correction bias is N(0, 0.02): it moves the ranking of
    experts whose scores lie within a few hundredths, not the weights
    (PERF.md section 6, PR 32: wider, a few experts take several times the
    mean load and the held share's work swings a seed). A routed expert's
    down projection is a SIXTH of the rule's (the `kimi_linear` reference
    draws a third, for the same reason): with random weights a token's 4th
    and 5th experts score alike, a score that bfloat16 rounds the other way
    swaps one expert's whole term, which the block's fourth norm then
    scales up with the rest, and a trained router's peaked weights would
    not. At a third the program's served-token gap read 0.01-0.03 on most
    seeds, 0.07-0.22 where a judged token met a swap and 0.311 once, beside
    a float8 control of 0.33-0.35 that does NOT come from the experts (it
    reads the same with their down projection zeroed): no limit could stand
    between the two (PERF.md section 2, PR 36)."""
    s = sizes(model)
    E, H, KV, d, V = s["E"], s["H"], s["KV"], s["d"], s["V"]
    w = lambda *shape, fan=E: (shape, 0.0, fan ** -0.5)  # noqa: E731
    scale = lambda n, std=0.02: {"w": ((n,), 1.0, std)}  # noqa: E731
    mlp = lambda F: {"gate": {"w": w(E, F)}, "xform": {"w": w(E, F)},  # noqa: E731
                     "out": {"w": w(F, E, fan=F)}}
    attn = {
        "wq": w(E, H, d), "wk": w(E, KV, d), "wv": w(E, KV, d),
        "wg": w(E, H * d), "q_norm": ((d,), 1.0, 0.1),
        "k_norm": ((d,), 1.0, 0.1), "out": {"w": w(H * d, E, fan=H * d)},
    }
    held = (s["lo"], s["hi"])
    G, Fm = s["hi"] - s["lo"], s["Fm"]
    moe = {
        "router": {"w": w(E, s["N"]), "b": ((s["N"],), 0.0, 0.02)},
        "experts": {"gate_up": ((G, E, 2 * Fm), 0.0, E ** -0.5, held),
                    "down": ((G, Fm, E), 0.0, Fm ** -0.5 / 6, held)},
        "shared": mlp(Fm),
    }
    blocks = [dict({"ln1": scale(E), "ln1_post": scale(E), "ln2": scale(E),
                    "ln2_post": scale(E), "attn": attn},
                   **({"ffn": mlp(s["F"])} if kind == "dense"
                      else {"moe": moe}))
              for _, kind in layer_kinds(model)]
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

_QUERY_ROWS = 1024  # queries whose score maps exist at once, a K/V head


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * _f32(w)


def _swiglu(h, p, quant):
    gated = jax.nn.silu(_mm("te,ef->tf", h, p["gate"]["w"], quant)) * _mm(
        "te,ef->tf", h, p["xform"]["w"], quant)
    return _mm("tf,fe->te", gated, p["out"]["w"], quant)


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


def _attention(h, p, s, kind, quant, fault):
    H, KV, d, W = s["H"], s["KV"], s["d"], s["W"]
    T = h.shape[0]
    q = _rms_norm(_mm("te,ehd->htd", h, p["wq"], quant), p["q_norm"], s["eps"])
    k = _rms_norm(_mm("te,ehd->htd", h, p["wk"], quant), p["k_norm"], s["eps"])
    v = _mm("te,ehd->htd", h, p["wv"], quant)
    gate = jax.nn.sigmoid(_mm("te,ei->ti", h, p["wg"], quant))
    if kind == "window" or fault == "rope_on_full":
        q, k = _rotate(q, s["theta"]), _rotate(k, s["theta"])
    banded = kind == "window" and fault != "no_window"
    rows = _QUERY_ROWS if T % _QUERY_ROWS == 0 else T
    j = jnp.arange(T)[None, :]

    def head(xs):  # a K/V head with the H / KV query heads it serves
        q_g, k_h, v_h = xs  # (G, T, d), (T, d), (T, d)

        def piece(xs):
            q_b, i0 = xs  # (G, rows, d)
            i = i0 + jnp.arange(rows)[:, None]
            keep = (j <= i) & (i - j < W) if banded else j <= i
            scores = _mm("gtd,sd->gts", q_b, k_h, quant) / math.sqrt(d)
            maps = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
            return _mm("gts,sd->gtd", maps, v_h, quant)

        G = q_g.shape[0]
        o = jax.lax.map(piece, (
            q_g.reshape(G, T // rows, rows, d).swapaxes(0, 1),
            jnp.arange(0, T, rows)))  # (pieces, G, rows, d)
        return o.swapaxes(0, 1).reshape(G, T, d)

    o = jax.lax.map(head, (q.reshape(KV, H // KV, T, d), k, v))  # (KV, G, T, d)
    o = o.reshape(H, T, d).swapaxes(0, 1).reshape(T, H * d) * gate
    return _mm("ti,io->to", o, p["out"]["w"], quant)


def _moe(h, p, s, quant, fault):
    """Router over all N experts, then every HELD expert in turn over
    every token, weighted by what the router gave it there (0 for a token
    that did not choose it)."""
    scores = jax.nn.sigmoid(_mm("te,en->tn", h, p["router"]["w"], quant))
    _, chosen = jax.lax.top_k(scores + _f32(p["router"]["b"]), s["top"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * s["scaling"]
    # (T, N): the weight a token gives an expert, 0 where not chosen
    dense = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(weights)
    Fm = s["Fm"]
    held = s["lo"] + jnp.arange(s["hi"] - s["lo"])
    if fault == "wrong_held_range":
        held = (held + s["hi"] - s["lo"]) % s["N"]

    def expert(y, xs):
        e, gate_up, down = xs
        gu = _mm("te,ef->tf", h, gate_up, quant)
        out = _mm("tf,fe->te", jax.nn.silu(gu[:, :Fm]) * gu[:, Fm:], down,
                  quant)
        return y + dense[:, e][:, None] * out, None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (held, p["experts"]["gate_up"], p["experts"]["down"]))
    return y + _swiglu(h, p["shared"], quant)


@lru_cache(maxsize=None)
def _layer_fn(kinds: tuple, frozen_sizes: tuple, quant, fault):
    """One block over one sequence (T, E), jitted once a pair of kinds:
    the weights arrive in the stored dtype and are widened where used."""
    s = dict(frozen_sizes)
    attn_kind, mlp_kind = kinds
    eps = s["eps"]

    @jax.jit
    def layer(x, blk):
        h = _rms_norm(x, blk["ln1"]["w"], eps)
        a = _attention(h, blk["attn"], s, attn_kind, quant, fault)
        x = x + _rms_norm(a, blk["ln1_post"]["w"], eps)
        h = _rms_norm(x, blk["ln2"]["w"], eps)
        y = (_swiglu(h, blk["ffn"], quant) if mlp_kind == "dense"
             else _moe(h, blk["moe"], s, quant, fault))
        return x + _rms_norm(y, blk["ln2_post"]["w"], eps)

    return layer


def _frozen(model: dict) -> tuple:
    return tuple(sorted(sizes(model).items()))


def hidden(params, idx, model: dict, quant=None, fault=None):
    """(B, T) token ids -> the last layer's output (B, T, E), float32,
    before the final norm; a sequence at a time."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    scale = math.sqrt(model["n_embd"])
    rows = []
    for ids in idx:
        x = _f32(params["tok_emb"][ids]) * scale
        for kinds, blk in zip(layer_kinds(model), params["blocks"]):
            x = _layer_fn(kinds, _frozen(model), quant, fault)(x, blk)
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

_HEAD_ROWS = 1024  # positions whose logits exist at once: 0.1 GB at V = 25,024


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

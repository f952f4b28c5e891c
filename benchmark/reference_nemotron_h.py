"""The plain reference of the `nemotron_h` language model (NVIDIA's
Nemotron-3-Super, ``model_type: nemotron_h``, as
``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``'s ``config.json`` sizes
it), written from the layers' equations in straightforward ``jax.numpy``:
float32 arithmetic, every matrix product under ``precision="highest"``, the
Mamba-2 mixer as the TOKEN-BY-TOKEN recurrence (a ``lax.scan`` over time;
the program scans a prompt in chunks as matrix products, ops/ssd.py, and
this file knows nothing of chunks), attention over whole sequences with
full (T, T) maps, the experts a plain loop over the held ones with every
token offered to each, no kernels, no cache, no grouping. It imports
nothing of the program and takes nothing the program has made: the weights
come from :func:`make_params` (this file, from the seed), and the program
is handed the same tree.

  layer l:  x = x + F_l(RMSNorm(x)),  F_l by hybrid_override_pattern;
            logits = RMSNorm(x) W_head   (no bias but the convolution's,
            eps 1e-5, untied head, no position information of any kind)
  M:  [z (Di) ; xBC (Di + 2 G N) ; dt (Hm)] = h W_in          Di = Hm heads x P
      xBC = silu(conv_K(xBC) + b_conv)  (causal, depthwise);  [x ; B (G x N) ; C (G x N)] = xBC
      dt = softplus(dt + dt_bias);  A_p = -exp(A_log_p)       one scalar a head p
      H_t,p = exp(dt_t,p A_p) H_t-1,p + dt_t,p x_t,p (x) B_t,g(p)   H (P, N), g(p) = p // (Hm / G)
      y_t,p = H_t,p C_t,g(p) + D_p x_t,p
      y = y * silu(z);  y = y / rms(y over each group's Di / G channels) * w;  out = y W_out
  *:  q (H heads of d), k, v (KV heads, each shared by H / KV query heads), NO rotation;
      causal softmax(q k^T / sqrt(d)) v, W_o
  E:  s = sigmoid(h W_r) over N;  chosen = the top largest of s + b
      w_i = scaling * s_i / sum_chosen s;  u = h W_in_latent
      E_i(u) = W_down,i relu(W_up,i u)^2                      (ungated, in the latent)
      y = (sum_{i chosen and HELD} w_i E_i(u)) W_out_latent + W_down relu(W_up h)^2

``held_experts`` ``[lo, hi)`` is an expert-parallel share: the tree holds
those experts only, the router ranks all ``num_experts``, and what the
absent experts would add is left out BEFORE the latent's way out, here as
in the program. Expert ``e``'s weights are drawn from a key of their own,
so the shares of one seed are slices of one uncut model. The published
multi-token-prediction module is left out (the configuration's file says
why).

The parameter tree's names and shapes are the checkpoint layout the program
reads (``models/nemotron_h.py``; weights stored ``(in, out)``), every leaf
in the configuration's ``param_dtype``. 4.65 G parameters are 18.6 GB in
float32, so the weights stay in the stated dtype and are widened where they
are used, an expert at a time, and the sequences go through one at a time,
each CUT to the whole thousand of positions past its last token that is
not 0 (the harness lays sequences out padded with zeros to the ring's
length; the model is causal, so what it gives at a position does not
depend on what follows, and the positions cut off read a gap of 0).

``quant`` is the lower-precision control of the benchmark's `correct`
(PERF.md section 2): every matrix product, the router's, attention's two
and the recurrence's read of the state included, takes its operands rounded
to float8 (e4m3, one scale a tensor). The configuration states bfloat16
compute, so float8 is the step below. ``fault`` plants one of ``FAULTS`` in
the model (``selftest_nemotron_h.py --witness``): what a served-token gap
has to tell from rounding.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: the planted faults: every head reading group 0's B and C; the gated norm
#: over all channels at once; relu without the square (routed and shared);
#: the router's weights not renormalised; the first held expert zeroed, and
#: the first EIGHTH of the held experts zeroed (one expert of 128 held is
#: 1/22 of a row's routed weight on one position in 23: too little for any
#: limit that passes a bfloat16 program, PERF.md section 2); the state
#: dropped at every multiple of chunk_size (a chunked scan that loses the
#: state it is handed)
FAULTS = (None, "group0_bc", "norm_all_channels", "relu_no_square",
          "weights_not_renormalised", "held_expert_zeroed",
          "held_eighth_zeroed", "chunk_state_dropped")
#: how far a routed expert's down projection is scaled below the rule's
#: (:func:`param_spec`)
ROUTED_DOWN_SCALE = 1.0 / 3.0
LAYERS = {"M": "mamba2", "*": "attention", "E": "moe"}


# -- sizes -----------------------------------------------------------------


def sizes(model: dict) -> dict:
    """Every size from a configuration file's ``model`` group, the
    defaults being the program's (``config.py:ModelConfig``)."""
    if model["model"] != "nemotron_h":
        raise ValueError(f"no reference for model kind {model['model']!r}")
    E, H, N = model["n_embd"], model["n_head"], model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    Hm, P = model["mamba_num_heads"], model.get("mamba_head_dim", 64)
    G, Ns = model.get("n_groups", 1), model.get("ssm_state_size", 128)
    return {
        "E": E, "H": H, "KV": model.get("kv_heads") or H, "d": E // H,
        "V": model["vocab_size"],
        "Hm": Hm, "P": P, "G": G, "Ns": Ns, "Di": Hm * P,
        "Dc": Hm * P + 2 * G * Ns, "K": model.get("mamba_d_conv", 4),
        "Q": model.get("chunk_size", 128),
        "N": N, "top": model.get("experts_per_token", 8),
        "Fm": model.get("moe_hidden", 1024),
        "Fs": model.get("moe_shared_hidden", 0),
        "Lz": model.get("moe_latent_size", 0) or E,
        "latent": bool(model.get("moe_latent_size", 0)),
        "scaling": model.get("routed_scaling", 1.0),
        "lo": lo, "hi": hi or N,
        "eps": model.get("norm_eps") or 1e-6,
        "dtype": model.get("param_dtype", "float32"),
    }


def layer_kinds(model: dict) -> list:
    """``"mamba2"``, ``"attention"`` or ``"moe"`` for every layer, by the
    published pattern's letters."""
    if model.get("mlp_act", "silu") != "relu2":
        raise ValueError("the reference is of the published relu2 experts")
    return [LAYERS[c] for c in model["hybrid_override_pattern"]]


def param_spec(model: dict) -> dict:
    """The tree of ``(shape, mean, std)`` that :func:`make_params` fills;
    an expert leaf carries a fourth item, the range of experts it holds.
    Every leaf is random, by the rule of ``reference_kimi_linear.py``: a
    projection's entries have a standard deviation of ``fan_in ** -0.5``
    of the width it reads, so that at any width a layer's output outweighs
    the token's own embedding in the residual stream; norm scales N(1,
    0.02), the mixer's gated norm N(1, 0.1). The recurrence is exercised
    on both sides by ``reference_jamba.py``'s rule: ``A = -exp(A_log)``
    has a median of -2.7 (0.5 to 13 over two sigma) and ``dt =
    softplus(N(-2.5, 1.4))`` (the bias N(-2.5, 1), the projection's part
    N(0, 1)) a median of 0.08, so a head's decay ``exp(dt A)`` runs from
    0.997 (a state that remembers for hundreds of tokens) to 1e-5 (one
    that forgets at once); ``D`` N(1, 0.3). The router's correction bias
    is N(0, 0.02), as kimi's: it moves the ranking of experts whose scores
    lie within a few hundredths, not the weights. A routed expert's down
    projection is ``ROUTED_DOWN_SCALE`` of the rule's: with random weights
    a token's 22nd and 23rd experts of 512 score alike (a trained router's
    are peaked), so a score that bfloat16 activations round the other way
    swaps a whole term of weight 5 / 22, and at the full scale those swaps,
    not the arithmetic, would set the served-token gap (PERF.md section 2
    has the witness's readings). Every relu^2 MLP's down projection (the
    shared expert's, and each routed expert's own) is drawn CENTRED: the
    entries of a column sum to zero over the hidden width (a fifth item of
    the leaf's spec, the axis). ``relu(.)^2`` of a standard normal has a
    mean of 0.5 beside a standard deviation of 1.1, so an uncentred random
    down projection adds the SAME vector, two fifths of its output's norm,
    to every token; the next router then scores every token with the same
    offset an expert (0.3 sigma by the last layer), a few experts get twice
    the mean's rows and others half, and how many of the held experts a
    decode step has to read, 55% of its time, differs by a tenth from seed
    to seed (the cell's `itl_mean_ms` spread 5.7-6.6% over sets of six with
    uncentred draws: PERF.md section 6). A trained model's router is kept
    even by its correction bias, which is what that bias is for; centring
    is this draw's way to the same routing."""
    s = sizes(model)
    E, H, KV, d, V = s["E"], s["H"], s["KV"], s["d"], s["V"]
    Hm, Di, Dc, K = s["Hm"], s["Di"], s["Dc"], s["K"]
    Lz, Fm, Fs = s["Lz"], s["Fm"], s["Fs"]
    w = lambda *shape, fan=E: (shape, 0.0, fan ** -0.5)  # noqa: E731
    scale = lambda n, std=0.02: {"w": ((n,), 1.0, std)}  # noqa: E731
    mamba2 = {
        "in_proj": w(E, Di + Dc + Hm),
        "conv_w": ((K, Dc), 0.0, 0.3), "conv_b": ((Dc,), 0.0, 0.1),
        "dt_bias": ((Hm,), -2.5, 1.0), "A_log": ((Hm,), 1.0, 0.8),
        "D": ((Hm,), 1.0, 0.3), "norm": ((Di,), 1.0, 0.1),
        "out_proj": w(Di, E, fan=Di),
    }
    attn = {"wq": w(E, H, d), "wk": w(E, KV, d), "wv": w(E, KV, d),
            "out": {"w": w(H * d, E, fan=H * d)}}
    held = (s["lo"], s["hi"])
    G = s["hi"] - s["lo"]
    moe = {
        "router": {"w": w(E, s["N"]), "b": ((s["N"],), 0.0, 0.02)},
        "experts": {"up": ((G, Lz, Fm), 0.0, Lz ** -0.5, held),
                    "down": ((G, Fm, Lz), 0.0,
                             Fm ** -0.5 * ROUTED_DOWN_SCALE, held, 0)},
        "shared": {"up": {"w": w(E, Fs)},
                   "down": {"w": w(Fs, E, fan=max(Fs, 1)) + (None, 0)}},
    }
    if s["latent"]:
        moe["latent_in"] = w(E, Lz)
        moe["latent_out"] = w(Lz, E, fan=Lz)
    by_kind = {"mamba2": {"ln1": scale(E), "mamba2": mamba2},
               "attention": {"ln1": scale(E), "attn": attn},
               "moe": {"ln2": scale(E), "moe": moe}}
    return {"tok_emb": w(V, E),
            "blocks": [by_kind[kind] for kind in layer_kinds(model)],
            "ln_f": scale(E), "lm_head": {"w": w(E, V)}}


def _is_leaf_spec(x) -> bool:
    return (isinstance(x, tuple) and len(x) in (3, 4, 5)
            and isinstance(x[0], tuple))


def make_params(seed: int, model: dict, sharding=None):
    """Weights from the seed in the configuration's ``param_dtype``, made
    on the device a leaf at a time (every leaf its own ``fold_in`` of the
    seed's key, every expert of an expert leaf its own ``fold_in`` of the
    leaf's; drawn in float32, centred over ``centre`` where the spec gives
    that axis (of one expert's matrix), then rounded once)."""
    dtype = jnp.dtype(sizes(model)["dtype"])
    leaves, treedef = jax.tree_util.tree_flatten(
        param_spec(model), is_leaf=_is_leaf_spec)
    key = jax.random.key(seed % (2**31))

    @partial(jax.jit, static_argnums=(1, 2, 3, 4, 5), out_shardings=sharding)
    def draw(k, shape, mean, std, held=None, centre=None):
        def normal(kk, sh):
            x = mean + std * jax.random.normal(kk, sh, jnp.float32)
            if centre is not None:
                x = x - jnp.mean(x, axis=centre, keepdims=True)
            return x.astype(dtype)

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
_CUT = 1024  # a sequence is cut to whole multiples of this many positions


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * _f32(w)


def _mamba2(h, p, s, quant, fault):
    Hm, P, G, Ns = s["Hm"], s["P"], s["G"], s["Ns"]
    Di, Dc, K, T = s["Di"], s["Dc"], s["K"], h.shape[0]
    zxd = _mm("te,ef->tf", h, p["in_proj"], quant)
    z, xbc, dt = zxd[:, :Di], zxd[:, Di:Di + Dc], zxd[:, Di + Dc:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Dc), jnp.float32), xbc])
    taps = _f32(p["conv_w"])
    xbc = jax.nn.silu(_f32(p["conv_b"]) + sum(
        padded[k:k + T] * taps[k] for k in range(K)))
    x = xbc[:, :Di].reshape(T, Hm, P)
    Bm = xbc[:, Di:Di + G * Ns].reshape(T, G, Ns)
    Cm = xbc[:, Di + G * Ns:].reshape(T, G, Ns)
    if fault == "group0_bc":
        Bm, Cm = (jnp.broadcast_to(m[:, :1], m.shape) for m in (Bm, Cm))
    # a head reads its group's B and C
    Bh, Ch = (jnp.repeat(m, Hm // G, axis=1) for m in (Bm, Cm))  # (T, Hm, Ns)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))  # (T, Hm)
    A = -jnp.exp(_f32(p["A_log"]))
    drop = fault == "chunk_state_dropped"

    def step(H_prev, xs):  # H (Hm, P, Ns)
        x_t, b_t, c_t, dt_t, t = xs
        if drop:
            H_prev = jnp.where((t > 0) & (t % s["Q"] == 0), 0.0, H_prev)
        H_t = (jnp.exp(dt_t * A)[:, None, None] * H_prev
               + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        # the read of the state is a product: the control rounds it
        y = jnp.einsum("hpn,hn->hp", _fake_quant(H_t, quant),
                       _fake_quant(c_t, quant), precision=HIGHEST)
        return H_t, y

    _, y = jax.lax.scan(step, jnp.zeros((Hm, P, Ns), jnp.float32),
                        (x, Bh, Ch, dt, jnp.arange(T)))
    y = (y + _f32(p["D"])[:, None] * x).reshape(T, Di)
    y = y * jax.nn.silu(z)
    groups = 1 if fault == "norm_all_channels" else G
    yg = y.reshape(T, groups, Di // groups)
    y = (yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                            + s["eps"])).reshape(T, Di) * _f32(p["norm"])
    return _mm("ti,ie->te", y, p["out_proj"], quant)


def _attention(h, p, s, quant):
    H, KV, d, T = s["H"], s["KV"], s["d"], h.shape[0]
    q = _mm("te,ehd->htd", h, p["wq"], quant)
    k = jnp.repeat(_mm("te,ehd->htd", h, p["wk"], quant), H // KV, axis=0)
    v = jnp.repeat(_mm("te,ehd->htd", h, p["wv"], quant), H // KV, axis=0)
    rows = _QUERY_ROWS if T % _QUERY_ROWS == 0 else T
    j = jnp.arange(T)[None, :]

    def head(xs):  # one head at a time: a batch of (T, T) maps would not fit
        q_h, k_h, v_h = xs

        def piece(xs):
            q_b, i0 = xs
            keep = j <= i0 + jnp.arange(rows)[:, None]
            scores = _mm("td,sd->ts", q_b, k_h, quant) / math.sqrt(d)
            maps = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
            return _mm("ts,sd->td", maps, v_h, quant)

        o = jax.lax.map(piece, (q_h.reshape(T // rows, rows, d),
                                jnp.arange(0, T, rows)))
        return o.reshape(T, d)

    o = jax.lax.map(head, (q, k, v))  # (H, T, d)
    return _mm("ti,io->to", o.swapaxes(0, 1).reshape(T, H * d),
               p["out"]["w"], quant)


def route(h, p, s, quant=None, fault=None):
    """``(T, N)``: the weight a token gives an expert, 0 where it did not
    choose it."""
    scores = jax.nn.sigmoid(_mm("te,en->tn", h, p["w"], quant))
    _, chosen = jax.lax.top_k(scores + _f32(p["b"]), s["top"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if fault != "weights_not_renormalised":
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(picked * s["scaling"])


def _relu2(x, fault):
    r = jax.nn.relu(x)
    return r if fault == "relu_no_square" else r * r


def routed_terms(h, p, s, quant=None, fault=None):
    """The held experts' weighted sum IN THE LATENT, (T, Lz): what a
    share adds before the latent's way out."""
    dense = route(h, p["router"], s, quant, fault)
    u = _mm("te,el->tl", h, p["latent_in"], quant) if s["latent"] else h
    held = s["lo"] + jnp.arange(s["hi"] - s["lo"])
    zeroed = {"held_expert_zeroed": 1,  # how many from ``lo`` on add nothing
              "held_eighth_zeroed": max(1, (s["hi"] - s["lo"]) // 8)}.get(
                  fault, 0)

    def expert(y, xs):
        e, up, down = xs
        out = _mm("tf,fl->tl", _relu2(_mm("tl,lf->tf", u, up, quant), fault),
                  down, quant)
        return y + jnp.where(e < s["lo"] + zeroed, 0.0,
                             dense[:, e])[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                        (held, p["experts"]["up"], p["experts"]["down"]))
    return y


def latent_out(y, p, s, quant=None):
    return _mm("tl,le->te", y, p["latent_out"], quant) if s["latent"] else y


def shared_expert(h, p, quant=None, fault=None):
    return _mm("tf,fe->te", _relu2(_mm("te,ef->tf", h, p["shared"]["up"]["w"],
                                       quant), fault),
               p["shared"]["down"]["w"], quant)


def _moe(h, p, s, quant, fault):
    return (latent_out(routed_terms(h, p, s, quant, fault), p, s, quant)
            + shared_expert(h, p, quant, fault))


@lru_cache(maxsize=None)
def _layer_fn(kind: str, frozen_sizes: tuple, quant, fault):
    """One layer over one sequence (T, E), jitted once a kind: the weights
    arrive in the stored dtype and are widened where used."""
    s = dict(frozen_sizes)

    @jax.jit
    def layer(x, blk):
        if kind == "moe":
            h = _rms_norm(x, blk["ln2"]["w"], s["eps"])
            return x + _moe(h, blk["moe"], s, quant, fault)
        h = _rms_norm(x, blk["ln1"]["w"], s["eps"])
        if kind == "mamba2":
            return x + _mamba2(h, blk["mamba2"], s, quant, fault)
        return x + _attention(h, blk["attn"], s, quant)

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
        for kind, blk in zip(layer_kinds(model), params["blocks"]):
            x = _layer_fn(kind, _frozen(model), quant, fault)(x, blk)
        rows.append(x)
    return rows


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
    logits fit at once and are computed whole (T <= ``_CUT``, or no id 0
    at a row's end); :func:`make_token_gaps` goes a piece at a time."""
    head = _head_fn(_frozen(model), quant)
    T = idx.shape[1]
    rows = []
    for ids in idx:
        x = hidden(params, ids[None], model, quant, fault,
                   served=jnp.ones((1, T), jnp.int32))[0]
        rows.append(head(_head_leaves(params), x))
    return jnp.stack(rows)


# -- serving: how far below the reference's best a served token lies --------

_HEAD_ROWS = 1024  # positions whose logits exist at once: 134 MB at V = 32,768


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

"""The plain reference of the `control` and `diff` language models.

Written from the reference repository's equations (PAPER.md, SURVEY.md:
`control.py`, `diff_transformer.py`, `train.py`), in straightforward
``jax.numpy``: float32 throughout, every matrix product under
``precision="highest"``, full (T, T) attention maps, no kernels, no cache,
no batching tricks. It imports nothing of the program and takes nothing the
program has made: the weights come from :func:`make_params` (this file,
from the seed), and the program is handed the same tree.

The parameter tree's names and shapes are the checkpoint layout the program
reads (weights stored ``(in, out)``):

  tok_emb (V, E) [diff: pos_emb (T, E)]
  blocks[l]: ln1{w,b} attn{...} ln2{w,b} ffn{gate{w,b} xform{w,b} out{w,b}}
    diff attn:    wq, wk (2, E, H, d)  wv (E, H, 2d)  lambda_q, lambda_k
                  (2, H, d)  gn{w,b} (2dH)  out{w (2dH, E), b}
    control attn: wq, wk, wv (E, H, d)  out{w (dH, E), b}
  ln_f{w,b}  lm_head{w (E, V), b}

``quant`` is the lower-precision control of the benchmark's `correct`
(PERF.md section 2): every matrix product, attention's two included, takes
its operands rounded to float8 (e4m3, one scale a tensor) or to int8. The
configurations state bfloat16 compute, so float8 is the step below, the one
a later PR would be tempted by. Everything between the products stays
float32, which makes this the gentlest such path: a real one is no closer.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5  # nn.LayerNorm default; diff_transformer.py:17-19
OUTPUT_SCALE = 1.0 - 0.8  # diff_transformer.py:86,91: a constant
ROPE_THETA = 10000.0  # control.py:6


# -- sizes -----------------------------------------------------------------


def sizes(model: dict) -> dict:
    """Head count and widths from a configuration file's ``model`` group.
    `control` doubles the head count (train.py:226) so both recipes have
    q/k heads of the same width."""
    kind, E, H = model["model"], model["n_embd"], model["n_head"]
    if kind == "control":
        H = H * model.get("control_head_multiplier", 1)
        d = E // H
        return {"kind": kind, "H": H, "d": d, "dv": d, "streams": 1}
    if kind == "diff":
        d = E // (2 * H)
        return {"kind": kind, "H": H, "d": d, "dv": 2 * d, "streams": 2}
    raise ValueError(f"no reference for model kind {kind!r}")


def param_spec(model: dict) -> dict:
    """The tree of ``(shape, mean, std)`` that :func:`make_params` fills.
    Every leaf is random, biases, norm scales and lambda vectors too (a
    trained checkpoint has none at its initial value, and a zero lambda
    vector would hide the lambda path from the gradient check)."""
    s = sizes(model)
    E, V, T = model["n_embd"], model["vocab_size"], model["block_size"]
    H, d, dv = s["H"], s["d"], s["dv"]
    w = lambda *shape: (shape, 0.0, 0.02)  # noqa: E731  (control.py:134)
    ln = lambda n: {"w": ((n,), 1.0, 0.02), "b": ((n,), 0.0, 0.02)}  # noqa: E731
    lin = lambda i, o: {"w": w(i, o), "b": ((o,), 0.0, 0.02)}  # noqa: E731
    if s["kind"] == "diff":
        attn = {
            "wq": w(2, E, H, d), "wk": w(2, E, H, d), "wv": w(E, H, dv),
            "lambda_q": ((2, H, d), 0.0, 0.3),
            "lambda_k": ((2, H, d), 0.0, 0.3),
            "gn": ln(H * dv), "out": lin(H * dv, E),
        }
    else:
        attn = {"wq": w(E, H, d), "wk": w(E, H, d), "wv": w(E, H, dv),
                "out": lin(H * dv, E)}
    block = {
        "ln1": ln(E), "attn": attn, "ln2": ln(E),
        "ffn": {"gate": lin(E, 4 * E), "xform": lin(E, 4 * E),
                "out": lin(4 * E, E)},
    }
    spec = {"tok_emb": w(V, E)}
    if s["kind"] == "diff":
        spec["pos_emb"] = w(T, E)
    spec["blocks"] = [block for _ in range(model["n_layer"])]
    spec["ln_f"] = ln(E)
    spec["lm_head"] = lin(E, V)
    return spec


def _is_leaf_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def make_params(seed: int, model: dict, sharding=None):
    """Float32 weights from the seed, made on the device in one jitted
    call (every leaf its own ``fold_in`` of the seed's key)."""
    spec = param_spec(model)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf_spec)

    @partial(jax.jit, out_shardings=sharding)
    def build(key):
        out = [
            mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            for i, (shape, mean, std) in enumerate(leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(jax.random.key(seed % (2**31)))


# -- the lower-precision control -------------------------------------------


def _fake_quant(x, quant):
    """``x`` rounded to the lower precision and back; the gradient passes
    straight through, as it does in a real low-precision matmul whose
    backward uses the rounded operands."""
    if quant is None:
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if quant == "fp8":
        scale = 448.0 / amax  # e4m3's largest finite value
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _fake_quant(a, quant), _fake_quant(b, quant),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


# -- forward ---------------------------------------------------------------


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)  # biased
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["w"] + p["b"]


def _linear(x, p, quant):
    return _mm("...i,io->...o", x, p["w"], quant) + p["b"]


def _softmax_maps(q, k, quant):
    """(B, T, H, d) x (B, T, H, d) -> causal softmax maps (B, H, T, T)."""
    T, d = q.shape[1], q.shape[-1]
    scores = _mm("bthd,bshd->bhts", q, k, quant) / math.sqrt(d)
    keep = jnp.tril(jnp.ones((T, T), bool))
    return jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)


def _rope(x):
    """control.py:4-22: consecutive feature pairs rotated by t * theta_j."""
    T, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (ROPE_THETA ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(T, dtype=jnp.float32), freqs)  # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attn_control(h, p, quant):
    q = _rope(_mm("bte,ehd->bthd", h, p["wq"], quant))
    k = _rope(_mm("bte,ehd->bthd", h, p["wk"], quant))
    v = _mm("bte,ehd->bthd", h, p["wv"], quant)
    o = _mm("bhts,bshd->bthd", _softmax_maps(q, k, quant), v, quant)
    return _linear(o.reshape(*h.shape[:2], -1), p["out"], quant)


def _attn_diff(h, p, layer, quant):
    """diff_transformer.py:41-91; ``layer`` is 1-based (:43, :161)."""
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * (layer - 1.0))
    lq, lk = p["lambda_q"], p["lambda_k"]
    lam = jnp.mean(jnp.exp(lq[0] * lk[0]) - jnp.exp(lq[1] * lk[1])
                   + lam_init, axis=-1)  # (H,)
    maps = []
    for s in range(2):
        q = _mm("bte,ehd->bthd", h, p["wq"][s], quant)
        k = _mm("bte,ehd->bthd", h, p["wk"][s], quant)
        maps.append(_softmax_maps(q, k, quant))
    v = _mm("bte,ehd->bthd", h, p["wv"], quant)
    diff = maps[0] - lam[None, :, None, None] * maps[1]
    o = _mm("bhts,bshd->bthd", diff, v, quant).reshape(*h.shape[:2], -1)
    o = _layer_norm(o, p["gn"]) * OUTPUT_SCALE
    return _linear(o, p["out"], quant)


def _block(x, blk, layer, kind, quant):
    """One pre-LN residual block (control.py:92-111)."""
    h = _layer_norm(x, blk["ln1"])
    if kind == "diff":
        x = x + _attn_diff(h, blk["attn"], layer, quant)
    else:
        x = x + _attn_control(h, blk["attn"], quant)
    h = _layer_norm(x, blk["ln2"])
    f = blk["ffn"]
    gated = jax.nn.silu(_linear(h, f["gate"], quant)) * _linear(
        h, f["xform"], quant)
    return x + _linear(gated, f["out"], quant)


def forward(params, idx, model: dict, quant=None):
    """(B, T) token ids -> float32 logits (B, T, V). The blocks run as one
    ``lax.scan`` over the stacked layers: the same arithmetic as a Python
    loop, an eighth of the float32 program to compile."""
    kind = sizes(model)["kind"]
    x = params["tok_emb"][idx]
    if kind == "diff":
        x = x + params["pos_emb"][: idx.shape[1]]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                     *params["blocks"])
    layers = jnp.arange(1, len(params["blocks"]) + 1, dtype=jnp.float32)
    x, _ = jax.lax.scan(
        lambda x, bl: (_block(x, bl[0], bl[1], kind, quant), None),
        x, (stacked, layers))
    return _linear(_layer_norm(x, params["ln_f"]), params["lm_head"], quant)


def loss_sum(params, x, y, model: dict, quant=None):
    """Summed next-token cross entropy of (B, T) inputs and targets."""
    logits = forward(params, x, model, quant)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


# -- training: loss, gradient, AdamW (train.py:236-283) ---------------------


def _schedule(count, opt: dict):
    """CosineWarmupScheduler (train.py:109-123): step k runs at the rate
    computed for count k, so the first step's rate is 0."""
    count = jnp.asarray(count, jnp.float32)
    base, lo = opt["learning_rate"], opt["min_lr"]
    warm, total = opt["warmup_iters"], opt["max_iters"]
    progress = (count - warm) / max(total - warm, 1)
    decay = lo + (base - lo) * 0.5 * (1.0 + jnp.cos(jnp.pi * progress))
    return jnp.where(count < warm, base * count / max(warm, 1), decay)


def _tree_norms(tree):
    return jax.tree_util.tree_map(lambda a: jnp.sqrt(jnp.sum(a * a)), tree)


def make_train_steps(model: dict, opt: dict, rows_per_block: int, quant=None):
    """``run(params, xs, ys) -> readings`` over ``xs, ys`` of shape
    (steps, rows, T): the loss of every step, the leaf norms of the first
    gradient as the optimizer gets it (clipped to the global norm), and
    the leaf norms of the parameters' change after the last step. The
    batch goes through in blocks of ``rows_per_block`` rows, gradients
    summed, so the float32 maps of all rows never exist at once; the
    compiled programs are one block's gradient and one AdamW update."""
    b1, b2, eps = opt["beta1"], opt["beta2"], 1e-8
    tmap = jax.tree_util.tree_map
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y: loss_sum(p, x, y, model, quant)))
    add = jax.jit(lambda acc, g: tmap(jnp.add, acc, g), donate_argnums=(0,))

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, mu, nu, grads, k, n):
        grads = tmap(lambda g: g / n, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in
                             jax.tree_util.tree_leaves(grads)))
        clip = opt["grad_clip"]
        grads = tmap(lambda g: g * clip / jnp.maximum(gnorm, clip), grads)
        mu = tmap(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1 ** (k + 1.0), 1 - b2 ** (k + 1.0)
        lr = _schedule(k, opt)
        params = tmap(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + opt["weight_decay"] * p),
            params, mu, nu)
        return params, mu, nu, _tree_norms(grads)

    delta_norms = jax.jit(lambda a, b: _tree_norms(tmap(jnp.subtract, a, b)))

    def run(params, xs, ys):
        p0 = params
        params = tmap(jnp.copy, params)
        mu = tmap(jnp.zeros_like, params)
        nu = tmap(jnp.zeros_like, params)
        losses, first_grad = [], None
        for k in range(xs.shape[0]):
            acc, loss = tmap(jnp.zeros_like, params), 0.0
            for r in range(0, xs.shape[1], rows_per_block):
                lo, g = grad_fn(params, xs[k, r:r + rows_per_block],
                                ys[k, r:r + rows_per_block])
                acc, loss = add(acc, g), loss + lo
            n = float(xs[k].size)
            params, mu, nu, gn = update(params, mu, nu, acc,
                                        jnp.float32(k), jnp.float32(n))
            if k == 0:
                first_grad = gn
            losses.append(loss / n)
        return {"losses": jnp.stack(losses), "first_grad_norms": first_grad,
                "delta_norms": delta_norms(params, p0)}

    return run


# -- serving: how far below the reference's best a served token lies --------


def make_token_gaps(model: dict, quant=None):
    """``gaps(params, seqs, served) -> (B, T)``: at every position, the
    reference's best logit minus its logit of ``served[b, t]``, the token
    that followed position t. With ``quant`` the token judged is the one
    the lower precision puts first at that position instead (the control:
    it need not decode)."""

    @jax.jit
    def gaps(params, seqs, served):
        logits = forward(params, seqs, model)
        if quant is not None:
            served = jnp.argmax(forward(params, seqs, model, quant), axis=-1)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
        return best - got

    return gaps

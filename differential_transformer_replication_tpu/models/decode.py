"""KV-cache incremental decoding.

The reference's ``generate`` recomputes the full O(T^2) forward for every
new token (control.py:163-171, diff_transformer.py:177-185,
Ndiff_transformer.py:232-241 — "no KV cache", SURVEY.md section 3.4).
``models/generate.py`` reproduces that behavior; this module is the
idiomatic-TPU upgrade: per-layer K/V caches make each new token O(T).
The cache is a RING over block_size slots, so the RoPE families
(control/ndiff) keep the O(T)/token fast path arbitrarily far PAST
block_size: each step attends over exactly the last block_size keys
(RoPE scores depend only on relative positions, so absolute-position
rotation needs no re-rotating as the window rolls). Past the boundary
this is SLIDING-WINDOW ATTENTION — the standard KV-cached long-decode
semantics — NOT a bit-reproduction of the reference's crop
(control.py:163-171), and no O(T)/token scheme can be one for depth
>= 2: the reference recomputes the whole cropped forward each step, so
when the window slides, EVERY remaining position loses its oldest
visible key and all its deep-layer activations change — Omega(M^2)
recompute per token is inherent to crop semantics. The ring instead
keeps each cached activation as computed with its own full window
(receptive field grows with depth, strictly containing the crop's).
The two are exactly equal for single-layer models and everywhere up to
the block boundary (tests/test_decode.py pins both). The diff family's
learned absolute position table cannot roll at all (each window slide
would re-embed every cached position), so it keeps the hard in-window
bound and the windowed ``generate`` beyond it.

One chunked code path serves both phases — ``forward_chunk`` processes L
tokens starting at position ``pos`` against the cache, so prefill is a
single chunk at pos=0 and decoding is a chunk of length 1. All three
model families run through the shared multi-stream form (ops/streams.py):
per-stream K caches, per-stream softmax over the cached keys, coefficient
combine, then the family's post-attention stack (plain concat for
control; GroupLayerNorm + the constant 0.2 scale for diff/ndiff,
diff_transformer.py:90-91).

Family differences preserved (same citations as models/{control,diff,
ndiff}.py): control/ndiff rotate q/k with RoPE at absolute positions and
have no position table; diff adds its learned absolute position embedding
at the input instead. Generation is eval-mode: no dropout anywhere.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models.generate import sample_token
from differential_transformer_replication_tpu.models import (
    afmoe,
    deepseek_v2,
    common,
    jamba,
    kimi_linear,
    lfm2,
    nemotron_h,
)
from differential_transformer_replication_tpu.ops import (
    apply_rope,
    diff_lambda,
    lambda_init_schedule,
    ndiff_lambdas,
    ndiff_signs,
    rope_cos_sin,
)
from differential_transformer_replication_tpu.ops.decode_attention import (
    decode_attention_multi,
    decode_attention_multi_paged,
    decode_attention_multi_reference,
    dequantize_kv,
    quantize_kv,
)
from differential_transformer_replication_tpu.ops.kv_write import (
    position_on_lanes,
    write_rows,
)
from differential_transformer_replication_tpu.ops.lambdas import OUTPUT_SCALE
from differential_transformer_replication_tpu.ops.ring_attention import (
    ring_decode_attention,
)
from differential_transformer_replication_tpu.ops.streams import (
    NEG_INF,
    diff_coeffs,
    ndiff_coeffs,
    vanilla_coeffs,
)


def _n_streams(cfg: ModelConfig) -> int:
    return {"control": 1, "diff": 2, "ndiff": cfg.n_terms}.get(cfg.model, 1)


def _uses_rope(cfg: ModelConfig) -> bool:
    return cfg.model in ("control", "ndiff")


# the families whose layers are of several kinds (:func:`_hybrid_walk`),
# each with the module that holds its ``embed``
HYBRID = {"jamba": jamba, "kimi_linear": kimi_linear, "afmoe": afmoe,
          "deepseek_v2": deepseek_v2, "nemotron_h": nemotron_h, "lfm2": lfm2}
# a family's flavour of attention over a K/V ring, ``(ring_qkv, ring_out)``
# as its module hands them (nemotron_h's ``*`` layers attend as jamba's do;
# lfm2's ``"full"`` layers norm a head and then rotate it, afmoe's do not
# rotate, and nothing follows their read but jamba's output projection)
RING_ATTENTION = {"jamba": (jamba.ring_qkv, jamba.ring_out),
                  "afmoe": (afmoe.ring_qkv, afmoe.ring_out),
                  "nemotron_h": (jamba.ring_qkv, jamba.ring_out),
                  "lfm2": (lfm2.ring_qkv, jamba.ring_out)}
# Ring positions that a prefill chunk's blocked attention
# (:func:`_attend_ring_blocked`) reads at a time
ATTEND_KEY_BLOCK = 1024


def has_recurrent_state(cfg: ModelConfig) -> bool:
    """Whether a sequence's cache holds state that every token overwrites
    (a Mamba, a Mamba-2 or a KDA layer's, or an lfm2 convolution's window
    alone): such a slot has to be zeroed
    before a new sequence enters it, where a ring is simply masked by
    positions. Told by what the layers keep, not by the family's name."""
    return any(KINDS[kind].recurrent for kind in cfg.layer_kinds())


def kv_store_dtype(cfg: ModelConfig) -> str:
    """Resolved KV-cache storage dtype: ``"int8"`` or a float dtype
    string (``kv_cache_dtype == "auto"`` stores ``compute_dtype``, the
    pre-quantization behavior)."""
    if cfg.kv_cache_dtype == "int8":
        return "int8"
    if cfg.kv_cache_dtype == "bf16":
        return "bfloat16"
    return cfg.compute_dtype


def apply_logit_pipeline(logits: jnp.ndarray, allowed: jnp.ndarray,
                         counts: jnp.ndarray, rep: jnp.ndarray,
                         pres: jnp.ndarray,
                         freq: jnp.ndarray) -> jnp.ndarray:
    """The per-row logit-processor pipeline of the serving engine's
    structured-decoding subsystem (serving/constrain.py): repetition /
    presence / frequency penalties over the request's generated-token
    histogram, then the constraint mask. ONE definition shared by the
    L=1 pool sampler and the fused spec-verify accept step
    (serving/engine.py) — the Leviathan accept/reject test preserves
    the target distribution only if drafter proposals and verify rows
    see IDENTICAL logit processing, and greedy constrained+spec
    bit-parity needs the same argmax surface in both formulations.

    ``logits`` (B, V) float; ``allowed`` (B, V) bool constraint mask
    (all-ones for unconstrained rows); ``counts`` (B, V) int32
    occurrence histogram of the row's generated tokens; ``rep`` /
    ``pres`` / ``freq`` (B,) float penalties (1.0 / 0.0 / 0.0 = off).
    Rows with every penalty off and an all-ones mask pass through
    BIT-IDENTICAL (a ``where`` selects the raw logits), so the
    pre-pipeline sampler's outputs — and every pinned bit-repro test —
    are unchanged for unconstrained traffic. Applied BEFORE top-k and
    temperature: the threshold and the draw both see the processed
    surface.
    """
    seen = counts > 0
    cf = counts.astype(logits.dtype)
    # GPT-style repetition penalty: shrink positive logits, push
    # negative ones further down, for every already-generated token
    r = rep[:, None]
    penalized = jnp.where(
        seen,
        jnp.where(logits > 0, logits / r, logits * r),
        logits,
    )
    penalized = (
        penalized
        - pres[:, None] * seen.astype(logits.dtype)
        - freq[:, None] * cf
    )
    inactive = (rep == 1.0) & (pres == 0.0) & (freq == 0.0)
    x = jnp.where(inactive[:, None], logits, penalized)
    return jnp.where(allowed, x, -jnp.inf)


def quality_vector(lp: jnp.ndarray, proc: jnp.ndarray,
                   tokens: jnp.ndarray,
                   prev: jnp.ndarray,
                   top2: jnp.ndarray = None) -> jnp.ndarray:
    """Fixed-shape per-slot quality vector, computed INSIDE the jitted
    sample/verify step (obs/quality.py is the host-side consumer):

      [..., 0] sampled-distribution entropy in nats — over ``lp``, the
               log-softmax of the distribution actually drawn from
               (penalties + constraint mask + top-k + temperature all
               applied), so a collapsing or flattening model moves it
               immediately;
      [..., 1] top-1 logit margin on the processed surface ``proc``
               (pre-top-k/temperature): the argmax's confidence gap,
               the signal spec-verify acceptance already keys on;
      [..., 2] repetition flag — sampled token equals the previous
               emitted token (``prev < 0`` = no previous token); the
               engine accumulates the host-side run length from it.

    Shapes: ``lp``/``proc`` (..., V), ``tokens``/``prev`` (...) int32;
    returns (..., 3) float32. Runtime arrays only — no shape depends
    on request state, so inactive slots pass through and the decode
    compile count stays pinned. ``top2``, when given, is the caller's
    already-computed two largest PROCESSED logits (..., >=2) — the
    samplers have a descending sort of ``proc`` on hand for the top-k
    threshold, and reusing its head keeps the tail out of a second
    full top_k (which breaks XLA's sampler fusion and dominates the
    telemetry cost on small models). NaN-degradation contract:
    fully-masked rows give entropy 0 over the -inf mass (``where``
    keeps the 0*inf NaN out) and an infinite margin; genuinely
    non-finite logits propagate as non-finite values the host treats
    as "no signal" (never a crash — that guard is the sampler's
    finite-ok column).
    """
    finite = jnp.isfinite(lp)
    plogp = jnp.where(finite, jnp.exp(lp) * lp, 0.0)
    entropy = -jnp.sum(plogp, axis=-1)
    if top2 is None and proc.shape[-1] >= 2:
        top2 = jax.lax.top_k(proc, 2)[0]
    if top2 is not None:
        margin = top2[..., 0] - top2[..., 1]
    else:  # degenerate single-token vocab: no runner-up to compare
        margin = jnp.zeros(proc.shape[:-1], proc.dtype)
    repeat = ((tokens == prev) & (prev >= 0))
    return jnp.stack([
        entropy.astype(jnp.float32),
        margin.astype(jnp.float32),
        repeat.astype(jnp.float32),
    ], axis=-1)


def init_cache(cfg: ModelConfig, batch_size: int) -> list:
    """The pool of ``batch_size`` slots: for every layer what its kind's
    record keeps (``KINDS``), each ring ``cfg.ring_len(kind)`` long: K/V
    rings (:func:`_kv_zeros`; the reference families' are ``block_size``
    long), rings of latents, recurrent states (zeros being a sequence's
    start) or, for a layer without a mixer, ``{}``."""
    return [KINDS[kind].zeros(cfg, batch_size, cfg.ring_len(kind))
            for kind in cfg.layer_kinds()]


def _kv_zeros(cfg: ModelConfig, rows: int, M: int) -> dict:
    """K/V rings of ``M`` positions, HEAD-MAJOR so the per-(slot, head)
    ring is contiguous — the fused decode kernel's native layout
    (ops/decode_attention.py) and an equivalent einsum for the XLA chunk
    path: K is per-stream (S, B, H, M, d); V is shared across streams
    (B, H, M, dv). ``cfg.kv_cache_dtype == "int8"`` stores symmetric
    per-head-scale int8 values plus fp32 scales (``k_scale`` (S, B, H, M)
    / ``v_scale`` (B, H, M)) — about half the bf16 bytes per slot;
    otherwise the resolved float dtype (:func:`kv_store_dtype`)."""
    S, H = _n_streams(cfg), cfg.n_kv_head
    store = kv_store_dtype(cfg)
    layer = {"k": jnp.zeros((S, rows, H, M, cfg.head_size), jnp.dtype(store)),
             "v": jnp.zeros((rows, H, M, cfg.value_size), jnp.dtype(store))}
    if store == "int8":
        layer.update(k_scale=jnp.zeros((S, rows, H, M), jnp.float32),
                     v_scale=jnp.zeros((rows, H, M), jnp.float32))
    return layer


def _dequant_layer(layer_cache: dict, dtype):
    """The layer's (K, V) as float arrays in ``dtype``: a cast-free read
    on the float path, a fused multiply on the int8 path (the Pallas
    kernel instead dequantizes inside its tile loads)."""
    if "k_scale" in layer_cache:
        return (
            dequantize_kv(layer_cache["k"], layer_cache["k_scale"], dtype),
            dequantize_kv(layer_cache["v"], layer_cache["v_scale"], dtype),
        )
    return layer_cache["k"], layer_cache["v"]


def _write_chunk(layer_cache: dict, ks: jnp.ndarray, v: jnp.ndarray,
                 slot) -> dict:
    """Write one chunk's new K/V — ks (S, B, L, H, d), v (B, L, H, dv) —
    into the ring at ``slot``, quantizing on the int8 path so the chunk's
    own attention (and every later step) reads exactly what the cache
    holds."""
    k_new = ks.transpose(0, 1, 3, 2, 4)  # (S, B, H, L, d)
    v_new = v.transpose(0, 2, 1, 3)  # (B, H, L, dv)
    out = dict(layer_cache)
    if "k_scale" in layer_cache:
        kq, ksc = quantize_kv(k_new)
        vq, vsc = quantize_kv(v_new)
        out["k"] = jax.lax.dynamic_update_slice(
            layer_cache["k"], kq, (0, 0, 0, slot, 0)
        )
        out["k_scale"] = jax.lax.dynamic_update_slice(
            layer_cache["k_scale"], ksc, (0, 0, 0, slot)
        )
        out["v"] = jax.lax.dynamic_update_slice(
            layer_cache["v"], vq, (0, 0, slot, 0)
        )
        out["v_scale"] = jax.lax.dynamic_update_slice(
            layer_cache["v_scale"], vsc, (0, 0, slot)
        )
    else:
        dt = layer_cache["k"].dtype
        out["k"] = jax.lax.dynamic_update_slice(
            layer_cache["k"], k_new.astype(dt), (0, 0, 0, slot, 0)
        )
        out["v"] = jax.lax.dynamic_update_slice(
            layer_cache["v"], v_new.astype(dt), (0, 0, slot, 0)
        )
    return out


def _stacked_wq(p_attn: dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Normalize the per-family weight layouts to stacked (S, E, H, d)."""
    wq, wk = p_attn["wq"], p_attn["wk"]
    if wq.ndim == 3:  # control: (E, H, d)
        wq, wk = wq[None], wk[None]
    return wq, wk


def _layer_coeffs(cfg: ModelConfig, p_attn: dict, layer_idx: int) -> jnp.ndarray:
    """(S, H) combine coefficients for this layer (1-based layer_idx for
    the dynamic lambda_init schedule, diff_transformer.py:43,161)."""
    if cfg.model == "control":
        return vanilla_coeffs(cfg.n_head)
    if cfg.model == "diff":
        lam = diff_lambda(
            p_attn["lambda_q"][0], p_attn["lambda_k"][0],
            p_attn["lambda_q"][1], p_attn["lambda_k"][1],
            lambda_init_schedule(layer_idx),
        )
        return diff_coeffs(lam)
    lams = ndiff_lambdas(
        p_attn["lambda_q"], p_attn["lambda_k"], lambda_init_schedule(layer_idx)
    )
    return ndiff_coeffs(lams, ndiff_signs(cfg.n_terms))


def _chunk_qkv(
    x: jnp.ndarray,  # (B, L, E) normed input chunk
    p_attn: dict,
    cfg: ModelConfig,
    cos: jnp.ndarray,  # (L, d/2) tables pre-sliced at [pos, pos+L)
    sin: jnp.ndarray,
):
    """The chunk's queries, keys (S, B, L, H, d) and values
    (B, L, H, dv), rotated at their absolute positions."""
    wq, wk = _stacked_wq(p_attn)
    qs = jnp.einsum("ble,sehd->sblhd", x, wq.astype(x.dtype))
    ks = jnp.einsum("ble,sehd->sblhd", x, wk.astype(x.dtype))
    v = jnp.einsum("ble,ehd->blhd", x, p_attn["wv"].astype(x.dtype))
    if _uses_rope(cfg):
        qs = apply_rope(qs, cos, sin)
        ks = apply_rope(ks, cos, sin)
    return qs, ks, v


def _attn_out(heads: jnp.ndarray, p_attn: dict,
              cfg: ModelConfig) -> jnp.ndarray:
    """The attention half's tail on the concatenated heads (..., H*dv):
    the diff families' group norm and constant scale, then the output
    projection. It is about no ring, so the decode step runs it once
    over the pool's rows."""
    if cfg.model in ("diff", "ndiff"):
        heads = common.apply_group_norm(heads, p_attn["gn"], cfg)
        heads = heads * OUTPUT_SCALE  # constant 0.2 (diff_transformer.py:91)
    return common.linear(heads, p_attn["out"])


def _chunk_attend(
    qs: jnp.ndarray,  # (S, B, L, H, d)
    p_attn: dict,
    new_cache: dict,  # the layer's cache AFTER the chunk's write
    pos,  # scalar int: absolute position of the chunk start
    layer_idx: int,
    cfg: ModelConfig,
    window: int = 0,  # visibility clip; 0/None = the cache size M
) -> jnp.ndarray:
    """Attend the chunk's rows over the ring that already holds their
    own K/V (update-then-attend) and combine the streams: the
    concatenated heads (B, L, H*dv), before :func:`_attn_out`."""
    _, B, L = qs.shape[:3]
    M = cfg.block_size
    W = int(window) if window else M
    k_cache, v_cache = _dequant_layer(new_cache, qs.dtype)

    scale = 1.0 / (cfg.head_size ** 0.5)
    scores = (
        jnp.einsum("sblhd,sbhmd->sbhlm", qs, k_cache).astype(jnp.float32) * scale
    )
    # Ring-aware causal mask over absolute positions. After this chunk's
    # write the latest absolute position is ``last``; slot m then holds
    # absolute position ``last - ((last - m) mod M)`` (the most recent
    # write to that slot; negative = never written). Chunk row l sits at
    # absolute pos+l and may see a slot iff its held position is in the
    # sliding window [row - W + 1, row] — which also masks same-chunk
    # future rows and unwritten (zero) slots. W < M (an explicit
    # ``window``) clips visibility tighter than the cache — used by the
    # append-oracle test to validate the ring arithmetic.
    visible = _ring_visible(pos, L, M, W)
    scores = jnp.where(visible[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)  # per-stream, fp32

    coeffs = _layer_coeffs(cfg, p_attn, layer_idx)  # (S, H)
    combined = jnp.einsum("sh,sbhlm->bhlm", coeffs, probs)
    out = jnp.einsum("bhlm,bhme->blhe", combined.astype(qs.dtype), v_cache)
    return out.reshape(B, L, -1)  # concat heads


def _ring_visible(pos, L: int, M: int, W: int, first=0,
                  count: int = 0) -> jnp.ndarray:
    """(L, M) bool: which slots of a ring of M row ``l`` of a chunk at
    ``pos`` may see, after the chunk's own write (:func:`_chunk_attend`);
    with ``count``, of the ``count`` slots from ``first`` on."""
    rows = pos + jnp.arange(L)[:, None]
    slots = first + jnp.arange(count or M)[None, :]
    last = pos + L - 1
    held = last - jax.lax.rem(
        jnp.asarray(last, jnp.int32) - slots, jnp.asarray(M, jnp.int32)
    )
    return (held <= rows) & (held >= 0) & (held > rows - W)


def _attn_chunk(
    x: jnp.ndarray,  # (B, L, E) normed input chunk
    p_attn: dict,
    layer_cache: dict,
    pos,  # scalar int: absolute position of the chunk start
    layer_idx: int,
    cfg: ModelConfig,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    window: int = 0,
) -> Tuple[jnp.ndarray, dict]:
    qs, ks, v = _chunk_qkv(x, p_attn, cfg, cos, sin)
    # RING cache: slot = pos mod M, so positions past block_size roll over
    # the oldest entries instead of clamping. Keys are rotated at their
    # ABSOLUTE position; RoPE scores depend only on (q_pos - k_pos), so
    # the rolled window needs no re-rotating (sliding-window attention —
    # see the module docstring for how this relates to the reference's
    # crop semantics). The write quantizes on the int8 path, so the
    # chunk's own attention reads exactly what later decode steps will
    # read.
    slot = jax.lax.rem(jnp.asarray(pos, jnp.int32), cfg.block_size)
    with jax.named_scope("kv_write"):
        new_cache = _write_chunk(layer_cache, ks, v, slot)
    heads = _chunk_attend(qs, p_attn, new_cache, pos, layer_idx, cfg, window)
    return _attn_out(heads, p_attn, cfg), new_cache


def _embed_chunk(params: dict, tokens: jnp.ndarray, pos,
                 cfg: ModelConfig, rope_len: int):
    """The chunk's input rows (B, L, E) and its RoPE tables sliced at
    [pos, pos+L) (None for the diff family, which adds its learned
    absolute positions here instead, diff_transformer.py:158)."""
    L, M = tokens.shape[1], cfg.block_size
    compute = jnp.dtype(cfg.compute_dtype)
    x = params["tok_emb"][tokens].astype(compute)
    if cfg.model == "diff":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_emb"], pos, L, axis=0
        ).astype(compute)
        return x, None, None
    cos_full, sin_full = rope_cos_sin(cfg.head_size, max(int(rope_len), M))
    return (
        x,
        jax.lax.dynamic_slice_in_dim(cos_full, pos, L, axis=0),
        jax.lax.dynamic_slice_in_dim(sin_full, pos, L, axis=0),
    )


@jax.named_scope("lm_head")
def _lm_head(params: dict, x: jnp.ndarray, cfg: ModelConfig):
    x = common.apply_pre_norm(x, params["ln_f"], cfg)
    return common.linear(x, params["lm_head"])


def forward_chunk(
    params: dict,
    tokens: jnp.ndarray,  # (B, L) at absolute positions [pos, pos+L)
    pos,
    cache: list,
    cfg: ModelConfig,
    rope_len: int = 0,
    window: int = 0,
    valid=None,
) -> Tuple[jnp.ndarray, list]:
    """Process a chunk against the cache. Returns ((B, L, V) logits,
    updated cache). Prefill = one big chunk at pos=0; decode = L=1.

    ``valid`` (the hybrid families only; a runtime scalar, 1 <= valid <= L)
    says that only the chunk's first ``valid`` tokens are the sequence's
    and the rest padding up to a compiled shape: the cache comes back as
    after ``valid`` tokens, and the logits are those of the LAST REAL
    token alone, (B, 1, V). A prompt's tail then costs one program, not
    one a binary digit of its length (each of which reads every weight).

    The cache is a RING over ``block_size`` slots, so RoPE families
    (control/ndiff) may run ``pos`` past block_size indefinitely — the
    oldest keys roll off at O(T) per token (sliding-window attention;
    the module docstring relates this to the reference's crop,
    control.py:163-171). ``rope_len`` sizes the rotation tables
    (>= pos + L; defaults to block_size for the in-window case);
    ``window`` optionally clips visibility tighter than the cache size
    (test/oracle use). The DIFF family's learned absolute position
    table (diff_transformer.py:158) makes cached reuse past block_size
    architecturally impossible — every cached K/V would need
    recomputing under the shifted position embeddings — so concrete
    positions fail loudly there (the repo's fail-loud convention) and
    models/generate.py remains its sliding-window path. Other
    concrete-position chunks that cannot be represented also fail
    loudly: RoPE positions past the table (pass a bigger ``rope_len``),
    multi-token chunks at rolled positions (their in-chunk writes would
    evict keys still visible to earlier rows), and writes wrapping the
    ring slice boundary."""
    B, L = tokens.shape
    M = cfg.block_size
    if isinstance(pos, int):
        if cfg.model in HYBRID and pos + L > M:
            raise ValueError(
                f"chunk [{pos}, {pos + L}) exceeds block_size {M}: the "
                f"{cfg.model} family's "
                + ("MLA layers see every earlier position"
                   if "latent" in cfg.layer_kinds() else
                   "attention layers see every earlier position"
                   if cfg.full_layers_rotate else
                   f"{'full ' if 'full' in cfg.layer_kinds() else ''}"
                   "attention layers carry no position")
                + ", so a rolled ring would silently become "
                "sliding-window attention"
            )
        if ("window" in cfg.layer_kinds() and L > cfg.ring_slack
                and pos + L > cfg.ring_len("window")):
            raise ValueError(
                f"chunk [{pos}, {pos + L}) of {L} tokens rolls the sliding "
                f"layers' ring of {cfg.ring_len('window')} and is longer "
                f"than its slack past the window of {cfg.sliding_window} "
                f"({cfg.ring_slack}): its writes would evict keys that its "
                "earlier rows still see; feed it in chunks of the slack"
            )
        if cfg.model == "diff" and pos + L > M:
            raise ValueError(
                f"chunk [{pos}, {pos + L}) exceeds block_size {M}: the diff "
                "family's learned absolute position table cannot roll (each "
                "slide would re-embed every cached position); use "
                "models.generate for its sliding-window behavior"
            )
        if not cfg.cannot_roll and pos + L > max(int(rope_len), M):
            raise ValueError(
                f"chunk [{pos}, {pos + L}) exceeds the RoPE table length "
                f"{max(int(rope_len), M)}: pass rope_len >= the final "
                "position or the cos/sin slice would silently clamp and "
                "mis-rotate"
            )
        if pos >= M and L > 1:
            raise ValueError(
                f"multi-token chunk at rolled position {pos} >= block_size "
                f"{M}: its in-chunk writes would evict keys still inside "
                "earlier rows' sliding windows (silently shrinking their "
                "attention); feed rolled positions one token at a time"
            )
        if (pos % M) + L > M:
            raise ValueError(
                f"chunk [{pos}, {pos + L}) wraps the ring boundary (slot "
                f"{pos % M} + {L} > {M}): split it at the boundary"
            )
    if cfg.model in HYBRID:
        return _hybrid_chunk(params, tokens, pos, cache, cfg, window, valid)
    if valid is not None:
        raise ValueError(
            f"forward_chunk(valid=...) pads a chunk of the "
            f"{', '.join(HYBRID)} families only; the {cfg.model!r} family "
            "runs whole chunks"
        )
    x, cos, sin = _embed_chunk(params, tokens, pos, cfg, rope_len)
    new_cache = []
    for li, blk in enumerate(params["blocks"], 1):  # 1-based (diff_transformer.py:161)
        with jax.named_scope("attn_norm"):
            h = common.apply_pre_norm(x, blk["ln1"], cfg)
        with jax.named_scope("attn"):
            a, layer_cache = _attn_chunk(
                h, blk["attn"], cache[li - 1], pos, li, cfg, cos, sin,
                window=window,
            )
        # residual add + ln2 + SwiGLU + down-proj + residual — the same
        # ffn_impl dispatch as the training blocks (dropout-free here:
        # generation is eval-mode)
        x = common.apply_block_ffn(x, a, blk, cfg)
        new_cache.append(layer_cache)
    return _lm_head(params, x, cfg), new_cache


# ---------------------------------------------------------------------------
# The layer kinds. ``config.py`` says which kind each layer of a
# configuration is and how long its ring (``layer_kinds``, ``ring_len``,
# ``ring_window``); ONE table, ``KINDS``, says what a kind DOES, and nothing
# else in the package says it. A record (:class:`LayerKind`) holds
#
# - ``leaves``, ``zeros``: what a layer keeps in a cache slot, each leaf's
#   name with its pool axis, and the leaves themselves from ``(cfg, rows,
#   ring length)`` as :func:`init_cache` builds them;
# - ``state``: the leaf every token overwrites (through the step's update
#   kernel, or, where the kind keeps a convolution's window alone, the
#   window itself). Such a kind is ``recurrent``: nothing masks a state by
#   position, so a slot that takes a new sequence is zeroed first
#   (:func:`reset_slot_state`; serving/engine.py does so on admission);
# - ``blocks``: how its ring is read (None: it keeps none; False: whole
#   under a mask; True: in blocks as far as it is live); ``rolls``: under
#   multi-token chunks; ``latents``: a position holds one latent, no K/V;
# - ``chunk(h, blk, layer_cache, cfg, pos, ring, valid)`` and ``step(h, blk,
#   layer_cache, cfg, live, pos, ring)``, each ``-> (a, layer_cache)``: its
#   mixer over a prefill chunk and a decode step, on the layer's input normed
#   under ``scope`` (a named scope the per-layer metrics read), with the
#   weights in the block's leaf ``params``;
# - ``refuses``: for the host tier, speculation, paging and int8 storage
#   either nothing or the message serving/engine.py raises (``str.format``
#   over ``model``, ``mixers``, ``ring``, ``block``), in the table's order.
#
# A new kind is one record beside its family's module. A K/V ring is served
# in its family's flavour (``RING_ATTENTION``). A slot's rings may be of
# several lengths; where a row's K/V lands and what a row sees is worked out
# once a length (:class:`_Ring`). The reference families' layers are of the
# kind ``"attention"`` too: their walk (:func:`forward_chunk`'s loop,
# :func:`_decode_step`) shares the record's leaves, not its chunk and step.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerKind:
    params: Optional[str] = None
    leaves: Mapping[str, int] = field(default_factory=dict)
    zeros: Callable = lambda cfg, rows, M: {}
    state: Optional[str] = None
    name: str = ""  # what the refusals call a recurrent kind's layer
    blocks: Optional[bool] = None
    rolls: bool = False
    latents: bool = False
    scope: str = "attn_norm"
    chunk: Optional[Callable] = None
    step: Optional[Callable] = None
    refuses: Mapping[str, str] = field(default_factory=dict)

    @property
    def recurrent(self) -> bool:
        return self.state is not None


class _Ring(NamedTuple):
    """Where the rows of a chunk or a step stand in the rings of one
    length: ``at``, where their K/V goes (a chunk: the first row's ring
    position; a step: a target a row, -1 for none); ``visible``, what each
    row sees of the ring after the write ((L, M) a chunk, (B, 1, M) a
    step; None for a kind read in blocks); ``window``, the positions a
    row sees, itself among them."""
    at: jnp.ndarray
    visible: Optional[jnp.ndarray]
    window: int


def _rings(cfg: ModelConfig, one) -> dict:
    """``{mixer kind: one(ring length, window, blocked)}`` for the kinds
    of ``cfg``'s layers that keep a ring, ``one`` called once a length
    (a family's rings are all read in blocks or none is)."""
    by_len, out = {}, {}
    for kind in cfg.layer_kinds():
        if KINDS[kind].blocks is None or kind in out:
            continue
        M = cfg.ring_len(kind)
        if M not in by_len:
            by_len[M] = one(M, cfg.ring_window(kind), KINDS[kind].blocks)
        out[kind] = by_len[M]
    return out


def _write_chunk_wrapping(layer_cache: dict, ks: jnp.ndarray, v: jnp.ndarray,
                          slot) -> dict:
    """:func:`_write_chunk` for a ring that rolls under multi-token chunks
    (afmoe's sliding layers): the chunk's rows may run over the ring's end
    and go on at its start, which one ``dynamic_update_slice`` cannot
    write. The chunk is laid at the head of a ring of zeros, turned by
    ``slot``, and selected over what the ring held: dense passes over one
    slot's ring (10 MB at the published sizes), no scatter."""
    out = {}
    for key, rows in (("k", ks.transpose(0, 1, 3, 2, 4)),
                      ("v", v.transpose(0, 2, 1, 3))):
        ring = layer_cache[key]
        R, L = ring.shape[-2], rows.shape[-2]
        laid = jnp.pad(rows.astype(ring.dtype),
                       [(0, 0)] * (rows.ndim - 2) + [(0, R - L), (0, 0)])
        fresh = jax.lax.rem(jnp.arange(R) - slot + R, R) < L
        out[key] = jnp.where(fresh[:, None],
                             jnp.roll(laid, slot, axis=-2), ring)
    return out


def _attend_ring_blocked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         pos, window: int) -> jnp.ndarray:
    """A chunk's queries ``q`` (B, L, H, d), the first at absolute
    position ``pos``, over rings ``k``, ``v`` (B, KV, M, d) that already
    hold the chunk's own keys; every K/V head serves H / KV query heads;
    returns (B, L, H * d). ``jamba.attend`` under :func:`_ring_visible`,
    but the ring is read ``ATTEND_KEY_BLOCK`` positions at a time, with a
    running softmax (float32), and only as far as it has been written: the
    trip count is traced (``ceil((pos + L) / block)``, the whole ring once
    it has rolled), so one program serves every position. A chunk of
    1,024 at a ring's start then writes and re-reads float32 scores of
    1,024 x 1,024 a head, not of 1,024 x 8,192: 0.39 ms a layer there and
    1.9 ms at position 6,144 of the full ring, where a Pallas flash kernel
    over the same blocks (tiles of 128 chunk positions x 512 ring
    positions) took 0.50 and 2.66 and was taken out again (my chip run,
    PR 36: PERF.md section 6)."""
    B, L, H, d = q.shape
    KV, M = k.shape[1], k.shape[2]
    KB = math.gcd(M, ATTEND_KEY_BLOCK)
    f32 = jnp.float32
    qg = q.reshape(B, L, KV, H // KV, d)
    take = jax.lax.dynamic_slice_in_dim

    def block(j, carry):
        top, total, acc = carry
        scores = jnp.einsum("blkgd,bkmd->bkglm", qg, take(k, j * KB, KB, 2)
                            ).astype(f32) / math.sqrt(d)
        vis = _ring_visible(pos, L, M, window, j * KB, KB)[None, None, None]
        new_top = jnp.maximum(
            top, jnp.max(jnp.where(vis, scores, NEG_INF), axis=-1))
        probs = jnp.where(vis, jnp.exp(scores - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        acc = acc * keep[..., None] + jnp.einsum(
            "bkglm,bkmd->bkgld", probs.astype(q.dtype), take(v, j * KB, KB, 2)
        ).astype(f32)
        return new_top, total * keep + jnp.sum(probs, axis=-1), acc

    stat = jnp.full((B, KV, H // KV, L), NEG_INF, f32)
    blocks = jnp.minimum((jnp.asarray(pos, jnp.int32) + L - 1) // KB + 1,
                         M // KB)
    _, total, acc = jax.lax.fori_loop(
        0, blocks, block,
        (stat, jnp.zeros_like(stat), jnp.zeros(stat.shape + (d,), f32)))
    out = (acc / total[..., None]).astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, L, H * d)


def _latent_chunk(h, blk, layer_cache, cfg, pos, ring, valid):
    with jax.named_scope("mla"):
        with jax.named_scope("mla_latent_write"):
            rows = kimi_linear.mla_latent(
                h, blk["mla"], cfg,
                pos + jnp.arange(h.shape[1]) if cfg.mla_rotary else None)
            latent = jax.lax.dynamic_update_slice(
                layer_cache["latent"],
                rows[:, None].astype(layer_cache["latent"].dtype),
                (0, 0, ring.at, 0))
        # the widened form, the ring in blocks as far as it is written
        a = kimi_linear.mla_chunk_attend(h, blk["mla"], cfg, latent[:, 0],
                                         pos)
    return a, {"latent": latent}


def _latent_step(h, blk, layer_cache, cfg, live, pos, ring):
    with jax.named_scope("mla"):
        with jax.named_scope("mla_latent_write"):
            rows = kimi_linear.mla_latent(
                h, blk["mla"], cfg, pos if cfg.mla_rotary else None)
            layer_cache = _write_ring(
                layer_cache,
                {"latent": rows[:, None].astype(
                    layer_cache["latent"].dtype)}, ring.at)
        # the absorbed form, a row's live blocks alone
        a = kimi_linear.mla_step_attend(
            h, blk["mla"], cfg, layer_cache["latent"], pos, live)
    return a, layer_cache


def _ring_chunk(h, blk, layer_cache, cfg, pos, ring, valid, *, kind, write):
    ring_qkv, ring_out = RING_ATTENTION[cfg.model]
    with jax.named_scope("attn"):
        q, k, v, rest = ring_qkv(h, blk["attn"], cfg, pos, kind)
        with jax.named_scope("kv_write"):
            layer_cache = write(layer_cache, k[None], v, ring.at)
        if ring.visible is None:  # in blocks, as far as the ring is written
            with jax.named_scope("attn_" + kind):
                o = _attend_ring_blocked(
                    q, layer_cache["k"][0].astype(q.dtype),
                    layer_cache["v"].astype(q.dtype), pos, ring.window)
        else:
            k_c, v_c = _dequant_layer(layer_cache, q.dtype)
            o = jamba.attend(q, k_c[0], v_c, ring.visible)
    return ring_out(o, rest, blk, cfg), layer_cache


def _ring_step(h, blk, layer_cache, cfg, live, pos, ring, *, kind):
    ring_qkv, ring_out = RING_ATTENTION[cfg.model]
    with jax.named_scope("attn"):
        q, k, v, rest = ring_qkv(h, blk["attn"], cfg, pos, kind)
        with jax.named_scope("kv_write"):
            layer_cache = _write_ring(
                layer_cache, _store_rows(layer_cache, k[None], v), ring.at)
        k_c, v_c = _dequant_layer(layer_cache, q.dtype)
        if ring.visible is None:  # a row's live ring blocks alone
            with jax.named_scope("attn_" + kind):
                o = ring_decode_attention(q, k_c[0], v_c, pos, live,
                                          ring.window)
        else:  # every slot's ring whole
            o = jamba.attend(q[:, None], k_c[0], v_c, ring.visible)[:, 0]
    return ring_out(o, rest, blk, cfg), layer_cache


def _recurrent(params: str, state: str, mixer_chunk, mixer_step, zero_state,
               **record) -> LayerKind:
    """A kind that keeps ``state`` (its scope's name too) beside a
    convolution's window ``conv``, or, with ``state="conv"``, the window
    ALONE (lfm2's short convolution, whose window is all that a token
    overwrites): the family's mixer ``(h, p, cfg, conv[, state], until) ->
    (a, conv[, state])`` carries the leaves on from where the last chunk
    left them, as far as ``valid`` goes, and a step overwrites them for the
    ``live`` slots; ``zero_state`` is a sequence's start, in that order."""
    kept = tuple(dict.fromkeys(("conv", state)))

    def run(fn, h, blk, layer_cache, cfg, until):
        with jax.named_scope(state):
            a, *new = fn(h, blk[params], cfg,
                         *(layer_cache[leaf] for leaf in kept), until)
        return a, dict(zip(kept, new))

    return LayerKind(
        params=params, state=state, scope=state,
        leaves=dict.fromkeys((state, "conv"), 0),
        zeros=lambda cfg, rows, M: dict(zip(kept, zero_state(cfg, rows))),
        chunk=lambda h, blk, c, cfg, pos, ring, valid: run(
            mixer_chunk, h, blk, c, cfg, valid),
        step=lambda h, blk, c, cfg, live, pos, ring: run(
            mixer_step, h, blk, c, cfg, live), **record)


def _kv_ring(kind: str, blocks: bool, write: Callable = _write_chunk,
             **record) -> LayerKind:
    """A K/V ring in the family's own flavour (``RING_ATTENTION``);
    ``write`` puts a chunk into it."""
    return LayerKind(
        params="attn", blocks=blocks, zeros=_kv_zeros,
        leaves={"k": 1, "v": 0, "k_scale": 1, "v_scale": 0},
        chunk=partial(_ring_chunk, kind=kind, write=write),
        step=partial(_ring_step, kind=kind), **record)


def _refusals(lead: str, tail: str, int8: str, **asked: str) -> dict:
    return dict({feature: lead + what + tail
                 for feature, what in asked.items()},
                int8="kv_cache_dtype='int8' is not available for the "
                     "{model} family: " + int8)


def _no_snapshot(drafts: str = "", kept: str = "float32") -> dict:
    """A K/V ring can be cut, shared, rolled back or shipped at any
    position; a recurrent state is overwritten every token and what it was
    earlier is gone. ``{mixers}``: the configuration's recurrent kinds;
    ``kept``: what the state is stored as."""
    return _refusals(
        "the {model} family keeps a recurrent state a {mixers} layer, and ",
        " needs a snapshot of that state at a position, which the engine "
        "does not take",
        int8="its attention layers' decode path reads float rings "
             "(grouped-query K/V, or MLA's latents), and a quantized "
             "{mixers} state does not exist yet (the state is " + kept + ")",
        host_tier="the host tier (host_tier_bytes; preemption and resume)",
        spec="speculation (spec_mode; rejected drafts roll the cache back"
             + drafts + ")",
        paging="paging (kv_page_size > 0; with it the prefix cache, whose "
               "hits resume a sequence at the shared prefix's end)")


# serving/pages.py keeps one page table a slot for every layer, and a page,
# a rolled-back draft or a stashed slot means the same ring positions in
# every layer: not where the sliding rings are shorter, and roll
_TWO_RING_LENGTHS = _refusals(
    "the {model} family keeps rings of two lengths a slot (a sliding "
    "layer's of {ring}, a full layer's of {block}), and ", "",
    int8="its prefill writes a chunk into a rolling ring by a select over "
         "the float ring, and its grouped-query decode path reads float "
         "rings",
    host_tier="the host tier (host_tier_bytes) stashes and restores a slot "
              "as pages of one page table",
    spec="speculation (spec_mode) verifies several rows a slot in one "
         "step, whose writes into a rolled sliding ring would evict keys "
         "that the step's earlier rows still see, and whose rejected rows "
         "cannot be rolled back there",
    paging="paging (kv_page_size > 0; with it the prefix cache) maps every "
           "layer's ring through ONE page table a slot, block_size long")

# a ring of latents is addressed by position like a K/V ring and all rings
# are of one length; what is missing is code, named here
_LATENT_RING = _refusals(
    "the {model} family keeps a ring of latents a slot and MLA layer, and ",
    "",
    int8="int8 latents do not exist yet (quantize_kv scales a K/V head; a "
         "latent is key and value of every head at once, and its shared "
         "key part would need a scale of its own), and the live-latent "
         "read takes float latents",
    host_tier="the host tier (host_tier_bytes) stashes and restores a slot "
              "as the pages of a page table, which this pool does not have "
              "(no paging over latents yet)",
    spec="speculation (spec_mode) verifies several rows a slot in one "
         "step: the hybrid decode loop advances one row a slot, and the "
         "live-latent read (ops/mla.py latent_decode_attention) takes one "
         "query position a slot",
    paging="paging (kv_page_size > 0; with it the prefix cache) maps K and "
           "V leaves through a page table: serving/pages.py and the paged "
           "decode programs know no `latent` leaf, and the live-latent "
           "read takes a slot's ring whole, not pages")


KINDS = {
    # grouped-query attention over the whole ring under a mask (jamba's; the
    # ring cannot roll); the reference families keep the same leaves
    "attention": _kv_ring("attention", blocks=False),
    # a Mamba-1 mixer (jamba): ssm (B, N, Di) in ``ssm_state_dtype`` and the
    # convolution's last inputs, conv (B, K-1, Di)
    "mamba": _recurrent(
        "mamba", "ssm", jamba.mixer_chunk, jamba.mixer_step,
        jamba.zero_state, name="Mamba", refuses=_no_snapshot()),
    # a Mamba-2 (SSD) mixer (nemotron_h): ssm (B, N, heads x P) float32, conv
    # (B, K-1, Di + 2 n_groups N) over x, B and C together
    "mamba2": _recurrent(
        "mamba2", "ssm", nemotron_h.mixer_chunk, nemotron_h.mixer_step,
        nemotron_h.zero_state, name="Mamba-2", refuses=_no_snapshot(
            "; the published multi-token-prediction module, which this "
            "family leaves out, would be its draft head")),
    # a KDA delta-rule mixer (kimi_linear): kda (B, H, d, d) float32, conv
    # (B, K-1, 3 H d) over q, k and v
    "kda": _recurrent(
        "kda", "kda", kimi_linear.kda_chunk, kimi_linear.kda_step,
        kimi_linear.kda_zero_state, name="KDA", refuses=_no_snapshot()),
    # a gated short convolution (lfm2): conv (B, K-1, E), its last gated
    # inputs in the compute dtype, is the layer's WHOLE cache: no state
    # stands beside the window, and the window is what every token overwrites
    "shortconv": _recurrent(
        "conv", "conv", lfm2.conv_chunk, lfm2.conv_step, lfm2.zero_window,
        name="short-convolution", refuses=_no_snapshot(
            kept="the convolution's window of conv_taps - 1 gated inputs in "
                 "the compute dtype")),
    # sliding-window attention (afmoe): a ring of ``sliding_ring`` positions
    # that rolls, shorter than the slot's full rings
    "window": _kv_ring("window", True, _write_chunk_wrapping, rolls=True,
                       refuses=_TWO_RING_LENGTHS),
    # attention over every earlier position (afmoe's full layers and
    # nemotron_h's ``*`` layers, without a position of their own; lfm2's,
    # rotated: ``RING_ATTENTION`` says which)
    "full": _kv_ring("full", blocks=True),
    # MLA over a ring of latents (kimi_linear's MLA layers, every deepseek_v2
    # layer): ONE "head" a position, rank + rope wide, that every head reads
    "latent": LayerKind(
        params="mla", blocks=True, latents=True, scope="mla",
        refuses=_LATENT_RING, chunk=_latent_chunk, step=_latent_step,
        leaves={"latent": 0},
        zeros=lambda cfg, rows, M: {"latent": jnp.zeros(
            (rows, 1, M, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            jnp.dtype(cfg.compute_dtype))}),
    # no mixer: the layer is its feed-forward part alone (a nemotron_h ``E``
    # layer); its cache entry is ``{}``, which a walk over its items passes by
    "none": LayerKind(),
}

# Each cache leaf's pool axis (K and its scales carry the stream axis first):
# the single source of truth for every per-slot slice/scatter/merge over the
# cache (serving/engine.py), and the page axis; the recurrent kinds' leaves
# (a state beside a window, or a window alone); the block's leaves that hold
# a mixer.
KV_CACHE_BATCH_AXIS = {leaf: axis for record in KINDS.values()
                       for leaf, axis in record.leaves.items()}
STATE_LEAVES = tuple(dict.fromkeys(
    leaf for record in KINDS.values() if record.recurrent
    for leaf in record.leaves))
MIXER_LEAVES = tuple(dict.fromkeys(
    record.params for record in KINDS.values() if record.params))


def _mlp(x, blk: dict, cfg: ModelConfig, live=None):
    """A layer's MLP of either kind on the residual ``x``: ``(x + y, the
    held experts' load or None)``; a layer that is a mixer alone (no
    ``ffn`` and no ``moe`` leaf: a nemotron_h ``M`` or ``*`` layer) has no
    second half and hands ``x`` back."""
    if "ffn" not in blk and "moe" not in blk:
        return x, None
    if "ln2_post" in blk:  # afmoe: either kind between two norms
        return afmoe.mlp(x, blk, cfg, live)
    if "moe" in blk:
        return kimi_linear.moe(x, blk, cfg, live)
    return jamba.ffn(x, blk, cfg), None


def _hybrid_walk(params: dict, tokens: jnp.ndarray, cache: list,
                 cfg: ModelConfig, rings: dict, mixer, live=None, valid=None):
    """The hybrid families' stack over a chunk or a step: embed; a layer:
    the pre-norm, ``mixer(record, h, blk, layer_cache, ring)`` (the
    record's ``chunk`` or ``step`` at the caller's positions), the
    residual, the MLP; the head, for a padded chunk on the row of its last
    real token (``valid``). A layer without a mixer hands its empty cache
    entry on. A step (``live`` given) gathers the experts' load. Returns
    ``(logits, the cache after, the loads' sum or None)``."""
    x = HYBRID[cfg.model].embed(params, tokens, cfg)  # afmoe's is scaled
    new_cache, loads = [], []
    for blk, layer_cache, kind in zip(params["blocks"], cache,
                                      cfg.layer_kinds()):
        record = KINDS[kind]
        if record.params:
            with jax.named_scope(record.scope):
                h = jamba.norm(x, blk["ln1"], cfg)
            a, layer_cache = mixer(record, h, blk, layer_cache,
                                   rings.get(kind))
            x = x + a
        new_cache.append(layer_cache)
        x, load = _mlp(x, blk, cfg, live)
        if load is not None and live is not None:
            # a router limited to groups adds the rows that kept a held one
            load, *reached = load if isinstance(load, tuple) else (load,)
            loads.append(jnp.stack([jnp.sum(load), jnp.max(load),
                                    jnp.sum(load > 0), *reached]))
    if valid is not None:
        x = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
    with jax.named_scope("lm_head"):
        # the loads' sum has always stood under this scope: the device
        # time the per-layer metrics count as the head's holds it
        return (jamba.lm_head(params, x, cfg), new_cache,
                sum(loads) if loads else None)


def _hybrid_chunk(params: dict, tokens: jnp.ndarray, pos, cache: list,
                  cfg: ModelConfig, window: int = 0, valid=None):
    """:func:`forward_chunk` for the hybrid families: every recurrent
    layer's state enters as the state before the chunk and leaves as the
    state after it, so a prompt may arrive in any chunks. With ``valid``
    the recurrent layers stop their state there (``jamba.mixer_chunk``,
    ``kimi_linear.kda_chunk``); the attention layers write the padding's
    keys and values (latents) into ring positions past the sequence's
    end, which no query sees (a query sees no later position) and which
    the tokens that come to stand there overwrite before they attend. In
    a ring that rolls (afmoe's sliding layers) the chunk, padding
    included, evicts the positions a ring length before its own, which
    lie outside the window of each of its rows and of every later row as
    long as the chunk is no longer than the ring's slack past the window
    (``cfg.ring_slack``: serving/engine.py holds ``prefill_chunk`` to it,
    :func:`forward_chunk` a concrete chunk)."""
    L = tokens.shape[1]
    rings = _rings(cfg, lambda M, W, blocked: _Ring(
        jax.lax.rem(jnp.asarray(pos, jnp.int32), M),
        None if blocked else _ring_visible(pos, L, M, int(window) or W),
        int(window) or W))
    return _hybrid_walk(
        params, tokens, cache, cfg, rings,
        lambda record, h, blk, layer_cache, ring: record.chunk(
            h, blk, layer_cache, cfg, pos, ring, valid), valid=valid)[:2]


def _hybrid_decode(params: dict, tokens: jnp.ndarray, pos, cache: list,
                   cfg: ModelConfig, active=None):
    """The hybrid families' decode step over the whole slot pool, one
    batch: ``((B, V) logits, updated cache, expert load)``. The attention
    layers write their row into the ring in place (``ops/kv_write.py``)
    and read the pool (a row's live ring or latent blocks alone,
    ``ops/ring_attention.py``, ``ops/mla.py``; jamba's every ring whole
    under a mask); the recurrent layers advance the active slots' states
    (``ops/ssm.py``, ``ops/ssd.py``, ``ops/kda.py``). A row that is not
    ``active`` leaves every leaf of its slot as it is and meets no
    expert. ``load`` (3,) int32, summed over the expert layers:
    the (row, expert) assignments that fell on held experts, the largest
    count on one expert, and the held experts that got a row at all (whose
    weights the step had to read); a router limited to groups
    (deepseek_v2) adds a fourth, the live rows that kept a group this
    share holds, which alone can meet a held expert. None for a family
    without experts."""
    B = tokens.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    live = jnp.ones((B,), bool) if active is None else active

    def ring(M: int, W: int, blocked: bool) -> _Ring:
        # a ring that is not read in blocks cannot roll (jamba's: pos < M
        # always), so slot m holds a live key iff m <= pos; the blocked
        # kinds' rows find what they see in ops/ring_attention.py and
        # ops/mla.py
        visible = (None if blocked else
                   jnp.arange(M)[None, None, :] <= pos[:, None, None])
        return _Ring(_write_targets(pos, active, M), visible, W)

    return _hybrid_walk(
        params, tokens, cache, cfg, _rings(cfg, ring),
        lambda record, h, blk, layer_cache, ring: record.step(
            h, blk, layer_cache, cfg, live, pos, ring), live=live)


def live_kv(pos: np.ndarray, active: np.ndarray, window: int) -> dict:
    """What a decode step's rows hold of their rings, from the positions
    and the mask the engine built (NumPy: no device read), summed over the
    ACTIVE rows: ``live_window``, the positions a sliding layer's ring
    holds live, ``min(pos + 1, window)`` a row; ``live_full``, a full
    layer's, ``pos + 1``; ``rolled``, the rows whose position has passed
    the window (``pos >= window``), for which a sliding layer reads less
    than a full one. The ``decode`` span's ``kv`` argument (a family with
    sliding layers); the rule stands here, beside the program that reads
    the rings."""
    at = pos[active].astype(np.int64)
    return {"live_window": int(np.minimum(at + 1, window).sum()),
            "live_full": int((at + 1).sum()),
            "rolled": int((at >= window).sum())}


def reset_slot_state(cache: list, slot) -> list:
    """``cache`` with slot ``slot``'s recurrent state (the leaves of every
    recurrent kind's layers, ``STATE_LEAVES``) zeroed, in place under a jit
    that donates the pool; rings are left as they are (positions mask
    them) and a layer without a mixer has nothing to zero. ``slot`` is a
    runtime scalar."""
    return [
        {key: (jax.lax.dynamic_update_slice_in_dim(
                   leaf, jnp.zeros((1,) + leaf.shape[1:], leaf.dtype),
                   slot, axis=0)
               if key in STATE_LEAVES else leaf)
         for key, leaf in layer.items()}
        for layer in cache
    ]


# ---------------------------------------------------------------------------
# Paged KV cache (serving/pages.py): the pool's batch axis indexes
# PHYSICAL PAGES of page_size tokens instead of whole slots. A slot's
# logical block_size ring maps onto pages through a per-slot page-table
# row (runtime int32 arrays — allocation/free/sharing never recompiles).
# Physical page 0 is the reserved trash page: unallocated logical pages
# and inactive rows' decode writes land there, so the jitted step needs
# no masking. KV_CACHE_BATCH_AXIS doubles as the page-axis table: the
# page axis sits exactly where the slot axis sat.
# ---------------------------------------------------------------------------


def init_cache_paged(cfg: ModelConfig, num_pages: int,
                     page_size: int) -> list:
    """Per-layer paged K/V pools: the :func:`init_cache` layout with
    ``(num_pages, page_size)`` replacing ``(batch, block_size)`` on
    each leaf — K (S, P, H, ps, d), V (P, H, ps, dv), plus the fp32
    scale planes on the int8 path. ``num_pages`` INCLUDES the reserved
    trash page 0 (serving/pages.py:PagePool)."""
    if cfg.block_size % page_size:
        raise ValueError(
            f"page_size ({page_size}) must divide block_size "
            f"({cfg.block_size}): the ring mask assumes whole pages"
        )
    return init_cache(cfg.replace(block_size=page_size), num_pages)


def _gather_row(leaf: jnp.ndarray, page_row: jnp.ndarray, axis: int):
    """One slot's contiguous ring view from its page-table row: gather
    the row's pages on the page axis, fold (pages, page_size) into one
    token axis, and re-add the batch-1 axis forward_chunk expects."""
    g = jnp.take(leaf, page_row, axis=axis)
    g = jnp.moveaxis(g, axis, axis + 1)  # page axis next to tokens
    shape = (
        g.shape[:axis + 1]
        + (g.shape[axis + 1] * g.shape[axis + 2],)
        + g.shape[axis + 3:]
    )
    return jnp.expand_dims(g.reshape(shape), axis)


def _scatter_row(leaf: jnp.ndarray, new_row: jnp.ndarray,
                 page_row: jnp.ndarray, axis: int):
    """Inverse of :func:`_gather_row`: split the ring view back into
    pages and scatter them to the row's physical pages. Duplicate trash
    entries in the row collide harmlessly (page 0 is write-only
    garbage); shared prefix pages receive their own unchanged values
    (the engine guarantees written positions live on private pages)."""
    r = jnp.squeeze(new_row, axis)
    pp = page_row.shape[0]
    shape = (
        r.shape[:axis + 1]
        + (pp, r.shape[axis + 1] // pp)
        + r.shape[axis + 2:]
    )
    r = jnp.moveaxis(r.reshape(shape), axis + 1, axis)
    idx = (slice(None),) * axis + (page_row,)
    return leaf.at[idx].set(r)


def gather_slot_cache(cache: list, page_row: jnp.ndarray) -> list:
    """A slot's per-layer batch-1 ring view through its page table —
    what the prefill chunk path (forward_chunk) runs against."""
    return [
        {key: _gather_row(c[key], page_row, KV_CACHE_BATCH_AXIS[key])
         for key in c}
        for c in cache
    ]


def scatter_slot_cache(cache: list, new_row: list,
                       page_row: jnp.ndarray) -> list:
    """Write an updated ring view back through the page table."""
    return [
        {key: _scatter_row(c[key], nr[key], page_row,
                           KV_CACHE_BATCH_AXIS[key])
         for key in c}
        for c, nr in zip(cache, new_row)
    ]


def copy_cache_pages(cache: list, src, dst) -> list:
    """Copy one physical page onto another across every layer/leaf —
    the device half of a copy-on-write fork (serving/pages.py): the
    shared page's prefix K/V lands in a private page the forking slot
    may write. ``src``/``dst`` are runtime int32 scalars, so forks
    never recompile."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = []
    for c in cache:
        layer = {}
        for key in c:
            axis = KV_CACHE_BATCH_AXIS[key]
            page = jnp.take(c[key], src, axis=axis)
            idx = (slice(None),) * axis + (dst,)
            layer[key] = c[key].at[idx].set(page)
        out.append(layer)
    return out


def _gather_pool_view(leaf: jnp.ndarray, page_tables: jnp.ndarray,
                      axis: int):
    """Every slot's ring view at once: (…, B, H, M, …) gathered from
    the paged leaf through the full (B, pages_per_slot) table — the
    XLA decode path's read (the Pallas kernel instead loads pages
    directly through the table, ops/decode_attention.py)."""
    B, pp = page_tables.shape
    g = jnp.take(leaf, page_tables.reshape(-1), axis=axis)
    g = g.reshape(
        leaf.shape[:axis] + (B, pp) + leaf.shape[axis + 1:]
    )
    g = jnp.moveaxis(g, axis + 1, axis + 2)  # pages next to tokens
    shape = (
        g.shape[:axis + 2]
        + (g.shape[axis + 2] * g.shape[axis + 3],)
        + g.shape[axis + 4:]
    )
    return g.reshape(shape)


# ---------------------------------------------------------------------------
# The decode step. ONE program (:func:`_decode_step`) advances N rows by a
# token each, every row at its own absolute position: the engine's step
# over its slots (tokens (B,), N = B) and speculation's batched verify
# (tokens (B, L), N = B * L: row (b, l) is slot b's last emitted token,
# l = 0, or its l-th draft token, seen by the rows after it and by none
# before: update-then-attend, all rows written first, then each row's mask
# ``col <= pos[b, l]``). What differs between the pool's two layouts, and
# between one row a slot and L, is bound once a step as a pair of
# functions (:func:`_pool_seam`): ``write``, where a row's K/V lands, and
# ``attend``, what a row reads. A row that must leave no trace (a free
# slot, a slot in mid-prefill, a verify row past its slot's draft) is
# turned away by the WRITE, never masked afterwards: the slot pool's step
# gives it no target (:func:`_write_targets`), the paged pool sends it to
# the reserved trash page, the slot pool's batched verify to a trash row
# past the slots. So the program's shapes never depend on which rows are
# live, and mixed traffic compiles nothing new.
# ---------------------------------------------------------------------------


@jax.named_scope("kv_merge")
def _write_targets(pos: jnp.ndarray, active, M: int) -> jnp.ndarray:
    """(B,) int32: the ring position each row's K/V goes to,
    ``pos[b] % M``, or -1 for a row that is not ``active`` and keeps
    what its ring holds (a free slot, a slot in mid-prefill, a verify
    row past its slot's draft). This B-sized select is all that a step
    decides about keeping: the pool itself is never selected over."""
    slot = jax.lax.rem(jnp.asarray(pos, jnp.int32), M)
    return slot if active is None else jnp.where(active, slot, -1)


def _store_rows(layer_cache: dict, ks: jnp.ndarray, v: jnp.ndarray) -> dict:
    """The rows' new K/V — ks (S, N, H, d), v (N, H, dv) — as the pool
    stores them, one array a leaf of the layer. The int8 path quantizes
    here, once for every ``write``, so the step's own attention (and
    every later step) reads exactly what the cache holds."""
    if "k_scale" in layer_cache:
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(v)
        rows = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
    else:
        rows = {"k": ks, "v": v}
    return {key: rows[key].astype(leaf.dtype)
            for key, leaf in layer_cache.items()}


def _write_ring(layer_cache: dict, rows: dict, targets: jnp.ndarray) -> dict:
    """``write`` for one row a slot of the slot pool: row b goes into its
    own ring at ``targets[b]`` (:func:`_write_targets`), in place in the
    donated pool, one ``ops/kv_write.py`` kernel a leaf: the float and
    the int8 leaves and the scale planes take the same route, addressed
    through ``KV_CACHE_BATCH_AXIS``. A row with no target costs the
    kernel no traffic: its grid step points at the block of its
    ``owner`` (``ops/kv_write.py:slot_owners``, made from ``targets``
    inside ``write_rows``: the nearest slot before it that writes),
    which the chip neither fetches nor writes back a second time, so a
    leaf's write moves a block for each ACTIVE row, not for each slot."""
    return {
        key: write_rows(leaf, rows[key], targets, KV_CACHE_BATCH_AXIS[key])
        for key, leaf in layer_cache.items()
    }


def _write_scatter(layer_cache: dict, rows: dict, where: jnp.ndarray,
                   pos: jnp.ndarray) -> dict:
    """``write`` by an XLA scatter: row n goes to entry ``where[n]`` of
    the pool's batch axis, at that entry's token ``pos[n] %`` its length.
    For a paged pool the entry is a physical page (the engine names the
    trash page for a row that must not land; the page size divides
    ``block_size``, so this is the in-page offset of ``pos % M``); for
    the slot pool under a batched verify it is a cache row (the trash row
    likewise; several rows of a slot then land in one ring, which the
    kernel of :func:`_write_ring` does not do). Collisions inside the
    trash entry are harmless: it is write-only garbage."""
    token = jax.lax.rem(pos, layer_cache["v"].shape[-2])
    out = {}
    for key, leaf in layer_cache.items():
        axis = KV_CACHE_BATCH_AXIS[key]
        at = (slice(None),) * axis + (where, slice(None), token)
        out[key] = leaf.at[at].set(jnp.moveaxis(rows[key], axis, 0))
    return out


# Rows of the slot pool that the own-ring attend covers in one block: it
# runs the blocks up to the one holding the highest ACTIVE row and no
# further (the scheduler admits into the lowest free slot, so the live
# slots crowd the low indices). 32 read on the chip (PERF.md section 6,
# PR 33): a block of 16 costs a full pool 4% of its step, 64 reads twice
# the rows at the chat cell's occupancy.
ATTEND_BLOCK_ROWS = 32


def _fused_attend(cfg: ModelConfig) -> bool:
    """The one read of ``decode_attention_impl`` outside ``config.py``."""
    return cfg.decode_attention_impl == "pallas"


def own_ring_attend(cfg: ModelConfig, paged: bool) -> bool:
    """Whether the L = 1 step over this pool attends each row over its
    own ring in XLA (:func:`_attend_own_ring`, the first line of
    :func:`_pool_seam`'s table), the attend that :func:`attend_rows`
    bounds: the engine asks, to know what its ``decode`` span may say."""
    return not paged and cfg.model not in HYBRID and not _fused_attend(cfg)


def attend_rows(active) -> "int | jnp.ndarray":
    """Rows of the pool that :func:`_attend_own_ring` reads for the mask
    ``active`` (B,): whole blocks of ``ATTEND_BLOCK_ROWS`` up to the one
    that holds the highest active row, 0 with none active, never more
    than B. THE rule, stated once: the decode program calls it on the
    traced mask, the engine on the NumPy mask it built (the ``decode``
    span's ``attend_rows``), and both read the same number."""
    B = active.shape[0]
    R = min(ATTEND_BLOCK_ROWS, B)
    xp = np if isinstance(active, np.ndarray) else jnp
    span = xp.max(xp.where(active, xp.arange(1, B + 1), 0))  # highest + 1
    rows = xp.minimum((span + R - 1) // R * R, B)
    return int(rows) if xp is np else rows


def _attend_own_ring(cfg: ModelConfig, active, qs, pos, layer_cache, p_attn,
                     layer_idx):
    """``attend`` for one row a slot of the slot pool, in XLA: each row
    over its own ring, a length-1 :func:`forward_chunk`'s attend
    (:func:`_chunk_attend`) under ``vmap``. The serve cells measure this
    one.

    With a mask (the engine's step) the rows go through that ``vmap`` a
    block of ``ATTEND_BLOCK_ROWS`` at a time, in a loop that stops after
    the block of the highest active row (:func:`attend_rows`): the rings
    past it are not read, and their rows come out as zeros. The trip
    count is traced, so one program serves every mask. A row that is
    read has exactly the math of the whole-pool ``vmap``, which
    ``active=None`` keeps (no loop).

    The loop takes a K or V leaf as the chip holds it (ring on the
    lanes: ``ops/kv_write.py:position_on_lanes``), read row-major, and
    swaps a block's two last axes back. The compiler lays a loop's body
    out before it sees the caller: handed the leaf as it is, the body
    expects it row-major and every block goes through a copy in VMEM
    first (3.1 ms a step for 2.1 at one block, 16.2 for 8.5 at a full
    pool: my chip run, PR 33); handed the view, the slice fuses into the
    score and value fusions, which read the pool where it lies."""

    def one(q, at, ring):
        # re-add the batch-1 axis forward_chunk's layout has
        ring = {key: jnp.expand_dims(leaf, KV_CACHE_BATCH_AXIS[key])
                for key, leaf in ring.items()}
        return _chunk_attend(q, p_attn, ring, at, layer_idx, cfg)

    ring_axes = {key: KV_CACHE_BATCH_AXIS[key] for key in layer_cache}
    rows_attend = jax.vmap(one, in_axes=(0, 0, ring_axes))
    if active is None:
        return rows_attend(qs, pos, layer_cache)

    B = pos.shape[0]
    R = min(ATTEND_BLOCK_ROWS, B)
    # K and V: (.., B, H, M, features); a scale plane has no feature axis
    swapped = {key for key, leaf in layer_cache.items()
               if leaf.ndim == ring_axes[key] + 4
               and position_on_lanes(*leaf.shape[-2:])}

    def chip_view(rows: dict) -> dict:  # its own inverse
        return {key: jnp.swapaxes(leaf, -1, -2) if key in swapped else leaf
                for key, leaf in rows.items()}

    pool = chip_view(layer_cache)
    take = jax.lax.dynamic_slice_in_dim

    def block(i, out):
        # the last block of a pool that R does not divide starts early
        # and recomputes rows the block before it wrote: the same values
        start = jnp.minimum(i * R, B - R)
        rings = chip_view({key: take(leaf, start, R, axis=ring_axes[key])
                           for key, leaf in pool.items()})
        heads = rows_attend(take(qs, start, R), take(pos, start, R), rings)
        return jax.lax.dynamic_update_slice_in_dim(out, heads, start, 0)

    out = jax.eval_shape(rows_attend, qs, pos, layer_cache)
    return jax.lax.fori_loop(0, (attend_rows(active) + R - 1) // R, block,
                             jnp.zeros(out.shape, out.dtype))


def _attend_pool(cfg: ModelConfig, shape: tuple, page_tables, fused: bool,
                 qs, pos, layer_cache, p_attn, layer_idx):
    """``attend`` over the pool as a whole, slot b's L rows with
    row-causal visibility (L = 1 for the plain step): the fused
    multi-query kernel of ops/decode_attention.py, which streams a ring
    (or, through the page table, its pages) once for its L rows and
    dequantizes int8 in the load, or its XLA oracle over a float view of
    the rings: the pages gathered through the table, or the slot pool
    short of its trash row, which is never attended."""
    B, L = (*shape, 1)[:2]  # the plain step is a block of one row a slot
    q = qs[:, :, 0, 0].swapaxes(0, 1)  # (N, S, 1, 1, H, d) -> (S, N, H, d)
    q = q.reshape(q.shape[:1] + (B, L) + q.shape[2:])
    pos = pos.reshape(B, L)
    coeffs = _layer_coeffs(cfg, p_attn, layer_idx)
    if fused:
        scales = {"k_scale": layer_cache.get("k_scale"),
                  "v_scale": layer_cache.get("v_scale")}
        if page_tables is None:
            return decode_attention_multi(
                q, layer_cache["k"], layer_cache["v"], pos, coeffs, **scales)
        return decode_attention_multi_paged(
            q, layer_cache["k"], layer_cache["v"], page_tables, pos, coeffs,
            **scales)
    if page_tables is None:
        view = {key: jax.lax.slice_in_dim(leaf, 0, B,
                                          axis=KV_CACHE_BATCH_AXIS[key])
                for key, leaf in layer_cache.items()}
    else:
        view = {key: _gather_pool_view(leaf, page_tables,
                                       KV_CACHE_BATCH_AXIS[key])
                for key, leaf in layer_cache.items()}
    k_eff, v_eff = _dequant_layer(view, q.dtype)
    return decode_attention_multi_reference(q, k_eff, v_eff, pos, coeffs)


def _pool_seam(cfg: ModelConfig, shape: tuple, pos: jnp.ndarray, active,
               where, page_tables):
    """The step's ``(write, attend)``, chosen here and nowhere else, from
    what the caller passes (page tables or none; tokens ``(B,)`` or
    ``(B, L)``) and ``cfg.decode_attention_impl``:

    ======================  ========================  ====================
    pool, rows a slot       write                     attend (xla | pallas)
    ======================  ========================  ====================
    slots, 1                ``_write_ring``           own ring | pool
    slots, L (trash row)    ``_write_scatter``        pool     | pool
    pages, 1 or L           ``_write_scatter``        pool     | pool
    ======================  ========================  ====================

    ``active`` (the rows that write; None = all) bounds both halves of
    the first line: ``_write_ring`` moves a block only for a row that
    writes, and the XLA own-ring attend reads the pool only up to the
    block of the highest active row (:func:`attend_rows`; with None it
    reads every ring). The other attends read the pool whole.

    ``write(layer_cache, rows) -> layer_cache`` takes what
    :func:`_store_rows` made; ``attend(qs, pos, layer_cache, p_attn,
    layer_idx)`` takes the rows' queries as :func:`_chunk_qkv` leaves
    them under the rows' vmap, (N, S, 1, 1, H, d), and returns the
    concatenated heads a row. A change to how a row is written or read
    (another kernel, another layout) is a change to one of the four
    functions above or a new line here."""
    own_ring = page_tables is None and len(shape) == 1
    if own_ring:
        write = partial(_write_ring,
                        targets=_write_targets(pos, active, cfg.block_size))
    else:
        write = partial(_write_scatter, pos=pos,
                        where=jnp.asarray(where, jnp.int32).reshape(-1))
    fused = _fused_attend(cfg)
    if own_ring and not fused:
        return write, partial(_attend_own_ring, cfg, active)
    return write, partial(_attend_pool, cfg, shape, page_tables, fused)


def _decode_step(params: dict, tokens: jnp.ndarray, pos, cache: list,
                 cfg: ModelConfig, rope_len: int, active=None, where=None,
                 page_tables=None) -> Tuple[jnp.ndarray, list]:
    """The K/V families' one decode program: embed, the layers, the head,
    for tokens ``(B,)`` or ``(B, L)`` with ``pos`` alike: N = B * L rows,
    each a length-1 :func:`forward_chunk` at its own position, against
    the pool :func:`_pool_seam` binds. Returns fp32 logits
    ``tokens.shape + (V,)`` and the updated cache.

    The rule of this loop (PR 29 measured it): only what is about a row's
    own position may sit under a ``vmap`` over the rows, which is its
    embedding, its Q/K/V rotated at its position (:func:`_chunk_qkv`)
    and, where ``attend`` is the row's own ring, that attend. Everything
    else runs ONCE over the N rows, ``(N, 1, 1, E)`` taken as M = N by
    the functions' own ``reshape(-1, E)``: the write, the norms, the
    attention's tail (:func:`_attn_out`), the FFN half and the head.
    Under a vmap a Pallas kernel gets the rows prepended to its grid, one
    row a grid step (the fused FFN kernel streamed its weights 256 times
    a layer for 256 rows), and a vmapped ``dynamic_update_slice`` hands
    back a NEW pool, which the chip fills through a copy of every ring.
    A row's math is a length-1 chunk's and its matmuls run at M = N, so a
    served token's logits equal a chunk's up to the reassociation of a
    reduction (tests/test_decode_rows.py states the tolerance). Rows that
    write nothing run the same projections, FFN and head on whatever
    their slot holds (static shapes are the point) and their logits mean
    nothing; whether their attention runs at all is the bound
    ``attend``'s business (the XLA own-ring attend skips the blocks of
    rows past the highest active one and hands those rows zeros, so no
    caller may read an inactive row's logits). The engine's
    admission guards own the concrete-position validity rules
    (serving/engine.py submit, ``generate_cached``'s checks); everything
    here is traced."""
    shape = tokens.shape
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    write, attend = _pool_seam(cfg, shape, pos, active, where, page_tables)
    with jax.named_scope("embed"):
        x, cos, sin = jax.vmap(
            lambda t, p: _embed_chunk(params, t[None, None], p, cfg, rope_len)
        )(tokens.reshape(-1), pos)  # x (N, 1, 1, E): a batch-1 chunk a row
    new_cache = []
    for li, blk in enumerate(params["blocks"], 1):  # 1-based schedule
        with jax.named_scope("attn_norm"):
            h = common.apply_pre_norm(x, blk["ln1"], cfg)
        with jax.named_scope("attn"):
            qs, ks, v = jax.vmap(
                lambda h, cos, sin: _chunk_qkv(h, blk["attn"], cfg, cos, sin)
            )(h, cos, sin)
            with jax.named_scope("kv_write"):
                layer_cache = write(cache[li - 1], _store_rows(
                    cache[li - 1],
                    ks[:, :, 0, 0].swapaxes(0, 1),  # (N, S, 1, 1, H, d) rows
                    v[:, 0, 0],  # (N, 1, 1, H, dv) rows
                ))
            heads = attend(qs, pos, layer_cache, blk["attn"], li)
            a = _attn_out(heads.reshape(x.shape[:-1] + (-1,)), blk["attn"],
                          cfg)
        x = common.apply_block_ffn(x, a, blk, cfg)
        new_cache.append(layer_cache)
    logits = _lm_head(params, x, cfg)[:, 0, -1].astype(jnp.float32)
    return logits.reshape(shape + logits.shape[-1:]), new_cache


def forward_decode_pool(
    params: dict,
    tokens: jnp.ndarray,  # (B,) current token per slot row
    pos,  # (B,) int32 absolute position per row (runtime array)
    cache: list,  # init_cache's pool, or init_cache_paged's with tables
    cfg: ModelConfig,
    rope_len: int = 0,
    active=None,  # (B,) bool: rows whose K/V is written; None = all
    page_tables=None,  # (B, pages_per_slot) int32: the pool is paged
    write_pages=None,  # (B,) int32 physical page a row's write goes to
) -> Tuple[jnp.ndarray, list]:
    """Advance the WHOLE pool by one token: ``((B, V) logits, updated
    cache)``. THE L = 1 entry point: the engine's step, every EXACT
    verify sub-step, the model drafter's rounds and ``generate_cached``'s
    loop all run this, on either layout and either
    ``decode_attention_impl`` (:func:`_pool_seam`). On the slot pool a
    row that is not ``active`` leaves its ring as it is and its logits
    mean nothing (the XLA attention stops at the highest active row:
    :func:`attend_rows`). With
    ``page_tables`` the physical place of every K/V row comes from
    runtime int32 tables, so pages can be allocated, freed, shared and
    forked between calls with ZERO recompiles (tests/test_pages.py), and
    the engine names the trash page in ``write_pages`` for a row that
    must not land. The hybrid families have their own loop, several
    kinds of layer, on the slot pool (:func:`_hybrid_decode`); a
    configuration with experts (``num_experts``) gains that loop's
    ``load`` as a third item."""
    if cfg.model in HYBRID:
        out = _hybrid_decode(params, tokens, pos, cache, cfg, active)
        return out if cfg.num_experts else out[:2]
    return _decode_step(params, tokens, pos, cache, cfg, rope_len,
                        active=active, where=write_pages,
                        page_tables=page_tables)


def forward_decode_spec(
    params: dict,
    tokens: jnp.ndarray,  # (B, L) per-row tokens (row 0 = last emitted)
    pos,  # (B, L) int32 absolute position per row
    cache: list,  # slot pool with R > B rows (a trash row), or paged
    cfg: ModelConfig,
    targets: jnp.ndarray,  # (B, L) int32: the cache row (slot pool) or
    #                        physical page (paged) each row is written to
    rope_len: int = 0,
    batched: bool = False,
    page_tables=None,  # (B, pages_per_slot) int32: the pool is paged
) -> Tuple[jnp.ndarray, list]:
    """Advance the whole pool by an L-row verify block (serving/spec.py):
    ``((B, L, V) logits, updated cache)``. THE verify entry point. Row
    (b, 0) reruns the slot's last emitted token exactly like
    :func:`forward_decode_pool`; rows 1..L-1 carry its draft tokens at
    pos+1.. with row-causal visibility. ``targets`` sends rows past a
    slot's draft length (and inactive slots' rows) to the trash row
    (batch index >= B) or the trash page, so the rejected suffix never
    lands in live cache state: the ring/page cursors "roll back" for
    free because visibility derives purely from position arithmetic, and
    draft lengths, page churn and COW forks between calls compile
    nothing new.

    Two verify formulations (``ServingConfig.spec_verify``):

    - ``batched=False`` (EXACT, the serving default): a static unroll
      of L :func:`forward_decode_pool` sub-steps inside one jitted
      program, on the slot pool over all R rows with the valid rows as
      the write mask. Every op keeps the plain decode step's shapes, so
      greedy spec output is bit-identical to non-spec decoding at ANY
      model size — the property the parity pins rely on.
    - ``batched=True``: all L rows in ONE :func:`_decode_step` — one
      fused multi-query attention call per layer (every ring or page
      streamed once for its L rows) and N = B * L rows through the
      projections and the FFN. This is the bandwidth-optimal TPU
      formulation (the KV stream and weight reads amortize over the L
      rows); large-contraction XLA matmuls may reassociate their
      reductions vs the 1-row step, so greedy ties can resolve
      differently at scale (bit-identical at the pinned test sizes; the
      sampled distribution is unchanged either way).
    """
    pos = jnp.asarray(pos, jnp.int32)
    targets = jnp.asarray(targets, jnp.int32)
    if batched:
        return _decode_step(params, tokens, pos, cache, cfg, rope_len,
                            where=targets, page_tables=page_tables)
    B, L = tokens.shape
    paged = page_tables is not None
    spare = 0 if paged else cache[0]["v"].shape[0] - B

    def column(x, l):
        return jnp.pad(x[:, l], (0, spare))

    rows = []
    for l in range(L):
        how = (dict(page_tables=page_tables, write_pages=targets[:, l])
               if paged else dict(active=column(targets < B, l)))
        logits, cache = forward_decode_pool(
            params, column(tokens, l), column(pos, l), cache, cfg,
            rope_len=rope_len, **how)
        rows.append(logits[:B])
    return jnp.stack(rows, axis=1), cache


@partial(
    jax.jit, static_argnames=("cfg", "max_new_tokens", "temperature", "top_k")
)
def generate_cached(
    params: dict,
    idx: jnp.ndarray,
    cfg: ModelConfig,
    max_new_tokens: int,
    rng: jax.Array,
    temperature: float = 1.0,
    top_k=None,
) -> jnp.ndarray:
    """KV-cached counterpart of models/generate.py: same sampling contract
    (temperature-1 categorical over the last position, prompt included in
    the return), O(T) per new token instead of O(T^2).

    RoPE families (control/ndiff) may generate PAST block_size: the ring
    cache rolls the oldest keys off, so every step attends over exactly
    the last block_size tokens at O(T)/token instead of the windowed
    recompute's O(T^2) — sliding-window attention semantics, which
    equals the reference's crop (control.py:163-171) exactly for
    single-layer models and up to the block boundary for any depth; for
    deeper models past the boundary the crop's per-step full recompute
    is Omega(M^2)/token by construction and the cached fast path keeps
    richer (own-window) activations instead — see the module docstring.
    The diff family (learned absolute position table,
    diff_transformer.py:158) cannot roll its cache — each window slide
    re-embeds every cached position — so it keeps the
    ``T0 + max_new_tokens <= block_size`` bound and models/generate.py
    for longer runs."""
    B, T0 = idx.shape
    M = cfg.block_size
    if cfg.model in HYBRID and T0 + max_new_tokens > M:
        raise ValueError(
            f"prompt ({T0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"block_size ({M}): the {cfg.model} family's cache cannot roll "
            "(its attention layers, afmoe's full ones, carry no position; "
            "deepseek_v2's see every earlier one)"
        )
    if cfg.model == "diff" and T0 + max_new_tokens > M:
        raise ValueError(
            f"prompt ({T0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"block_size ({M}) and the diff family's learned absolute "
            "position table cannot roll with a KV cache; use "
            "models.generate for its sliding-window behavior"
        )
    # the reference crops the prompt itself to the last block_size tokens
    # (control.py:165); rebasing the crop to position 0 is invariant for
    # RoPE (relative positions) and exact for diff (which fits by the
    # guard above)
    if T0 > M:
        idx_cond = idx[:, -M:]
        Tc = M
    else:
        idx_cond = idx
        Tc = T0
    total = Tc + max_new_tokens
    cache = init_cache(cfg, B)
    # a prompt that rolls a model's sliding rings goes in chunks of the
    # rings' slack (forward_chunk refuses a longer one there)
    rolls = "window" in cfg.layer_kinds() and Tc > cfg.ring_len("window")
    step = cfg.ring_slack if rolls else Tc
    for at in range(0, Tc, step):
        logits, cache = forward_chunk(params, idx_cond[:, at:at + step], at,
                                      cache, cfg, rope_len=total)
    samples = jnp.zeros((B, max_new_tokens), idx.dtype)

    rng, key0 = jax.random.split(rng)
    first = sample_token(
        key0, logits[:, -1, :].astype(jnp.float32), temperature, top_k
    ).astype(idx.dtype)
    samples = samples.at[:, 0].set(first)

    def body(i, carry):
        cache, samples, rng = carry
        rng, key = jax.random.split(rng)
        prev = samples[:, i - 1]
        # all B rows share the position here, but the step is the one the
        # serving engine runs with per-row positions
        last, cache, *_ = forward_decode_pool(
            params, prev, jnp.full((B,), Tc + i - 1, jnp.int32),
            cache, cfg, rope_len=total,
        )
        nxt = sample_token(
            key, last.astype(jnp.float32), temperature, top_k
        ).astype(samples.dtype)
        samples = samples.at[:, i].set(nxt)
        return cache, samples, rng

    if max_new_tokens > 1:
        _, samples, _ = jax.lax.fori_loop(
            1, max_new_tokens, body, (cache, samples, rng)
        )
    return jnp.concatenate([idx, samples], axis=1)

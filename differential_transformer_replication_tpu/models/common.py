"""Shared model plumbing: parameter init, linear/FFN application, loss.

Init parity with the reference's ``_init_weights`` (control.py:132-138,
identical in the other two files): every Linear weight ~ N(0, 0.02), every
Linear bias zero, embeddings ~ N(0, 0.02). LayerNorm weights/biases start
at ones/zeros, and the lambda vectors start at zero (diff_transformer.py:
35-38) — ``_init_weights`` only touches Linear/Embedding modules, so those
defaults survive in the reference too.

Weights are stored ``(in, out)`` so application is ``x @ W + b`` (the
transpose of torch's ``(out, in)`` storage; same distribution at init
since entries are iid).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.ops import (
    fused_group_norm,
    group_layer_norm,
    layer_norm,
    swiglu,
)
from differential_transformer_replication_tpu.ops.dropout import dropout
from differential_transformer_replication_tpu.ops.fused_ffn import fused_swiglu
from differential_transformer_replication_tpu.ops.fused_norm_residual import (
    fused_add_norm,
    fused_norm,
)
from differential_transformer_replication_tpu.ops.losses import (
    fused_linear_cross_entropy,
)

INIT_STD = 0.02  # control.py:134


def normal_init(key: jax.Array, shape, std: float = INIT_STD) -> jnp.ndarray:
    return jax.random.normal(key, shape, dtype=jnp.float32) * std


def linear_params(key: jax.Array, in_dim: int, out_dim: int, bias: bool = True) -> dict:
    p = {"w": normal_init(key, (in_dim, out_dim))}
    if bias:
        p["b"] = jnp.zeros((out_dim,), jnp.float32)
    return p


def linear(x: jnp.ndarray, p: dict) -> jnp.ndarray:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def layer_norm_params(dim: int) -> dict:
    return {"w": jnp.ones((dim,), jnp.float32), "b": jnp.zeros((dim,), jnp.float32)}


def apply_layer_norm(x: jnp.ndarray, p: dict) -> jnp.ndarray:
    return layer_norm(x, p["w"], p["b"])


def ffn_params(key: jax.Array, n_embd: int) -> dict:
    """The reference FFN: SwiGLU(n_embd -> 4*n_embd) then Linear(4*n_embd ->
    n_embd) then Dropout (control.py:100-104). All three linears carry
    biases (nn.Linear defaults)."""
    kg, kx, ko = jax.random.split(key, 3)
    return {
        "gate": linear_params(kg, n_embd, 4 * n_embd),
        "xform": linear_params(kx, n_embd, 4 * n_embd),
        "out": linear_params(ko, 4 * n_embd, n_embd),
    }


def apply_ffn(
    x: jnp.ndarray,
    p: dict,
    dropout_rate: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    h = swiglu(
        x,
        p["gate"]["w"].astype(x.dtype), p["gate"]["b"].astype(x.dtype),
        p["xform"]["w"].astype(x.dtype), p["xform"]["b"].astype(x.dtype),
    )
    out = linear(h, p["out"])
    return dropout(out, dropout_rate, rng)


# ---------------------------------------------------------------------------
# ffn_impl dispatch — the fused non-attention hot path (ISSUE 9 / ROADMAP
# item 5). "xla" is the reference composition above; "pallas" routes the
# block-boundary residual-add + LayerNorm through the single-pass kernel
# (ops/fused_norm_residual.py) and the SwiGLU chain through the fused
# MXU kernel (ops/fused_ffn.py). Selection mirrors attention_impl: one
# ModelConfig switch, all three families + decode.


def use_fused_ffn(cfg, mesh=None) -> bool:
    """Whether the fused Pallas FFN/norm kernels may be dispatched here.

    GSPMD cannot partition a bare ``pallas_call`` — the reason
    ``attention_impl='pallas'`` routes through the shard_map wrapper
    (parallel/shard_flash.py) on >1-device meshes. The fused FFN/norm
    kernels have no such wrapper, so any multi-device GSPMD placement
    (fsdp/tensor/sequence/pipeline, multi-process DP, or pure DP with
    ``dp_overlap`` off) falls back to the XLA composition — numerically
    identical, just un-fused. The overlap-DP hot path is unaffected:
    its shard_map body runs with ``mesh=None`` (every shard is a
    single-device program), so the fused kernels stay on there.
    """
    if cfg is None or cfg.ffn_impl != "pallas":
        return False
    return mesh is None or mesh.devices.size == 1


def apply_pre_norm(x: jnp.ndarray, p: dict, cfg, mesh=None) -> jnp.ndarray:
    """A standalone LayerNorm with no residual input — the block's first
    pre-LN and decode's ln_f — dispatched on ``cfg.ffn_impl``."""
    if use_fused_ffn(cfg, mesh):
        return fused_norm(x, p["w"], p["b"])
    return layer_norm(x, p["w"], p["b"])


def apply_group_norm(x: jnp.ndarray, p: dict, cfg, mesh=None) -> jnp.ndarray:
    """The full-width GroupLayerNorm over the head concat (diff/ndiff
    attention + decode), dispatched like :func:`apply_pre_norm` — the
    Pallas GLN is the fused_norm alias (ops/fused_norm_residual.py)."""
    if use_fused_ffn(cfg, mesh):
        return fused_group_norm(x, p["w"], p["b"])
    return group_layer_norm(x, p["w"], p["b"])


def apply_block_ffn(
    x: jnp.ndarray,
    attn_out: jnp.ndarray,
    blk: dict,
    cfg,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> jnp.ndarray:
    """The block's FFN half: attention residual add + pre-LN + SwiGLU +
    down-proj + dropout + FFN residual add (control.py:92-111's second
    half, identical across families).

    On the fused path the first three HBM round-trips collapse into two
    kernels: ``fused_add_norm`` produces the carried residual AND the
    normalized FFN input in one pass over the tile, and ``fused_swiglu``
    runs the gate/xform/SiLU/product chain in one kernel. With no
    gradient (serving, evaluation) the (M, 4E) pre-activations never
    reach HBM; under one the forward saves both for its backward, which
    ``cfg.remat`` holds to one block at a time. The down-proj + residual
    stay XLA: the row-parallel matmul is MXU-bound and XLA fuses the add
    into its epilogue.
    """
    rate = cfg.dropout
    if use_fused_ffn(cfg, mesh):
        p = blk["ffn"]
        with jax.named_scope("ffn_norm"):
            x, normed = fused_add_norm(
                x, attn_out, blk["ln2"]["w"], blk["ln2"]["b"]
            )
        with jax.named_scope("ffn"):
            h = fused_swiglu(
                normed,
                p["gate"]["w"], p["gate"]["b"],
                p["xform"]["w"], p["xform"]["b"],
            )
            return x + dropout(linear(h, p["out"]), rate, rng)
    with jax.named_scope("ffn_norm"):
        x = x + attn_out
        normed = apply_layer_norm(x, blk["ln2"])
    with jax.named_scope("ffn"):
        return x + apply_ffn(normed, blk["ffn"], rate, rng)


# jax.checkpoint policies selectable per run (ModelConfig.remat_policy):
# what the block remat may SAVE instead of recomputing. Resolved lazily —
# jax.checkpoint_policies is stable across the pinned versions.
REMAT_POLICIES = ("none", "dots", "dots_no_batch", "nothing", "everything")


def resolve_remat_policy(name: str):
    cp = jax.checkpoint_policies
    return {
        "none": None,  # jax.checkpoint default: save block inputs only
        "dots": cp.dots_saveable,
        "dots_no_batch": cp.dots_with_no_batch_dims_saveable,
        "nothing": cp.nothing_saveable,
        "everything": cp.everything_saveable,
    }[name]


def remat_block(block_fn, cfg):
    """Wrap a family's ``block_forward`` in jax.checkpoint under the
    configured save policy. static_argnums pins (layer_idx, cfg, mesh) —
    the uniform per-family block signature (models/registry.py)."""
    policy = resolve_remat_policy(cfg.remat_policy)
    kw = {} if policy is None else {"policy": policy}
    return jax.checkpoint(block_fn, static_argnums=(2, 3, 8), **kw)




def apply_tail(x: jnp.ndarray, params: dict, cfg=None, mesh=None) -> jnp.ndarray:
    """Final LayerNorm + untied lm head — identical across the three
    families (control.py:126-127, diff_transformer.py:164-165,
    Ndiff_transformer.py:220-221). ``params`` is the model params dict
    (or any dict carrying ``ln_f``/``lm_head``). The ln_f dispatches on
    ``cfg.ffn_impl`` like every block-boundary norm (``cfg=None`` =
    reference path)."""
    x = apply_pre_norm(x, params["ln_f"], cfg, mesh)
    return linear(x, params["lm_head"])


def fused_tail_loss(
    x: jnp.ndarray, params: dict, targets: jnp.ndarray, chunk: int,
    cfg=None, mesh=None,
) -> jnp.ndarray:
    """Final LayerNorm + chunked fused lm-head/cross-entropy
    (ops/losses.py) — the loss of :func:`apply_tail` +
    :func:`cross_entropy_loss` without ever materializing (B, T, V)
    logits."""
    x = apply_pre_norm(x, params["ln_f"], cfg, mesh)
    p = params["lm_head"]
    return fused_linear_cross_entropy(x, p["w"], p.get("b"), targets, chunk)


def _ce_primal(logits: jnp.ndarray, targets: jnp.ndarray):
    logits32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)  # (B, T)
    tgt = jnp.take_along_axis(logits32, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt), lse


@jax.custom_vjp
def cross_entropy_loss(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean cross-entropy over all (B*T) positions, matching the flattened
    ``F.cross_entropy`` call (control.py:153-159). Computed in float32 as
    ``mean(logsumexp - target_logit)``.

    Custom VJP: autodiff of the logsumexp materializes the softmax as a
    full (B, T, V) float32 tensor before the cast to the logits dtype —
    at the recipe scale that is a 786 MB HBM round-trip worth ~2% of the
    train step (profiled). The hand-written backward emits
    ``(softmax - onehot) * g / N`` directly in the logits dtype, which
    XLA fuses into a single elementwise pass over the logits."""
    loss, _ = _ce_primal(logits, targets)
    return loss


def _ce_fwd(logits, targets):
    loss, lse = _ce_primal(logits, targets)
    return loss, (logits, lse, targets)


def _ce_bwd(res, g):
    logits, lse, targets = res
    n = logits.size // logits.shape[-1]
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    d = (p - (iota == targets[..., None]).astype(jnp.float32)) * (g / n)
    return d.astype(logits.dtype), None


cross_entropy_loss.defvjp(_ce_fwd, _ce_bwd)


def tail_and_loss(x, params: dict, cfg, targets, mesh=None):
    """The shared end-of-forward dispatch for all three families: final
    LayerNorm + lm head + (optional) loss. With ``cfg.loss_chunk`` set and
    targets given, routes through the fused chunked loss (ops/losses.py)
    and returns ``(None, loss)`` — logits are never materialized by
    design. Otherwise the reference's dense shape: ``(logits, loss|None)``
    (control.py:147-159); the dense loss runs through
    ``dense_linear_cross_entropy`` (ops/losses.py), whose hand-written
    head backward skips XLA's fp32 transposed grad materialization, and
    the returned logits are an independent dense head application that
    training steps drop (DCE removes it when only the loss is consumed)."""
    if targets is None:
        with jax.named_scope("lm_head"):
            return apply_tail(x, params, cfg, mesh), None
    with jax.named_scope("lm_head_loss"):
        if cfg.loss_chunk:
            return None, fused_tail_loss(
                x, params, targets, cfg.loss_chunk, cfg, mesh
            )
        from differential_transformer_replication_tpu.ops.losses import (
            dense_linear_cross_entropy,
        )

        x_ln = apply_pre_norm(x, params["ln_f"], cfg, mesh)
        p = params["lm_head"]
        loss = dense_linear_cross_entropy(x_ln, p["w"], p.get("b"), targets)
        return linear(x_ln, p), loss


def split_rng(rng: Optional[jax.Array], n: int):
    """Split an optional dropout rng into n optional keys."""
    if rng is None:
        return (None,) * n
    return tuple(jax.random.split(rng, n))


def flash_bh_fn(
    x: jnp.ndarray,  # (B, T, E) normed block input
    wq: jnp.ndarray,  # (S, E, H, d) stacked query projections
    wk: jnp.ndarray,  # (S, E, H, d)
    wv: jnp.ndarray,  # (E, H, dv)
    coeffs: jnp.ndarray,  # (S, H) float32
    *,
    dropout_rate: float,
    rng,
    cos=None,  # RoPE tables (families without RoPE pass None)
    sin=None,
):
    """Build the ``flash_fn`` closure for :func:`dispatch_attention`: the
    kernel-native-layout fast path, shared by ALL THREE families
    (VERDICT r2 item 5 — it was diff-only, leaving the control half of
    every PPL-gap experiment slower by construction).

    Projects straight into the kernel's layout instead of transposing the
    stacked (S, B, T, H, d) arrays the dense path builds (XLA does not
    eliminate those copies; profiled ~0.5-1 ms at recipe scale): one
    packed token-major product where the token-major kernels cover the
    shape, a product a stream where only the packing does not fit, the
    (B*H, S, T, d) layout (einsum ``"bte,sehd->bhstd"`` + free reshape)
    under dropout or a long T.

    A family that passes tables rotates on EVERY branch the same way, and
    not as the dense reference does: the columns of Wq and Wk are
    re-ordered inside each head (``ops/rope.py:half_split``) and the two
    contiguous halves of q and k turn, on the tile in VMEM in the
    token-major kernels (``ops/flash.py:_tm_turn``), in HBM on the
    head-major branch. The reference's stride of two along the lanes cost
    the control recipe a sixth of its step in gathers and float32
    transposes (PERF.md section 6, PR 37). Parameters, checkpoints and the
    decode ring keep the published order; a family without tables takes
    the branches as they were."""

    def _fn():
        from differential_transformer_replication_tpu.ops.flash import (
            multi_stream_flash_attention_bh,
            multi_stream_flash_attention_tm,
            tm_packed_ok,
            use_tm,
        )
        from differential_transformer_replication_tpu.ops.rope import (
            apply_rope_halves,
            half_split,
        )

        B, T, E = x.shape
        S, _, H, d = wq.shape
        dv = wv.shape[-1]
        rate_live = dropout_rate if rng is not None else 0.0
        rope = None if cos is None else (cos, sin)
        wq_p, wk_p = wq, wk
        if rope is not None:
            # the rotation's pair (2i, 2i + 1) moves to (i, i + d/2), two
            # contiguous halves: q . k does not care where a feature
            # stands as long as both agree (ops/rope.py). The gradient
            # comes back through half_split's transpose, so in the
            # published order.
            wq_p, wk_p = half_split(wq), half_split(wk)
        # Ineligible shapes (exotic dv/d offset ratios, narrow lane
        # widths, four rotated streams — see tm_packed_ok) fall through
        # to the per-array tm path instead of tripping the kernel's spec
        # assert at trace time or the chip's VMEM at compile time.
        if use_tm(S, T, rate_live) and tm_packed_ok(
                S, H, d, dv, rope=rope is not None):
            # PACKED token-major fast path: ONE fused projection matmul
            # x @ [Wq..|Wk..|Wv]; the kernel reads column windows of its
            # output (and turns the q/k tiles of a RoPE family in VMEM)
            # and the backward emits one packed dproj — zero copies on
            # either side
            from differential_transformer_replication_tpu.ops.flash import (
                multi_stream_flash_attention_tm_packed,
            )

            wcat = jnp.concatenate(
                [wq_p[s].reshape(E, H * d) for s in range(S)]
                + [wk_p[s].reshape(E, H * d) for s in range(S)]
                + [wv.reshape(E, H * dv)],
                axis=1,
            ).astype(x.dtype)
            proj = x @ wcat  # (B, T, 2*S*H*d + H*dv)
            return multi_stream_flash_attention_tm_packed(
                proj, coeffs, B, H, S, d, dv, rope=rope
            )
        if use_tm(S, T, rate_live):
            # TOKEN-MAJOR fast path (ops/flash.py tm kernels): each
            # projection's matmul output feeds the kernel after a pure
            # reshape — no (B,T,H,d)->(B,H,T,d) transposes fwd or bwd, and
            # the (B,T,H,dv) output keeps the GroupLayerNorm reduce and
            # the out-projection contiguous (round-4 profile: ~660 MB/step
            # of HBM transpose copies + a 4.5 ms strided stat reduce on
            # the head-major path at recipe scale)
            wq_c = wq_p.astype(x.dtype)
            wk_c = wk_p.astype(x.dtype)
            qs = tuple(
                (x @ wq_c[s].reshape(E, H * d)).reshape(B, T, H, d)
                for s in range(S)
            )
            ks = tuple(
                (x @ wk_c[s].reshape(E, H * d)).reshape(B, T, H, d)
                for s in range(S)
            )
            v_tm = (x @ wv.astype(x.dtype).reshape(E, H * dv)).reshape(
                B, T, H, dv
            )
            return multi_stream_flash_attention_tm(
                qs, ks, v_tm, coeffs, B, H, rope=rope
            )
        q_r = jnp.einsum("bte,sehd->bhstd", x, wq_p.astype(x.dtype)).reshape(
            B * H, S, T, d
        )
        k_r = jnp.einsum("bte,sehd->bhstd", x, wk_p.astype(x.dtype)).reshape(
            B * H, S, T, d
        )
        v_r = jnp.einsum("bte,ehd->bhtd", x, wv.astype(x.dtype)).reshape(
            B * H, T, dv
        )
        if rope is not None:
            q_r = apply_rope_halves(q_r, cos, sin)
            k_r = apply_rope_halves(k_r, cos, sin)
        out = multi_stream_flash_attention_bh(
            q_r, k_r, v_r, coeffs, B, H,
            dropout_rate=dropout_rate, dropout_rng=rng,
        )
        return out.reshape(B, H, T, dv).transpose(0, 2, 1, 3)

    return _fn


def dispatch_attention(
    qs: jnp.ndarray,  # (S, B, T, H, d) stacked streams
    ks: jnp.ndarray,  # (S, B, T, H, d)
    v: jnp.ndarray,  # (B, T, H, dv)
    coeffs: jnp.ndarray,  # (S, H) float32 combine coefficients
    dense_fn,
    *,
    impl: str,
    mesh,
    dropout_rate: float,
    rng: Optional[jax.Array],
    flash_fn=None,
    seq_impl: str = "ring",
) -> jnp.ndarray:
    """The attention-backend dispatch shared by all three families.

    Every family's attention is the same multi-stream form
    (ops/streams.py), so backend selection is family-independent:
      1. >1 ``sequence`` mesh axis  -> sequence parallelism: ring
         attention (parallel/ring.py) or, with seq_impl == "ulysses",
         all-to-all re-sharding (parallel/ulysses.py),
      2. impl == "pallas", >1-device mesh -> shard_map'd flash
         (parallel/shard_flash.py),
      3. impl == "pallas"           -> fused flash kernel (ops/flash.py),
      4. otherwise                  -> ``dense_fn()``, the family's XLA
         reference op (ops/attention.py) closed over its own arguments.
    All parallel backends take the dropout (rate, rng) pair; dense_fn
    applies its own dropout internally.

    ``flash_fn`` (optional, () -> (B, T, H, dv)) overrides branch 3: a
    family that can project straight into the kernel's (B*H, S, T, d)
    layout supplies a closure calling multi_stream_flash_attention_bh,
    skipping the stacked-layout transposes on the hot single-device path
    (XLA does not eliminate them otherwise; see models/diff.py).
    """
    # lazy import: parallel/__init__ pulls in the training stack, which
    # imports models — importing at call (trace) time breaks the cycle
    from differential_transformer_replication_tpu.ops.flash import (
        multi_stream_flash_attention,
        use_flash,
    )
    from differential_transformer_replication_tpu.parallel.ring import (
        ring_multi_stream_attention,
        use_ring,
    )
    from differential_transformer_replication_tpu.parallel.shard_flash import (
        shard_flash_multi_stream_attention,
        use_shard_flash,
    )

    if use_ring(mesh):
        if seq_impl == "ulysses":
            from differential_transformer_replication_tpu.parallel.ulysses import (
                ulysses_multi_stream_attention,
            )

            return ulysses_multi_stream_attention(
                qs, ks, v, coeffs, mesh, impl,
                dropout_rate=dropout_rate, dropout_rng=rng,
            )
        return ring_multi_stream_attention(
            qs, ks, v, coeffs, mesh, impl,
            dropout_rate=dropout_rate, dropout_rng=rng,
        )
    if use_flash(impl, dropout_rate, rng):
        if use_shard_flash(mesh):
            return shard_flash_multi_stream_attention(
                qs, ks, v, coeffs, mesh,
                dropout_rate=dropout_rate, dropout_rng=rng,
            )
        if flash_fn is not None:
            return flash_fn()
        return multi_stream_flash_attention(
            qs, ks, v, coeffs, dropout_rate=dropout_rate, dropout_rng=rng
        )
    return dense_fn()


# ---------------------------------------------------------------------------
# Blocks-layout conversion — the SINGLE definition of the two layouts:
# canonical (list of per-layer dicts, what init() builds and checkpoints
# store) vs layer-stacked (one dict whose leaves carry a leading n_layer
# axis, what the pipeline-parallel path shards P('pipeline')). Used by
# parallel/pipeline.py and train/checkpoint.py.


def stack_block_list(blocks: list, stack_fn=None) -> dict:
    """List of per-layer dicts -> one dict of layer-stacked leaves.
    ``stack_fn`` defaults to ``jnp.stack`` (pass ``np.stack`` for host-side
    conversion of device_get'd states)."""
    fn = jnp.stack if stack_fn is None else stack_fn
    return jax.tree_util.tree_map(lambda *xs: fn(list(xs), axis=0), *blocks)


def unstack_block_tree(blocks: dict, n_layer: int) -> list:
    """Inverse of :func:`stack_block_list`."""
    return [
        jax.tree_util.tree_map(lambda x: x[i], blocks) for i in range(n_layer)
    ]

"""Ring attention: sequence/context parallelism over the mesh.

The reference caps sequence length at a dense-masked block_size=512
(train.py:63) and has no distributed machinery at all (SURVEY.md section
5.7-5.8). This module is the TPU-native long-context path: the sequence
dim is sharded over the mesh's ``sequence`` axis, each device keeps its
local Q shard, and K/V shards rotate around the ring via
``jax.lax.ppermute`` — P steps of blockwise attention with an
online-softmax accumulator, so no device ever holds the full sequence or
any (T, T) map. Collectives ride ICI; compute overlaps the rotation.

Like ops/flash.py, one implementation serves all three model families via
the multi-stream form: ``out = sum_s coeff[s,h] * softmax_s @ V``.

The op is wrapped in ``shard_map`` whose in_specs compose with the other
mesh axes: batch stays on ``data``/``fsdp``, heads stay on ``tensor``,
sequence is the ring axis. Everything outside attention (RoPE tables,
position embeddings, LayerNorm, FFN, loss) remains under automatic GSPMD
partitioning — attention is the only op whose sharding XLA cannot infer
profitably, because causal blockwise structure is a manual schedule.

Autodiff: ``ppermute`` transposes to ``ppermute``, so ``jax.grad``
through the ring gives the standard ring-attention backward (KV grads
rotate back around the ring).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from differential_transformer_replication_tpu.ops.flash import (
    auto_interpret,
    dropout_seed_from_rng,
    flash_chunk_attention,
    pick_block,
)
from differential_transformer_replication_tpu.ops.streams import (
    NEG_INF,
    diff_coeffs,
    ndiff_coeffs,
    vanilla_coeffs,
)

_BATCH_AXES = ("data", "fsdp")
_SEQ_AXIS = "sequence"
_HEAD_AXIS = "tensor"


def _ring_flash_body(
    qs: jnp.ndarray,  # (S, Bl, Tl, Hl, d) local shard
    ks: jnp.ndarray,  # (S, Bl, Tl, Hl, d)
    v: jnp.ndarray,  # (Bl, Tl, Hl, dv)
    coeffs: jnp.ndarray,  # (S, Hl) float32
    dropout_rate: float = 0.0,
    dropout_rng=None,
) -> jnp.ndarray:
    """Ring body whose per-chunk compute is the fused Pallas chunk kernel
    (ops/flash.py:flash_chunk_attention) — no Tl x Tl map is materialized
    even chunk-locally. Chunks merge exactly via the running logsumexp
    recurrence: with per-chunk normalized outputs o_c and logsumexps
    lse_c, ``lse' = logaddexp(lse, lse_c)`` and
    ``o' = o*exp(lse-lse') + o_c*exp(lse_c-lse')``.

    Dropout composes: each chunk drops its probabilities in-kernel after
    local normalization, the lse carries the UNdropped sums, and the
    merge re-weights exactly as in the dropout-free case — globally
    softmax-then-dropout. Masks hash (row, col - off), unique per (q, k)
    pair across the rotation; the caller folds the mesh position into
    the rng so shards decorrelate."""
    S, B, Tl, H, d = qs.shape
    dv = v.shape[-1]
    p = jax.lax.axis_size(_SEQ_AXIS)
    my = jax.lax.axis_index(_SEQ_AXIS)
    interpret = auto_interpret()
    bq = pick_block(128, Tl)
    bk = pick_block(128, Tl)
    blocks = (bq, bk, bq, bk)
    use_drop = dropout_rate > 0.0 and dropout_rng is not None
    rate = float(dropout_rate) if use_drop else 0.0
    seed = (
        dropout_seed_from_rng(dropout_rng)
        if use_drop
        else jnp.zeros((1, 2), jnp.float32)
    )

    # (S, B, Tl, H, d) -> (B*H, S, Tl, d)
    q_r = qs.transpose(1, 3, 0, 2, 4).reshape(B * H, S, Tl, d)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def step(t, carry):
        o, lse, ks_t, v_t = carry
        src = jax.lax.rem(my - t + p, p)
        off = ((my - src) * Tl).astype(jnp.float32).reshape(1, 1)
        k_r = ks_t.transpose(1, 3, 0, 2, 4).reshape(B * H, S, Tl, d)
        v_r = v_t.transpose(0, 2, 1, 3).reshape(B * H, Tl, dv)
        o_c, lse_c = flash_chunk_attention(
            q_r, k_r, v_r, off, seed, blocks, interpret, rate
        )
        lse_new = jnp.logaddexp(lse, lse_c)
        w_old = jnp.exp(lse - lse_new)[..., None]
        w_new = jnp.exp(lse_c - lse_new)[..., None]
        o_new = o * w_old + o_c.astype(jnp.float32) * w_new
        ks_n = jax.lax.ppermute(ks_t, _SEQ_AXIS, perm)
        v_n = jax.lax.ppermute(v_t, _SEQ_AXIS, perm)
        return o_new, lse_new, ks_n, v_n

    o0 = jnp.zeros((B * H, S, Tl, dv), jnp.float32)
    lse0 = jnp.full((B * H, S, Tl), NEG_INF, jnp.float32)
    o, lse, _, _ = jax.lax.fori_loop(0, p, step, (o0, lse0, ks, v))

    # combine streams with the per-head coefficients, back to (B, Tl, H, dv)
    o_bh = o.reshape(B, H, S, Tl, dv)
    out = jnp.einsum("sh,bhstd->bhtd", coeffs.astype(jnp.float32), o_bh)
    return out.transpose(0, 2, 1, 3).astype(v.dtype)


def _ring_shard_body(
    qs: jnp.ndarray,  # (S, Bl, Tl, Hl, d) local shard
    ks: jnp.ndarray,  # (S, Bl, Tl, Hl, d)
    v: jnp.ndarray,  # (Bl, Tl, Hl, dv)
    coeffs: jnp.ndarray,  # (S, Hl) float32
    dropout_rate: float = 0.0,
    dropout_rng=None,
) -> jnp.ndarray:
    """Runs on each device inside shard_map. Rotates (ks, v) around the
    ``sequence`` ring; accumulates S online-softmax streams against the
    local Q shard. Dropout (when a key is given) is applied to each
    step's probabilities before the PV accumulation while the normalizer
    keeps the undropped sums — softmax-then-dropout semantics globally;
    autodiff handles the backward (no mask regeneration needed on this
    dense path)."""
    S, B, Tl, H, d = qs.shape
    dv = v.shape[-1]
    p = jax.lax.axis_size(_SEQ_AXIS)
    my = jax.lax.axis_index(_SEQ_AXIS)
    scale = 1.0 / math.sqrt(d)
    use_drop = dropout_rate > 0.0 and dropout_rng is not None

    q32 = qs.astype(jnp.float32)
    rows = my * Tl + jax.lax.broadcasted_iota(jnp.int32, (Tl, Tl), 0)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def step(t, carry):
        m, l, acc, ks_t, v_t = carry
        # after t rotations this device holds the KV shard of ring position
        # (my - t) mod p
        src = jax.lax.rem(my - t + p, p)
        k32 = ks_t.astype(jnp.float32)
        s = jnp.einsum("sbthd,sbuhd->sbhtu", q32, k32) * scale
        cols = src * Tl + jax.lax.broadcasted_iota(jnp.int32, (Tl, Tl), 1)
        s = jnp.where((cols <= rows)[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new[..., None])  # (S, B, H, Tl, Tl)
        l_new = l * alpha + jnp.sum(pr, axis=-1)  # UNdropped normalizer
        pr_pv = pr
        if use_drop:
            keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_rng, t), 1.0 - dropout_rate,
                pr.shape,
            )
            pr_pv = jnp.where(keep, pr / (1.0 - dropout_rate), 0.0)
        pv = jnp.einsum("sbhtu,buhe->sbhte", pr_pv, v_t.astype(jnp.float32))
        acc_new = acc * alpha[..., None] + pv
        # rotate KV to the next device; the last step's rotation restores
        # the original placement (and keeps every step's collective uniform)
        ks_n = jax.lax.ppermute(ks_t, _SEQ_AXIS, perm)
        v_n = jax.lax.ppermute(v_t, _SEQ_AXIS, perm)
        return m_new, l_new, acc_new, ks_n, v_n

    m0 = jnp.full((S, B, H, Tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((S, B, H, Tl), jnp.float32)
    a0 = jnp.zeros((S, B, H, Tl, dv), jnp.float32)
    m, l, acc, _, _ = jax.lax.fori_loop(0, p, step, (m0, l0, a0, ks, v))

    # step 0 visits the local diagonal chunk, so l > 0 everywhere
    o_s = acc / l[..., None]  # (S, B, H, Tl, dv)
    out = jnp.einsum("sh,sbhte->bhte", coeffs.astype(jnp.float32), o_s)
    return out.transpose(0, 2, 1, 3).astype(v.dtype)  # (Bl, Tl, Hl, dv)


def sequence_shard_map(body, mesh: Mesh, qs, ks, v, coeffs, *, dropout_rng=None):
    """The shard_map scaffolding SHARED by both sequence-parallel
    strategies (ring here, all-to-all in parallel/ulysses.py): batch over
    data/fsdp, T over ``sequence``, heads over ``tensor``; ``body`` is
    ``(qs_l, ks_l, v_l, coeffs_l, rng) -> out_l``. With a key, the
    replicated rng is folded with the device's FULL mesh position before
    reaching body — the fold that keeps every shard's dropout masks
    independent; keeping it in one place keeps the two strategies'
    dropout semantics from drifting."""
    qk_spec = P(None, _BATCH_AXES, _SEQ_AXIS, _HEAD_AXIS, None)
    v_spec = P(_BATCH_AXES, _SEQ_AXIS, _HEAD_AXIS, None)
    c_spec = P(None, _HEAD_AXIS)

    if dropout_rng is not None:
        def folded(qs_l, ks_l, v_l, c_l, rng):
            pos = jax.lax.axis_index(_BATCH_AXES[0])
            for ax in (_BATCH_AXES[1], _HEAD_AXIS, _SEQ_AXIS):
                pos = pos * mesh.shape[ax] + jax.lax.axis_index(ax)
            return body(qs_l, ks_l, v_l, c_l, jax.random.fold_in(rng, pos))

        inner = jax.shard_map(
            folded,
            mesh=mesh,
            in_specs=(qk_spec, qk_spec, v_spec, c_spec, P()),
            out_specs=v_spec,
            check_vma=False,
        )
        return inner(qs, ks, v, coeffs, dropout_rng)

    inner = jax.shard_map(
        lambda a, b, c, d: body(a, b, c, d, None),
        mesh=mesh,
        in_specs=(qk_spec, qk_spec, v_spec, c_spec),
        out_specs=v_spec,
        check_vma=False,
    )
    return inner(qs, ks, v, coeffs)


def ring_multi_stream_attention(
    qs: jnp.ndarray,  # (S, B, T, H, d) global
    ks: jnp.ndarray,
    v: jnp.ndarray,  # (B, T, H, dv) global
    coeffs: jnp.ndarray,  # (S, H) float32
    mesh: Mesh,
    impl: str = "xla",
    *,
    dropout_rate: float = 0.0,
    dropout_rng=None,
) -> jnp.ndarray:
    """Causal multi-stream attention with the sequence dim ring-sharded
    over ``mesh``'s ``sequence`` axis. Global shapes in, global out —
    callable from inside an outer jit; composes with data/fsdp batch
    sharding and tensor head sharding.

    ``impl``: "xla" computes each chunk with dense masked softmax (Tl x Tl
    chunk-local maps); "pallas" runs the fused flash chunk kernel inside
    the ring, so even chunk-local memory stays O(Tl) — ring flash
    attention, the long-context configuration.

    With ``dropout_rate`` > 0 and a key, attention-prob dropout is live
    on both impls (each map dropped after normalization, inverted
    scaling); the replicated key is folded with the device's full mesh
    position inside the body so every shard draws independent masks."""
    body_fn = _ring_flash_body if impl == "pallas" else _ring_shard_body
    use_drop = dropout_rate > 0.0 and dropout_rng is not None
    return sequence_shard_map(
        lambda a, b, c, d, rng: body_fn(a, b, c, d, dropout_rate, rng),
        mesh, qs, ks, v, coeffs,
        dropout_rng=dropout_rng if use_drop else None,
    )


def ring_vanilla_attention(q, k, v, mesh: Mesh, impl: str = "xla", **kw):
    """Sequence-parallel form of ops.attention.vanilla_attention."""
    return ring_multi_stream_attention(
        q[None], k[None], v, vanilla_coeffs(q.shape[2]), mesh, impl, **kw
    )


def ring_diff_attention(
    q1, k1, q2, k2, v, lam, mesh: Mesh, impl: str = "xla", **kw
):
    """Sequence-parallel form of ops.attention.diff_attention:
    coeffs [1, -lambda] (diff_transformer.py:70)."""
    qs = jnp.stack([q1, q2])
    ks = jnp.stack([k1, k2])
    return ring_multi_stream_attention(
        qs, ks, v, diff_coeffs(lam), mesh, impl, **kw
    )


def ring_ndiff_attention(
    qs, ks, v, lams, signs, mesh: Mesh, impl: str = "xla", **kw
):
    """Sequence-parallel form of ops.attention.ndiff_attention: coeffs
    sign_s * lambda_{s,h} (Ndiff_transformer.py:119-123)."""
    return ring_multi_stream_attention(
        qs, ks, v, ndiff_coeffs(lams, signs), mesh, impl, **kw
    )


def use_ring(mesh: Optional[Mesh]) -> bool:
    """Ring attention applies when a mesh with a >1 sequence axis is
    threaded into the forward."""
    return mesh is not None and mesh.shape.get(_SEQ_AXIS, 1) > 1

"""The fused flash kernel on multi-device GSPMD meshes.

GSPMD cannot partition a bare ``pallas_call`` — on a >1-device mesh the
partitioner would all-gather every attention operand around the kernel
(or fail to compile). But the kernel's grid is already per-(batch, head):
batch and head are embarrassingly parallel for causal attention with an
unsharded sequence. So the composition is a ``shard_map`` whose in_specs
put batch on ``data``/``fsdp`` and heads on ``tensor`` — each device runs
the ordinary single-device kernel (ops/flash.py) on its local
(B/dp, T, H/tp) slice, with zero collectives inside attention. The
custom VJP differentiates through shard_map unchanged (batch/head
splitting needs no transposed collectives).

This is the missing composition called out in VERDICT r1 item 2 — it
makes ``attention_impl='pallas'`` work on the north-star DP/TP mesh
configs (BASELINE.json configs 3/5) instead of raising. Sequence-
parallel meshes take the ring path instead (parallel/ring.py), which
also reaches the chunk kernel via its own shard_map.

Reference analog: none — the reference computes attention per-head in
Python loops on one device (diff_transformer.py:89); this module plus
ops/flash.py is its TPU-native replacement at mesh scale.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from differential_transformer_replication_tpu.ops.flash import (
    multi_stream_flash_attention,
)
from differential_transformer_replication_tpu.ops.streams import (
    diff_coeffs,
    ndiff_coeffs,
    vanilla_coeffs,
)

_BATCH_AXES = ("data", "fsdp")
_HEAD_AXIS = "tensor"


def use_shard_flash(mesh: Optional[Mesh]) -> bool:
    """The shard_map wrapper applies whenever a >1-device mesh is threaded
    into the forward (and attention is not on the ring path — callers
    check ``use_ring`` first)."""
    return mesh is not None and mesh.devices.size > 1


def shard_flash_multi_stream_attention(
    qs: jnp.ndarray,  # (S, B, T, H, d) global
    ks: jnp.ndarray,  # (S, B, T, H, d)
    v: jnp.ndarray,  # (B, T, H, dv)
    coeffs: jnp.ndarray,  # (S, H) float32
    mesh: Mesh,
    *,
    dropout_rate: float = 0.0,
    dropout_rng=None,
) -> jnp.ndarray:
    """``multi_stream_flash_attention`` with batch sharded over
    data x fsdp and heads over tensor. Global shapes in, global out —
    callable from inside the outer GSPMD jit.

    With active dropout, the replicated rng key is folded with the
    device's mesh position inside the shard_map body: the kernel keys its
    masks on the LOCAL (b*H + h) grid index, which repeats across shards,
    so without the fold every batch/head shard would reuse the same
    masks."""
    qk_spec = P(None, _BATCH_AXES, None, _HEAD_AXIS, None)
    v_spec = P(_BATCH_AXES, None, _HEAD_AXIS, None)
    c_spec = P(None, _HEAD_AXIS)
    use_drop = dropout_rate > 0.0 and dropout_rng is not None

    if use_drop:
        def body(qs_l, ks_l, v_l, c_l, rng):
            pos = (
                jax.lax.axis_index(_BATCH_AXES[0]) * mesh.shape[_BATCH_AXES[1]]
                + jax.lax.axis_index(_BATCH_AXES[1])
            ) * mesh.shape[_HEAD_AXIS] + jax.lax.axis_index(_HEAD_AXIS)
            return multi_stream_flash_attention(
                qs_l, ks_l, v_l, c_l,
                dropout_rate=dropout_rate,
                dropout_rng=jax.random.fold_in(rng, pos),
            )

        inner = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(qk_spec, qk_spec, v_spec, c_spec, P()),
            out_specs=v_spec,
            check_vma=False,
        )
        return inner(qs, ks, v, coeffs, dropout_rng)

    def body(qs_l, ks_l, v_l, c_l):
        return multi_stream_flash_attention(qs_l, ks_l, v_l, c_l)

    inner = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qk_spec, qk_spec, v_spec, c_spec),
        out_specs=v_spec,
        check_vma=False,
    )
    return inner(qs, ks, v, coeffs)


def shard_flash_vanilla_attention(q, k, v, mesh: Mesh, **kw):
    """Mesh form of ops.flash.flash_vanilla_attention."""
    return shard_flash_multi_stream_attention(
        q[None], k[None], v, vanilla_coeffs(q.shape[2]), mesh, **kw
    )


def shard_flash_diff_attention(q1, k1, q2, k2, v, lam, mesh: Mesh, **kw):
    """Mesh form of ops.flash.flash_diff_attention: coeffs [1, -lambda]
    (diff_transformer.py:70)."""
    qs = jnp.stack([q1, q2])
    ks = jnp.stack([k1, k2])
    return shard_flash_multi_stream_attention(
        qs, ks, v, diff_coeffs(lam), mesh, **kw
    )


def shard_flash_ndiff_attention(qs, ks, v, lams, signs, mesh: Mesh, **kw):
    """Mesh form of ops.flash.flash_ndiff_attention: coeffs
    ``sign_s * lambda_{s,h}`` (Ndiff_transformer.py:119-123)."""
    return shard_flash_multi_stream_attention(
        qs, ks, v, ndiff_coeffs(lams, signs), mesh, **kw
    )

"""Multi-head latent attention without position information (the
kimi_linear family's MLA layers): what is cached a position is the latent
``[c ; k_r]`` of ``kv_lora_rank + qk_rope_head_dim`` values, shared by
every head, never keys and values a head.

The queries attend in the ABSORBED form: a head's key is ``[W_k^T c ;
k_r]`` and its value ``W_v^T c``, so ``q . k = (W_k q_nope) . c + q_r .
k_r`` and ``sum_m p_m v_m = W_v^T (sum_m p_m c_m)``: the widening matrix
``W_kvb`` is multiplied into the query and into the output, and the latent
is read as it lies in the cache, once for all heads. Widening the latent
instead would make ``n_head * (qk_nope + v)`` values a position, 14 times
the cache, on every read.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from differential_transformer_replication_tpu.ops.streams import NEG_INF


def attend_latent(q: jnp.ndarray, latent: jnp.ndarray, w_kvb: jnp.ndarray,
                  visible: jnp.ndarray) -> jnp.ndarray:
    """``q`` (B, L, H, nope + rope) over the latents ``latent`` (B, M,
    rank + rope) where ``visible`` (L, M) or (B, L, M) says so; ``w_kvb``
    (rank, H, nope + v) widens a latent to a head's key part and value.
    Returns (B, L, H * v); the softmax is float32."""
    B, L, H, dq = q.shape
    rank = w_kvb.shape[0]
    nope = dq - (latent.shape[-1] - rank)
    w = w_kvb.astype(q.dtype)
    absorbed = jnp.einsum("blhn,rhn->blhr", q[..., :nope], w[..., :nope])
    qq = jnp.concatenate([absorbed, q[..., nope:]], axis=-1)
    scores = jnp.einsum("blhr,bmr->bhlm", qq, latent,
                        preferred_element_type=jnp.float32) / math.sqrt(dq)
    vis = visible if visible.ndim == 3 else visible[None]
    probs = jax.nn.softmax(jnp.where(vis[:, None], scores, NEG_INF), axis=-1)
    mixed = jnp.einsum("bhlm,bmr->blhr", probs.astype(q.dtype),
                       latent[..., :rank])
    out = jnp.einsum("blhr,rhv->blhv", mixed, w[..., nope:])
    return out.reshape(B, L, -1)

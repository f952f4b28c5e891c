"""Multi-head latent attention: what is cached a position is the latent
``[c ; k_r]`` of ``kv_lora_rank + qk_rope_head_dim`` values, shared by
every head, never keys and values a head. The kimi_linear family's MLA
layers carry no position; the deepseek_v2 family's turn ``k_r`` and the
queries' last ``qk_rope_head_dim`` dimensions at the token's position
before they come here (models/kimi_linear.py), so nothing in this module
knows a position but as what a row may see.

The queries attend in the ABSORBED form: a head's key is ``[W_k^T c ;
k_r]`` and its value ``W_v^T c``, so ``q . k = (W_k q_nope) . c + q_r .
k_r`` and ``sum_m p_m v_m = W_v^T (sum_m p_m c_m)``: the widening matrix
``W_kvb`` is multiplied into the query and into the output, and the latent
is read as it lies in the cache, once for all heads. Widening the latent
instead would make ``n_head * (qk_nope + v)`` values a position, 14 times
the cache, on every read.

Three reads of the ring of latents:

- :func:`attend_latent`, absorbed, the latents whole under a visibility
  mask, float32 scores ``(B, H, L, M)``: kimi_linear's full forward over a
  whole sequence and the tests' reference. NEITHER serving program calls
  it: over a pool of rings it reads every slot's ring whole, dead
  positions and free slots too (3.88 ms of the kimi cell's decode step,
  a hundred times its live latents: ledger, PR 43);
- :func:`latent_decode_attention`, absorbed, one token a slot of the pool,
  a Pallas kernel (``mla_latent_decode_fwd``) that reads a row's LIVE
  latent blocks alone, once for all heads, with an online float32 softmax:
  no score leaves the chip, and a step costs the live positions rounded
  up to blocks, not slots x ring (THE decode read of a layer of kind
  ``"latent"``: every deepseek_v2 layer, kimi_linear's MLA layers);
- :func:`chunk_attention`, a prefill chunk's of the same layers, a Pallas
  kernel (``mla_chunk_widened_fwd``): absorbed, a (query, key) pair costs ``2 H
  (2 rank + rope)`` operations, 278 k at 128 heads and a rank of 512;
  WIDENED, keys and values a head made from the latents (``W_kvb``) for
  the positions the chunk sees, ``2 H (nope + rope + v)``, 82 k, plus the
  widening itself. The ring is read ``KEY_BLOCK`` positions at a time as
  far as it has been written, under a running float32 softmax on the
  chip, so no ``(H, L, M)`` score tensor exists. The same blocks in XLA
  (a ``fori_loop`` of einsums, widened or absorbed) wrote and re-read a
  block's float32 scores in HBM and took 2.5-3.6 times as long at a chunk
  of 1,024 (my chip runs, PR 40: PERF.md section 6).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from differential_transformer_replication_tpu import kernel_names
from differential_transformer_replication_tpu.ops.flash import auto_interpret
from differential_transformer_replication_tpu.ops.kv_write import (
    position_on_lanes,
    slot_owners,
)
from differential_transformer_replication_tpu.ops.streams import NEG_INF

KEY_BLOCK = 512  # ring positions a block of either blocked read holds


def key_block(M: int) -> int:
    """Positions a block holds of a ring of ``M``: the largest common
    divisor with ``KEY_BLOCK`` (a ring is whole blocks)."""
    return math.gcd(M, KEY_BLOCK)


def absorb_queries(q: jnp.ndarray, w_kvb: jnp.ndarray,
                   rope: int) -> jnp.ndarray:
    """``q`` (.., H, nope + rope) -> (.., H, rank + rope): a head's key
    widening ``W_k`` multiplied into its query, so that the query meets
    the latent as the cache holds it."""
    nope = q.shape[-1] - rope
    absorbed = jnp.einsum("...hn,rhn->...hr", q[..., :nope],
                          w_kvb.astype(q.dtype)[..., :nope])
    return jnp.concatenate([absorbed, q[..., nope:]], axis=-1)


def attend_latent(q: jnp.ndarray, latent: jnp.ndarray, w_kvb: jnp.ndarray,
                  visible: jnp.ndarray,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """``q`` (B, L, H, nope + rope) over the latents ``latent`` (B, M,
    rank + rope) where ``visible`` (L, M) or (B, L, M) says so; ``w_kvb``
    (rank, H, nope + v) widens a latent to a head's key part and value.
    The scores are scaled by ``scale`` (None: ``(nope + rope) ** -0.5``).
    Returns (B, L, H * v); the softmax is float32."""
    B, L, H, dq = q.shape
    rank = w_kvb.shape[0]
    nope = dq - (latent.shape[-1] - rank)
    w = w_kvb.astype(q.dtype)
    qq = absorb_queries(q, w, dq - nope)
    scores = jnp.einsum("blhr,bmr->bhlm", qq, latent,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dq) if scale is None else scores * scale
    vis = visible if visible.ndim == 3 else visible[None]
    probs = jax.nn.softmax(jnp.where(vis[:, None], scores, NEG_INF), axis=-1)
    mixed = jnp.einsum("bhlm,bmr->blhr", probs.astype(q.dtype),
                       latent[..., :rank])
    out = jnp.einsum("blhr,rhv->blhv", mixed, w[..., nope:])
    return out.reshape(B, L, -1)


# -- the two kernels' running softmax (float32, in VMEM scratch) -----------------


def _start(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _join(s, visible, values, contract: int, m_scr, l_scr, acc_scr):
    """One block's scores ``s`` (rows, block) float32 joined to the
    running maximum, sum and weighted ``values`` (whose axis ``contract``
    is the block's positions)."""
    s = jnp.where(visible, s, NEG_INF)
    top = m_scr[...]
    new_top = jnp.maximum(top, jnp.max(s, axis=-1, keepdims=True))
    keep = jnp.exp(top - new_top)
    p = jnp.where(visible, jnp.exp(s - new_top), 0.0)
    l_scr[...] = l_scr[...] * keep + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * keep + jax.lax.dot_general(
        p.astype(values.dtype), values, (((1,), (contract,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = new_top


def _finish(l_scr, acc_scr):
    return acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


# -- a prefill chunk's read as a kernel: widened on the chip ---------------------


def _chunk_kernel(n_ref, pos_ref, q_ref, lat_ref, w_ref, o_ref, m_scr, l_scr,
                  acc_scr, *, scale: float, rank: int, nope: int):
    KB = lat_ref.shape[1]
    L = q_ref.shape[2]
    j = pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when(j == 0)
    def _():
        _start(m_scr, l_scr, acc_scr)

    @pl.when(j < n_ref[0])
    def _():
        lat = lat_ref[0]  # (KB, rank + rope)
        q = q_ref[0, 0]  # (L, nope + rope)
        # this head's keys and values of the block, from the latents
        wide = jnp.dot(lat[:, :rank], w_ref[0],
                       preferred_element_type=jnp.float32).astype(lat.dtype)
        s = (jax.lax.dot_general(
            q[:, :nope], wide[:, :nope], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                q[:, nope:], lat[:, rank:], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * scale  # (L, KB)
        cols = j * KB + jax.lax.broadcasted_iota(jnp.int32, (L, KB), 1)
        rows = pos_ref[0] + jax.lax.broadcasted_iota(jnp.int32, (L, KB), 0)
        _join(s, cols <= rows, wide[:, nope:], 0, m_scr, l_scr, acc_scr)

    @pl.when(j == last)
    def _():
        o_ref[0, 0] = _finish(l_scr, acc_scr).astype(o_ref.dtype)


def chunk_attention(q: jnp.ndarray, latent: jnp.ndarray, w_kvb: jnp.ndarray,
                    pos, scale: float,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """A chunk's queries ``q`` (B, L, H, nope + rope), the first at
    absolute position ``pos`` (a traced scalar), over a ring ``latent``
    (B, M, rank + rope) that already holds the chunk's own latents at
    ``pos .. pos + L - 1`` and has not rolled; query ``i`` sees the
    positions up to its own. Returns (B, L, H * v). The WIDENED form, as
    a Pallas kernel (``mla_chunk_widened_fwd``). The grid is ``(sequence,
    head, ring block)``; a grid step holds a head's queries of the WHOLE chunk
    (L x (nope + rope)), widens one block of latents to that head's keys
    and values on the chip (the head's ``(rank, nope + v)`` slice of
    ``W_kvb`` stays resident over the head's blocks), and joins the block's
    scores to a running float32 softmax in VMEM: no score, and no widened
    key or value, reaches HBM. The blocks past the chunk's end point at the
    last block read and cost neither traffic nor work (the trip count rides
    as scalar prefetch: one program serves every position)."""
    if interpret is None:
        interpret = auto_interpret()
    B, L, H, dq = q.shape
    M, rank = latent.shape[1], w_kvb.shape[0]
    rope = latent.shape[-1] - rank
    nope, KB = dq - rope, key_block(M)
    vd = w_kvb.shape[-1] - nope
    # a head's queries as the rows of a tile; the padding rows see only
    # what the chunk's last row sees and are cut off
    tile = 32 // q.dtype.itemsize
    Lp = -(-L // tile) * tile
    qh = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, Lp - L), (0, 0)))
    w = w_kvb.astype(q.dtype).transpose(1, 0, 2)  # (H, rank, nope + v)
    pos = jnp.asarray(pos, jnp.int32).reshape(1)
    n = jnp.minimum((pos + L - 1) // KB + 1, M // KB)

    def block(b, h, j, n_ref, pos_ref):
        return b, jnp.minimum(j, n_ref[0] - 1), 0

    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, rank=rank, nope=nope),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, M // KB),
            in_specs=[
                pl.BlockSpec((1, 1, Lp, dq), lambda b, h, j, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, KB, rank + rope), block),
                pl.BlockSpec((1, rank, nope + vd),
                             lambda b, h, j, *_: (h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, Lp, vd),
                                   lambda b, h, j, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Lp, 1), jnp.float32),
                pltpu.VMEM((Lp, 1), jnp.float32),
                pltpu.VMEM((Lp, vd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Lp, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20,
        ),
        name=kernel_names.MLA_CHUNK_WIDENED,
        interpret=interpret,
    )(n, pos, qh, latent.astype(q.dtype), w)
    return out[:, :, :L].transpose(0, 2, 1, 3).reshape(B, L, H * vd)


# -- the decode step's read: a row's live latent blocks ------------------------


def live_blocks(pos, live, M: int):
    """(B,) int32: the ring blocks row ``b`` holds latents in, ``pos //
    block + 1`` (the ring has not rolled: ``pos < M``), 0 for a row that
    is not ``live``. THE rule of what the kernel reads, for traced
    positions and NumPy ones alike."""
    xp = jnp if isinstance(pos, jax.Array) else __import__("numpy")
    return xp.where(live, pos // key_block(M) + 1, 0).astype(xp.int32)


def _decode_kernel(row_ref, lo_ref, n_ref, count_ref, pos_ref, q_ref, lat_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, scale: float, rank: int,
                   on_lanes: bool):
    del row_ref, lo_ref, n_ref  # the index maps' alone
    KB = lat_ref.shape[3 if on_lanes else 2]
    b, j = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _():
        _start(m_scr, l_scr, acc_scr)

    @pl.when(j < count_ref[b])
    def _():
        # key and value of all heads: (rank + rope, KB) with the ring on
        # the lanes, else (KB, rank + rope)
        lat = lat_ref[0, 0]
        s = jax.lax.dot_general(
            q_ref[0], lat, (((1,), (0 if on_lanes else 1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, KB)
        slots = j * KB + jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)
        values = lat[:rank] if on_lanes else lat[:, :rank]
        _join(s, slots <= pos_ref[b], values, 1 if on_lanes else 0,
              m_scr, l_scr, acc_scr)

    @pl.when(j == last)
    def _():
        o_ref[0] = _finish(l_scr, acc_scr).astype(o_ref.dtype)


def latent_decode_attention(qq: jnp.ndarray, latent: jnp.ndarray,
                            pos: jnp.ndarray, live: jnp.ndarray, rank: int,
                            scale: float,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """One token a slot: the absorbed queries ``qq`` (B, H, rank + rope)
    (:func:`absorb_queries`) of the tokens at absolute positions ``pos``
    (B,) over the pool's rings of latents ``latent`` (B, 1, M, rank +
    rope), which already hold the tokens' own latents and have not
    rolled. Returns the probability-weighted latents (B, H, rank) in
    ``qq``'s dtype, to be widened by ``W_v``; a row that is not ``live``
    (B, bool) reads nothing and comes out as zeros.

    The grid is ``(slot, ring block)``. A block is read once for ALL
    heads: the latent is key and value of every head, so a grid step is
    two products of the slot's ``(H, .)`` queries with one ``(block, rank
    + rope)`` tile. Step ``(b, j)`` points at the row's ``j``-th block
    while it has one and at its last one afterwards; a row that is not
    live points at the block the live row before it left: the steps past a
    row's blocks, and a whole free slot, fetch nothing (the pipeline skips
    a fetch whose block index did not change, as ``ops/kv_write.py``,
    ``ops/moe.py`` and ``ops/ring_attention.py`` lean on) and compute
    nothing.

    The pool is taken AS THE CHIP HOLDS IT. A leaf ``(.., M, 576)`` lies
    with the ring on the lanes (``ops/kv_write.py:position_on_lanes``: 576
    fills no whole 128-lane tile), and the row write before this read
    takes it so; handed the row-major leaf, this kernel made the compiler
    copy every layer's pool into that layout and back, 604 MB each way a
    layer and step (my compile for the described v5e, PR 40). So the
    kernel reads the swapped view in blocks of ``(rank + rope, block)``:
    the scores are a plain product with it, the values the transposed
    one."""
    if interpret is None:
        interpret = auto_interpret()
    B, H, width = qq.shape
    M = latent.shape[2]
    KB = key_block(M)
    # the heads are the rows of a tile (16 rows of a packed 16-bit type, 8
    # of a 32-bit one); the padding rows are zeros
    tile = 32 // qq.dtype.itemsize
    Hp = -(-H // tile) * tile
    qp = jnp.pad(qq, ((0, 0), (0, Hp - H), (0, 0)))
    pos = jnp.asarray(pos, jnp.int32)
    count = live_blocks(pos, live, M)
    # a row that is not live rides on the block its owner left (the live
    # row before it; before the first live row, on that row's first)
    slots = jnp.arange(B, dtype=jnp.int32)
    owner = slot_owners(jnp.where(live, 0, -1))
    final = jnp.maximum(count, 1) - 1
    row = jnp.where(live, slots, owner)
    lo = jnp.where(live | (owner >= slots), 0, final[owner])
    n = jnp.where(live, count, 1)

    on_lanes = position_on_lanes(M, width)
    pool = jnp.swapaxes(latent, -1, -2) if on_lanes else latent

    def ring_index(b, j, row_ref, lo_ref, n_ref, *_):
        block = lo_ref[b] + jnp.minimum(j, n_ref[b] - 1)
        return (row_ref[b], 0, 0, block) if on_lanes else (
            row_ref[b], 0, block, 0)

    def own(b, j, *_):
        return b, 0, 0

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, rank=rank,
                          on_lanes=on_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, M // KB),
            in_specs=[
                pl.BlockSpec((1, Hp, width), own),
                pl.BlockSpec((1, 1, width, KB) if on_lanes
                             else (1, 1, KB, width), ring_index),
            ],
            out_specs=pl.BlockSpec((1, Hp, rank), own),
            scratch_shapes=[
                pltpu.VMEM((Hp, 1), jnp.float32),
                pltpu.VMEM((Hp, 1), jnp.float32),
                pltpu.VMEM((Hp, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hp, rank), qq.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20,
        ),
        name=kernel_names.MLA_LATENT_DECODE,
        interpret=interpret,
    )(row, lo, n, count, pos, qp, pool)
    return out[:, :H]

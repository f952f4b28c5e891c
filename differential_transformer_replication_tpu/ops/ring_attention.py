"""Decode attention over a slot pool of grouped K/V rings that reads a
row's LIVE ring blocks only (the ``afmoe`` family's decode step, PR 36).

One token a slot: row ``b`` at absolute position ``pos[b]`` attends over
its own ring of ``M`` positions, of which it sees the last ``window``
(itself among them). ``jamba.attend`` under a visibility mask reads every
slot's ring whole whatever it holds: 7.5 GB a step in the cell where 1.3 GB
are live (PERF.md section 6, PR 36). Here the grid is ``(slot, ring
block)``, a block holds ``block`` positions of ALL K/V heads (every K/V
head serves ``H / KV`` query heads, so a ring is read once, not once a
query head), and the blocks a row holds ride as scalar prefetch:

- a row that has not rolled its ring (``pos < M``) holds the blocks from
  the one of its oldest visible position to the one of ``pos``; a row that
  has rolled holds the whole ring (the window is most of it);
- grid step ``(b, j)`` points at the row's ``j``-th such block while it has
  one and at its last one afterwards; a row that is not live points at the
  block the row before it left, so the steps past a row's blocks, and a
  whole free slot, fetch nothing (the pipeline skips a fetch whose block
  index did not change, as ``ops/kv_write.py`` and ``ops/moe.py`` lean on)
  and compute nothing;
- the softmax runs online in float32 over a row's blocks (running maximum,
  sum and weighted values in VMEM scratch, a K/V head's query heads as the
  rows of one small matrix product); a row that is not live comes out as
  zeros.

What a step reads is then the live positions rounded up to whole blocks.

The rings are taken AS THE CHIP LAYS THEM OUT. A pool leaf's layout follows
its shape (``ops/kv_write.py:position_on_lanes``): heads of 128 (afmoe,
nemotron_h) lie row-major, a block is ``(KV, block, d)``; heads of 64 (lfm2)
put the ring on the lanes, and the kernel then reads the transposed view, a
block ``(KV, d, block)``, with the two products' contractions turned to
match. Handed the row-major leaf there, the compiler copied the K and the V
pool into that layout and back every step (2.15 GB of temporaries beside a
pool of 1.08 GB, my described-v5e compile, PR 47), as
``ops/mla.py:latent_decode_attention`` found for its latents (PR 40).
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

RING_BLOCK = 512  # ring positions a grid step reads (of every K/V head)


def ring_block(M: int) -> int:
    """Positions a grid step reads of a ring of ``M``: the largest common
    divisor with ``RING_BLOCK`` (a ring is whole blocks)."""
    return math.gcd(M, RING_BLOCK)


def row_blocks(pos, live, M: int, window: int):
    """``(first, count)`` (B,) int32: the ring blocks row ``b`` holds
    visible keys in, ``count`` of them from ``first`` on; 0 for a row that
    is not ``live``. THE rule of what the kernel reads, in array code for
    the traced positions and for NumPy ones alike."""
    xp = jnp if isinstance(pos, jax.Array) else __import__("numpy")
    KB = ring_block(M)
    oldest = xp.maximum(pos - window + 1, 0) // KB
    rolled = pos >= M
    first = xp.where(rolled, 0, oldest)
    count = xp.where(rolled, M // KB, pos // KB - oldest + 1)
    return (first.astype(xp.int32),
            xp.where(live, count, 0).astype(xp.int32))


def _kernel(row_ref, lo_ref, n_ref, live_ref, pos_ref, q_ref, k_ref, v_ref,
            o_ref, m_scr, l_scr, acc_scr, *, ring: int, window: int,
            on_lanes: bool):
    del row_ref, n_ref  # the index maps' alone
    KV, G, d = q_ref.shape[1:]
    # a K/V block: (KV, KB, d), or (KV, d, KB) with the ring on the lanes
    KB = k_ref.shape[3 if on_lanes else 2]
    over_d, over_ring = (0, 1) if on_lanes else (1, 0)
    b, j = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1
    pos, first, count = pos_ref[b], lo_ref[b], live_ref[b]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j < count)
    def _():
        # the position ring slot m holds for a row at `pos`: this lap's
        # up to the row's own slot, the lap before's past it
        slots = (first + j) * KB + jax.lax.broadcasted_iota(
            jnp.int32, (1, KB), 1)
        own = jax.lax.rem(pos, jnp.int32(ring))
        held = jnp.where(slots <= own, slots, slots - ring) + (pos - own)
        visible = (held >= 0) & (pos - held < window)
        scale = 1.0 / math.sqrt(d)
        for h in range(KV):  # a K/V head's query heads: one small product
            rows = slice(h * G, (h + 1) * G)
            s = jax.lax.dot_general(
                q_ref[0, h], k_ref[0, h], (((1,), (over_d,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, KB)
            s = jnp.where(visible, s, NEG_INF)
            top = m_scr[rows]
            new_top = jnp.maximum(top, jnp.max(s, axis=-1, keepdims=True))
            keep = jnp.exp(top - new_top)
            p = jnp.where(visible, jnp.exp(s - new_top), 0.0)
            l_scr[rows] = l_scr[rows] * keep + jnp.sum(p, axis=-1,
                                                       keepdims=True)
            acc_scr[rows] = acc_scr[rows] * keep + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, h],
                (((1,), (over_ring,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[rows] = new_top

    @pl.when(j == last)
    def _():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.reshape(KV, G, d).astype(o_ref.dtype)


def ring_decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          pos: jnp.ndarray, live: jnp.ndarray, window: int,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """``q`` (B, H, d), a token a slot at absolute position ``pos`` (B,),
    over the pool's rings ``k``, ``v`` (B, KV, M, d) that already hold the
    token's own key and value; row ``b`` sees the last ``window`` positions
    of its ring. Returns (B, H * d) in ``q``'s dtype; a row that is not
    ``live`` (B, bool) reads nothing and comes out as zeros."""
    if interpret is None:
        interpret = auto_interpret()
    B, H, d = q.shape
    KV, M = k.shape[1], k.shape[2]
    G, KB = H // KV, ring_block(M)
    # a K/V head's query heads as the rows of a tile (16 rows of a packed
    # 16-bit type, 8 of a 32-bit one); the padding rows are zeros
    Gp = -(-G // (32 // q.dtype.itemsize)) * (32 // q.dtype.itemsize)
    qg = jnp.pad(q.reshape(B, KV, G, d), ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    pos = jnp.asarray(pos, jnp.int32)
    first, count = row_blocks(pos, live, M, window)
    # a row that is not live rides on the block its owner left (the live
    # row before it; before the first live row, on that row's first)
    slots = jnp.arange(B, dtype=jnp.int32)
    owner = slot_owners(jnp.where(live, 0, -1))
    final = first + jnp.maximum(count, 1) - 1
    row = jnp.where(live, slots, owner)
    lo = jnp.where(live, first,
                   jnp.where(owner < slots, final[owner], first[owner]))
    n = jnp.where(live, count, 1)

    on_lanes = position_on_lanes(M, d)
    if on_lanes:  # the chip's own layout of the leaves, as row-major views
        k, v = jnp.swapaxes(k, -1, -2), jnp.swapaxes(v, -1, -2)

    def ring_index(b, j, row_ref, lo_ref, n_ref, *_):
        row = row_ref[b]
        block = lo_ref[b] + jnp.minimum(j, n_ref[b] - 1)
        return (row, 0, 0, block) if on_lanes else (row, 0, block, 0)

    ring_block_shape = (1, KV, d, KB) if on_lanes else (1, KV, KB, d)

    def own(b, j, *_):
        return b, 0, 0, 0

    out = pl.pallas_call(
        functools.partial(_kernel, ring=M, window=window, on_lanes=on_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, M // KB),
            in_specs=[
                pl.BlockSpec((1, KV, Gp, d), own),
                pl.BlockSpec(ring_block_shape, ring_index),
                pl.BlockSpec(ring_block_shape, ring_index),
            ],
            out_specs=pl.BlockSpec((1, KV, Gp, d), own),
            scratch_shapes=[
                pltpu.VMEM((KV * Gp, 1), jnp.float32),
                pltpu.VMEM((KV * Gp, 1), jnp.float32),
                pltpu.VMEM((KV * Gp, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20,
        ),
        name=kernel_names.RING_GQA_DECODE,
        interpret=interpret,
    )(row, lo, n, count, pos, qg, k, v)
    return out[:, :, :G].reshape(B, H * d)


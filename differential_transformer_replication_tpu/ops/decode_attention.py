"""Fused single-query decode attention over the serving slot pool, with
optional int8 KV storage — the decode-side counterpart of ops/flash.py.

Serving decode is one token per step per slot: the engine's hot loop
(serving/engine.py) runs L=1 attention over every slot's ring KV cache.
As plain XLA ops (models/decode.py:``_attn_chunk``) that materializes the
per-stream fp32 score/softmax maps ``(S, B, H, M)`` in HBM every layer of
every step, and on TPU the decode step is bandwidth-bound: the K/V cache
stream dominates, so the score-map round-trips bound both inter-token
latency and how many concurrent slots fit at equal HBM.

This module is the fused alternative:

- :func:`decode_attention` — a Pallas kernel, grid ``(B*H, nk)``, that
  streams each slot row's ring cache tile-by-tile, runs the S per-stream
  softmaxes ONLINE (flash-style running max/sum carried in VMEM scratch),
  applies the lambda-weighted combine coefficients
  (models/decode.py:``_layer_coeffs`` — control S=1, diff S=2, ndiff S=N)
  in-kernel, and writes only the ``(B, H, dv)`` output. Per-stream
  attention maps and fp32 scores never reach HBM. The paged and the
  multi-query (speculative verify) entry points are the same kernel
  with page-table index maps and L > 1 query rows per program.
- int8 KV: :func:`quantize_kv` stores K/V rows as int8 with one fp32
  scale per (stream,) slot/head/token vector; the kernel feeds the int8
  tiles to the MXU (the cast to the compute dtype is exact) and applies
  the per-token scales to the score / probability rows, so the HBM
  stream is genuinely half the bf16 bytes (plus a ~4/d scale overhead)
  and nothing is rounded to the compute dtype on the way.
  :func:`dequantize_kv` is the XLA twin used by the un-fused path and
  the parity oracles.
- :func:`decode_attention_reference` — the plain-XLA twin (same masking
  and fp32 softmax), used when ``decode_attention_impl == "xla"`` and by
  tests/tools/decode_attn_sweep.py as the parity baseline.
- :func:`quantize_params_int8` — the weight-side satellite: per-channel
  symmetric int8 quantize + dequantize of every matmul weight for
  ``load_params_for_inference(..., quantize="int8")``.

Ring-mask note: a decode row at absolute position ``pos`` over a ring of
``M = block_size`` slots sees slot ``m`` iff the position it holds is
non-negative, which reduces to ``m <= pos`` (for ``pos >= M`` every slot
holds a live key) — the same arithmetic ``_attn_chunk`` derives for its
general chunk case, collapsed for L=1 (see models/decode.py).

Kernel naming: the kernel body is ``_dattn_kernel`` so XLA op names
carry the ``_dattn_`` needle tools/profile_step.py buckets on.
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
from differential_transformer_replication_tpu.ops.flash import (
    auto_interpret,
    pick_block,
)
from differential_transformer_replication_tpu.ops.kv_write import (
    position_on_lanes,
)
from differential_transformer_replication_tpu.ops.streams import NEG_INF

# K tile length streamed per grid step; clipped to a divisor of the cache
# length (pick_block). 512 keeps the int8 tile above the (32, 128) int8
# tiling floor and the VMEM footprint at O(S * block * d) per program.
_DEFAULT_BLOCK_K = 512


# ---------------------------------------------------------------------------
# int8 KV quantization (per-vector symmetric scales)
# ---------------------------------------------------------------------------


def quantize_kv(x: jnp.ndarray):
    """Symmetric int8 quantization over the LAST axis.

    One fp32 scale per leading-index vector (for a K row that is per
    (stream, slot, head, token) — the "per-head scale" granularity), so
    ``|dequant(q) - x| <= scale / 2`` elementwise. Returns
    ``(int8 values, fp32 scales)`` with ``scales.shape == x.shape[:-1]``.
    All-zero vectors get a tiny floor scale instead of a 0/0 NaN.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.round(xf / scale[..., None]).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """XLA-side inverse of :func:`quantize_kv` (the fused kernel performs
    the same multiply inside its tile loads instead)."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# The fused kernel
# ---------------------------------------------------------------------------


def _dattn_kernel(
    *refs,
    # after the scalar-prefetch refs (the page table, consumed by the
    # index maps):
    #   q_ref    (1, L, S, d)   this (b, h)'s per-(row, stream) queries
    #   k_ref    (S, 1, block_k, d)  stored dtype (float) or int8;
    #            (S, 1, d, block_k) with ``k_on_lanes``
    #   v_ref    (1, block_k, dv); (1, dv, block_k) with ``v_on_lanes``
    #   pos_ref  (L, BH) int32 SMEM: absolute position per row, program
    #   c_ref    (S, H) float32 SMEM combine coefficients (_layer_coeffs)
    #   [ks_ref (S, 1, 1, block_k), vs_ref (1, 1, block_k) if quantized]
    #   out_ref  (1, L, dv)
    #   scratch  m (L*S, 1), l (L*S, 1), acc (L*S, dv) — all fp32
    n_prefetch: int,
    n_heads: int,
    quantized: bool,
    k_on_lanes: bool = False,
    v_on_lanes: bool = False,
):
    refs = refs[n_prefetch:]
    q_ref, k_ref, v_ref, pos_ref, c_ref = refs[:5]
    if quantized:
        ks_ref, vs_ref, out_ref, m_scr, l_scr, acc_scr = refs[5:]
    else:
        out_ref, m_scr, l_scr, acc_scr = refs[5:]
    L, S, d = q_ref.shape[1:]
    block_k = k_ref.shape[3 if k_on_lanes else 2]
    bh = pl.program_id(0)  # read at top level (interpreter cannot lower
    j = pl.program_id(1)   # program_id inside when-bodies; see ops/flash.py)
    nk = pl.num_programs(1)
    # per-row positions; the tile-skip bound is the rows' max (static
    # unroll over the tiny L keeps the SMEM reads scalar-indexed)
    pos_l = [pos_ref[l, bh] for l in range(L)]
    pos_max = functools.reduce(jnp.maximum, pos_l)
    scale = 1.0 / math.sqrt(d)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Ring visibility collapses to col <= pos for a decode row (module
    # docstring); a tile entirely past every row's position is skipped
    # outright. Rows and streams are a STATIC unroll of 2-D (1, .) x
    # (block_k, .) matmuls: every row runs the same op sequence whatever
    # L is (the greedy spec/non-spec bit-parity pin depends on that),
    # and the MXU gets a free lhs dimension (a batched dot with no free
    # lhs dim is not expressible in Mosaic).
    @pl.when(j * block_k <= pos_max)
    def _():
        cdtype = q_ref.dtype
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        v_j = v_ref[0]  # (block_k, dv) | (dv, block_k)
        if quantized:
            # int8 -> compute dtype is exact; the per-token scales are
            # applied on the (1, block_k) score / probability rows
            # below, where they sit on lanes like the rows themselves
            v_j = v_j.astype(jnp.float32).astype(cdtype)
            v_sc = vs_ref[0]  # (1, block_k)
        for s_i in range(S):
            k_j = k_ref[s_i, 0]  # (block_k, d) | (d, block_k)
            if quantized:
                k_j = k_j.astype(jnp.float32).astype(cdtype)
                k_sc = ks_ref[s_i, 0] * scale  # (1, block_k)
            for l in range(L):
                i = l * S + s_i
                q = q_ref[0, l, s_i:s_i + 1, :]  # (1, d)
                s = jax.lax.dot_general(
                    q, k_j,
                    dimension_numbers=(
                        ((1,), (0 if k_on_lanes else 1,)), ((), ())
                    ),
                    preferred_element_type=jnp.float32,
                )  # (1, block_k)
                s = s * k_sc if quantized else s * scale
                s = jnp.where(cols <= pos_l[l], s, NEG_INF)
                m_prev = m_scr[i:i + 1]  # (1, 1)
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True)
                )
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)  # (1, block_k)
                l_scr[i:i + 1] = (
                    l_scr[i:i + 1] * alpha
                    + jnp.sum(p, axis=-1, keepdims=True)
                )
                if quantized:
                    p = p * v_sc
                pv = jax.lax.dot_general(
                    p.astype(v_j.dtype), v_j,
                    dimension_numbers=(
                        ((1,), (1 if v_on_lanes else 0,)), ((), ())
                    ),
                    preferred_element_type=jnp.float32,
                )  # (1, dv)
                acc_scr[i:i + 1] = acc_scr[i:i + 1] * alpha + pv
                m_scr[i:i + 1] = m_new

    @pl.when(j == nk - 1)
    def _():
        h = jax.lax.rem(bh, jnp.int32(n_heads))
        for l in range(L):
            combined = None
            for s_i in range(S):
                i = l * S + s_i
                # l >= 1 always (a row's own position is visible to
                # it); the floor only guards never-stepped rows
                o_s = acc_scr[i:i + 1] / jnp.maximum(l_scr[i:i + 1], 1e-30)
                term = o_s * c_ref[s_i, h]
                combined = term if combined is None else combined + term
            out_ref[0, l:l + 1, :] = combined.astype(out_ref.dtype)


def _dattn_call(
    qs: jnp.ndarray,  # (S, B, L, H, d)
    k: jnp.ndarray,  # (S, R, H, M, d) slot pool | (S, P, H, ps, d) pages
    v: jnp.ndarray,  # (R, H, M, dv) | (P, H, ps, dv)
    pos: jnp.ndarray,  # (B, L) int32
    coeffs: jnp.ndarray,  # (S, H)
    k_scale: Optional[jnp.ndarray],  # k.shape[:-1] fp32 (int8 path)
    v_scale: Optional[jnp.ndarray],  # v.shape[:-1] fp32
    page_tables: Optional[jnp.ndarray],  # (B, pages_per_slot) | None
    block_k: int,
    interpret: Optional[bool],
) -> jnp.ndarray:
    """One ``pallas_call`` behind all four public entry points: grid
    ``(B*H, tiles)``, L query rows per program, K/V tiles addressed
    either directly (slot pool: row ``bh`` of the head-major pool, tile
    ``j``) or through the scalar-prefetched page table (row
    ``page_tables[b, j] * H + h``, the whole page). Returns
    ``(B, L, H, dv)`` in the query dtype.

    Mosaic wants the last two dims of every block either full or
    (8, 128)-aligned, so single rows ride behind a singleton axis: the
    output block is ``(1, L, dv)`` and the scale planes are viewed as
    ``(.., 1, M)`` (zero-copy: M is already the minor axis).

    The slot pool is read in the layout the chip holds it in
    (ops/kv_write.py:``position_on_lanes``): where the chip puts the
    ring on the lanes (the recipe's 512 x 96 and 512 x 192), K and V
    ride in as their ``(.., d, M)`` views and the two matmuls contract
    the other axis of the tile; a row-major view there would have XLA
    copy the whole pool into another layout for every call."""
    S, B, L, H, d = qs.shape
    rows, M = k.shape[1] * H, k.shape[3]
    dv = v.shape[-1]
    BH = B * H
    if interpret is None:
        interpret = auto_interpret()
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")

    k_on_lanes = v_on_lanes = False
    if page_tables is None:
        prefetch = []
        bk = pick_block(block_k or _DEFAULT_BLOCK_K, M)
        n_tiles = M // bk
        lane_tiles = bk == M or bk % 128 == 0
        k_on_lanes = lane_tiles and position_on_lanes(M, d)
        v_on_lanes = lane_tiles and position_on_lanes(M, dv)

        def kv_index(bh, j):
            return bh, j
    else:
        prefetch = [jnp.asarray(page_tables, jnp.int32)]
        bk = M  # one grid step streams one page
        n_tiles = page_tables.shape[1]

        def kv_index(bh, j, pt_ref):
            return pt_ref[bh // H, j] * H + bh % H, 0

    # (S, B, L, H, d) -> (BH, L, S, d): tiny, one token per row
    q = qs.transpose(1, 3, 2, 0, 4).reshape(BH, L, S, d)
    # zero-copy views: the pools are head-major (models/decode.py)
    k = (jnp.swapaxes(k, -1, -2).reshape(S, rows, d, M) if k_on_lanes
         else k.reshape(S, rows, M, d))
    v = (jnp.swapaxes(v, -1, -2).reshape(rows, dv, M) if v_on_lanes
         else v.reshape(rows, M, dv))
    # (L, BH): column b*H+h carries slot b's row positions
    pos_bh = jnp.repeat(jnp.asarray(pos, jnp.int32).T, H, axis=1)

    def fixed(*idx):
        return lambda bh, j, *pt: idx

    inputs = [q, k, v, pos_bh, coeffs.astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((1, L, S, d), lambda bh, j, *pt: (bh, 0, 0, 0),
                     memory_space=pltpu.VMEM),
        (pl.BlockSpec((S, 1, d, bk),
                      lambda *a: (0, kv_index(*a)[0], 0, kv_index(*a)[1]),
                      memory_space=pltpu.VMEM) if k_on_lanes else
         pl.BlockSpec((S, 1, bk, d),
                      lambda *a: (0, *kv_index(*a), 0),
                      memory_space=pltpu.VMEM)),
        (pl.BlockSpec((1, dv, bk),
                      lambda *a: (kv_index(*a)[0], 0, kv_index(*a)[1]),
                      memory_space=pltpu.VMEM) if v_on_lanes else
         pl.BlockSpec((1, bk, dv), lambda *a: (*kv_index(*a), 0),
                      memory_space=pltpu.VMEM)),
        pl.BlockSpec((L, BH), fixed(0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((S, H), fixed(0, 0), memory_space=pltpu.SMEM),
    ]
    if quantized:
        inputs += [
            k_scale.reshape(S, rows, 1, M).astype(jnp.float32),
            v_scale.reshape(rows, 1, M).astype(jnp.float32),
        ]

        def scale_index(*a):
            row, tile = kv_index(*a)
            return row, 0, tile

        in_specs += [
            pl.BlockSpec((S, 1, 1, bk), lambda *a: (0, *scale_index(*a)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk), scale_index,
                         memory_space=pltpu.VMEM),
        ]
    out = pl.pallas_call(
        functools.partial(
            _dattn_kernel, n_prefetch=len(prefetch), n_heads=H,
            quantized=quantized, k_on_lanes=k_on_lanes,
            v_on_lanes=v_on_lanes,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(BH, n_tiles),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, L, dv),
                                   lambda bh, j, *pt: (bh, 0, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((L * S, 1), jnp.float32),
                pltpu.VMEM((L * S, 1), jnp.float32),
                pltpu.VMEM((L * S, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, L, dv), qs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        name=kernel_names.DECODE_ATTENTION,
        interpret=interpret,
    )(*prefetch, *inputs)
    # (BH, L, dv) -> (B, L, H, dv)
    return out.reshape(B, H, L, dv).transpose(0, 2, 1, 3)


def decode_attention(
    qs: jnp.ndarray,  # (S, B, H, d) current-token queries (post-RoPE)
    k_cache: jnp.ndarray,  # (S, B, H, M, d) stored dtype or int8
    v_cache: jnp.ndarray,  # (B, H, M, dv)
    pos,  # (B,) int32 absolute position of each row's current token
    coeffs: jnp.ndarray,  # (S, H) float32 combine coefficients
    *,
    k_scale: Optional[jnp.ndarray] = None,  # (S, B, H, M) fp32 (int8 path)
    v_scale: Optional[jnp.ndarray] = None,  # (B, H, M) fp32
    block_k: int = 0,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused single-query multi-stream attention over the slot pool.

    The cache rides in the kernel-native pool layout (models/decode.py
    ``init_cache``): head-major, so the per-(b, h) ``(M, d)`` ring is
    contiguous and the grid flattens to ``B*H`` programs with zero-copy
    reshapes. The current token's K/V must already be written into the
    cache at ``pos % M`` (the same update-then-attend order
    ``_attn_chunk`` uses). Returns ``(B, H, dv)`` in the query dtype.
    """
    return _dattn_call(
        qs[:, :, None], k_cache, v_cache,
        jnp.asarray(pos, jnp.int32)[:, None], coeffs, k_scale, v_scale,
        None, block_k, interpret,
    )[:, 0]


def decode_attention_paged(
    qs: jnp.ndarray,  # (S, B, H, d) current-token queries (post-RoPE)
    k_pages: jnp.ndarray,  # (S, P, H, ps, d) stored dtype or int8
    v_pages: jnp.ndarray,  # (P, H, ps, dv)
    page_tables: jnp.ndarray,  # (B, pages_per_slot) int32
    pos,  # (B,) int32 absolute position of each row's current token
    coeffs: jnp.ndarray,  # (S, H) float32 combine coefficients
    *,
    k_scale: Optional[jnp.ndarray] = None,  # (S, P, H, ps) fp32
    v_scale: Optional[jnp.ndarray] = None,  # (P, H, ps) fp32
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused single-query decode attention THROUGH a page table.

    Same online-softmax multi-stream kernel as :func:`decode_attention`
    with one change: the KV tiles are loaded page-indexed. The page
    table rides as a SCALAR-PREFETCH operand
    (``pltpu.PrefetchScalarGridSpec``), so each K/V BlockSpec index map
    resolves grid step ``(bh, j)`` — row ``b = bh // H``, logical page
    ``j`` — to physical tile ``page_tables[b, j] * H + h`` of the
    head-major page pool (models/decode.py:``init_cache_paged``; the
    per-(page, head) ``(ps, d)`` tile is contiguous, so the reshape to
    ``(S, P*H, ps, d)`` is zero-copy). The tile length IS the page
    size: one grid step streams one page, int8 dequantization stays
    fused in the load. Because the table is a runtime int32 array,
    allocating/freeing/sharing/forking pages between calls compiles
    NOTHING new — the zero-recompile pin the serving engine keeps.

    Hardware note: a page is a whole K/V block (its last two dims are
    the array's own), so the chip's compiler takes every page size that
    divides block_size — bf16 and int8, down to one token
    (tests/test_tpu_compile.py; run on a v5e at 1..256 in PR 21). Small
    pages cost grid steps, and pages under the dtype's sublane tile
    (16 rows bf16, 32 int8) are presumably padded in HBM.
    """
    return _dattn_call(
        qs[:, :, None], k_pages, v_pages,
        jnp.asarray(pos, jnp.int32)[:, None], coeffs, k_scale, v_scale,
        page_tables, 0, interpret,
    )[:, 0]


def decode_attention_reference(
    qs: jnp.ndarray,  # (S, B, H, d)
    k_cache: jnp.ndarray,  # (S, B, H, M, d) FLOAT (dequantize first)
    v_cache: jnp.ndarray,  # (B, H, M, dv)
    pos,  # (B,) int32
    coeffs: jnp.ndarray,  # (S, H) float32
) -> jnp.ndarray:
    """Plain-XLA twin of :func:`decode_attention`: identical masking and
    fp32 per-stream softmax, materialized maps — the un-fused baseline
    (``decode_attention_impl == "xla"``) and the sweep/test oracle."""
    S, B, H, M, d = k_cache.shape
    scale = 1.0 / math.sqrt(d)
    scores = (
        jnp.einsum("sbhd,sbhmd->sbhm", qs, k_cache).astype(jnp.float32)
        * scale
    )
    visible = jnp.arange(M)[None, :] <= jnp.asarray(pos, jnp.int32)[:, None]
    scores = jnp.where(visible[None, :, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    combined = jnp.einsum("sh,sbhm->bhm", coeffs.astype(jnp.float32), probs)
    return jnp.einsum("bhm,bhme->bhe", combined.astype(v_cache.dtype), v_cache)


# ---------------------------------------------------------------------------
# Multi-query (L <= k rows per slot) variant — the speculative-decoding
# verify kernel (serving/spec.py). Each of a slot's L rows carries its
# own query token at its own absolute position (the last emitted token
# plus the k draft tokens); every row streams the SAME ring cache (or
# the same page-table-resolved pages) with ROW-CAUSAL visibility
# ``col <= pos[b, l]``, so row l sees the K/V rows 0..l wrote this very
# step (update-then-attend order, positions pos..pos+l) and nothing a
# later row wrote. It is the same kernel: the single-query entry points
# above are its L = 1 case.
# ---------------------------------------------------------------------------


def decode_attention_multi(
    qs: jnp.ndarray,  # (S, B, L, H, d) per-row queries (post-RoPE)
    k_cache: jnp.ndarray,  # (S, R, H, M, d) stored dtype or int8; R >= B
    v_cache: jnp.ndarray,  # (R, H, M, dv)
    pos,  # (B, L) int32 absolute position of each row's token
    coeffs: jnp.ndarray,  # (S, H) float32 combine coefficients
    *,
    k_scale: Optional[jnp.ndarray] = None,  # (S, R, H, M) fp32 (int8)
    v_scale: Optional[jnp.ndarray] = None,  # (R, H, M) fp32
    block_k: int = 0,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused multi-query decode attention over the slot pool: the
    speculative verify step's kernel. Row (b, l) attends slot b's ring
    cache with visibility ``col <= pos[b, l]`` — row-causal over the
    K/V rows this very step wrote (update-then-attend, positions
    pos..pos+L-1 written before any row attends). The cache may carry
    MORE batch rows than there are query slots (``R > B``: the spec
    engine's trash row rides at index B and is never attended).
    Returns ``(B, L, H, dv)`` in the query dtype."""
    return _dattn_call(
        qs, k_cache, v_cache, jnp.asarray(pos, jnp.int32), coeffs,
        k_scale, v_scale, None, block_k, interpret,
    )


def decode_attention_multi_paged(
    qs: jnp.ndarray,  # (S, B, L, H, d) per-row queries (post-RoPE)
    k_pages: jnp.ndarray,  # (S, P, H, ps, d) stored dtype or int8
    v_pages: jnp.ndarray,  # (P, H, ps, dv)
    page_tables: jnp.ndarray,  # (B, pages_per_slot) int32
    pos,  # (B, L) int32 absolute position per row
    coeffs: jnp.ndarray,  # (S, H) float32 combine coefficients
    *,
    k_scale: Optional[jnp.ndarray] = None,  # (S, P, H, ps) fp32
    v_scale: Optional[jnp.ndarray] = None,  # (P, H, ps) fp32
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Multi-query verify attention THROUGH a page table: each of the
    L rows attends the paged ring through the SAME scalar-prefetch
    page-table index maps as :func:`decode_attention_paged` (one grid
    step streams one physical page, int8 dequant fused in the load)
    with row-causal ``col <= pos[b, l]`` visibility. Runtime int32
    tables ⇒ page churn between calls compiles nothing new."""
    return _dattn_call(
        qs, k_pages, v_pages, jnp.asarray(pos, jnp.int32), coeffs,
        k_scale, v_scale, page_tables, 0, interpret,
    )


def decode_attention_multi_reference(
    qs: jnp.ndarray,  # (S, B, L, H, d)
    k_cache: jnp.ndarray,  # (S, B, H, M, d) FLOAT (dequantize first)
    v_cache: jnp.ndarray,  # (B, H, M, dv)
    pos,  # (B, L) int32
    coeffs: jnp.ndarray,  # (S, H) float32
) -> jnp.ndarray:
    """Plain-XLA twin of :func:`decode_attention_multi`: a STATIC
    unroll over the tiny L, each row running EXACTLY
    :func:`decode_attention_reference`'s op sequence at its own
    position — a batched ``sbhlm`` einsum would reassociate the
    contractions and break the bit-parity the greedy spec/non-spec pin
    depends on. Returns ``(B, L, H, dv)``."""
    L = qs.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    rows = [
        decode_attention_reference(qs[:, :, l], k_cache, v_cache,
                                   pos[:, l], coeffs)
        for l in range(L)
    ]
    return jnp.stack(rows, axis=1)  # (B, L, H, dv)


# ---------------------------------------------------------------------------
# int8 weight quantization (load_params_for_inference satellite)
# ---------------------------------------------------------------------------

_QKV_KEYS = ("wq", "wk", "wv")


def quantize_weight_int8(w: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Symmetric per-output-channel int8 quantize + dequantize of one
    matmul weight: one fp32 scale per slice along the CONTRACTION
    ``axis``, so every output channel keeps its own dynamic range.
    Returns the dequantized weight in the input dtype (the int8 form is
    transient — "dequant-on-load")."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.round(wf / scale).astype(jnp.int8)
    return (q.astype(jnp.float32) * scale).astype(w.dtype)


def quantize_params_int8(params: dict) -> dict:
    """Apply :func:`quantize_weight_int8` to every matmul weight in a
    model params tree: the attention projections (``wq``/``wk``/``wv``,
    contraction axis = the embedding axis) and every Linear ``w``
    (attention out-proj, FFN gate/xform/out, lm head; contraction axis
    0). Embeddings, norms, lambda vectors and biases pass through
    untouched — quantizing those buys nothing (tiny) and costs accuracy
    disproportionately."""

    def walk(node, name=None):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, name) for v in node)
        if name in _QKV_KEYS:
            # (E, H, d) or stacked (S, E, H, d): E is always axis -3
            return quantize_weight_int8(node, axis=-3)
        if name == "w" and getattr(node, "ndim", 0) == 2:
            return quantize_weight_int8(node, axis=0)
        return node

    return walk(params)

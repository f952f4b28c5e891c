"""Fused SwiGLU FFN — the Pallas kernel for the block's MLP half.

The reference FFN (control.py:100-104, shared by all three families) is
``silu(x @ Wg + bg) * (x @ Wx + bx)`` behind a pre-LN. Un-fused, XLA
materializes BOTH (M, 4E) pre-activations to HBM, reads them back for
the silu/product pass, and writes the (M, 4E) hidden — at the recipe
scale (M = 16384 rows, 4E = 3072) that is ~500 MB of pure epilogue
traffic per layer per direction, the largest un-fused block in the
round-4/5 step decompositions (BASELINE.md). This kernel computes the
whole chain tile-by-tile: the gate and xform matmuls feed the MXU from
one VMEM-resident activation tile, the SiLU and elementwise product run
on the fp32 accumulators in registers, and only the final hidden tile
ever reaches HBM.

Grid layout is (hidden-tiles, row-tiles) with rows INNER so the weight
column blocks stay VMEM-resident across the whole row sweep — weights
stream exactly once per call instead of once per row tile.

One entry point: :func:`fused_swiglu` — gate/xform matmuls -> SiLU ->
product; the caller supplies an already-normalized activation (the
training blocks feed it from ops/fused_norm_residual.py's add+LN
kernel, which owns the pre-LN at every block boundary — a standalone
LN never precedes the FFN without a residual add in front, so there
is deliberately no LN-in-front variant here).

Backward is a custom VJP around ONE Pallas kernel that recomputes the
pre-activations tile-by-tile (flash-style: matmul recompute is cheaper
than an (M, 4E) x2 HBM round-trip of saved activations), produces the
gate/xform pre-activation cotangents, and accumulates the fp32 weight
and bias gradients in-kernel across the row grid. The two remaining
contractions (``dg @ Wg^T + dt @ Wx^T``) run as plain XLA ops on those
outputs — they are MXU-bound matmuls XLA already schedules well.

Interpret-mode fallback on CPU (like ops/flash.py), so the tier-1 CPU
suite exercises the real kernel code paths.
"""

from __future__ import annotations

import functools
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
_DEFAULT_BLOCK_M = 256
_DEFAULT_BLOCK_F = 512


def _pre_acts(xn, wg_ref, bg_ref, wx_ref, bx_ref):
    """(bm, bf) fp32 gate/xform pre-activations for one tile pair: the
    MXU contraction in the stored dtype with fp32 accumulation."""
    g = jax.lax.dot_general(
        xn, wg_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + bg_ref[...].astype(jnp.float32)
    t = jax.lax.dot_general(
        xn, wx_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + bx_ref[...].astype(jnp.float32)
    return g, t


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _ffn_fwd_kernel(*refs):
    x_ref, wg_ref, bg_ref, wx_ref, bx_ref, outh_ref = refs
    xn = x_ref[...]
    g, t = _pre_acts(xn, wg_ref, bg_ref, wx_ref, bx_ref)
    outh_ref[...] = (g * jax.nn.sigmoid(g) * t).astype(outh_ref.dtype)


def _specs(E, F, bm, bf):
    """(in_specs sans gh, shared index maps) for both kernels. Grid is
    (F//bf, M//bm) — j (hidden tile) OUTER, i (row tile) inner."""
    x_spec = pl.BlockSpec((bm, E), lambda j, i: (i, 0), memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((E, bf), lambda j, i: (0, j), memory_space=pltpu.VMEM)
    b_spec = pl.BlockSpec((1, bf), lambda j, i: (0, j), memory_space=pltpu.VMEM)
    h_spec = pl.BlockSpec((bm, bf), lambda j, i: (i, j), memory_space=pltpu.VMEM)
    in_specs = [x_spec, w_spec, b_spec, w_spec, b_spec]
    return in_specs, x_spec, w_spec, b_spec, h_spec


def _fwd_call(x2, wg, bg2, wx, bx2, *, block_m, block_f, interpret):
    M, E = x2.shape
    F = wg.shape[1]
    bm = pick_block(block_m, M)
    bf = pick_block(block_f, F)
    in_specs, *_, h_spec = _specs(E, F, bm, bf)
    inputs = (x2, wg, bg2, wx, bx2)
    return pl.pallas_call(
        _ffn_fwd_kernel,
        grid=(F // bf, M // bm),
        in_specs=in_specs,
        out_shape=jax.ShapeDtypeStruct((M, F), x2.dtype),
        out_specs=h_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        name=kernel_names.FUSED_FFN_FWD,
        interpret=interpret,
    )(*inputs)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _ffn_bwd_kernel(*refs):
    """Recompute g/t for one tile pair, emit the pre-activation
    cotangents (dg, dt — consumed by the XLA ``@ W^T`` contractions for
    dx), and accumulate fp32 dWg/dbg/dWx/dbx across the row grid while
    the weight column blocks are resident."""
    (x_ref, wg_ref, bg_ref, wx_ref, bx_ref, gh_ref,
     dg_ref, dt_ref, dwg_ref, dbg_ref, dwx_ref, dbx_ref) = refs
    xn = x_ref[...]
    i = pl.program_id(1)
    g, t = _pre_acts(xn, wg_ref, bg_ref, wx_ref, bx_ref)
    sg = jax.nn.sigmoid(g)
    silu = g * sg
    gh = gh_ref[...].astype(jnp.float32)
    dg = gh * t * (sg * (1.0 + g * (1.0 - sg)))  # d silu(g) = sg(1+g(1-sg))
    dt = gh * silu
    dg_lp = dg.astype(dg_ref.dtype)  # low-precision twin: what XLA's
    dt_lp = dt.astype(dt_ref.dtype)  # un-fused backward would carry
    dg_ref[...] = dg_lp
    dt_ref[...] = dt_lp
    pwg = jax.lax.dot_general(
        xn, dg_lp, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (E, bf) fp32
    pwx = jax.lax.dot_general(
        xn, dt_lp, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    pbg = jnp.sum(dg, axis=0, keepdims=True)
    pbx = jnp.sum(dt, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        dwg_ref[...] = pwg
        dbg_ref[...] = pbg
        dwx_ref[...] = pwx
        dbx_ref[...] = pbx

    @pl.when(i > 0)
    def _acc():
        dwg_ref[...] += pwg
        dbg_ref[...] += pbg
        dwx_ref[...] += pwx
        dbx_ref[...] += pbx


def _bwd_call(x2, wg, bg2, wx, bx2, gh, *, block_m, block_f, interpret):
    M, E = x2.shape
    F = wg.shape[1]
    bm = pick_block(block_m, M)
    bf = pick_block(block_f, F)
    in_specs, x_spec, w_spec, b_spec, h_spec = _specs(E, F, bm, bf)
    in_specs = in_specs + [h_spec]
    inputs = (x2, wg, bg2, wx, bx2, gh)
    dwb_spec = pl.BlockSpec((1, bf), lambda j, i: (0, j), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _ffn_bwd_kernel,
        grid=(F // bf, M // bm),
        in_specs=in_specs,
        out_shape=[
            jax.ShapeDtypeStruct((M, F), x2.dtype),       # dg
            jax.ShapeDtypeStruct((M, F), x2.dtype),       # dt
            jax.ShapeDtypeStruct((E, F), jnp.float32),    # dWg
            jax.ShapeDtypeStruct((1, F), jnp.float32),    # dbg
            jax.ShapeDtypeStruct((E, F), jnp.float32),    # dWx
            jax.ShapeDtypeStruct((1, F), jnp.float32),    # dbx
        ],
        out_specs=[h_spec, h_spec, w_spec, dwb_spec, w_spec, dwb_spec],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        name=kernel_names.FUSED_FFN_BWD,
        interpret=interpret,
    )(*inputs)


def _dxn(dg, dt, wg, wx):
    """dg @ Wg^T + dt @ Wx^T in the stored dtype (what the un-fused XLA
    backward carries), fp32 MXU accumulation."""
    out = jax.lax.dot_general(
        dg, wg, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        dt, wx, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(dg.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrappers (2D) — the public API reshapes
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _swiglu2(x2, wg, bg2, wx, bx2, block_m, block_f, interpret):
    return _fwd_call(
        x2, wg, bg2, wx, bx2,
        block_m=block_m, block_f=block_f, interpret=interpret,
    )


def _swiglu2_fwd(x2, wg, bg2, wx, bx2, block_m, block_f, interpret):
    h = _swiglu2(x2, wg, bg2, wx, bx2, block_m, block_f, interpret)
    return h, (x2, wg, bg2, wx, bx2)


def _swiglu2_bwd(block_m, block_f, interpret, res, gh):
    x2, wg, bg2, wx, bx2 = res
    dg, dt, dwg, dbg, dwx, dbx = _bwd_call(
        x2, wg, bg2, wx, bx2, gh,
        block_m=block_m, block_f=block_f, interpret=interpret,
    )
    dx = _dxn(dg, dt, wg, wx)
    return (dx, dwg.astype(wg.dtype), dbg.astype(bg2.dtype),
            dwx.astype(wx.dtype), dbx.astype(bx2.dtype))


_swiglu2.defvjp(_swiglu2_fwd, _swiglu2_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def fused_swiglu(
    x: jnp.ndarray,
    w_gate: jnp.ndarray,
    b_gate: jnp.ndarray,
    w_xform: jnp.ndarray,
    b_xform: jnp.ndarray,
    *,
    block_m: int = _DEFAULT_BLOCK_M,
    block_f: int = _DEFAULT_BLOCK_F,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused ``silu(x @ Wg + bg) * (x @ Wx + bx)`` (ops/swiglu.py's math,
    one HBM pass over the activation per hidden tile). ``x``: (..., E);
    weights (E, F) — cast to ``x.dtype`` here exactly like
    ``models/common.apply_ffn`` does before the reference op."""
    if interpret is None:
        interpret = auto_interpret()
    E = x.shape[-1]
    x2 = x.reshape(-1, E)
    h = _swiglu2(
        x2,
        w_gate.astype(x.dtype), b_gate.astype(x.dtype).reshape(1, -1),
        w_xform.astype(x.dtype), b_xform.astype(x.dtype).reshape(1, -1),
        block_m, block_f, interpret,
    )
    return h.reshape(x.shape[:-1] + (w_gate.shape[1],))

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

Two forwards share that kernel body. The primal (serving, evaluation,
generation: every call with no gradient) writes the hidden and nothing
else. The forward that runs under a gradient also writes the two
pre-activations it already holds in its fp32 accumulators, rounded once
to the activation dtype, as the backward's residuals: on a v5e the
(M, 4E) x2 round-trip costs about a third of what recomputing the two
products did (PERF.md, PR 43), because the kernels are bound by the MXU
the recompute competed for. ``ModelConfig.remat`` is how a run trades
the two residuals back: under it they live for one block only.

Backward is a custom VJP around ONE Pallas kernel that reads the saved
pre-activations, produces the gate/xform pre-activation cotangents in
their place (aliased), and accumulates the fp32 weight and bias
gradients in-kernel across the row grid: the two products it owns and
no other. The two remaining contractions (``dg @ Wg^T + dt @ Wx^T``)
run as plain XLA ops on those outputs — they are MXU-bound matmuls XLA
already schedules well.

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
    """``refs`` ends in the hidden tile alone (the primal) or in the
    hidden and both pre-activation tiles (the gradient's forward): the
    hidden is computed from the fp32 accumulators either way."""
    x_ref, wg_ref, bg_ref, wx_ref, bx_ref, outh_ref, *res_refs = refs
    xn = x_ref[...]
    g, t = _pre_acts(xn, wg_ref, bg_ref, wx_ref, bx_ref)
    outh_ref[...] = (g * jax.nn.sigmoid(g) * t).astype(outh_ref.dtype)
    if res_refs:
        g_ref, t_ref = res_refs
        g_ref[...] = g.astype(g_ref.dtype)
        t_ref[...] = t.astype(t_ref.dtype)


def _specs(E, bm, bf):
    """(x, weight, bias, hidden) block specs, shared by both kernels.
    Grid is (F//bf, M//bm) — j (hidden tile) OUTER, i (row tile) inner."""
    x_spec = pl.BlockSpec((bm, E), lambda j, i: (i, 0), memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((E, bf), lambda j, i: (0, j), memory_space=pltpu.VMEM)
    b_spec = pl.BlockSpec((1, bf), lambda j, i: (0, j), memory_space=pltpu.VMEM)
    h_spec = pl.BlockSpec((bm, bf), lambda j, i: (i, j), memory_space=pltpu.VMEM)
    return x_spec, w_spec, b_spec, h_spec


def _fwd_call(x2, wg, bg2, wx, bx2, *, block_m, block_f, interpret,
              residuals=False):
    """``h``, or ``(h, g, t)`` with ``residuals``: the pre-activations in
    ``h``'s dtype, tiles and index map."""
    M, E = x2.shape
    F = wg.shape[1]
    bm = pick_block(block_m, M)
    bf = pick_block(block_f, F)
    x_spec, w_spec, b_spec, h_spec = _specs(E, bm, bf)
    h_shape = jax.ShapeDtypeStruct((M, F), x2.dtype)
    return pl.pallas_call(
        _ffn_fwd_kernel,
        grid=(F // bf, M // bm),
        in_specs=[x_spec, w_spec, b_spec, w_spec, b_spec],
        out_shape=[h_shape] * 3 if residuals else h_shape,
        out_specs=[h_spec] * 3 if residuals else h_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        name=kernel_names.FUSED_FFN_FWD,
        interpret=interpret,
    )(x2, wg, bg2, wx, bx2)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _ffn_bwd_kernel(*refs):
    """Read the forward's saved g/t for one tile pair, emit the
    pre-activation cotangents over them (dg, dt — consumed by the XLA
    ``@ W^T`` contractions for dx), and accumulate fp32 dWg/dbg/dWx/dbx
    across the row grid while their column blocks are resident."""
    (x_ref, g_ref, t_ref, gh_ref,
     dg_ref, dt_ref, dwg_ref, dbg_ref, dwx_ref, dbx_ref) = refs
    xn = x_ref[...]
    i = pl.program_id(1)
    g = g_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
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


_SCOPED_VMEM_DEFAULT = 16 << 20  # what Mosaic grants a v5e kernel unasked
_BWD_VMEM_BUDGET = 64 << 20      # half of a v5e's VMEM


def _bwd_vmem(E, bm, bf, itemsize):
    """(x's, the weight gradients', the whole) VMEM footprint of the
    backward, as Mosaic asked for it over sixteen (E, dtype, tile) cases
    compiled for a v5e (PERF.md, PR 43) and a tenth: the x block in two
    buffers and turned for ``x^T dg``; dWg and dWx with their bias rows
    in two buffers and one product in flight; g, t, gh, dg, dt in two
    buffers and three fp32 (bm, bf) temporaries."""
    x = 4 * bm * E * itemsize
    grads = 5 * (E + 8) * bf * 4
    rest = 10 * bm * bf * itemsize + 3 * bm * bf * 4
    return x, grads, int(1.1 * (x + grads + rest))


def _bwd_tiles(E, itemsize):
    """(block_m, block_f) of the backward, from the widths alone. With no
    weight block in its VMEM the kernel affords a far larger tile than
    the forward's: a wider one re-reads ``x`` fewer times (``F / bf``
    passes), a taller one re-writes the resident weight-gradient blocks
    fewer times. 1024 x 1024 is the recipe's (E 768, bf16; sweep and cell
    runs on a v5e, PERF.md, PR 43); a wider ``E`` or float32 halves the
    side whose own block weighs more until the footprint fits the
    budget. ``pick_block`` then cuts both to divisors of the shape."""
    bm = bf = 1024
    while max(bm, bf) > 128:
        x, grads, whole = _bwd_vmem(E, bm, bf, itemsize)
        if whole <= _BWD_VMEM_BUDGET:
            break
        if grads > x and bf > 128:
            bf //= 2
        else:
            bm //= 2
    return bm, bf


def _bwd_call(x2, g, t, gh, *, interpret):
    M, E = x2.shape
    F = g.shape[1]
    itemsize = x2.dtype.itemsize
    block_m, block_f = _bwd_tiles(E, itemsize)
    bm = pick_block(block_m, M)
    bf = pick_block(block_f, F)
    x_spec, w_spec, b_spec, h_spec = _specs(E, bm, bf)
    # a limit, not an allocation; never under what a kernel gets unasked
    limit = max(_bwd_vmem(E, bm, bf, itemsize)[2], _SCOPED_VMEM_DEFAULT)
    return pl.pallas_call(
        _ffn_bwd_kernel,
        grid=(F // bf, M // bm),
        in_specs=[x_spec, h_spec, h_spec, h_spec],
        out_shape=[
            jax.ShapeDtypeStruct((M, F), x2.dtype),       # dg
            jax.ShapeDtypeStruct((M, F), x2.dtype),       # dt
            jax.ShapeDtypeStruct((E, F), jnp.float32),    # dWg
            jax.ShapeDtypeStruct((1, F), jnp.float32),    # dbg
            jax.ShapeDtypeStruct((E, F), jnp.float32),    # dWx
            jax.ShapeDtypeStruct((1, F), jnp.float32),    # dbx
        ],
        out_specs=[h_spec, h_spec, w_spec, b_spec, w_spec, b_spec],
        # dg over g, dt over t: a tile is read before it is written, so
        # the backward holds no second pair of (M, F) arrays
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=limit,
        ),
        name=kernel_names.FUSED_FFN_BWD,
        interpret=interpret,
    )(x2, g, t, gh)


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
    h, g, t = _fwd_call(
        x2, wg, bg2, wx, bx2,
        block_m=block_m, block_f=block_f, interpret=interpret,
        residuals=True,
    )
    return h, (x2, wg, wx, g, t)


def _swiglu2_bwd(block_m, block_f, interpret, res, gh):
    x2, wg, wx, g, t = res
    dg, dt, dwg, dbg, dwx, dbx = _bwd_call(x2, g, t, gh, interpret=interpret)
    dx = _dxn(dg, dt, wg, wx)
    # a bias arrives in its weight's dtype (fused_swiglu casts all four)
    return (dx, dwg.astype(wg.dtype), dbg.astype(wg.dtype),
            dwx.astype(wx.dtype), dbx.astype(wx.dtype))


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

"""Fused residual-add + LayerNorm — the block-boundary Pallas kernel.

Every transformer block boundary in this codebase is the same three-op
sequence: ``x = x + delta`` (residual add), then ``layer_norm(x)`` for
the next consumer. Un-fused, XLA runs it as three HBM round-trips over
the (B, T, E) activation — write the sum, read it back for the fp32
statistics, read it again for the normalize/affine pass (round-4/5
profiles: the ``add``/``reduce``/``multiply`` families around the
attention and FFN entry points). This kernel does all of it in ONE pass:
each (block_m, E) tile is loaded once, the residual sum is written back
for the carry, and the normalized output is produced from the same
VMEM-resident tile.

Numerics are EXACTLY :func:`ops.norms.layer_norm`'s: the add happens in
the stored dtype (the residual stream's compute dtype, matching
``x + delta`` at the XLA level), statistics are computed in float32 with
BIASED variance and ``eps`` inside the square root
(diff_transformer.py:17-19), the affine runs in float32 against the
fp32 scale/bias params, and only the final result is cast back. The
full-width reduction lives inside one tile (the last axis is never
split), so there is no cross-tile statistics plumbing.

Backward is a custom VJP with a single Pallas kernel: the standard
LayerNorm backward (recomputing statistics from the saved post-add
activation — cheaper than saving (M, 1) stats tensors with lane-width-1
layouts), the residual passthrough cotangent added in the same pass, and
the scale/bias gradients accumulated across the row grid in fp32.

``group_layer_norm`` is a full-width LayerNorm in this codebase
(ops/norms.py parity note), so the Group aliases are the same kernels.

Exports (all differentiable, interpret-mode on CPU like ops/flash.py):
  - ``fused_add_norm(x, delta, w, b)   -> (x + delta, LN(x + delta))``
  - ``fused_norm(x, w, b)              -> LN(x)``
  - ``fused_add_group_norm`` / ``fused_group_norm`` — the GLN aliases.
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


def _stats(xf: jnp.ndarray, eps: float):
    """fp32 mean / xhat for one (block_m, E) tile — layer_norm's exact
    formula: biased variance, eps inside the sqrt, division (not rsqrt,
    which differs in the last ulp)."""
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    c = xf - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    denom = jnp.sqrt(var + eps)
    return c / denom, denom


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _addnorm_fwd_kernel(*refs, eps: float, has_delta: bool):
    if has_delta:
        x_ref, d_ref, w_ref, b_ref, outx_ref, outn_ref = refs
        x = x_ref[...] + d_ref[...]  # stored dtype, like the XLA add
        outx_ref[...] = x
    else:
        x_ref, w_ref, b_ref, outn_ref = refs
        x = x_ref[...]
    xhat, _ = _stats(x.astype(jnp.float32), eps)
    outn_ref[...] = (xhat * w_ref[...] + b_ref[...]).astype(outn_ref.dtype)


def _fwd_call(x2, d2, w2, b2, *, eps, has_delta, block_m, interpret):
    M, E = x2.shape
    bm = pick_block(block_m, M)
    grid = (M // bm,)
    row_spec = pl.BlockSpec((bm, E), lambda i: (i, 0), memory_space=pltpu.VMEM)
    par_spec = pl.BlockSpec((1, E), lambda i: (0, 0), memory_space=pltpu.VMEM)
    in_specs = [row_spec] + ([row_spec] if has_delta else []) + [par_spec, par_spec]
    out_shapes = [jax.ShapeDtypeStruct((M, E), x2.dtype)]
    out_specs = [row_spec]
    if has_delta:
        out_shapes = [jax.ShapeDtypeStruct((M, E), x2.dtype)] + out_shapes
        out_specs = [row_spec] + out_specs
    inputs = (x2, d2, w2, b2) if has_delta else (x2, w2, b2)
    return pl.pallas_call(
        functools.partial(_addnorm_fwd_kernel, eps=eps, has_delta=has_delta),
        grid=grid,
        in_specs=in_specs,
        out_shape=out_shapes,
        out_specs=out_specs,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name=kernel_names.FUSED_ADD_NORM_FWD,
        interpret=interpret,
    )(*inputs)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _addnorm_bwd_kernel(*refs, eps: float, has_gx: bool):
    """dx for one tile + fp32 dw/db partials accumulated across the grid.

    ``x_ref`` holds the POST-add activation (the forward's carry output),
    so statistics recompute is one VPU pass over the already-resident
    tile. With the residual carry cotangent ``gx`` present, the add's
    passthrough is summed in the same pass (d/dx and d/ddelta are the
    same array; the wrapper returns it for both).
    """
    if has_gx:
        x_ref, w_ref, gn_ref, gx_ref, dx_ref, dw_ref, db_ref = refs
    else:
        x_ref, w_ref, gn_ref, dx_ref, dw_ref, db_ref = refs
    i = pl.program_id(0)
    xhat, denom = _stats(x_ref[...].astype(jnp.float32), eps)
    gn = gn_ref[...].astype(jnp.float32)
    dxh = gn * w_ref[...]  # (bm, E) fp32
    m1 = jnp.mean(dxh, axis=-1, keepdims=True)
    m2 = jnp.mean(dxh * xhat, axis=-1, keepdims=True)
    dx = (dxh - m1 - xhat * m2) / denom
    if has_gx:
        dx = dx + gx_ref[...].astype(jnp.float32)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    pw = jnp.sum(gn * xhat, axis=0, keepdims=True)  # (1, E) fp32
    pb = jnp.sum(gn, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = pw
        db_ref[...] = pb

    @pl.when(i > 0)
    def _acc():
        dw_ref[...] += pw
        db_ref[...] += pb


def _bwd_call(x2, w2, gn2, gx2, *, eps, block_m, interpret):
    M, E = x2.shape
    has_gx = gx2 is not None
    bm = pick_block(block_m, M)
    grid = (M // bm,)
    row_spec = pl.BlockSpec((bm, E), lambda i: (i, 0), memory_space=pltpu.VMEM)
    par_spec = pl.BlockSpec((1, E), lambda i: (0, 0), memory_space=pltpu.VMEM)
    in_specs = [row_spec, par_spec, row_spec] + ([row_spec] if has_gx else [])
    inputs = (x2, w2, gn2) + ((gx2,) if has_gx else ())
    return pl.pallas_call(
        functools.partial(_addnorm_bwd_kernel, eps=eps, has_gx=has_gx),
        grid=grid,
        in_specs=in_specs,
        out_shape=[
            jax.ShapeDtypeStruct((M, E), x2.dtype),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
        ],
        out_specs=[row_spec, par_spec, par_spec],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name=kernel_names.FUSED_ADD_NORM_BWD,
        interpret=interpret,
    )(*inputs)


# ---------------------------------------------------------------------------
# custom_vjp wrappers (2D, (M, E)) — the public API reshapes
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _add_norm2(x2, d2, w2, b2, eps, block_m, interpret):
    return _fwd_call(
        x2, d2, w2, b2, eps=eps, has_delta=True, block_m=block_m,
        interpret=interpret,
    )


def _add_norm2_fwd(x2, d2, w2, b2, eps, block_m, interpret):
    xnew, normed = _add_norm2(x2, d2, w2, b2, eps, block_m, interpret)
    return (xnew, normed), (xnew, w2)


def _add_norm2_bwd(eps, block_m, interpret, res, ct):
    xnew, w2 = res
    gx, gn = ct
    dx, dw, db = _bwd_call(
        xnew, w2, gn, gx, eps=eps, block_m=block_m, interpret=interpret
    )
    # x and delta enter only through their sum: one cotangent serves both
    return dx, dx, dw, db


_add_norm2.defvjp(_add_norm2_fwd, _add_norm2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm2(x2, w2, b2, eps, block_m, interpret):
    return _fwd_call(
        x2, None, w2, b2, eps=eps, has_delta=False, block_m=block_m,
        interpret=interpret,
    )[0]


def _norm2_fwd(x2, w2, b2, eps, block_m, interpret):
    return _norm2(x2, w2, b2, eps, block_m, interpret), (x2, w2)


def _norm2_bwd(eps, block_m, interpret, res, gn):
    x2, w2 = res
    dx, dw, db = _bwd_call(
        x2, w2, gn, None, eps=eps, block_m=block_m, interpret=interpret
    )
    return dx, dw, db


_norm2.defvjp(_norm2_fwd, _norm2_bwd)


def _flatten(x: jnp.ndarray):
    E = x.shape[-1]
    return x.reshape(-1, E), x.shape


def fused_add_norm(
    x: jnp.ndarray,
    delta: jnp.ndarray,
    weight: jnp.ndarray,
    bias: jnp.ndarray,
    eps: float = 1e-5,
    *,
    block_m: int = _DEFAULT_BLOCK_M,
    interpret: Optional[bool] = None,
):
    """``(x + delta, layer_norm(x + delta, weight, bias))`` in one fused
    pass. ``x``/``delta``: (..., E) in the compute dtype; ``weight``/
    ``bias``: (E,) float32 (the LN params are never downcast, matching
    ops/norms.py). Differentiable via the fused backward kernel."""
    if interpret is None:
        interpret = auto_interpret()
    x2, shape = _flatten(x)
    d2, _ = _flatten(delta)
    w2 = weight.astype(jnp.float32).reshape(1, -1)
    b2 = bias.astype(jnp.float32).reshape(1, -1)
    xnew, normed = _add_norm2(x2, d2, w2, b2, float(eps), block_m, interpret)
    return xnew.reshape(shape), normed.reshape(shape)


def fused_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    bias: jnp.ndarray,
    eps: float = 1e-5,
    *,
    block_m: int = _DEFAULT_BLOCK_M,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Single-pass :func:`ops.norms.layer_norm` (no residual input)."""
    if interpret is None:
        interpret = auto_interpret()
    x2, shape = _flatten(x)
    w2 = weight.astype(jnp.float32).reshape(1, -1)
    b2 = bias.astype(jnp.float32).reshape(1, -1)
    return _norm2(x2, w2, b2, float(eps), block_m, interpret).reshape(shape)


# The reference's GroupLayerNorm IS a full-width LayerNorm (ops/norms.py
# parity note) — same kernels, alias kept so call sites document which
# reference module they replicate.
fused_add_group_norm = fused_add_norm
fused_group_norm = fused_norm

"""One decode step's K/V rows, written into the slot pool IN PLACE.

A decode step writes one position of every slot's ring: for K
``S*H`` vectors of ``d`` values a slot, out of a pool leaf of
``S*B*H*M*d``. XLA cannot do that in place on the chip: the pool's
on-device layout is decided by its shape, and for the recipe's
``(.., M=512, d=96)`` the chip puts M on the lanes and d on the
sublanes (``{3,4,2,1,0:T(8,128)(2,1)}``: 96 would waste a quarter of
every 128-lane tile), while its scatter and dynamic-update-slice want d
on the lanes — so every XLA formulation (a scatter, the vmapped
``dynamic_update_slice``, a loop of slices) copies the whole pool into
another layout, writes there, and copies it back (my compiles for the
described v5e, PR 25). This kernel takes the leaf as the chip holds it
(``input_output_aliases``: the pool is the donated operand, no second
pool exists), and one grid step a slot reads the smallest aligned block
that holds the position (128 lanes of the ring, or one packed sublane
tile of it), replaces one lane (sublane) of it and writes it back. The
slots ride as scalar prefetch and pick the block; a slot whose row is to
be kept (``targets < 0``: a free or mid-prefill slot) writes back what
it read.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from differential_transformer_replication_tpu import kernel_names
from differential_transformer_replication_tpu.ops.flash import auto_interpret

_LANES = 128


def position_on_lanes(M: int, features: int) -> bool:
    """Whether the chip holds a ``(.., M, features)`` leaf with the ring
    on the lanes: it does when the ring fills whole 128-lane tiles and
    the features do not (the recipe's 512 x 96 and 512 x 192; a head of
    128 keeps the row-major layout). A wrong guess costs a copy of the
    pool, never a wrong value; ``tests/test_tpu_compile.py`` pins the
    recipe's shapes."""
    return M % _LANES == 0 and features % _LANES != 0


def _write_kernel(targets_ref, vals_ref, leaf_ref, out_ref, *, block: int,
                  on_lanes: bool):
    """``leaf_ref`` (A, 1, G, R, C): slot ``b``'s aligned block around
    its position; ``vals_ref`` (1, R, A*G) columns (ring on the lanes) or
    (1, A*G, C) rows (ring on the sublanes), in the 32-bit type the
    select runs in. The select is an iota compare, so nothing indexes a
    packed dtype at a dynamic offset."""
    # a kept slot (-1) matches no position: rem of a negative stays
    # negative
    offset = jax.lax.rem(targets_ref[pl.program_id(0)], block)
    A, _, G = leaf_ref.shape[:3]
    for a in range(A):
        for g in range(G):
            j = a * G + g
            old = leaf_ref[a, 0, g]
            hit = jax.lax.broadcasted_iota(
                jnp.int32, old.shape, 1 if on_lanes else 0
            ) == offset
            new = (vals_ref[0, :, j:j + 1] if on_lanes
                   else vals_ref[0, j:j + 1, :])
            out_ref[a, 0, g] = jnp.where(
                hit, new, old.astype(vals_ref.dtype)
            ).astype(old.dtype)


def write_rows(leaf: jnp.ndarray, rows: jnp.ndarray, targets: jnp.ndarray,
               batch_axis: int, interpret=None) -> jnp.ndarray:
    """``leaf`` with ``rows[.., b, ..]`` at ring position ``targets[b]``
    of slot ``b``, for every ``b`` with ``targets[b] >= 0``; the other
    slots keep every value. ``leaf`` is a cache leaf of
    models/decode.py:init_cache with its pool axis at ``batch_axis``:
    K ``(S, B, H, M, d)``, V ``(B, H, M, dv)``, or a scale plane
    ``(S, B, H, M)`` / ``(B, H, M)``; ``rows`` is the leaf without its M
    axis, in the leaf's dtype. The result aliases ``leaf``: under a jit
    that donates the pool nothing of the pool's size is allocated."""
    if interpret is None:
        interpret = auto_interpret()
    A = math.prod(leaf.shape[:batch_axis])
    B, H, M = leaf.shape[batch_axis:batch_axis + 3]
    wide = jnp.int32 if jnp.issubdtype(leaf.dtype, jnp.integer) else jnp.float32
    swapped = False
    if leaf.ndim == batch_axis + 4:  # K, V: (.., B, H, M, features)
        F, G = leaf.shape[-1], H
        on_lanes = swapped = position_on_lanes(M, F)
        # the chip's own layout of the leaf, as a row-major view
        view = (jnp.swapaxes(leaf, -1, -2) if swapped else leaf).reshape(
            (A, B, G) + ((F, M) if swapped else (M, F))
        )
    else:  # a scale plane: the heads are the rows, the ring the lanes
        F, G, on_lanes = H, 1, True
        view = leaf.reshape(A, B, 1, H, M)
    vals = rows.reshape(A, B, G, F)
    # (A, B, G, F) -> one (F, A*G) or (A*G, F) matrix a slot
    vals = (vals.transpose(1, 3, 0, 2) if on_lanes
            else vals.transpose(1, 0, 2, 3)).astype(wide)
    vals = vals.reshape((B, F, A * G) if on_lanes else (B, A * G, F))
    if on_lanes:
        block = _LANES if M % _LANES == 0 else M
        leaf_block = (A, 1, G, F, block)
    else:
        packed = 8 * 4 // leaf.dtype.itemsize  # positions a sublane tile
        block = packed if M % packed == 0 else M
        leaf_block = (A, 1, G, block, F)

    def leaf_index(b, targets_ref):
        tile = jnp.maximum(targets_ref[b], 0) // block
        return (0, b, 0, 0, tile) if on_lanes else (0, b, 0, tile, 0)

    spec = pl.BlockSpec(leaf_block, leaf_index, memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_write_kernel, block=block, on_lanes=on_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1,) + vals.shape[1:],
                             lambda b, targets_ref: (b, 0, 0),
                             memory_space=pltpu.VMEM),
                spec,
            ],
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        # operands: targets, vals, view -> the pool is updated in place
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name=kernel_names.KV_ROW_WRITE,
        interpret=interpret,
    )(jnp.asarray(targets, jnp.int32), vals, view)
    if swapped:
        return jnp.swapaxes(out.reshape(leaf.shape[:-2] + (F, M)), -1, -2)
    return out.reshape(leaf.shape)

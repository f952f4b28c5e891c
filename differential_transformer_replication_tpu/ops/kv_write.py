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
pool exists), and the grid step of a slot that writes reads the smallest
aligned block that holds the position (128 lanes of the ring, or one
packed sublane tile of it), replaces one lane (sublane) of it and writes
it back. The slots ride as scalar prefetch and pick the block.

A slot whose row is to be kept (``targets < 0``: a free or mid-prefill
slot) moves NOTHING (PR 31; until then it wrote back what it read, and at
the chat cell's 12% occupancy seven eighths of the kernel's traffic
changed no value). The grid stays one step a slot, so the program's
shapes do not depend on which slots are live; what a kept step does is
point at the block of its OWNER (:func:`slot_owners`: the nearest slot
before it that writes). The chip's pipeline fetches an input block only
when its index differs from the previous step's and writes an output
block back only when the next step's differs, so a run of kept steps
behind a writer costs no traffic: the writer's result stays in VMEM and
goes out once, when the next writer's step (or the grid's end) comes. A
kept step therefore must not touch ``out_ref``: its input buffer still
holds the block as it was BEFORE its owner's write, and copying it would
undo that write. The interpreter keeps no buffer between grid steps and
cannot see any of this; ``chip_smoke.py --phase kv_write`` runs the
kernel compiled on the chip against NumPy.
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


def slot_owners(targets: jnp.ndarray) -> jnp.ndarray:
    """(B,) int32: for every slot the slot whose block its grid step
    points at: itself if it writes (``targets[b] >= 0``), else the
    nearest slot before it that does; the slots before the first writer
    take the first writer (they ride on the fetch it needs anyway), and
    with no writer at all every slot takes slot 0. With every slot
    writing this is the identity, and the kernel moves what it always
    moved. B-sized work, no pool in it."""
    slots = jnp.arange(targets.shape[0], dtype=jnp.int32)
    writes = targets >= 0
    behind = jax.lax.cummax(jnp.where(writes, slots, -1), axis=0)
    first = jnp.argmax(writes).astype(jnp.int32)  # 0 when none writes
    return jnp.where(behind < 0, first, behind)


def _write_kernel(targets_ref, owner_ref, tile_ref, vals_ref, leaf_ref,
                  out_ref, *, block: int, on_lanes: bool):
    """``leaf_ref`` (A, 1, G, R, C): the aligned block around the position
    of slot ``b``'s owner; ``vals_ref`` (1, R, A*G) columns (ring on the
    lanes) or (1, A*G, C) rows (ring on the sublanes) of that owner, in
    the 32-bit type the select runs in. The select is an iota compare, so
    nothing indexes a packed dtype at a dynamic offset.

    Only a step whose slot writes runs the body. A kept step leaves
    ``out_ref`` alone: the buffer is its owner's result, still resident
    and not yet written back, while ``leaf_ref`` is the block as it was
    fetched before that write. Step 0 is the exception, for it has no
    step before it: kept, it copies the block through (its ``-1``
    matches no position), so that the one block a grid without any
    writer points at goes back as it came."""
    b = pl.program_id(0)
    del owner_ref, tile_ref  # the index maps' alone

    @pl.when((targets_ref[b] >= 0) | (b == 0))
    def _():
        # rem of a negative stays negative
        offset = jax.lax.rem(targets_ref[b], block)
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
    slots keep every value and cost no traffic (the module's docstring).
    ``leaf`` is a cache leaf of
    models/decode.py:init_cache with its pool axis at ``batch_axis``:
    K ``(S, B, H, M, d)``, V ``(B, H, M, dv)``, or a scale plane
    ``(S, B, H, M)`` / ``(B, H, M)``; ``rows`` is the leaf without its M
    axis, in the leaf's dtype. The result aliases ``leaf``: under a jit
    that donates the pool nothing of the pool's size is allocated."""
    if interpret is None:
        interpret = auto_interpret()
    targets = jnp.asarray(targets, jnp.int32)
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

    # books with ``_write_targets``: what a step decides about keeping.
    # The block a grid step points at, as two vectors the index maps
    # only load from: arithmetic there sits between the kernel's DMAs
    # (the owner's target looked up inside the map cost the dense case
    # 1-4%, my chip runs, PR 31)
    with jax.named_scope("kv_merge"):
        owner = slot_owners(targets)
        tiles = jnp.maximum(targets[owner], 0) // block

    def leaf_index(b, targets_ref, owner_ref, tile_ref):
        slot, tile = owner_ref[b], tile_ref[b]
        return (0, slot, 0, 0, tile) if on_lanes else (0, slot, 0, tile, 0)

    spec = pl.BlockSpec(leaf_block, leaf_index, memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_write_kernel, block=block, on_lanes=on_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1,) + vals.shape[1:],
                             lambda b, targets_ref, owner_ref, tile_ref: (
                                 owner_ref[b], 0, 0),
                             memory_space=pltpu.VMEM),
                spec,
            ],
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        # operands: targets, owner, tiles, vals, view -> the pool is
        # updated in place
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name=kernel_names.KV_ROW_WRITE,
        interpret=interpret,
    )(targets, owner, tiles, vals, view)
    if swapped:
        return jnp.swapaxes(out.reshape(leaf.shape[:-2] + (F, M)), -1, -2)
    return out.reshape(leaf.shape)

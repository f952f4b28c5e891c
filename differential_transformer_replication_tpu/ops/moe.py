"""A layer of routed experts, of which this program may hold a share.

:func:`route` ranks ALL ``num_experts`` experts a token (a sigmoid router
with a correction bias that only moves the ranking, weights renormalised
over the chosen and scaled); :func:`route_grouped` is the softmax router
that keeps a token inside a few groups of experts. :func:`experts` adds the terms of the HELD
experts ``[lo, hi)`` only: the (token, expert) assignments are sorted by
expert and the held ones multiplied in groups, a group a held expert; no
token is dropped and there is no capacity. An assignment that fell on an
absent expert, or came from a row that is not live, is sorted behind the
last group and adds nothing. What the absent experts would add is left
out; no code stands in for the chip that holds them.

The grouped product (``moe_grouped_matmul``) is a Pallas kernel over a
PADDED grouping: every group starts on a whole tile of ``tm`` rows, so a
row tile belongs to one expert and a grid step is one plain matrix product
of its rows with that expert's weights (the tile's expert rides as scalar
prefetch and picks the weight block). The weights are what a decode step
pays for (7 MB an expert and 3-4 rows each in the kimi_linear cell, 19 MB
in deepseek_v2's, 11 MB in a latent in nemotron_h's, 18.9 MB in lfm2's):
consecutive tiles of one expert keep its block, an expert nobody chose is
never fetched, and the tiles behind the last group point at the last block
and cost no traffic. Where the program holds EVERY expert (lfm2's cell: 64
of 64) no assignment is sorted behind the groups, the load sums to rows x
``experts_per_token``, and a decode step over many rows reads nearly every
expert of a layer. ``tm`` follows the rows an expert can expect, 16 in a
decode step (64 at a prompt chunk of 1,024 on 64 held experts); XLA's own
grouped product (``jax.lax.ragged_dot``) tiles 512 rows whatever the
groups hold and took 3.0 times as long at the decode step's shapes (my
chip run, PR 32).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from differential_transformer_replication_tpu import kernel_names
from differential_transformer_replication_tpu.ops.flash import auto_interpret

_HIGHEST = jax.lax.Precision.HIGHEST
_WEIGHT_BLOCK_BYTES = 5 << 20  # an expert's (K, tn) block; two are in flight


def route(h: jnp.ndarray, w: jnp.ndarray, bias: jnp.ndarray, k: int,
          scaling: float, eps: float = 0.0
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``h`` (T, E) -> ``(experts (T, k) int32, weights (T, k) float32)``.
    The scores are float32 under the highest precision: the ranking
    decides which weights a token meets, and a rounded score flips it.
    ``eps`` (static) is what a published router adds to the chosen scores'
    sum before it divides (the lfm2 family's 1e-6; the others' add
    nothing, and at 0 nothing is added here)."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.dot(h.astype(f32), w.astype(f32),
                                    precision=_HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias.astype(f32), k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    if eps:
        total = total + eps
    return chosen.astype(jnp.int32), picked / total * scaling


def route_grouped(h: jnp.ndarray, w: jnp.ndarray, k: int, scaling: float,
                  n_group: int, topk_group: int):
    """:func:`route`'s sibling for a router limited to groups (the
    deepseek_v2 family: ``topk_method: group_limited_greedy``, a group the
    experts of one device). ``h`` (T, E) -> ``(experts (T, k) int32,
    weights (T, k) float32, kept (T, n_group) bool)``: the scores are a
    SOFTMAX over all experts; expert ``e`` lies in group ``e // (N /
    n_group)``; a group scores as its best expert, a token keeps the
    ``topk_group`` best groups and the ``k`` best experts inside them
    (ties go to the lower index, as ``top_k`` breaks them), each weighted
    ``scaling`` times its probability: nothing is renormalised. Float32
    under the highest precision, for :func:`route`'s reason."""
    f32 = jnp.float32
    scores = jax.nn.softmax(jnp.dot(h.astype(f32), w.astype(f32),
                                    precision=_HIGHEST), axis=-1)
    T, N = scores.shape
    best = jnp.max(scores.reshape(T, n_group, N // n_group), axis=-1)
    _, groups = jax.lax.top_k(best, topk_group)
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], groups].set(True)
    # a probability is positive: an expert outside the kept groups, at -1,
    # ranks behind every expert inside them
    inside = jnp.repeat(kept, N // n_group, axis=-1)
    picked, chosen = jax.lax.top_k(jnp.where(inside, scores, -1.0), k)
    return chosen.astype(jnp.int32), picked * scaling, kept


def _row_tile(assignments: int, groups: int) -> int:
    """Rows a tile: the power of two from 16 (a packed bfloat16 tile's
    sublanes) to 128 next above the rows a group holds if all fall on
    held experts evenly."""
    return min(128, max(16, pl.next_power_of_2(-(-assignments // groups))))


def _col_tile(K: int, N: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``N`` and keeps a (K, tn)
    weight block under ``_WEIGHT_BLOCK_BYTES`` (``N`` itself if none)."""
    fits = [tn for tn in range(128, N + 1, 128)
            if N % tn == 0 and K * tn * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits) if fits else N


def _matmul_kernel(group_ref, used_ref, x_ref, w_ref, o_ref):
    del group_ref  # the index maps' alone

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, tile_group: jnp.ndarray,
                   used: jnp.ndarray, tm: int, out_dtype=None,
                   interpret=None) -> jnp.ndarray:
    """``x`` (M, K), tiles of ``tm`` rows of which tile ``t < used[0]``
    holds rows of group ``tile_group[t]`` only, times ``w`` (G, K, N):
    (M, N). Tiles from ``used`` on are not computed and their rows are
    whatever the buffer held."""
    if interpret is None:
        interpret = auto_interpret()
    M, K = x.shape
    G, _, N = w.shape
    tn = _col_tile(K, N, w.dtype.itemsize)

    def tile(t, used_ref):
        return jnp.minimum(t, jnp.maximum(used_ref[0] - 1, 0))

    return pl.pallas_call(
        _matmul_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # row tiles innermost: one expert's tiles follow each other
            # and keep its weight block
            grid=(N // tn, M // tm),
            in_specs=[
                pl.BlockSpec((tm, K), lambda j, t, g, u: (tile(t, u), 0)),
                pl.BlockSpec((1, K, tn),
                             lambda j, t, g, u: (g[tile(t, u)], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, t, g, u: (tile(t, u), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20,
        ),
        name=kernel_names.MOE_GROUPED_MATMUL,
        interpret=interpret,
    )(tile_group, used, x, w)


def experts(h: jnp.ndarray, chosen: jnp.ndarray, weights: jnp.ndarray,
            p: dict, lo: int, live: Optional[jnp.ndarray] = None):
    """``h`` (T, E); ``chosen``, ``weights`` (T, k) from :func:`route`;
    ``p`` the held experts' weights for the experts ``lo .. lo + G - 1``:
    ``gate_up`` (G, E, 2 F) and ``down`` (G, F, E), an expert
    ``W_down(silu(gate) * up)``, or ``up`` (G, E, F) in ``gate_up``'s
    place, an UNGATED expert ``W_down relu(W_up h)^2`` (the nemotron_h
    family's, where ``E`` is the latent's width); ``live`` (T,) bool,
    the rows that are a sequence's (None = all). Returns ``(y (T, E) in
    h's dtype, load (G,) int32)``: the held experts' weighted sum a row,
    and how many assignments fell on each held expert."""
    T, k = chosen.shape
    gated = "gate_up" in p
    w_in = p["gate_up"] if gated else p["up"]
    G, E, F2 = w_in.shape
    tm = _row_tile(T * k, G)
    local = chosen - lo
    held = (local >= 0) & (local < G)
    if live is not None:
        held = held & live[:, None]
    group = jnp.where(held, local, G).reshape(-1)  # G: behind every group
    load = jnp.zeros((G + 1,), jnp.int32).at[group].add(1)[:G]
    # every group on whole tiles: where its rows start, padded and not
    padded = -(-load // tm) * tm
    ends = jnp.cumsum(padded)
    starts = jnp.append(ends - padded, 0)  # [G]: unused
    first = jnp.append(jnp.cumsum(load) - load, 0)
    order = jnp.argsort(group, stable=True)
    rank = jnp.arange(T * k) - first[group[order]]
    tiles = -(-(T * k + G * (tm - 1)) // tm)
    # the padded row of every assignment, by its place in `chosen`; an
    # assignment that is not held points behind the last tile
    where = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.where(group[order] < G, starts[group[order]] + rank, tiles * tm))
    source = jnp.full((tiles * tm,), T, jnp.int32).at[where].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    rows = jnp.concatenate([h, jnp.zeros((1, E), h.dtype)])[source]
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles) * tm, side="right"),
        G - 1).astype(jnp.int32)
    used = (ends[-1:] // tm).astype(jnp.int32)
    gu = grouped_matmul(rows, w_in.astype(h.dtype), tile_group, used, tm)
    if gated:
        act = jax.nn.silu(gu[:, :F2 // 2]) * gu[:, F2 // 2:]
    else:
        act = jnp.square(jax.nn.relu(gu))
    out = grouped_matmul(act, p["down"].astype(h.dtype), tile_group, used,
                         tm, jnp.float32)
    # a row no tile computed is no product of anything: select, not scale
    terms = jnp.where(held[..., None],
                      out[jnp.minimum(where, tiles * tm - 1)].reshape(T, k, E),
                      0.0)
    return jnp.einsum("tk,tke->te", weights, terms).astype(h.dtype), load

"""Rotary position embeddings.

Real-arithmetic equivalent of the reference's complex-number formulation
(control.py:4-22, duplicated Ndiff_transformer.py:4-22): the reference packs
consecutive feature pairs ``(x[2i], x[2i+1])`` into complex numbers and
multiplies by ``exp(i * t * theta_j)``. Here we keep everything real (TPUs
have no complex MXU path): split even/odd lanes, rotate, re-interleave.

Parity notes:
  - frequencies: ``1 / theta**(2j/d)`` for ``j in [0, d/2)`` (control.py:6),
  - the rotation is computed in float32 and cast back to the input dtype,
    matching the reference's explicit upcast (control.py:17,22),
  - the table is truncated to the actual sequence length at apply time
    (control.py:18).

Two pairings of one rotation. :func:`apply_rope` pairs feature ``2i``
with ``2i + 1`` as the reference does: the dense reference, the prefill
chunk and the decode step use it (layout rule in its docstring), and
parameters, checkpoints and the K ring keep that published order. A stride
of two along the LANES is the one thing the chip's layout cannot slice (the
compiler turns it into a gather that puts the feature first and float32
transposes of the whole activation: a sixth of the control recipe's step,
PERF.md section 6, PR 37). So the kernel path
(``models/common.py:flash_bh_fn``) re-orders the columns of ``Wq`` and
``Wk`` inside each head by :func:`half_split` before projecting, which
puts the pair at ``(i, i + d/2)``, and turns the two contiguous halves:
the token-major kernels on the tile in VMEM (``ops/flash.py:_tm_turn``,
from these tables), the head-major branch in HBM with
:func:`apply_rope_halves`. The same angles, the same float32 arithmetic,
the same cast back, and ``q . k`` is the same sum in another order.
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
import numpy as np


def rope_cos_sin(head_dim: int, max_seq_len: int, theta: float = 10000.0):
    """Precompute the (cos, sin) tables, each of shape ``(max_seq_len, head_dim // 2)``.

    Equivalent to the modulus/argument of ``precompute_freqs_cis``
    (control.py:4-9): ``torch.polar(ones, outer(t, freqs))`` has
    ``cos(t * f_j) + i sin(t * f_j)`` entries.
    """
    j = jnp.arange(0, head_dim, 2, dtype=jnp.float32)[: head_dim // 2]
    freqs = 1.0 / (theta ** (j / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)  # (T, d/2)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    headed: bool | None = None,
) -> jnp.ndarray:
    """Rotate ``x``.

    Layout rule (when ``headed`` is None): ndim >= 4 means the merged-head
    layout ``(..., T, H, d)`` (tables broadcast over the head axis); ndim <=
    3 means ``(..., T, d)``, the reference's per-head layout
    (control.py:11-22). Pass ``headed`` explicitly for ambiguous ranks
    (an unbatched ``(T, H, d)`` is rank 3 and would otherwise be rotated by
    head index).

    ``cos``/``sin`` have shape ``(>=T, d//2)`` and are truncated to T
    (control.py:18). Pairing is over consecutive features, matching
    ``x.reshape(*, -1, 2)`` + ``view_as_complex`` (control.py:17): the even
    lane is the real part, the odd lane the imaginary part.
    """
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    x_even = xf[..., 0::2]
    x_odd = xf[..., 1::2]

    if headed is None:
        headed = x.ndim >= 4
    if headed:
        # (..., T, H, d): broadcast tables over the head axis.
        seq_len = x.shape[-3]
        c = cos[:seq_len][:, None, :]
        s = sin[:seq_len][:, None, :]
    else:
        seq_len = x.shape[-2]
        c = cos[:seq_len]
        s = sin[:seq_len]

    rot_even = x_even * c - x_odd * s
    rot_odd = x_even * s + x_odd * c
    out = jnp.stack([rot_even, rot_odd], axis=-1).reshape(x.shape)
    return out.astype(orig_dtype)


def half_split(w: jnp.ndarray) -> jnp.ndarray:
    """Re-order the last axis ``[0, 1, 2, ...]`` to ``[0, 2, ..., d-2, 1,
    3, ..., d-1]``: the reference's pair ``(2i, 2i + 1)`` moves to ``(i,
    i + d/2)``. Written as a transpose of the ``(d/2, 2)`` view, which the
    chip does in place of the gather that indexing would be."""
    d = w.shape[-1]
    return jnp.swapaxes(w.reshape(*w.shape[:-1], d // 2, 2), -1, -2).reshape(
        w.shape
    )


def _turn_halves(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """Dimension i of ``x`` (..., d) turns with i + d/2 by the angle whose
    ``cos``/``sin`` (float32) broadcast against a half of ``x``: float32
    inside, cast back to ``x``'s dtype."""
    h = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    a, b = xf[..., :h], xf[..., h:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def apply_rope_halves(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """:func:`apply_rope` for an ``x`` (..., T, d) whose features stand in
    :func:`half_split`'s order: the same tables, truncated to T the
    same way, turn dimension i with i + d/2."""
    seq_len = x.shape[-2]
    return _turn_halves(x, cos[:seq_len], sin[:seq_len])


def apply_rope_half(x: jnp.ndarray, pos: jnp.ndarray,
                    theta: float = 10000.0) -> jnp.ndarray:
    """Rotate ``x`` (..., d) at the absolute positions ``pos``, which
    broadcast against ``x``'s leading axes, in the ``rotate_half`` pairing
    (dimension i turns with i + d/2, as ``transformers`` rotates; the
    reference families' :func:`apply_rope` pairs 2i with 2i + 1): the
    ``afmoe`` family's sliding layers. The angles come from the positions
    themselves, in float32, so no table bounds a sequence; the result is
    cast back to ``x``'s dtype."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.asarray(pos, jnp.float32)[..., None] * freqs
    return _turn_halves(x, jnp.cos(angles), jnp.sin(angles))


# -- YaRN (the deepseek_v2 family's rotary key part) ---------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term ``0.1 mscale ln(factor) + 1`` (1
    for a factor of at most 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(d: int, theta: float, beta_fast: float,
                          beta_slow: float, original_max: int):
    """``(low, high)``: the frequency indices between which YaRN blends
    the scaled and the plain frequency. ``d(r) = d ln(original_max / (2 pi
    r)) / (2 ln theta)`` is the index whose wave turns ``r`` times over
    the trained length; ``low = floor(d(beta_fast))``, ``high =
    ceil(d(beta_slow))``, both held to ``[0, d - 1]``."""
    at = lambda r: d * math.log(original_max / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(theta))
    return (max(math.floor(at(beta_fast)), 0),
            min(math.ceil(at(beta_slow)), d - 1))


def yarn_frequencies(d: int, theta: float, scaling: Optional[dict]):
    """``(f (d/2,) float32 NumPy, cos/sin multiplier)``: the angle of pair
    ``i`` at position ``t`` is ``t f_i``. Without ``scaling`` the plain
    ``theta ** (-2i/d)`` and 1. With a YaRN block (``factor``,
    ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``,
    ``original_max_position_embeddings``) a pair below ``low`` keeps its
    plain frequency (its wave turns often inside the trained length), one
    above ``high`` is slowed by ``factor``, and those between blend
    linearly: ``f_i = (1 - g_i) plain_i / factor + g_i plain_i``, ``g_i =
    1 - clip((i - low) / (high - low), 0, 1)``. The multiplier is
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``.
    Static arithmetic on the sizes: constants of the program."""
    plain = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if not scaling:
        return plain.astype(np.float32), 1.0
    low, high = yarn_correction_range(
        d, theta, scaling["beta_fast"], scaling["beta_slow"],
        scaling["original_max_position_embeddings"])
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    f = ramp * plain / scaling["factor"] + (1.0 - ramp) * plain
    mult = (yarn_mscale(scaling["factor"], scaling["mscale"])
            / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
    return f.astype(np.float32), mult


def apply_rope_pairs_at(x: jnp.ndarray, pos: jnp.ndarray, freqs,
                        mult: float = 1.0) -> jnp.ndarray:
    """Rotate ``x`` (..., d), whose features stand in the PUBLISHED order
    (dimension 2i turns with 2i + 1), at the absolute positions ``pos``,
    which broadcast against ``x``'s leading axes, by the angles ``pos *
    freqs`` (:func:`yarn_frequencies`); cos and sin times ``mult``. The
    result stands in :func:`half_split`'s order (pair i at ``(i, i +
    d/2)``): the pair is moved once, by a transpose, and the rotation
    turns contiguous halves (the module docstring says what a stride of
    two along the lanes costs the chip). A query and a key that both went
    through here meet dimension for dimension, so ``q . k`` is the
    published sum in another order; nothing else may read the result
    feature by feature. The angles come from the positions themselves, in
    float32, so no table bounds a sequence."""
    angles = jnp.asarray(pos, jnp.float32)[..., None] * jnp.asarray(freqs)
    return _turn_halves(half_split(x), jnp.cos(angles) * mult,
                        jnp.sin(angles) * mult)

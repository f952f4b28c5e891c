"""The Mamba-2 (SSD) mixer's sequence operators (the ``nemotron_h`` family):

    H_t,p = exp(dt_t,p A_p) H_t-1,p + dt_t,p x_t,p (x) B_t,g(p)
    y_t,p = H_t,p C_t,g(p) + D_p x_t,p

a head ``p`` of ``P`` channels with ONE decay ``A_p`` and a state of
``(P, N)`` values; ``B_t`` and ``C_t`` (``N`` values) are shared by the
heads of a group ``g(p) = p // (heads / groups)``. Beside ``ops/ssm.py``
(Mamba-1: a decay a (channel, state), one ``B``/``C`` for every channel,
``N = 16``), whose ``causal_conv`` the mixer shares.

- :func:`chunk_scan` over a prompt chunk of L tokens from an initial state
  to the last one, the recurrence as matrix products over sub-chunks of
  ``Q = chunk_size`` tokens (the state-space duality): inside a sub-chunk
  ``Y = ((C B^T) . L)(dt . X)`` with ``L_ts = exp(sum_{s<r<=t} a_r)``,
  between sub-chunks the state is handed on, ``H' = exp(sum a) H +
  sum_s exp(sum_{r>s} a_r) dt_s x_s (x) B_s``. XLA einsums: the operands of
  the products are in the inputs' dtype (bfloat16 served, as the published
  kernels take them), every sum, every decay and the state float32.
  :func:`recurrent_scan` is the same sum token by token (a ``lax.scan``):
  the form the tests hold the chunks to, and the one the chunks were
  measured against (PERF.md section 6).
- :func:`state_update`, the decode step's one-token update over the slot
  pool, always the ``ssm_ssd_state_update`` kernel (interpret mode off the
  TPU; :func:`state_update_xla` is its twin for the tests): the pool is the
  donated operand and only the ACTIVE slots' states move, a grid step a
  (slot, group), the slots compacted through scalar prefetch as
  ``ops/ssm.py``'s kernel compacts them.

A state is held ``(N, heads * P)``, channels on the lanes as ``ops/ssm.py``
holds its own: the decay, ``dt x`` and ``y`` are then rows over the lanes,
and ``B_t``, ``C_t`` columns over the sublanes. The kernel takes ``B`` and
``C`` as the rows XLA has (``N`` on the lanes) and turns each into a column
on the chip (a diagonal select and a lane sum: exact), where ``ops/ssm.py``
has XLA spread them over a lane tile first: at ``N = 128`` and 8 groups
that spread would be 1 MB a slot and layer beside a state of 4 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from differential_transformer_replication_tpu import kernel_names
from differential_transformer_replication_tpu.ops.flash import auto_interpret
from differential_transformer_replication_tpu.ops.ssm import _lane_width


# ---------------------------------------------------------------------------
# a prompt chunk
# ---------------------------------------------------------------------------


def recurrent_scan(x, dt, A, Bm, Cm, D, h0):
    """The recurrence token by token. ``x`` (B, L, H, P); ``dt`` (B, L, H)
    float32 (0 = a step that leaves the state alone); ``A``, ``D`` (H,);
    ``Bm``, ``Cm`` (B, L, G, N); ``h0`` (B, N, H P). Returns ``(y (B, L, H,
    P) float32, the last state (B, N, H P) float32)``."""
    f32 = jnp.float32
    B, L, H, P = x.shape
    G, N = Bm.shape[2:]
    Af, Df = A.astype(f32), D.astype(f32)

    def step(h, xs):  # h (B, N, G, R, P)
        x_t, d_t, b_t, c_t = xs
        x_t = x_t.reshape(B, G, H // G, P)
        d_t = d_t.reshape(B, G, H // G)
        h = (jnp.exp(d_t * Af.reshape(G, -1))[:, None, :, :, None] * h
             + b_t.transpose(0, 2, 1)[:, :, :, None, None]
             * (d_t[..., None] * x_t)[:, None])
        y = jnp.sum(h * c_t.transpose(0, 2, 1)[:, :, :, None, None], axis=1)
        return h, y + Df.reshape(G, -1, 1) * x_t

    xs = tuple(jnp.swapaxes(a.astype(f32), 0, 1) for a in (x, dt, Bm, Cm))
    hL, ys = jax.lax.scan(step, h0.astype(f32).reshape(B, N, G, H // G, P), xs)
    return (jnp.swapaxes(ys, 0, 1).reshape(B, L, H, P),
            hL.reshape(B, N, H * P))


def chunk_scan(x, dt, A, Bm, Cm, D, h0, chunk: int):
    """:func:`recurrent_scan` in the chunked matrix form, sub-chunks of
    ``chunk`` tokens (``L`` is padded to whole sub-chunks with ``dt = 0``,
    which leaves the state as it is)."""
    f32 = jnp.float32
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    Q = min(chunk, L)
    nc = -(-L // Q)
    if nc * Q != L:
        pad = lambda a: jnp.pad(  # noqa: E731
            a, ((0, 0), (0, nc * Q - L)) + ((0, 0),) * (a.ndim - 2))
        x, dt, Bm, Cm = pad(x), pad(dt), pad(Bm), pad(Cm)
    cd = x.dtype
    xs = x.reshape(Bsz, nc, Q, G, R, P)
    dts = dt.astype(f32).reshape(Bsz, nc, Q, G, R)
    Bs = Bm.astype(cd).reshape(Bsz, nc, Q, G, N)
    Cs = Cm.astype(cd).reshape(Bsz, nc, Q, G, N)
    # the log-decays and their running sum inside a sub-chunk, (B, c, G, R, Q)
    a = (dts * A.astype(f32).reshape(G, R)).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(a, axis=-1)
    dtq = dts.transpose(0, 1, 3, 4, 2)
    # inside a sub-chunk: ((C B^T) . L)(dt . X)
    cb = jnp.einsum("bctgn,bcsgn->bcgts", Cs, Bs, preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    mix = (cb[:, :, :, None] * decay * dtq[..., None, :]).astype(cd)
    y = jnp.einsum("bcgrts,bcsgrp->bctgrp", mix, xs,
                   preferred_element_type=f32)
    # what a sub-chunk adds to the state, decayed to its end
    to_end = jnp.exp(cum[..., -1:] - cum) * dtq  # (B, c, G, R, Q)
    xw = (xs.astype(f32) * to_end.transpose(0, 1, 4, 2, 3)[..., None]
          ).astype(cd)
    added = jnp.einsum("bcsgn,bcsgrp->bcngrp", Bs, xw,
                       preferred_element_type=f32)
    whole = jnp.exp(cum[..., -1])  # (B, c, G, R): a sub-chunk's decay

    def hand_on(h, ins):
        w, s = ins
        return w[:, None, :, :, None] * h + s, h

    hL, h_in = jax.lax.scan(
        hand_on, h0.astype(f32).reshape(Bsz, N, G, R, P),
        (jnp.swapaxes(whole, 0, 1), jnp.swapaxes(added, 0, 1)))
    # the incoming state's part: exp(cumsum a) C H_in
    y_in = jnp.einsum("bctgn,cbngrp->bctgrp", Cs, h_in.astype(cd),
                      preferred_element_type=f32)
    y = y + y_in * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y + D.astype(f32).reshape(G, R, 1) * xs.astype(f32)
    return (y.reshape(Bsz, nc * Q, H, P)[:, :L], hL.reshape(Bsz, N, H * P))


# ---------------------------------------------------------------------------
# the decode step's update of the pool
# ---------------------------------------------------------------------------


def _per_channel(v: jnp.ndarray, P: int) -> jnp.ndarray:
    """A value a head (.., H) -> one a channel (.., H P)."""
    return jnp.repeat(v, P, axis=-1)


def _rows(x, dt, A, D):
    """The update's rows over the channels, float32 (S, H P): the decay
    ``exp(dt A)``, ``dt x`` and ``D x``."""
    f32 = jnp.float32
    P = x.shape[-1] // dt.shape[-1]
    xf, dtf = x.astype(f32), dt.astype(f32)
    return (_per_channel(jnp.exp(dtf * A.astype(f32)), P),
            _per_channel(dtf, P) * xf, _per_channel(D.astype(f32), P) * xf)


def state_update_xla(state, x, dt, A, Bm, Cm, D, active):
    """One token a slot: ``state`` (S, N, H P) float32; ``x`` (S, H P);
    ``dt`` (S, H) float32; ``A``, ``D`` (H,); ``Bm``, ``Cm`` (S, G, N);
    ``active`` (S,) bool. Returns ``(y (S, H P) float32, the pool)`` with
    the rows that are not active keeping every bit of their state (their
    ``y`` is 0)."""
    f32 = jnp.float32
    S, N, Di = state.shape
    G = Bm.shape[1]
    decay, dtx, dx = _rows(x, dt, A, D)
    spread = lambda m: jnp.repeat(  # noqa: E731  (S, G, N) -> (S, N, H P)
        m.astype(f32).transpose(0, 2, 1), Di // G, axis=-1)
    h = decay[:, None] * state.astype(f32) + spread(Bm) * dtx[:, None]
    y = jnp.sum(h * spread(Cm), axis=1) + dx
    return (jnp.where(active[:, None], y, 0.0),
            jnp.where(active[:, None, None], h.astype(state.dtype), state))


def _column(row: jnp.ndarray) -> jnp.ndarray:
    """``row`` (1, N) -> (N, 1), exactly: the row on a diagonal, summed
    over the lanes (every sum is one value and zeros)."""
    N = row.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _update_kernel(order_ref, n_ref, decay_ref, dtx_ref, b_ref, c_ref,
                   st_ref, y_ref, out_ref, *, lane: int):
    """Grid step ``(i, g)`` advances group ``g``'s channels of slot
    ``order[i]`` if ``i < n``. Past the active slots the index maps stay
    on the last active slot's last block, so nothing is fetched or written
    back for the others."""
    del order_ref
    i, g = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    Dg = st_ref.shape[-1]

    @pl.when(i < n)
    def _():
        b, c = _column(b_ref[0, 0]), _column(c_ref[0, 0])  # (N, 1)
        for j in range(Dg // lane):
            s = slice(j * lane, (j + 1) * lane)
            h = decay_ref[0, :, s] * st_ref[0, :, s] + b * dtx_ref[0, :, s]
            out_ref[0, :, s] = h
            y_ref[0, :, s] = jnp.sum(h * c, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(n == 0, jnp.logical_and(i == 0, g == 0)))
    def _():  # no slot is active: the one block that is written back
        out_ref[...] = st_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def state_update(state, x, dt, A, Bm, Cm, D, active, interpret=None):
    """:func:`state_update_xla` as the ``ssm_ssd_state_update`` kernel:
    the result aliases ``state``, so under a jit that donates the pool
    nothing of the pool's size is allocated, and a slot that is not
    active is neither read nor written."""
    if interpret is None:
        interpret = auto_interpret()
    f32 = jnp.float32
    S, N, Di = state.shape
    G = Bm.shape[1]
    Dg = Di // G
    lane = _lane_width(Dg)
    decay, dtx, dx = _rows(x, dt, A, D)
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32)[None]

    def at(i, g, order_ref, n_ref):
        """The (slot, group) of grid step ``(i, g)``: past the active
        slots, the last active slot's last group."""
        last = jnp.maximum(n_ref[0] - 1, 0)
        return (order_ref[jnp.minimum(i, last)],
                jnp.where(i < n_ref[0], g, G - 1))

    def row(i, g, o, n_):
        s, gg = at(i, g, o, n_)
        return (s, 0, gg)

    def col(i, g, o, n_):
        s, gg = at(i, g, o, n_)
        return (s, gg, 0, 0)

    y, new = pl.pallas_call(
        functools.partial(_update_kernel, lane=lane),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, G),
            in_specs=[
                pl.BlockSpec((1, 1, Dg), row),
                pl.BlockSpec((1, 1, Dg), row),
                pl.BlockSpec((1, 1, 1, N), col),
                pl.BlockSpec((1, 1, 1, N), col),
                pl.BlockSpec((1, N, Dg), row),
            ],
            out_specs=[pl.BlockSpec((1, 1, Dg), row),
                       pl.BlockSpec((1, N, Dg), row)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, 1, Di), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands: order, n, decay, dt x, B, C, state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        name=kernel_names.SSD_STATE_UPDATE,
        interpret=interpret,
    )(order, n, decay[:, None], dtx[:, None], Bm.astype(f32)[:, :, None],
      Cm.astype(f32)[:, :, None], state)
    return jnp.where(active[:, None], y[:, 0] + dx, 0.0), new

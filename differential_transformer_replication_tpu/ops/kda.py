"""Kimi Delta Attention's sequence operators: the gated delta rule

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

a head, with a state ``S`` of ``(dk, dv)`` float32, a log-decay ``g_t <= 0``
a key channel and a write strength ``beta_t`` in (0, 1) (arXiv:2510.26692).

- :func:`chunk_fwd` (``kda_chunk_fwd``) runs a chunk of L tokens from a
  state to the state after it, 64 tokens at a time: inside a block the
  recurrence is solved in closed form (a unit lower-triangular system),
  between blocks the state is handed on by a ``lax.scan``. XLA einsums,
  no kernel: a block's work is matrix products of 64 x 128 that the
  compiler tiles well, and the one part a kernel would have to get right,
  the decays, is kept exact here by never forming ``exp(-G)``. Every
  decay that is applied is ``exp(G_t - G_s)`` with ``s <= t``, at most 1,
  taken a channel at a time before the sum over channels; a per-token
  decay of 1e-4 underflows to 0 where the true product is 0 and nothing
  overflows.
- :func:`state_update`, the decode step's one-token update over the slot
  pool, the ``kda_state_update`` kernel: the pool is the donated operand
  (``input_output_aliases``) and only the active slots' states move, a
  slot a grid step, the slots compacted through scalar prefetch (the
  ``ssm_state_update`` pattern, ops/ssm.py). Off the TPU it runs in
  interpret mode. :func:`state_update_xla`, a select over the whole
  pool, is the plain form the tests hold it to.

A state is held ``(H, dk, dv)``, values on the lanes. The kernel needs a
head's ``exp(g)``, ``k`` and ``q`` as COLUMNS over the key channels (they
scale the state's rows); XLA hands them over as one ``(dk, 3 H)`` tile a
slot, a lane a head, so the kernel only slices a lane and broadcasts it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from differential_transformer_replication_tpu import kernel_names
from differential_transformer_replication_tpu.ops.flash import auto_interpret

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(eq: str, a, b):
    return jnp.einsum(eq, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(n: jnp.ndarray) -> jnp.ndarray:
    """``(I + n)^-1`` for strictly lower-triangular ``n`` (.., C, C):
    ``n`` is nilpotent, so the inverse is the finite product
    ``(I - n)(I + n^2)(I + n^4)...``, log2(C) squarings."""
    C = n.shape[-1]
    inv = jnp.eye(C, dtype=n.dtype) - n
    power = n
    for _ in range(max(C - 1, 1).bit_length() - 1):
        power = _mm("...ij,...jk->...ik", power, power)
        inv = inv + _mm("...ij,...jk->...ik", inv, power)
    return inv


def _block(S, xs):
    """One block of C tokens of every (sequence, head): ``S`` (B, H, dk,
    dv); ``q``, ``k``, ``g`` (B, H, C, dk), ``v`` (B, H, C, dv), ``beta``
    (B, H, C). Returns ``(S after the block, o (B, H, C, dv))``.

    With ``G`` the running sum of ``g`` inside the block and
    ``u_t = beta_t (v_t - S'_t^T k_t)`` the recurrence unrolls to
    ``(I + Diag(beta) tril(A, -1)) U = Diag(beta) (V - (exp(G) k) S)``,
    ``O = (exp(G) q) S + tril(Bq) U`` and
    ``S_C = Diag(exp(G_C)) S + (exp(G_C - G) k)^T U`` where
    ``A_ts = sum_c k_tc k_sc exp(G_tc - G_sc)`` and ``Bq`` the same with
    ``q_t``."""
    q, k, v, g, beta = xs
    C = q.shape[2]
    G = jnp.cumsum(g, axis=2)
    t = jnp.arange(C)
    seen = t[:, None] >= t[None, :]  # s <= t
    # (B, H, t, s, c): k_s decayed from s to t, 0 where s is after t
    decayed = k[:, :, None] * jnp.exp(jnp.where(
        seen[:, :, None], G[:, :, :, None] - G[:, :, None], -jnp.inf))
    A = jnp.sum(k[:, :, :, None] * decayed, axis=-1)
    Bq = jnp.sum(q[:, :, :, None] * decayed, axis=-1)
    T = _unit_lower_inverse(
        jnp.where(t[:, None] > t[None, :], beta[..., None] * A, 0.0))
    eG = jnp.exp(G)
    rhs = beta[..., None] * (v - _mm("bhtc,bhcv->bhtv", eG * k, S))
    U = _mm("bhts,bhsv->bhtv", T, rhs)
    o = _mm("bhtc,bhcv->bhtv", eG * q, S) + _mm("bhts,bhsv->bhtv", Bq, U)
    last = G[:, :, -1:]
    S = jnp.exp(last).swapaxes(-1, -2) * S + _mm(
        "bhtc,bhtv->bhcv", jnp.exp(last - G) * k, U)
    return S, o


def chunk_fwd(q, k, v, g, beta, state, valid=None):
    """``q``, ``k``, ``g`` (B, L, H, dk); ``v`` (B, L, H, dv); ``beta``
    (B, L, H); ``state`` (B, H, dk, dv). Returns ``(o (B, L, H, dv)
    float32, the state after the chunk, float32)``; zeros are a
    sequence's start. With ``valid`` (a runtime scalar) the steps from
    ``valid`` on are padding: ``g = 0`` and ``beta = 0`` there leave the
    state where step ``valid`` put it."""
    f32 = jnp.float32
    L = q.shape[1]
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if valid is not None:
        real = jnp.arange(L)[None, :, None] < valid
        g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real, beta, 0.0)
    C = min(CHUNK, L)
    pad = -L % C

    def blocks(a):  # (B, L, H, ..) -> (blocks, B, H, C, ..)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((a.shape[0], -1, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    last, o = jax.lax.scan(_block, state.astype(f32),
                           tuple(map(blocks, (q, k, v, g, beta))))
    # (blocks, B, H, C, dv) -> (B, L, H, dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    return o.reshape((o.shape[0], -1) + o.shape[3:])[:, :L], last


def recurrence(q, k, v, g, beta, state):
    """:func:`chunk_fwd` token by token, the defining form (tests)."""
    f32 = jnp.float32

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (B, H, ..)
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.sum(k_t[..., None] * S, axis=-2))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.sum(q_t[..., None] * S, axis=-2)

    xs = tuple(jnp.swapaxes(a.astype(f32), 0, 1) for a in (q, k, v, g, beta))
    last, o = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.swapaxes(o, 0, 1), last


# ---------------------------------------------------------------------------
# the decode step's update of the pool
# ---------------------------------------------------------------------------


def state_update_xla(state, q, k, v, g, beta, active):
    """One token a slot: ``state`` (S, H, dk, dv) float32; ``q``, ``k``,
    ``g`` (S, H, dk); ``v`` (S, H, dv); ``beta`` (S, H); ``active`` (S,)
    bool. Returns ``(o (S, H, dv) float32, the pool)`` with the rows that
    are not active keeping every bit of their state (their ``o`` is 0)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    S = jnp.exp(g)[..., None] * state.astype(f32)
    u = beta[..., None] * (v - jnp.sum(k[..., None] * S, axis=-2))
    S = S + k[..., None] * u[..., None, :]
    o = jnp.sum(q[..., None] * S, axis=-2)
    keep = active[:, None, None]
    return (jnp.where(keep, o, 0.0),
            jnp.where(keep[..., None], S.astype(state.dtype), state))


def _update_kernel(order_ref, n_ref, cols_ref, rows_ref, st_ref, o_ref,
                   out_ref):
    """Grid step ``i`` advances slot ``order[i]`` if ``i < n``, every head
    of it; past the active slots the index maps stay on the last active
    slot's blocks, so nothing is fetched or written back for the others.
    ``cols_ref`` (1, dk, 3 H): a lane a head of ``exp(g)``, then of ``k``,
    then of ``q``; ``rows_ref`` (1, 2, H, dv): ``beta * v`` and ``beta``
    spread over the lanes; ``st_ref``, ``out_ref`` (1, H, dk, dv);
    ``o_ref`` (1, H, dv)."""
    i = pl.program_id(0)
    n = n_ref[0]
    H = st_ref.shape[1]

    @pl.when(i < n)
    def _():
        for h in range(H):
            decay = cols_ref[0, :, h:h + 1]  # (dk, 1)
            k = cols_ref[0, :, H + h:H + h + 1]
            q = cols_ref[0, :, 2 * H + h:2 * H + h + 1]
            S = decay * st_ref[0, h].astype(jnp.float32)
            u = (rows_ref[0, 0, h:h + 1]
                 - rows_ref[0, 1, h:h + 1] * jnp.sum(k * S, axis=0,
                                                     keepdims=True))
            S = S + k * u
            out_ref[0, h] = S.astype(out_ref.dtype)
            o_ref[0, h:h + 1] = jnp.sum(q * S, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _():  # no slot is active: the one block that is written back
        out_ref[...] = st_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def state_update(state, q, k, v, g, beta, active, interpret=None):
    """:func:`state_update_xla` as the ``kda_state_update`` kernel: the
    result aliases ``state``, so under a jit that donates the pool nothing
    of the pool's size is allocated, and a slot that is not active is not
    read. A grid step is one slot, all its heads: 2 MiB of state in and
    out."""
    if interpret is None:
        interpret = auto_interpret()
    f32 = jnp.float32
    S, H, dk, dv = state.shape
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32)[None]
    beta = beta.astype(f32)[..., None]
    # (S, 3 H, dk) -> (S, dk, 3 H): the heads' columns side by side
    col = jnp.concatenate([jnp.exp(g.astype(f32)), k.astype(f32),
                           q.astype(f32)], axis=1).swapaxes(-1, -2)
    row = jnp.stack([beta * v.astype(f32),
                     jnp.broadcast_to(beta, (S, H, dv))], axis=1)

    def slot(i, order_ref, n_ref):
        return order_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]

    pool = pl.BlockSpec((1, H, dk, dv), lambda i, o, n: (slot(i, o, n), 0, 0, 0))
    o, new = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, dk, 3 * H),
                             lambda i, o, n: (slot(i, o, n), 0, 0)),
                pl.BlockSpec((1, 2, H, dv),
                             lambda i, o, n: (slot(i, o, n), 0, 0, 0)),
                pool,
            ],
            out_specs=[
                pl.BlockSpec((1, H, dv), lambda i, o, n: (slot(i, o, n), 0, 0)),
                pool,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, H, dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands: order, n, cols, rows, state
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20,
        ),
        name=kernel_names.KDA_STATE_UPDATE,
        interpret=interpret,
    )(order, n, col, row, state)
    return jnp.where(active[:, None, None], o, 0.0), new

"""The Mamba-1 mixer's sequence operators: the causal depthwise convolution
with a carried window, and the selective scan

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) * B_t
    y_t = h_t . C_t + D * u_t

per channel (``Di`` of them) and state (``N`` a channel), as XLA ops and as
two Pallas kernels selected by ``ModelConfig.ssm_impl``:

- :func:`selective_scan` over a chunk of L tokens from an initial state to
  the last one. ``xla``: a ``lax.scan`` over time, differentiable.
  ``pallas`` (``ssm_scan_fwd``): the state stays in VMEM over the chunk and
  the ``(L, Di, N)`` states never reach HBM; forward only.
- :func:`state_update`, the decode step's one-token update over the slot
  pool. ``xla``: a select over the whole pool. ``pallas``
  (``ssm_state_update``): the pool is the donated operand
  (``input_output_aliases``) and only the active slots' states move, one
  grid step a slot, the slots compacted through scalar prefetch.

A state is held ``(N, Di)``, channels on the lanes: with ``N = 16`` on the
lanes seven eighths of every vector register would be padding. ``B_t`` and
``C_t`` are one value a state, the same for every channel; the kernels take
them already spread over one 128-lane tile (``(.., N, 128)``, made by XLA:
16 x 128 floats a token) because a TPU kernel cannot turn a row of 16 lanes
into a column of 16 sublanes cheaply. The recurrence runs in float32
whatever the inputs' dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from differential_transformer_replication_tpu import kernel_names
from differential_transformer_replication_tpu.ops.flash import auto_interpret

_LANES = 128
_SUB = 8  # time steps a loop iteration of the scan kernel (one sublane tile)
_SCAN_TIME_BLOCK = 128


def causal_conv(u: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                window: jnp.ndarray, valid=None):
    """Causal depthwise convolution of ``u`` (B, L, Di) with ``w`` (K, Di)
    taps a channel and bias ``b`` (Di,; None: the convolution has none,
    the lfm2 family's), continuing a sequence whose last
    K-1 inputs are ``window`` (B, K-1, Di; zeros at a sequence's start):
    ``out[t] = b + sum_k w[k] * x[t + k - (K-1)]``. Returns ``(out, the
    new window)``, float32 and ``window``'s dtype. With ``valid`` (a
    runtime scalar, 1 <= valid <= L) only the first ``valid`` steps are a
    sequence's and the rest padding: the new window is the one after step
    ``valid``."""
    K, L = w.shape[0], u.shape[1]
    full = jnp.concatenate([window.astype(jnp.float32),
                            u.astype(jnp.float32)], axis=1)
    wf = w.astype(jnp.float32)
    if b is None:
        out = sum(full[:, k:k + L] * wf[k] for k in range(K))
    else:
        out = b.astype(jnp.float32) + sum(
            full[:, k:k + L] * wf[k] for k in range(K))
    if valid is None:
        return out, full[:, L:].astype(window.dtype)
    last = jax.lax.dynamic_slice_in_dim(full, valid, K - 1, axis=1)
    return out, last.astype(window.dtype)


# ---------------------------------------------------------------------------
# the chunk's scan
# ---------------------------------------------------------------------------


def selective_scan_xla(u, delta, A, Bm, Cm, D, h0):
    """``u``, ``delta`` (B, L, Di); ``A`` (Di, N); ``Bm``, ``Cm`` (B, L, N);
    ``D`` (Di,); ``h0`` (B, N, Di). Returns ``(y (B, L, Di) float32, the
    last state (B, N, Di) float32)``."""
    f32 = jnp.float32
    At = A.astype(f32).T  # (N, Di)
    Df = D.astype(f32)

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = (jnp.exp(d_t[:, None, :] * At) * h
             + (d_t * u_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1) + Df * u_t

    xs = tuple(jnp.swapaxes(a.astype(f32), 0, 1) for a in (u, delta, Bm, Cm))
    hL, ys = jax.lax.scan(step, h0.astype(f32), xs)
    return jnp.swapaxes(ys, 0, 1), hL


def _scan_kernel(u_ref, dt_ref, b_ref, c_ref, at_ref, d_ref, h0_ref,
                 y_ref, hout_ref, h_scr, *, groups: int, lane: int):
    """One (sequence, channel block, time block): ``u_ref``, ``dt_ref``,
    ``y_ref`` (Lb, Db); ``b_ref``, ``c_ref`` (Lb, N, lane); ``at_ref``,
    ``h0_ref``, ``hout_ref``, ``h_scr`` (N, Db); ``d_ref`` (1, Db), with
    Db = groups * lane. The time blocks of a channel block run in order
    and hand the state on in ``h_scr``."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    cols = [slice(g * lane, (g + 1) * lane) for g in range(groups)]
    at = [at_ref[:, c] for c in cols]
    dsk = d_ref[...]

    def body(i, hs):
        base = pl.multiple_of(i * _SUB, _SUB)
        u8 = u_ref[pl.ds(base, _SUB), :].astype(jnp.float32)
        d8 = dt_ref[pl.ds(base, _SUB), :]
        rows = []
        for j in range(_SUB):
            b_t = b_ref[base + j]  # (N, lane)
            c_t = c_ref[base + j]
            u_t, d_t = u8[j:j + 1], d8[j:j + 1]  # (1, Db)
            du = d_t * u_t
            new, y = [], []
            for g, c in enumerate(cols):
                h = jnp.exp(d_t[:, c] * at[g]) * hs[g] + du[:, c] * b_t
                new.append(h)
                y.append(jnp.sum(h * c_t, axis=0, keepdims=True))
            hs = tuple(new)
            rows.append(jnp.concatenate(y, axis=1) + dsk * u_t)
        y_ref[pl.ds(base, _SUB), :] = jnp.concatenate(rows, axis=0)
        return hs

    hs = jax.lax.fori_loop(0, u_ref.shape[0] // _SUB, body,
                           tuple(h_scr[:, c] for c in cols))
    for g, c in enumerate(cols):
        h_scr[:, c] = hs[g]

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        hout_ref[...] = h_scr[...]


def _lane_width(Di: int) -> int:
    return _LANES if Di % _LANES == 0 else Di


def _spread(m: jnp.ndarray, lane: int) -> jnp.ndarray:
    """``(.., N)`` -> ``(.., N, lane)`` float32: every state's value over
    one lane tile, the form the kernels read B and C in."""
    return jnp.broadcast_to(m.astype(jnp.float32)[..., None],
                            m.shape + (lane,))


def selective_scan_pallas(u, delta, A, Bm, Cm, D, h0, interpret=None):
    """:func:`selective_scan_xla` as the ``ssm_scan_fwd`` kernel. ``L`` is
    padded to whole time blocks with ``delta = 0``, which leaves the state
    as it is (``exp(0) = 1``, nothing added)."""
    if interpret is None:
        interpret = auto_interpret()
    f32 = jnp.float32
    B, L, Di = u.shape
    N = A.shape[1]
    lane = _lane_width(Di)
    groups = 2 if Di % (2 * lane) == 0 else 1
    Db = groups * lane
    Lb = min(_SCAN_TIME_BLOCK, -(-L // _SUB) * _SUB)
    Lp = -(-L // Lb) * Lb
    pad = lambda a: jnp.pad(a, ((0, 0), (0, Lp - L)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
    u_p, dt_p = pad(u), pad(delta.astype(f32))
    b_p, c_p = pad(_spread(Bm, lane)), pad(_spread(Cm, lane))
    seq = lambda b, d, t: (b, t, d)  # noqa: E731
    per_t = lambda b, d, t: (b, t, 0, 0)  # noqa: E731
    chan = lambda b, d, t: (0, d)  # noqa: E731
    state = lambda b, d, t: (b, 0, d)  # noqa: E731
    y, hL = pl.pallas_call(
        functools.partial(_scan_kernel, groups=groups, lane=lane),
        grid=(B, Di // Db, Lp // Lb),
        in_specs=[
            pl.BlockSpec((None, Lb, Db), seq),
            pl.BlockSpec((None, Lb, Db), seq),
            pl.BlockSpec((None, Lb, N, lane), per_t),
            pl.BlockSpec((None, Lb, N, lane), per_t),
            pl.BlockSpec((N, Db), chan),
            pl.BlockSpec((1, Db), chan),
            pl.BlockSpec((None, N, Db), state),
        ],
        out_specs=[
            pl.BlockSpec((None, Lb, Db), seq),
            pl.BlockSpec((None, N, Db), state),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Lp, Di), f32),
            jax.ShapeDtypeStruct((B, N, Di), f32),
        ],
        scratch_shapes=[pltpu.VMEM((N, Db), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name=kernel_names.SSM_SCAN_FWD,
        interpret=interpret,
    )(u_p, dt_p, b_p, c_p, A.astype(f32).T, D.astype(f32)[None],
      h0.astype(f32))
    return y[:, :L], hL


def selective_scan(u, delta, A, Bm, Cm, D, h0, impl: str = "xla"):
    """The chunk's scan, dispatched on ``ssm_impl``."""
    fn = selective_scan_pallas if impl == "pallas" else selective_scan_xla
    return fn(u, delta, A, Bm, Cm, D, h0)


# ---------------------------------------------------------------------------
# the decode step's update of the pool
# ---------------------------------------------------------------------------


def state_update_xla(state, u, delta, A, Bm, Cm, D, active):
    """One token a slot: ``state`` (S, N, Di) in its storage dtype; ``u``,
    ``delta`` (S, Di); ``Bm``, ``Cm`` (S, N); ``active`` (S,) bool. Returns
    ``(y (S, Di) float32, the pool)`` with the rows that are not active
    keeping every bit of their state (their ``y`` is 0)."""
    f32 = jnp.float32
    u, delta = u.astype(f32), delta.astype(f32)
    h = (jnp.exp(delta[:, None, :] * A.astype(f32).T) * state.astype(f32)
         + (delta * u)[:, None, :] * Bm.astype(f32)[:, :, None])
    y = jnp.sum(h * Cm.astype(f32)[:, :, None], axis=1) + D.astype(f32) * u
    keep = active[:, None, None]
    return (jnp.where(active[:, None], y, 0.0),
            jnp.where(keep, h.astype(state.dtype), state))


def _update_kernel(order_ref, n_ref, u_ref, dt_ref, b_ref, c_ref, at_ref,
                   d_ref, st_ref, y_ref, out_ref, *, lane: int):
    """Grid step ``i`` advances slot ``order[i]`` if ``i < n``. Past the
    active slots the index maps stay on the last active slot's blocks, so
    nothing is fetched or written back for the others."""
    i = pl.program_id(0)
    n = n_ref[0]
    Di = st_ref.shape[-1]

    @pl.when(i < n)
    def _():
        b, c = b_ref[0], c_ref[0]  # (N, lane)
        for g in range(Di // lane):
            s = slice(g * lane, (g + 1) * lane)
            u, dt = u_ref[0, :, s].astype(jnp.float32), dt_ref[0, :, s]
            h = (jnp.exp(dt * at_ref[:, s]) * st_ref[0, :, s].astype(jnp.float32)
                 + (dt * u) * b)
            out_ref[0, :, s] = h.astype(out_ref.dtype)
            y_ref[0, :, s] = (jnp.sum(h * c, axis=0, keepdims=True)
                              + d_ref[:, s] * u)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _():  # no slot is active: the one block that is written back
        out_ref[...] = st_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def state_update_pallas(state, u, delta, A, Bm, Cm, D, active,
                        interpret=None):
    """:func:`state_update_xla` as the ``ssm_state_update`` kernel: the
    result aliases ``state``, so under a jit that donates the pool nothing
    of the pool's size is allocated, and a slot that is not active is not
    read."""
    if interpret is None:
        interpret = auto_interpret()
    f32 = jnp.float32
    S, N, Di = state.shape
    lane = _lane_width(Di)
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32)[None]

    def slot(i, order_ref, n_ref):
        return order_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]

    row = pl.BlockSpec((1, 1, Di), lambda i, o, n: (slot(i, o, n), 0, 0))
    col = pl.BlockSpec((1, N, lane), lambda i, o, n: (slot(i, o, n), 0, 0))
    pool = pl.BlockSpec((1, N, Di), lambda i, o, n: (slot(i, o, n), 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_update_kernel, lane=lane),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                row, row, col, col,
                pl.BlockSpec((N, Di), lambda i, o, n: (0, 0)),
                pl.BlockSpec((1, Di), lambda i, o, n: (0, 0)),
                pool,
            ],
            out_specs=[row, pool],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, 1, Di), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands: order, n, u, delta, B, C, A^T, D, state
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name=kernel_names.SSM_STATE_UPDATE,
        interpret=interpret,
    )(order, n, u[:, None], delta.astype(f32)[:, None], _spread(Bm, lane),
      _spread(Cm, lane), A.astype(f32).T, D.astype(f32)[None], state)
    return jnp.where(active[:, None], y[:, 0], 0.0), new


def state_update(state, u, delta, A, Bm, Cm, D, active, impl: str = "xla"):
    """The decode step's update, dispatched on ``ssm_impl``."""
    fn = state_update_pallas if impl == "pallas" else state_update_xla
    return fn(state, u, delta, A, Bm, Cm, D, active)

"""Fused multi-stream flash attention — the Pallas TPU kernel.

The reference materializes full ``(T, T)`` attention maps per head and per
softmax stream (diff_transformer.py:57-70, control.py:52-62,
Ndiff_transformer.py:102-123). On TPU the O(T^2) memory traffic, not the
FLOPs, is the bottleneck, so this module computes the same math as an
online-softmax (flash) kernel that never materializes a T x T map.

One kernel serves all three model families, because each one's attention is
a *linear combination of softmax streams over a shared V*:

    out = sum_s coeff[s, h] * causal_softmax(Q_s K_s^T / sqrt(d)) @ V

  - control (control.py:52-62):            S=1, coeff = [1]
  - diff    (diff_transformer.py:70):      S=2, coeff = [1, -lambda_h]
  - ndiff   (Ndiff_transformer.py:119-123): S=n, coeff = sign_s * lambda_{s,h}

The kernel runs S online-softmax accumulators in one pass sharing the V
tiles (SURVEY.md section 7.7: "exploit linearity"), with the per-stream
coefficients applied at combine time. Scores, softmax and accumulation are
float32; tile matmuls feed the MXU in the input dtype.

Backward is a custom VJP with two Pallas kernels (dq; dk/dv) that recompute
probabilities from the saved per-stream log-sum-exp — the standard flash
backward, generalized to S streams. The per-stream outputs O_s are saved
from the forward so that d(coeff) and the flash "delta" rowsum need no
extra recompute pass.

Attention-probability dropout (diff_transformer.py:58-67) is fused
in-kernel: counter-based hash masks of the global coordinates, identical
across forward/backward and across tilings — see the dropout section
below and tests/test_flash_dropout.py.

Two kernel generations, dispatched on T (measured on v5e at the
flagship diff shapes):
  - full-K/V-resident (T <= _KV_TILE_THRESHOLD = 4096): each grid step
    holds the whole per-(b,h) K/V in VMEM; fastest at short T, stops
    compiling for training at T=5120.
  - KV-tiled (T > 4096): K/V stream through a third grid dimension with
    scratch accumulators, so VMEM holds O(block) state regardless of T.
    Verified training on one chip at T=8192 (10.7x the dense XLA path)
    and T=16384.
Sequence parallelism composes on top — parallel/ring.py shards T across
the mesh and with impl="pallas" runs the chunk kernel per ring step, so
each device only ever sees T/num_shards.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from differential_transformer_replication_tpu import kernel_names
from differential_transformer_replication_tpu.ops.streams import (
    NEG_INF,
    diff_coeffs,
    ndiff_coeffs,
    vanilla_coeffs,
)


def auto_interpret() -> bool:
    """Compiled Mosaic on TPU; interpreter everywhere else (CPU CI)."""
    return jax.default_backend() != "tpu"


_auto_interpret = auto_interpret  # internal callers


def use_flash(impl: str, dropout_rate: float, rng) -> bool:
    """Single dispatch predicate shared by all three model families.

    Attention-prob dropout is fused in-kernel (counter-based masks; see
    multi_stream_flash_attention), so the pallas path now applies
    regardless of the dropout setting. The signature keeps the
    (rate, rng) arguments so call sites document what the predicate once
    depended on — both are inert here.
    """
    del dropout_rate, rng
    return impl == "pallas"


def pick_block(desired: int, total: int) -> int:
    """Largest divisor of ``total`` that is <= desired (block shapes must
    tile the sequence exactly)."""
    b = min(desired, total)
    while total % b:
        b -= 1
    return b


_pick_block = pick_block  # internal callers


# Tile defaults by TPU generation, measured via tools/flash_sweep.py on
# v5e (see multi_stream_flash_attention's docstring). VMEM budgets differ
# across generations, so unknown kinds get conservative 256-tiles that
# compile everywhere rather than the widest measured winner.
# (blocks are (block_q, block_k, block_q_train, block_k_train))
_TUNED_BLOCKS = {
    # with bf16 MXU operands the 1024-wide K train tile fits VMEM in the
    # bare-op sweeps (tools/flash_sweep.py: +5% at T=512, +24-29% at
    # T=2048-8192 over 512-square) — but see the T-dependent cap in
    # multi_stream_flash_attention: the resident bwd kernels can't afford
    # it at 1024 < T <= _KV_TILE_THRESHOLD under the full model
    "v5 lite": (512, 1024, 512, 1024),
    "v5e": (512, 1024, 512, 1024),
}
_CONSERVATIVE_BLOCKS = (256, 512, 256, 256)


def tuned_block_key(device_kind: str) -> Optional[str]:
    """The ``_TUNED_BLOCKS`` key a ``device_kind`` string matches, or
    None (a v5e reports ``"TPU v5 lite"``)."""
    kind = device_kind.lower()
    return next((key for key in _TUNED_BLOCKS if key in kind), None)


def default_blocks() -> tuple:
    """(block_q, block_k, block_q_train, block_k_train) for the current
    backend: tuned tiles on known TPU kinds, conservative ones (with a
    warning that names the kind) on others, tuned for the interpreter
    (tile size is semantics-free there)."""
    if jax.default_backend() != "tpu":
        return _TUNED_BLOCKS["v5 lite"]
    kind = jax.devices()[0].device_kind
    key = tuned_block_key(kind)
    if key is None:
        warnings.warn(
            "no tuned flash tiles for TPU kind %r; using the conservative "
            "%r" % (kind, _CONSERVATIVE_BLOCKS)
        )
        return _CONSERVATIVE_BLOCKS
    return _TUNED_BLOCKS[key]


# ---------------------------------------------------------------------------
# In-kernel attention-probability dropout (diff_transformer.py:58-67: each
# softmax map is dropped out independently, before the lambda combine).
#
# The randomness is a counter-based hash of the GLOBAL (row, col) position,
# the (b*H + h) grid index, the stream index, and a per-call seed — pure
# uint32 arithmetic, so the same code runs compiled on TPU and in the
# Pallas interpreter, and a plain-jnp twin (dropout_keep_reference) can
# reproduce the kernel's masks bit-exactly for parity tests. Because the
# mask is a function of global coordinates only, the forward and both
# backward kernels regenerate identical masks regardless of their tilings.
# The seed rides an SMEM (1, 2) float32 holding two exact 24-bit integers
# (no float<->int bitcasting needed in-kernel); the two words enter the
# hash at different rounds (dropout_keep_ids), so cross-call mask-field
# collisions need both words to match (~2^-48 per pair) and distinct
# (layer, step) calls don't birthday-collide over a full 40k-step training
# run the way a single 24-bit word would (~6k draws).
# ---------------------------------------------------------------------------


def _fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """32-bit finalizer (triple32-style avalanche); wraps mod 2^32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def dropout_keep_ids(seed_w0, seed_w1, bh, s_idx: int, row_ids, col_ids,
                     rate: float):
    """Bernoulli(1 - rate) keep mask for global attention positions.

    seed_w0/seed_w1: uint32 scalars (the two 24-bit seed words); bh:
    traced int scalar (b*H + h); s_idx: static stream index;
    row_ids/col_ids: int32 (bq, bk) global q/k positions. Returns bool
    (bq, bk). The two seed words enter at DIFFERENT rounds of the hash
    (w0 in the inner key, w1 xor'd between the finalizer rounds), so two
    calls regenerate the same mask field only if both 24-bit words
    collide jointly — ~2^-48 per pair, not the ~2^-32 a single folded
    key would give."""
    threshold = jnp.uint32(min(int(round(rate * (2.0**32))), 2**32 - 1))
    key = _fmix32(
        seed_w0
        ^ (bh.astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
        ^ jnp.uint32(s_idx * 0x27D4EB2F)
    )
    x = (
        row_ids.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        ^ col_ids.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    )
    return _fmix32(_fmix32(x + key) ^ (seed_w1 * jnp.uint32(0x9E3779B1))) >= threshold


def _read_seed_words(seed_ref):
    """The seed's two exact-24-bit float32 words as uint32 scalars. Works
    on the SMEM ref in-kernel and on the (1, 2) array in the jnp twin —
    both index as [0, i]."""
    w0 = seed_ref[0, 0].astype(jnp.int32).astype(jnp.uint32)
    w1 = seed_ref[0, 1].astype(jnp.int32).astype(jnp.uint32)
    return w0, w1


def _keep_mask_block(seed_ref, bh, S: int, q_start, k_start, bq: int, bk: int,
                     rate: float, off=None):
    """(S, bq, bk) keep mask for one score block (kernel-side).

    ``off`` is the ring-chunk causal offset: subtracting it from the
    column coordinate recovers a per-device-unique K position
    (``k_local - off = k_global - my*Tl``), so on the sequence-parallel
    ring every (q, k) pair hashes distinctly across the rotation steps
    while the aligned paths (off=0) keep plain global coordinates —
    which is also what dropout_keep_reference reproduces."""
    # f32 -> i32 -> u32: Mosaic has no direct f32->u32 cast; each seed word
    # is a 24-bit integer so the value survives exactly
    w0, w1 = _read_seed_words(seed_ref)
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if off is not None:
        cols = cols - off
    return jnp.stack(
        [dropout_keep_ids(w0, w1, bh, s, rows, cols, rate) for s in range(S)]
    )


def _apply_keep(p, keep, rate: float):
    """Inverted dropout on (already-softmaxed or unnormalized) probs."""
    return jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)


def _scale_streams(x, c_ref, bh_id, S: int):
    """(bq, bk) -> (S, bq, bk): scale one block by each stream's scalar
    combine coefficient (SMEM (BH, S) table), statically unrolled —
    Mosaic rejects the equivalent (S, 1, 1)-broadcast formulation
    ("unsupported shape cast") for S >= 2. The FACTORED backward's
    per-stream dP expansion; see _bwd_dq_kernel."""
    return jnp.stack([x * c_ref[bh_id, s] for s in range(S)])


def _combine_streams(p, c_ref, bh_id, S: int):
    """(S, bq, bk) -> (bq, bk): sum of streams weighted by their scalar
    combine coefficients (statically unrolled, see _scale_streams)."""
    acc = p[0] * c_ref[bh_id, 0]
    for s in range(1, S):
        acc = acc + p[s] * c_ref[bh_id, s]
    return acc


def dropout_seed_from_rng(rng) -> jnp.ndarray:
    """(1, 2) float32 carrying two 24-bit seed words (48 bits total) drawn
    from a jax PRNG key — each exactly representable in float32, so SMEM
    can carry them without bitcasting."""
    bits = jax.random.bits(rng, (1, 2), jnp.uint32) >> 8
    return bits.astype(jnp.float32)


def dropout_keep_reference(seed: jnp.ndarray, BH: int, S: int, T: int,
                           rate: float) -> jnp.ndarray:
    """Plain-jnp twin of the kernels' mask generation: (BH, S, T, T) keep
    booleans, bit-exact with what the compiled/interpreted kernels use for
    the same ``seed`` (a (1, 2) float32 from :func:`dropout_seed_from_rng`).
    Test/oracle use only — it materializes full T x T masks."""
    w0, w1 = _read_seed_words(seed)
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    out = []
    for bh in range(BH):
        bh_t = jnp.asarray(bh, jnp.int32)
        out.append(
            jnp.stack(
                [
                    dropout_keep_ids(w0, w1, bh_t, s, rows, cols, rate)
                    for s in range(S)
                ]
            )
        )
    return jnp.stack(out)


# ---------------------------------------------------------------------------
# Shared kernel math
# ---------------------------------------------------------------------------


_BIAS_MAX_T = 1024  # resident kernels switch to the additive-mask fast
# path at T <= this: the (T, T) fp32 bias tile costs VMEM stripes of
# (block, T) per program, fine at 1024 (2 MB) but a VMEM hazard toward
# the 4096 resident limit


def causal_bias(T: int, off) -> jnp.ndarray:
    """(T, T) fp32 ADDITIVE causal mask: 0 where column c is visible to
    row r (``c <= r + off``), NEG_INF elsewhere. Built ONCE per kernel
    call outside the grid (XLA CSEs the identical subgraph across
    layers) and added onto the scores inside — one VPU pass per tile
    instead of the two iotas + compare + select the in-kernel mask
    generation costs per PROGRAM (measured ~2-3 ms/step at the recipe
    scale across the three resident kernels). Adding the finite
    NEG_INF sentinel reproduces the select exactly: a finite score
    plus -1e30 rounds to -1e30 in fp32."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    off_i = jnp.asarray(off, jnp.int32).reshape(())
    return jnp.where(cols <= rows + off_i, 0.0, NEG_INF).astype(jnp.float32)


def _scores_plus_bias(q_blk, k_blk, bias_blk, scale):
    """Score block with the precomputed additive causal mask — the
    bias-mode twin of :func:`_masked_scores` (same MXU contraction,
    dtype rules, and masking semantics; see :func:`causal_bias`)."""
    s = jax.lax.dot_general(
        q_blk, k_blk,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale
    return s + bias_blk[None]


def _masked_scores(q_blk, k_blk, q_start, k_start, off, scale):
    """The score/mask block every kernel shares: ``(S, bq, bk)`` fp32
    scores ``Q K^T * scale`` with offset-causal masking (column c visible
    to row r iff ``k_start + c <= q_start + r + off``), plus the boolean
    keep-mask. q_blk/k_blk: (S, bq|bk, d) in the STORED dtype — on bf16
    inputs the MXU runs the native bf16 x bf16 -> fp32 contraction
    (preferred_element_type), which is what the XLA attention path and
    the reference's fp16-AMP matmuls (train.py:263) do; upcasting
    operands to fp32 first would run the MXU at a fraction of peak."""
    bq, bk = q_blk.shape[1], k_blk.shape[1]
    s = jax.lax.dot_general(
        q_blk, k_blk,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale
    row_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = (col_ids <= row_ids + off)[None, :, :]
    return jnp.where(keep, s, NEG_INF), keep


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # (1, S, block_q, d)
    k_ref,  # (1, S, T, d)
    v_ref,  # (1, T, dv)
    off_ref,  # (1, 1) float32 SMEM: causal row offset (0 = aligned causal;
    #           +-k*Tl for ring chunks whose K lives k shards away)
    seed_ref,  # (1, 2) float32 SMEM: dropout seed (unread when rate == 0)
    *refs,  # [c_ref (BH, S) SMEM if emit_combined] then the outputs:
    #         [out_ref (1, block_q, dv) if emit_combined]
    #         [oall_ref (1, S, block_q, dv), lse_ref (1, S, block_q)
    #          if save_residuals]
    block_k: int,
    save_residuals: bool,
    emit_combined: bool = True,
    dropout_rate: float = 0.0,
    use_bias: bool = False,
):
    """One online-softmax body for all three forward modes: the combined
    primal (coeff-weighted sum of streams), the residual-saving VJP
    forward, and the per-stream ring chunk (no combine; offset-causal).
    ``use_bias`` swaps the in-kernel iota mask for the precomputed
    additive bias stripe (:func:`causal_bias`), delivered as an extra
    (block_q, T) input right before the outputs in ``refs``."""
    if use_bias:
        bias_ref, *refs = refs
    else:
        bias_ref = None
    if emit_combined:
        c_ref, *outs = refs
    else:
        c_ref, outs = None, list(refs)

    S, block_q, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    T = k_ref.shape[2]
    dv = v_ref.shape[2]
    nk = T // block_k
    bh_id = pl.program_id(0)  # read at top level: the interpreter cannot
    i = pl.program_id(1)      # lower program_id inside cond/when bodies
    q_start = i * block_q
    off = off_ref[0, 0].astype(jnp.int32)

    q = q_ref[0]  # (S, block_q, d) stored dtype — MXU-native
    scale = 1.0 / math.sqrt(d)

    def body(j, carry):
        m, l, acc = carry

        def compute(carry):
            m, l, acc = carry
            k_j = k_ref[0, :, pl.ds(j * block_k, block_k), :]
            v_j = v_ref[0, pl.ds(j * block_k, block_k), :]
            if use_bias:
                s = _scores_plus_bias(
                    q, k_j, bias_ref[:, pl.ds(j * block_k, block_k)], scale
                )
            else:
                s, _ = _masked_scores(q, k_j, q_start, j * block_k, off, scale)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # (S, block_q)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[:, :, None])
            # the normalizer accumulates the UNdropped p: softmax first,
            # then dropout on the normalized map (diff_transformer.py:58-67)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            p_pv = p
            if dropout_rate > 0.0:
                keep = _keep_mask_block(
                    seed_ref, bh_id, S, q_start, j * block_k,
                    block_q, block_k, dropout_rate, off,
                )
                p_pv = _apply_keep(p, keep, dropout_rate)
            pv = jax.lax.dot_general(
                p_pv.astype(v_j.dtype), v_j,
                dimension_numbers=(((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (S, block_q, dv) fp32 accum
            acc_new = acc * alpha[:, :, None] + pv
            return m_new, l_new, acc_new

        # causal skip: K block j is entirely in the future of this Q block
        return jax.lax.cond(
            j * block_k <= q_start + block_q - 1 + off, compute, lambda c: c,
            carry,
        )

    m0 = jnp.full((S, block_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((S, block_q), jnp.float32)
    a0 = jnp.zeros((S, block_q, dv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))

    # aligned-causal rows always see the diagonal (l >= 1); ring chunks can
    # have fully masked rows, where l_safe keeps o finite and lse lands at
    # ~NEG_INF so the chunk gets zero weight in the logsumexp merge
    l_safe = jnp.maximum(l, 1e-30)
    o_s = acc / l_safe[:, :, None]  # (S, block_q, dv)
    if emit_combined:
        # combine streams with the per-(b,h) scalar coefficients (SMEM)
        bh = pl.program_id(0)
        out_ref = outs[0]
        combined = c_ref[bh, 0] * o_s[0]
        for s in range(1, S):
            combined += c_ref[bh, s] * o_s[s]
        out_ref[0] = combined.astype(out_ref.dtype)
        outs = outs[1:]
    if save_residuals:
        oall_ref, lse_ref = outs
        oall_ref[0] = o_s.astype(oall_ref.dtype)
        lse_ref[0] = (m + jnp.log(l_safe)).astype(lse_ref.dtype)


def _fwd_call(
    q: jnp.ndarray,  # (BH, S, T, d)
    k: jnp.ndarray,  # (BH, S, T, d)
    v: jnp.ndarray,  # (BH, T, dv)
    coeffs: jnp.ndarray,  # (BH, S) float32
    *,
    block_q: int,
    block_k: int,
    save_residuals: bool,
    interpret: bool,
    dropout_seed: Optional[jnp.ndarray] = None,  # (1, 2) float32
    dropout_rate: float = 0.0,
):
    BH, S, T, d = q.shape
    dv = v.shape[-1]
    nq = T // block_q
    seed = (
        dropout_seed
        if dropout_seed is not None
        else jnp.zeros((1, 2), jnp.float32)
    )
    if T > _KV_TILE_THRESHOLD:
        # stream K/V through the grid past the full-residency envelope
        results = _tiled_fwd_call(
            q, k, v, jnp.zeros((1, 1), jnp.float32), coeffs,
            block_q=block_q, block_k=block_k,
            save_residuals=save_residuals, emit_combined=True,
            interpret=interpret,
            dropout_seed=seed, dropout_rate=dropout_rate,
        )
        if save_residuals:
            return results
        return results[0], None, None
    use_bias = T <= _BIAS_MAX_T
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, save_residuals=save_residuals,
        emit_combined=True, dropout_rate=dropout_rate, use_bias=use_bias,
    )
    out_shapes = [jax.ShapeDtypeStruct((BH, T, dv), q.dtype)]
    out_specs = [
        pl.BlockSpec(
            (1, block_q, dv), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM
        ),
    ]
    if save_residuals:
        # residual buffers exist only on the VJP path; the inference primal
        # must not allocate (BH, S, T, dv) of dead HBM
        out_shapes += [
            jax.ShapeDtypeStruct((BH, S, T, dv), q.dtype),
            jax.ShapeDtypeStruct((BH, S, T), jnp.float32),
        ]
        out_specs += [
            pl.BlockSpec(
                (1, S, block_q, dv), lambda b, i: (b, 0, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, S, block_q), lambda b, i: (b, 0, i), memory_space=pltpu.VMEM
            ),
        ]
    in_specs = [
        pl.BlockSpec(
            (1, S, block_q, d), lambda b, i: (b, 0, i, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (1, S, T, d), lambda b, i: (b, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec((1, T, dv), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1), lambda b, i: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 2), lambda b, i: (0, 0), memory_space=pltpu.SMEM),
    ]
    inputs = [q, k, v, jnp.zeros((1, 1), jnp.float32), seed]
    if use_bias:
        in_specs.append(
            pl.BlockSpec((block_q, T), lambda b, i: (i, 0),
                         memory_space=pltpu.VMEM)
        )
        inputs.append(causal_bias(T, 0))
    # the whole (BH, S) scalar coefficient table rides in SMEM; a
    # per-bh block would violate Mosaic's (8, 128) tiling check
    in_specs.append(
        pl.BlockSpec((BH, S), lambda b, i: (0, 0), memory_space=pltpu.SMEM)
    )
    inputs.append(coeffs)
    results = pl.pallas_call(
        kernel,
        grid=(BH, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name=kernel_names.FLASH_FWD,
        interpret=interpret,
    )(*inputs)
    if save_residuals:
        return results
    return results[0], None, None


# ---------------------------------------------------------------------------
# KV-tiled variants: K/V stream through a third grid dimension with scratch
# accumulators, so VMEM holds only O(block) state regardless of T. Selected
# automatically past the full-K/V envelope (see _KV_TILE_THRESHOLD).
# ---------------------------------------------------------------------------

# measured on v5e: the full-K/V-resident kernels stop compiling for
# training at T=5120 (flagship shapes); stream K/V above this
_KV_TILE_THRESHOLD = 4096

# The BACKWARD can switch to the KV-tiled kernels earlier than the
# forward: the resident bwd kernels are the reason the train K tile is
# clamped to 512 at 1024 < T <= _KV_TILE_THRESHOLD (see the clamp in
# multi_stream_flash_attention_bh), while the tiled bwd holds only
# O(block) state and keeps the 1024-wide tile that measured +24-29% in
# bare-op sweeps. Kept equal to _KV_TILE_THRESHOLD by default. Lowering
# this knob to a value V routes the region V < T <= _KV_TILE_THRESHOLD
# backward through the tiled kernels (the dispatch is `T > threshold`,
# so e.g. V=1024 moves T=2048/4096 off the resident backward; T <= V
# stays resident and clamped).
_BWD_KV_TILE_THRESHOLD = _KV_TILE_THRESHOLD


def _tiled_fwd_kernel(
    q_ref,  # (1, S, block_q, d)    constant over the k grid dim
    k_ref,  # (1, S, block_k, d)    streamed
    v_ref,  # (1, block_k, dv)      streamed
    off_ref,  # (1, 1) float32 SMEM
    seed_ref,  # (1, 2) float32 SMEM: dropout seed (unread when rate == 0)
    *refs,  # [c_ref if emit_combined] outputs [out][oall, lse] then
    #         scratch: m (S, block_q), l (S, block_q), acc (S, block_q, dv)
    save_residuals: bool,
    emit_combined: bool,
    dropout_rate: float = 0.0,
):
    if emit_combined:
        c_ref, *rest = refs
    else:
        c_ref, rest = None, list(refs)
    m_scr, l_scr, acc_scr = rest[-3:]
    outs = rest[:-3]

    S, block_q, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    block_k = k_ref.shape[2]
    bh = pl.program_id(0)  # read outside pl.when: the interpreter cannot
    j = pl.program_id(2)   # lower program_id from inside a when-body
    nk = pl.num_programs(2)
    q_start = pl.program_id(1) * block_q
    off = off_ref[0, 0].astype(jnp.int32)
    scale = 1.0 / math.sqrt(d)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_k <= q_start + block_q - 1 + off)
    def _():
        q = q_ref[0]
        k_j = k_ref[0]
        v_j = v_ref[0]
        s, _ = _masked_scores(q, k_j, q_start, j * block_k, off, scale)
        m = m_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, :, None])
        # normalizer accumulates the UNdropped p (softmax then dropout)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1)
        p_pv = p
        if dropout_rate > 0.0:
            keep = _keep_mask_block(
                seed_ref, bh, S, q_start, j * block_k,
                block_q, block_k, dropout_rate, off,
            )
            p_pv = _apply_keep(p, keep, dropout_rate)
        pv = jax.lax.dot_general(
            p_pv.astype(v_j.dtype), v_j,
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha[:, :, None] + pv
        m_scr[:] = m_new

    @pl.when(j == nk - 1)
    def _():
        l_safe = jnp.maximum(l_scr[:], 1e-30)
        o_s = acc_scr[:] / l_safe[:, :, None]
        rest_outs = list(outs)
        if emit_combined:
            out_ref = rest_outs[0]
            combined = c_ref[bh, 0] * o_s[0]
            for s_i in range(1, S):
                combined += c_ref[bh, s_i] * o_s[s_i]
            out_ref[0] = combined.astype(out_ref.dtype)
            rest_outs = rest_outs[1:]
        if save_residuals:
            oall_ref, lse_ref = rest_outs
            oall_ref[0] = o_s.astype(oall_ref.dtype)
            lse_ref[0] = (m_scr[:] + jnp.log(l_safe)).astype(lse_ref.dtype)


def _tiled_fwd_call(
    q, k, v, offset, coeffs, *,
    block_q, block_k, save_residuals, emit_combined, interpret,
    dropout_seed=None, dropout_rate: float = 0.0,
):
    BH, S, T, d = q.shape
    dv = v.shape[-1]
    nq, nk = T // block_q, T // block_k
    seed = (
        dropout_seed
        if dropout_seed is not None
        else jnp.zeros((1, 2), jnp.float32)
    )
    in_specs = [
        pl.BlockSpec((1, S, block_q, d), lambda b, i, j: (b, 0, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, S, block_k, d), lambda b, i, j: (b, 0, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1), lambda b, i, j: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 2), lambda b, i, j: (0, 0), memory_space=pltpu.SMEM),
    ]
    inputs = [q, k, v, offset, seed]
    if emit_combined:
        in_specs.append(
            pl.BlockSpec((BH, S), lambda b, i, j: (0, 0),
                         memory_space=pltpu.SMEM)
        )
        inputs.append(coeffs)
    out_shapes, out_specs = [], []
    if emit_combined:
        out_shapes.append(jax.ShapeDtypeStruct((BH, T, dv), q.dtype))
        out_specs.append(
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM)
        )
    if save_residuals:
        out_shapes += [
            jax.ShapeDtypeStruct((BH, S, T, dv), q.dtype),
            jax.ShapeDtypeStruct((BH, S, T), jnp.float32),
        ]
        out_specs += [
            pl.BlockSpec((1, S, block_q, dv), lambda b, i, j: (b, 0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ]
    results = pl.pallas_call(
        functools.partial(
            _tiled_fwd_kernel, save_residuals=save_residuals,
            emit_combined=emit_combined, dropout_rate=dropout_rate,
        ),
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((S, block_q), jnp.float32),
            pltpu.VMEM((S, block_q), jnp.float32),
            pltpu.VMEM((S, block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name=kernel_names.FLASH_FWD_TILED,
        interpret=interpret,
    )(*inputs)
    return results


def _tiled_dq_kernel(
    q_ref,  # (1, S, block_q, d)
    k_ref,  # (1, S, block_k, d)  streamed
    v_ref,  # (1, block_k, dv)    streamed
    do_ref,  # (1, block_q, dv) factored shared g | (1, S, block_q, dv)
    #          legacy (see _bwd_dq_kernel)
    lse_ref,  # (1, S, block_q)
    delta_ref,  # (1, S, block_q)
    off_ref,  # (1, 1) SMEM
    seed_ref,  # (1, 2) SMEM dropout seed
    c_ref,  # (BH, S) float32 SMEM combine coeffs (read only when factored)
    dq_ref,  # (1, S, block_q, d)
    dq_scr,  # (S, block_q, d) f32 scratch
    *,
    dropout_rate: float = 0.0,
    factored: bool = False,
):
    S, block_q, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    block_k = k_ref.shape[2]
    bh_id = pl.program_id(0)  # top-level read (see _tiled_fwd_kernel note)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = pl.program_id(1) * block_q
    off = off_ref[0, 0].astype(jnp.int32)
    scale = 1.0 / math.sqrt(d)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(j * block_k <= q_start + block_q - 1 + off)
    def _():
        q = q_ref[0]
        k_j = k_ref[0]
        v_j = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s, keep = _masked_scores(q, k_j, q_start, j * block_k, off, scale)
        p = jnp.where(keep, jnp.exp(s - lse[:, :, None]), 0.0)
        if factored:
            dp = _scale_streams(
                jax.lax.dot_general(
                    do, v_j,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ),
                c_ref, bh_id, S,
            )  # one matmul, per-stream scalar scale
        else:
            dp = jax.lax.dot_general(
                do, v_j,
                dimension_numbers=(((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        if dropout_rate > 0.0:
            # dP arrives through the dropout: dP~ = mask/keep * (dO V^T)
            dkeep = _keep_mask_block(
                seed_ref, bh_id, S, q_start, j * block_k,
                block_q, block_k, dropout_rate, off,
            )
            dp = _apply_keep(dp, dkeep, dropout_rate)
        ds = p * (dp - delta[:, :, None])
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k_j.dtype), k_j,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _tiled_dkv_kernel(
    q_ref,  # (1, S, block_q, d)  streamed (innermost grid dim)
    k_ref,  # (1, S, block_k, d)
    v_ref,  # (1, block_k, dv)
    do_ref,  # (1, block_q, dv) factored shared g | (1, S, block_q, dv)
    #          legacy — streamed either way (see _bwd_dq_kernel)
    lse_ref,  # (1, S, block_q)    streamed
    delta_ref,  # (1, S, block_q)  streamed
    off_ref,  # (1, 1) SMEM
    seed_ref,  # (1, 2) SMEM dropout seed
    c_ref,  # (BH, S) float32 SMEM combine coeffs (read only when factored)
    dk_ref,  # (1, S, block_k, d)
    dv_ref,  # (1, block_k, dv)
    dk_scr,  # (S, block_k, d) f32
    dv_scr,  # (block_k, dv) f32
    *,
    dropout_rate: float = 0.0,
    factored: bool = False,
):
    S, block_k, d = k_ref.shape[1], k_ref.shape[2], k_ref.shape[3]
    block_q = q_ref.shape[2]
    bh_id = pl.program_id(0)  # top-level read (see _tiled_fwd_kernel note)
    i = pl.program_id(2)
    nq = pl.num_programs(2)
    k_start = pl.program_id(1) * block_k
    off = off_ref[0, 0].astype(jnp.int32)
    scale = 1.0 / math.sqrt(d)

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(i * block_q + block_q - 1 + off >= k_start)
    def _():
        q_i = q_ref[0]
        k = k_ref[0]
        lse_i = lse_ref[0]
        delta_i = delta_ref[0]
        s, keep = _masked_scores(q_i, k, i * block_q, k_start, off, scale)
        p = jnp.where(keep, jnp.exp(s - lse_i[:, :, None]), 0.0)
        p_v = p
        dkeep = None
        if dropout_rate > 0.0:
            dkeep = _keep_mask_block(
                seed_ref, bh_id, S, i * block_q, k_start,
                block_q, block_k, dropout_rate, off,
            )
            p_v = _apply_keep(p, dkeep, dropout_rate)  # dropped map P~
        if factored:
            g_i = do_ref[0]  # (block_q, dv)
            # dV = (sum_s c_s P~_s)^T g: VPU combine, one matmul
            p_c = _combine_streams(p_v, c_ref, bh_id, S).astype(g_i.dtype)
            dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
                p_c, g_i,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = _scale_streams(
                jax.lax.dot_general(
                    g_i, v_ref[0],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ),
                c_ref, bh_id, S,
            )
        else:
            do_i = do_ref[0]
            p_lo = p_v.astype(do_i.dtype)
            dv_acc = dv_scr[:]
            for s_idx in range(S):
                # dV = sum_s P~_s^T dO_s (coeff already folded into dO_s)
                dv_acc = dv_acc + jax.lax.dot_general(
                    p_lo[s_idx], do_i[s_idx],
                    dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            dv_scr[:] = dv_acc
            dp = jax.lax.dot_general(
                do_i, v_ref[0],
                dimension_numbers=(((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        if dropout_rate > 0.0:
            dp = _apply_keep(dp, dkeep, dropout_rate)
        ds = p * (dp - delta_i[:, :, None])
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q_i.dtype), q_i,
            dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _tiled_bwd_call(
    q, k, v, do_s, lse, delta, offset, *, block_q, block_k, interpret,
    dropout_seed=None, dropout_rate: float = 0.0, coeffs=None,
):
    BH, S, T, d = q.shape
    dv_width = v.shape[-1]
    nq, nk = T // block_q, T // block_k
    factored = coeffs is not None
    c_arr = (
        coeffs.astype(jnp.float32)
        if factored
        else jnp.zeros((BH, S), jnp.float32)
    )
    seed = (
        dropout_seed
        if dropout_seed is not None
        else jnp.zeros((1, 2), jnp.float32)
    )
    off_spec = pl.BlockSpec((1, 1), lambda b, x, y: (0, 0),
                            memory_space=pltpu.SMEM)
    seed_spec = pl.BlockSpec((1, 2), lambda b, x, y: (0, 0),
                             memory_space=pltpu.SMEM)
    c_spec = pl.BlockSpec((BH, S), lambda b, x, y: (0, 0),
                          memory_space=pltpu.SMEM)
    if factored:
        do_spec_q = pl.BlockSpec((1, block_q, dv_width),
                                 lambda b, i, j: (b, i, 0),
                                 memory_space=pltpu.VMEM)
        do_spec_kv = pl.BlockSpec((1, block_q, dv_width),
                                  lambda b, j, i: (b, i, 0),
                                  memory_space=pltpu.VMEM)
    else:
        do_spec_q = pl.BlockSpec((1, S, block_q, dv_width),
                                 lambda b, i, j: (b, 0, i, 0),
                                 memory_space=pltpu.VMEM)
        do_spec_kv = pl.BlockSpec((1, S, block_q, dv_width),
                                  lambda b, j, i: (b, 0, i, 0),
                                  memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(
            _tiled_dq_kernel, dropout_rate=dropout_rate, factored=factored
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, S, block_q, d), lambda b, i, j: (b, 0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, block_k, d), lambda b, i, j: (b, 0, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv_width), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            do_spec_q,
            pl.BlockSpec((1, S, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            off_spec,
            seed_spec,
            c_spec,
        ],
        out_specs=pl.BlockSpec((1, S, block_q, d), lambda b, i, j: (b, 0, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, S, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((S, block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name=kernel_names.FLASH_BWD_DQ_TILED,
        interpret=interpret,
    )(q, k, v, do_s, lse, delta, offset, seed, c_arr)

    dk, dv = pl.pallas_call(
        functools.partial(
            _tiled_dkv_kernel, dropout_rate=dropout_rate, factored=factored
        ),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, S, block_q, d), lambda b, j, i: (b, 0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, block_k, d), lambda b, j, i: (b, 0, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv_width), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            do_spec_kv,
            pl.BlockSpec((1, S, block_q), lambda b, j, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, block_q), lambda b, j, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
            off_spec,
            seed_spec,
            c_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, S, block_k, d), lambda b, j, i: (b, 0, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv_width), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, T, dv_width), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv_width), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name=kernel_names.FLASH_BWD_DKV_TILED,
        interpret=interpret,
    )(q, k, v, do_s, lse, delta, offset, seed, c_arr)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref,  # (1, S, block_q, d)
    k_ref,  # (1, S, T, d)
    v_ref,  # (1, T, dv)
    do_ref,  # FACTORED: (1, block_q, dv) shared upstream grad g — the
    #          per-stream grads differ only by the scalar combine
    #          coefficient (dO_s = c_s * g), so dP needs ONE g V^T matmul
    #          scaled per stream instead of S. LEGACY (ring path, where
    #          each stream output has its own cotangent):
    #          (1, S, block_q, dv), coeff folded in.
    lse_ref,  # (1, S, block_q)
    delta_ref,  # (1, S, block_q)     rowsum(dO_s * O_s)
    off_ref,  # (1, 1) float32 SMEM: causal row offset (0 = aligned causal;
    #           +-kTl for ring chunks whose K lives k shards away)
    seed_ref,  # (1, 2) float32 SMEM dropout seed
    c_ref,  # (BH, S) float32 SMEM combine coeffs (read only when factored)
    *refs,  # [bias_ref (block_q, T) if use_bias] then dq_ref (1, S, block_q, d)
    block_k: int,
    dropout_rate: float = 0.0,
    factored: bool = False,
    use_bias: bool = False,
):
    if use_bias:
        bias_ref, dq_ref = refs
    else:
        bias_ref, (dq_ref,) = None, refs
    S, block_q, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    T = k_ref.shape[2]
    nk = T // block_k
    bh_id = pl.program_id(0)  # top-level read (see _tiled_fwd_kernel note)
    i = pl.program_id(1)
    q_start = i * block_q
    off = off_ref[0, 0].astype(jnp.int32)

    q = q_ref[0]
    do = do_ref[0]  # (block_q, dv) factored | (S, block_q, dv) legacy
    lse = lse_ref[0]  # (S, block_q) f32
    delta = delta_ref[0]  # (S, block_q) f32
    scale = 1.0 / math.sqrt(d)

    def body(j, dq):
        def compute(dq):
            k_j = k_ref[0, :, pl.ds(j * block_k, block_k), :]
            v_j = v_ref[0, pl.ds(j * block_k, block_k), :]
            if use_bias:
                # masked entries carry s = NEG_INF, so exp(s - lse) is 0
                # without a select (lse is finite on every row that has
                # any visible key; fully-masked ring rows get p = 1 with
                # an lse that zeroes their chunk weight AND cotangents
                # exactly, so ds/dv contributions stay 0 — same as the
                # select path)
                s = _scores_plus_bias(
                    q, k_j, bias_ref[:, pl.ds(j * block_k, block_k)], scale
                )
                p = jnp.exp(s - lse[:, :, None])
            else:
                s, keep = _masked_scores(
                    q, k_j, q_start, j * block_k, off, scale
                )
                p = jnp.where(keep, jnp.exp(s - lse[:, :, None]), 0.0)
            if factored:
                dp = _scale_streams(
                    jax.lax.dot_general(
                        do, v_j,
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ),
                    c_ref, bh_id, S,
                )  # one matmul, per-stream scalar scale
            else:
                dp = jax.lax.dot_general(
                    do, v_j,
                    dimension_numbers=(((2,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (S, block_q, block_k)
            if dropout_rate > 0.0:
                # dP arrives through the dropout: dP~ = mask/keep * (dO V^T)
                dkeep = _keep_mask_block(
                    seed_ref, bh_id, S, q_start, j * block_k,
                    block_q, block_k, dropout_rate, off,
                )
                dp = _apply_keep(dp, dkeep, dropout_rate)
            ds = p * (dp - delta[:, :, None])
            return dq + jax.lax.dot_general(
                ds.astype(k_j.dtype), k_j,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale
        return jax.lax.cond(
            j * block_k <= q_start + block_q - 1 + off, compute, lambda x: x, dq
        )

    dq0 = jnp.zeros((S, block_q, d), jnp.float32)
    dq = jax.lax.fori_loop(0, nk, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref,  # (1, S, T, d)
    k_ref,  # (1, S, block_k, d)
    v_ref,  # (1, block_k, dv)
    do_ref,  # (1, T, dv) factored shared g | (1, S, T, dv) legacy
    #          (see _bwd_dq_kernel)
    lse_ref,  # (1, S, T)
    delta_ref,  # (1, S, T)
    off_ref,  # (1, 1) float32 SMEM causal row offset (see _bwd_dq_kernel)
    seed_ref,  # (1, 2) float32 SMEM dropout seed
    c_ref,  # (BH, S) float32 SMEM combine coeffs (read only when factored)
    *refs,  # [bias_ref (T, block_k) if use_bias] then outputs
    #         dk_ref (1, S, block_k, d), dv_ref (1, block_k, dv)
    block_q: int,
    dropout_rate: float = 0.0,
    factored: bool = False,
    use_bias: bool = False,
):
    if use_bias:
        bias_ref, dk_ref, dv_ref = refs
    else:
        bias_ref, (dk_ref, dv_ref) = None, refs
    S, block_k, d = k_ref.shape[1], k_ref.shape[2], k_ref.shape[3]
    T = q_ref.shape[2]
    dv_width = v_ref.shape[2]
    nq = T // block_q
    bh_id = pl.program_id(0)  # top-level read (see _tiled_fwd_kernel note)
    j = pl.program_id(1)
    k_start = j * block_k
    off = off_ref[0, 0].astype(jnp.int32)

    k = k_ref[0]  # (S, block_k, d)
    scale = 1.0 / math.sqrt(d)

    def body(i, carry):
        dk, dv = carry

        def compute(carry):
            dk, dv = carry
            q_i = q_ref[0, :, pl.ds(i * block_q, block_q), :]
            lse_i = lse_ref[0, :, pl.ds(i * block_q, block_q)]
            delta_i = delta_ref[0, :, pl.ds(i * block_q, block_q)]
            if use_bias:
                # no select: see the twin comment in _bwd_dq_kernel
                s = _scores_plus_bias(
                    q_i, k, bias_ref[pl.ds(i * block_q, block_q), :], scale
                )
                p = jnp.exp(s - lse_i[:, :, None])
            else:
                s, keep = _masked_scores(
                    q_i, k, i * block_q, k_start, off, scale
                )
                p = jnp.where(keep, jnp.exp(s - lse_i[:, :, None]), 0.0)
            p_v = p
            dkeep = None
            if dropout_rate > 0.0:
                dkeep = _keep_mask_block(
                    seed_ref, bh_id, S, i * block_q, k_start,
                    block_q, block_k, dropout_rate, off,
                )
                p_v = _apply_keep(p, dkeep, dropout_rate)  # dropped map P~
            if factored:
                g_i = do_ref[0, pl.ds(i * block_q, block_q), :]  # (bq, dv)
                # dV = sum_s P~_s^T (c_s g) = (sum_s c_s P~_s)^T g — the
                # stream combine is a cheap VPU sum, leaving ONE matmul
                p_c = _combine_streams(p_v, c_ref, bh_id, S).astype(g_i.dtype)
                dv_new = dv + jax.lax.dot_general(
                    p_c, g_i,
                    dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dp = _scale_streams(
                    jax.lax.dot_general(
                        g_i, v_ref[0],
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ),
                    c_ref, bh_id, S,
                )  # one matmul, per-stream scalar scale
            else:
                do_i = do_ref[0, :, pl.ds(i * block_q, block_q), :]
                p_lo = p_v.astype(do_i.dtype)
                # dV = sum_s P~_s^T dO_s (coeff already folded into dO_s).
                # Mosaic can't contract two dims at once, so loop streams
                # statically — S is tiny (1, 2, or n_terms).
                dv_new = dv
                for s_idx in range(S):
                    dv_new = dv_new + jax.lax.dot_general(
                        p_lo[s_idx], do_i[s_idx],
                        dimension_numbers=(((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                dp = jax.lax.dot_general(
                    do_i, v_ref[0],
                    dimension_numbers=(((2,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            if dropout_rate > 0.0:
                dp = _apply_keep(dp, dkeep, dropout_rate)
            ds = p * (dp - delta_i[:, :, None])
            dk_new = dk + jax.lax.dot_general(
                ds.astype(q_i.dtype), q_i,
                dimension_numbers=(((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale
            return dk_new, dv_new

        # skip Q blocks entirely before this K block (causal: no grad flows)
        return jax.lax.cond(i * block_q + block_q - 1 + off >= k_start, compute,
                            lambda c: c, carry)

    dk0 = jnp.zeros((S, block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, dv_width), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nq, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# Whole-T fused backward: the (S, T, T) fp32 score/prob/grad
# intermediates must fit VMEM simultaneously (~8 MB at S=2, T=512) — the
# budget scales with the STREAM COUNT, so ndiff's n_terms=4 only takes
# this path at shorter T; past the budget the two-kernel form streams
# blocks instead.
_FUSED_BWD_BUDGET = 2 * 512 * 512  # max S * T * T


def _use_fused_bwd(S: int, T: int) -> bool:
    return S * T * T <= _FUSED_BWD_BUDGET


def _bwd_fused_kernel(
    q_ref,  # (1, S, T, d)
    k_ref,  # (1, S, T, d)
    v_ref,  # (1, T, dv)
    g_ref,  # (1, T, dv) shared upstream grad (factored form only)
    lse_ref,  # (1, S, T)
    delta_ref,  # (1, S, T)
    seed_ref,  # (1, 2) float32 SMEM dropout seed
    c_ref,  # (BH, S) float32 SMEM combine coeffs
    bias_ref,  # (T, T) additive causal mask (aligned: off = 0)
    dq_ref,  # (1, S, T, d)
    dk_ref,  # (1, S, T, d)
    dv_ref,  # (1, T, dv)
    *,
    dropout_rate: float = 0.0,
):
    """dQ, dK, dV in ONE program per (b*H): within _FUSED_BWD_BUDGET the
    full score matrix fits VMEM, so the softmax recompute (the QK^T
    matmul, the exp — the kernels' VPU floor — and the dP matmul) runs
    ONCE instead of once in each of the dq and dkv kernels, and q/k/v/g
    are read once. Straight-line code, no grid loops: the whole
    backward for one head is a single fused region."""
    S, T, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    bh_id = pl.program_id(0)
    q = q_ref[0]  # (S, T, d)
    k = k_ref[0]
    v = v_ref[0]  # (T, dv)
    g = g_ref[0]  # (T, dv)
    lse = lse_ref[0]  # (S, T) f32
    delta = delta_ref[0]  # (S, T) f32
    scale = 1.0 / math.sqrt(d)

    s = _scores_plus_bias(q, k, bias_ref[:, :], scale)  # (S, T, T) f32
    p = jnp.exp(s - lse[:, :, None])  # masked entries -> exp(-1e30) = 0
    p_v = p
    dkeep = None
    if dropout_rate > 0.0:
        dkeep = _keep_mask_block(
            seed_ref, bh_id, S, 0, 0, T, T, dropout_rate, None
        )
        p_v = _apply_keep(p, dkeep, dropout_rate)  # dropped map P~
    # dV = (sum_s c_s P~_s)^T g — one matmul after the VPU stream combine
    p_c = _combine_streams(p_v, c_ref, bh_id, S).astype(g.dtype)
    dv_ref[0] = jax.lax.dot_general(
        p_c, g,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)
    # dP_s = c_s * (g V^T), computed once and scaled per stream
    dp = _scale_streams(
        jax.lax.dot_general(
            g, v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ),
        c_ref, bh_id, S,
    )
    if dropout_rate > 0.0:
        dp = _apply_keep(dp, dkeep, dropout_rate)
    ds = (p * (dp - delta[:, :, None])).astype(q.dtype)
    dq_ref[0] = (
        jax.lax.dot_general(
            ds, k,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
    ).astype(dq_ref.dtype)
    dk_ref[0] = (
        jax.lax.dot_general(
            ds, q,
            dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
    ).astype(dk_ref.dtype)


def _fused_bwd_call(
    q, k, v, g, lse, delta, *, interpret,
    dropout_seed=None, dropout_rate: float = 0.0, coeffs=None,
):
    BH, S, T, d = q.shape
    dv_width = v.shape[-1]
    seed = (
        dropout_seed
        if dropout_seed is not None
        else jnp.zeros((1, 2), jnp.float32)
    )
    def spec4(shape):
        return pl.BlockSpec(shape, lambda b: (b, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    def spec3(shape):
        return pl.BlockSpec(shape, lambda b: (b, 0, 0),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, dropout_rate=dropout_rate),
        grid=(BH,),
        in_specs=[
            spec4((1, S, T, d)),
            spec4((1, S, T, d)),
            spec3((1, T, dv_width)),
            spec3((1, T, dv_width)),
            spec3((1, S, T)),
            spec3((1, S, T)),
            pl.BlockSpec((1, 2), lambda b: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((BH, S), lambda b: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((T, T), lambda b: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            spec4((1, S, T, d)),
            spec4((1, S, T, d)),
            spec3((1, T, dv_width)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, S, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, T, dv_width), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        name=kernel_names.FLASH_BWD_FUSED,
        interpret=interpret,
    )(q, k, v, g, lse, delta, seed, coeffs.astype(jnp.float32),
      causal_bias(T, 0))


def _bwd_call(
    q, k, v, do_s, lse, delta, offset=None, *,
    block_q: int, block_k: int, interpret: bool,
    dropout_seed=None, dropout_rate: float = 0.0, coeffs=None,
):
    """``coeffs`` (BH, S) switches the kernels to the FACTORED form:
    ``do_s`` is then the SHARED upstream grad g of shape (BH, T, dv) and
    the per-stream grads are recovered in-kernel as c_s * g — one dP/dV
    matmul instead of S, and S-fold less dO streamed. ``coeffs=None`` is
    the legacy per-stream form (the ring path's chunk cotangents cannot
    factor)."""
    BH, S, T, d = q.shape
    dv_width = v.shape[-1]
    nq, nk = T // block_q, T // block_k
    factored = coeffs is not None
    aligned = offset is None  # the main (non-ring) path: causal off = 0
    if offset is None:
        offset = jnp.zeros((1, 1), jnp.float32)
    seed = (
        dropout_seed
        if dropout_seed is not None
        else jnp.zeros((1, 2), jnp.float32)
    )
    if aligned and factored and _use_fused_bwd(S, T):
        # whole-T single-program backward: one softmax recompute serves
        # dq, dk AND dv (see _bwd_fused_kernel)
        return _fused_bwd_call(
            q, k, v, do_s, lse, delta, interpret=interpret,
            dropout_seed=seed, dropout_rate=dropout_rate, coeffs=coeffs,
        )
    if T > _BWD_KV_TILE_THRESHOLD:
        return _tiled_bwd_call(
            q, k, v, do_s, lse, delta, offset,
            block_q=block_q, block_k=block_k, interpret=interpret,
            dropout_seed=seed, dropout_rate=dropout_rate, coeffs=coeffs,
        )
    c_arr = (
        coeffs.astype(jnp.float32)
        if factored
        else jnp.zeros((BH, S), jnp.float32)
    )
    use_bias = T <= _BIAS_MAX_T
    bias = causal_bias(T, offset[0, 0].astype(jnp.int32)) if use_bias else None
    off_spec = pl.BlockSpec((1, 1), lambda b, i: (0, 0), memory_space=pltpu.SMEM)
    seed_spec = pl.BlockSpec((1, 2), lambda b, i: (0, 0), memory_space=pltpu.SMEM)
    c_spec = pl.BlockSpec((BH, S), lambda b, i: (0, 0), memory_space=pltpu.SMEM)
    if factored:
        do_spec_q = pl.BlockSpec((1, block_q, dv_width),
                                 lambda b, i: (b, i, 0),
                                 memory_space=pltpu.VMEM)
        do_spec_kv = pl.BlockSpec((1, T, dv_width), lambda b, j: (b, 0, 0),
                                  memory_space=pltpu.VMEM)
    else:
        do_spec_q = pl.BlockSpec((1, S, block_q, dv_width),
                                 lambda b, i: (b, 0, i, 0),
                                 memory_space=pltpu.VMEM)
        do_spec_kv = pl.BlockSpec((1, S, T, dv_width), lambda b, j: (b, 0, 0, 0),
                                  memory_space=pltpu.VMEM)

    dq_in_specs = [
        pl.BlockSpec((1, S, block_q, d), lambda b, i: (b, 0, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, S, T, d), lambda b, i: (b, 0, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, T, dv_width), lambda b, i: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        do_spec_q,
        pl.BlockSpec((1, S, block_q), lambda b, i: (b, 0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, S, block_q), lambda b, i: (b, 0, i),
                     memory_space=pltpu.VMEM),
        off_spec,
        seed_spec,
        c_spec,
    ]
    dq_inputs = [q, k, v, do_s, lse, delta, offset, seed, c_arr]
    if use_bias:
        dq_in_specs.append(
            pl.BlockSpec((block_q, T), lambda b, i: (i, 0),
                         memory_space=pltpu.VMEM)
        )
        dq_inputs.append(bias)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_k=block_k, dropout_rate=dropout_rate,
            factored=factored, use_bias=use_bias,
        ),
        grid=(BH, nq),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, S, block_q, d), lambda b, i: (b, 0, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, S, T, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name=kernel_names.FLASH_BWD_DQ,
        interpret=interpret,
    )(*dq_inputs)

    dkv_in_specs = [
        pl.BlockSpec((1, S, T, d), lambda b, j: (b, 0, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, S, block_k, d), lambda b, j: (b, 0, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv_width), lambda b, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        do_spec_kv,
        pl.BlockSpec((1, S, T), lambda b, j: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, S, T), lambda b, j: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        off_spec,
        seed_spec,
        c_spec,
    ]
    dkv_inputs = [q, k, v, do_s, lse, delta, offset, seed, c_arr]
    if use_bias:
        dkv_in_specs.append(
            pl.BlockSpec((T, block_k), lambda b, j: (0, j),
                         memory_space=pltpu.VMEM)
        )
        dkv_inputs.append(bias)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, dropout_rate=dropout_rate,
            factored=factored, use_bias=use_bias,
        ),
        grid=(BH, nk),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, S, block_k, d), lambda b, j: (b, 0, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv_width), lambda b, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, T, dv_width), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name=kernel_names.FLASH_BWD_DKV,
        interpret=interpret,
    )(*dkv_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper over (BH, S, T, d) layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, coeffs, seed, blocks, interpret, rate=0.0):
    """``blocks`` = (block_q, block_k, block_q_train, block_k_train).
    The inference primal and the differentiated path want different
    tilings, so they are tuned independently. ``seed`` is the (1, 2)
    float32 dropout seed (dropout_seed_from_rng); ``rate`` the static
    attention-prob dropout rate — both forward and backward regenerate
    the same counter-based masks from (seed, global coords)."""
    out, _, _ = _fwd_call(
        q, k, v, coeffs,
        block_q=blocks[0], block_k=blocks[1],
        save_residuals=False, interpret=interpret,
        dropout_seed=seed, dropout_rate=rate,
    )
    return out


def _flash_fwd(q, k, v, coeffs, seed, blocks, interpret, rate=0.0):
    out, o_all, lse = _fwd_call(
        q, k, v, coeffs,
        block_q=blocks[2], block_k=blocks[3],
        save_residuals=True, interpret=interpret,
        dropout_seed=seed, dropout_rate=rate,
    )
    return out, (q, k, v, coeffs, seed, o_all, lse)


def _flash_bwd(blocks, interpret, rate, res, g):
    q, k, v, coeffs, seed, o_all, lse = res
    g32 = g.astype(jnp.float32)
    o32 = o_all.astype(jnp.float32)
    c32 = coeffs.astype(jnp.float32)
    # one contraction feeds both residual quantities:
    #   base[bh, s, t] = <g_t, O_s,t> over the head dim
    #   dcoeffs[bh, s] = <g, O_s>           = base.sum(t)
    #   delta_s        = rowsum(dO_s * O_s) = c_s * base  (dO_s = c_s g)
    # delta stays valid with dropout: rowsum(dP~ . P) = rowsum(dA . P~)
    # = rowsum(dO . O) since elementwise products commute — the same
    # residuals serve both regimes.
    base = jnp.einsum("btd,bstd->bst", g32, o32)
    dcoeffs = base.sum(-1)
    delta = base * c32[:, :, None]
    # FACTORED backward: the kernels take the shared g once and scale by
    # c_s in-SMEM — S-fold less dO traffic and one dP/dV matmul each
    # (the (BH, S, T, dv) do_s materialization this replaced was also
    # pure HBM waste)
    dq, dk, dv = _bwd_call(
        q, k, v, g.astype(q.dtype), lse, delta,
        block_q=blocks[2], block_k=blocks[3], interpret=interpret,
        dropout_seed=seed, dropout_rate=rate, coeffs=c32,
    )
    return dq, dk, dv, dcoeffs.astype(coeffs.dtype), jnp.zeros_like(seed)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Chunk op: per-stream (O_s, lse_s) with a causal row offset — the building
# block for ring (sequence-parallel) flash attention
# ---------------------------------------------------------------------------


def _chunk_fwd_call(q, k, v, offset, *, block_q, block_k, interpret,
                    dropout_seed=None, dropout_rate: float = 0.0):
    """Per-stream (o_all, lse) with offset-causal masking — the unified
    forward kernel in its no-combine mode. off = +Tl*k means K lives k
    shards earlier in the ring (fully visible once off >= T); large
    negative off masks everything (the chunk then contributes weight
    exp(-inf) = 0 at merge time)."""
    BH, S, T, d = q.shape
    dv = v.shape[-1]
    nq = T // block_q
    seed = (
        dropout_seed
        if dropout_seed is not None
        else jnp.zeros((1, 2), jnp.float32)
    )
    if T > _KV_TILE_THRESHOLD:
        return _tiled_fwd_call(
            q, k, v, offset, None,
            block_q=block_q, block_k=block_k,
            save_residuals=True, emit_combined=False, interpret=interpret,
            dropout_seed=seed, dropout_rate=dropout_rate,
        )
    use_bias = T <= _BIAS_MAX_T
    in_specs = [
        pl.BlockSpec((1, S, block_q, d), lambda b, i: (b, 0, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, S, T, d), lambda b, i: (b, 0, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, T, dv), lambda b, i: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1), lambda b, i: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 2), lambda b, i: (0, 0), memory_space=pltpu.SMEM),
    ]
    inputs = [q, k, v, offset, seed]
    if use_bias:
        # the bias bakes the TRACED ring offset in — computed once per
        # chunk call instead of per (b*H) program
        in_specs.append(
            pl.BlockSpec((block_q, T), lambda b, i: (i, 0),
                         memory_space=pltpu.VMEM)
        )
        inputs.append(causal_bias(T, offset[0, 0].astype(jnp.int32)))
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_k=block_k, save_residuals=True,
            emit_combined=False, dropout_rate=dropout_rate,
            use_bias=use_bias,
        ),
        grid=(BH, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, S, block_q, dv), lambda b, i: (b, 0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, block_q), lambda b, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, T, dv), q.dtype),
            jax.ShapeDtypeStruct((BH, S, T), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name=kernel_names.FLASH_FWD_CHUNK,
        interpret=interpret,
    )(*inputs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_chunk_attention(q, k, v, offset, seed, blocks, interpret, rate=0.0):
    """Per-stream offset-causal flash chunk: ``(O_s, lse_s)`` for
    ``O_s = [dropout](softmax(Q_s K_s^T / sqrt(d) + offset-causal
    mask)) @ V``.

    q/k: (BH, S, T, d); v: (BH, T, dv); offset: (1, 1) float32 (traced —
    inside a shard_map ring it is a function of axis_index); ``seed`` a
    (1, 2) float32 dropout seed (zeros when rate == 0). Returns
    (o_all (BH, S, T, dv), lse (BH, S, T)); lse accumulates the UNdropped
    probabilities, so chunks still combine exactly via the running
    logsumexp merge (parallel/ring.py) — softmax-then-dropout semantics
    globally. Dropout masks hash (row, col - off), which is unique per
    (q, k) pair across the ring rotation on a given device."""
    return _chunk_fwd_call(
        q, k, v, offset, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret, dropout_seed=seed, dropout_rate=rate,
    )


def _flash_chunk_fwd(q, k, v, offset, seed, blocks, interpret, rate=0.0):
    o_all, lse = _chunk_fwd_call(
        q, k, v, offset, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret, dropout_seed=seed, dropout_rate=rate,
    )
    return (o_all, lse), (q, k, v, offset, seed, o_all, lse)


def _flash_chunk_bwd(blocks, interpret, rate, res, ct):
    q, k, v, offset, seed, o_all, lse = res
    do, dlse = ct  # cotangents for both outputs
    do32 = do.astype(jnp.float32)
    # dS = P * (dP_raw - delta + dlse): the lse cotangent folds into the
    # delta term of the standard flash backward (dlse_i distributes over the
    # row's probabilities). With dropout, only the dP term is masked (the
    # lse path sees undropped probabilities), which the kernels implement.
    delta_eff = (
        jnp.einsum("bstd,bstd->bst", do32, o_all.astype(jnp.float32))
        - dlse.astype(jnp.float32)
    )
    dq, dk, dv = _bwd_call(
        q, k, v, do.astype(q.dtype), lse, delta_eff, offset,
        block_q=blocks[2], block_k=blocks[3], interpret=interpret,
        dropout_seed=seed, dropout_rate=rate,
    )
    return dq, dk, dv, jnp.zeros_like(offset), jnp.zeros_like(seed)


flash_chunk_attention.defvjp(_flash_chunk_fwd, _flash_chunk_bwd)


# ---------------------------------------------------------------------------
# Public API — model-facing layouts (matching ops/attention.py conventions)
# ---------------------------------------------------------------------------


def multi_stream_flash_attention(
    qs: jnp.ndarray,  # (S, B, T, H, d)
    ks: jnp.ndarray,  # (S, B, T, H, d)
    v: jnp.ndarray,  # (B, T, H, dv)
    coeffs: jnp.ndarray,  # (S, H) float32
    *,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_q_train: Optional[int] = None,
    block_k_train: Optional[int] = None,
    interpret: Optional[bool] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """Fused causal attention: ``sum_s coeffs[s,h] * softmax(Q_s K_s^T /
    sqrt(d)) @ V`` without materializing any T x T map. Returns
    (B, T, H, dv).

    ``dropout_rate`` > 0 with a ``dropout_rng`` key applies attention-
    probability dropout INSIDE the kernel (each softmax map dropped
    independently after normalization, inverted scaling — the reference
    semantics, diff_transformer.py:58-67) via a counter-based hash of the
    global (stream, b*H+h, row, col) position, so forward and backward
    regenerate identical masks and no T x T mask is ever materialized.
    Without a key the rate is inert (eval semantics, like ops/dropout.py).

    Block defaults resolve per device kind (:func:`default_blocks`) with
    one T-dependent cap below. On v5e the tuned tiles are (512, 1024)
    for the no-grad primal and for the training path — the 1024-wide K
    train tile became compilable once the kernels switched to bf16 MXU
    operands (half the VMEM per tile) and measured 5-29% faster than
    512-square in bare-op sweeps (tools/flash_sweep.py). BUT in the
    RESIDENT backward region (1024 < T <= _BWD_KV_TILE_THRESHOLD, where
    the bwd kernels hold full-T q/do) the wide tile exhausts v5e's scoped
    VMEM under the full model, so the default train K tile is capped to
    512 there; the KV-tiled kernels past the threshold hold O(block)
    state and keep the wide tile. Unknown TPU kinds fall back to
    256-tiles."""
    if interpret is None:
        interpret = _auto_interpret()
    S, B, T, H, d = qs.shape
    dv = v.shape[-1]
    # (S, B, T, H, d) -> (B*H, S, T, d)
    q_r = qs.transpose(1, 3, 0, 2, 4).reshape(B * H, S, T, d)
    k_r = ks.transpose(1, 3, 0, 2, 4).reshape(B * H, S, T, d)
    v_r = v.transpose(0, 2, 1, 3).reshape(B * H, T, dv)
    out = multi_stream_flash_attention_bh(
        q_r, k_r, v_r, coeffs, B, H,
        block_q=block_q, block_k=block_k,
        block_q_train=block_q_train, block_k_train=block_k_train,
        interpret=interpret,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng,
    )  # (BH, T, dv)
    return out.reshape(B, H, T, dv).transpose(0, 2, 1, 3)


def multi_stream_flash_attention_bh(
    q_r: jnp.ndarray,  # (B*H, S, T, d) — the kernel's native layout
    k_r: jnp.ndarray,  # (B*H, S, T, d)
    v_r: jnp.ndarray,  # (B*H, T, dv)
    coeffs: jnp.ndarray,  # (S, H) float32
    B: int,
    H: int,
    *,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_q_train: Optional[int] = None,
    block_k_train: Optional[int] = None,
    interpret: Optional[bool] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """:func:`multi_stream_flash_attention` taking the kernel's native
    (B*H, S, T, d) layout directly and returning (B*H, T, dv). Callers
    that can emit their projections in this layout (einsum
    ``"bte,sehd->bhstd"`` + free reshape) skip the materialized
    transposes of the (S, B, T, H, d) entry — profiled ~0.5-1 ms of copy
    ops at recipe scale (within run-to-run noise on the full step, but
    visible in the per-op trace)."""
    if interpret is None:
        interpret = _auto_interpret()
    dq, dk, dqt, dkt = default_blocks()
    BH, S, T, d = q_r.shape
    bkt = block_k_train if block_k_train is not None else dkt
    if 1024 < T <= _BWD_KV_TILE_THRESHOLD and block_k_train is None:
        # the RESIDENT backward kernels hold full-T q/do plus the K/V
        # block: with the 1024-wide train K tile their fp32 p/dp/ds
        # blocks exceed v5e's 16M scoped VMEM from T=2048 (measured
        # under the full model; the bare-op sweep happens to fit;
        # re-verified round 3 AFTER the factored backward halved the dO
        # traffic — the wide tile still fails to compile at T=2048, so
        # the clamp is not stale). The
        # KV-tiled kernels past _BWD_KV_TILE_THRESHOLD hold only O(block)
        # state, so they keep the wide tile; lowering that knob moves
        # this clamp region with it.
        bkt = min(bkt, 512)
    blocks = (
        _pick_block(block_q if block_q is not None else dq, T),
        _pick_block(block_k if block_k is not None else dk, T),
        _pick_block(block_q_train if block_q_train is not None else dqt, T),
        _pick_block(bkt, T),
    )
    c_r = jnp.broadcast_to(
        coeffs.astype(jnp.float32).T[None], (B, H, S)
    ).reshape(B * H, S)
    if dropout_rate > 0.0 and dropout_rng is not None:
        seed = dropout_seed_from_rng(dropout_rng)
        rate = float(dropout_rate)
    else:
        seed = jnp.zeros((1, 2), jnp.float32)
        rate = 0.0
    return _flash(q_r, k_r, v_r, c_r, seed, blocks, interpret, rate)


# ---------------------------------------------------------------------------
# Token-major (tm) kernels: per-stream (B, T, H, d) operands in and
# (B, T, H, dv) out — the PROJECTION-NATIVE layout.
#
# The head-major entry above needs its operands as (BH, S, T, d), but a
# projection matmul physically produces token-major data: x @ W is
# (B, T, H*d), and the transpose to head-major is a materialized XLA copy
# (~660 MB/step HBM->HBM at recipe scale, per-op profile round 4). Worse,
# the head-major ATTENTION OUTPUT makes the downstream GroupLayerNorm
# reduce over a strided concat dim (measured 4.5 ms/step of stat reduces
# alone) and the out-projection re-transpose. These kernels instead read
# per-stream token-major arrays directly via squeezed BlockSpec dims
# (block (None, bq, None, d) on a (B, T, H, d) array -> a clean (bq, d)
# VMEM tile DMA'd with an H*d row stride) and write the output token-major,
# so the whole attention block — projections, kernel, GLN, out-proj, and
# every gradient — runs transpose-free.
#
# Scope (use_tm): the recipe-hot region only — dropout 0.0, T small enough
# for the additive-bias resident forward AND the fused whole-T backward
# (T and S within the _TM_BWD_MAX_* envelope). Everything else (long context,
# dropout, ring chunks) stays on the head-major path; dispatch via use_tm.
# ---------------------------------------------------------------------------

# Whole-T tm backward admission, SEPARATE from the head-major
# _FUSED_BWD_BUDGET. Two measured walls (round 5, v5e, recipe widths):
#   - streams scale gently: the kernel walks (head, stream) pairs
#     sequentially, so S only grows the resident per-stream q/k/dq/dk
#     arrays (~0.4 MB each) — S=4 at T=512 compiles and runs inside
#     _TM_VMEM_LIMIT with 256-row forward blocks (the r4 2*512*512 cap
#     was a holdover from the head-major straight-line kernel, not a tm
#     measurement), so ndiff's n_terms=4 recipe dispatches token-major
#     like diff/control instead of paying the bh transpose copies;
#   - T scales hard: the backward's T x T fp32 score/prob transients are
#     duplicated across the unrolled head loop, so T=1024 at S=1 blows
#     scoped VMEM (73 MB measured). T stays capped at 512; longer T
#     belongs to the head-major / KV-tiled paths.
_TM_BWD_MAX_T = 512
_TM_BWD_MAX_S = 4


def use_tm(S: int, T: int, rate: float) -> bool:
    """True when the token-major kernels cover this config: no attention
    dropout (the tm kernels drop the counter-based mask machinery), the
    resident additive-bias forward applies, and the whole-T fused backward
    fits its measured VMEM envelope (see the admission constants above)."""
    return rate == 0.0 and T <= _TM_BWD_MAX_T and S <= _TM_BWD_MAX_S


def _tm_bias(T: int) -> jnp.ndarray:
    """bf16 additive causal mask for the tm kernels — half the VMEM of the
    fp32 :func:`causal_bias` (the kernels upcast when adding to the fp32
    scores; bf16 rounds NEG_INF to ~-1.0e30, still an exact zero after
    exp)."""
    return causal_bias(T, 0).astype(jnp.bfloat16)


def tm_rope_table(cos: jnp.ndarray, sin: jnp.ndarray, T: int) -> jnp.ndarray:
    """The tm kernels' rotation operand ``(2, T, d)`` float32 from the
    tables of ``ops/rope.py:rope_cos_sin`` (``(>=T, d/2)``): row 0 is
    ``[cos | cos]``, row 1 ``[-sin | sin]``, so that a head's features in
    ``half_split``'s order turn as ``x * t[0] + swap(x) * t[1]``
    (:func:`_tm_turn`), dimension i with i + d/2."""
    c, s = cos[:T].astype(jnp.float32), sin[:T].astype(jnp.float32)
    return jnp.stack([jnp.concatenate([c, c], -1),
                      jnp.concatenate([-s, s], -1)])


def _tm_half_swap(d: int, dtype) -> jnp.ndarray:
    """The ``(d, d)`` 0/1 matrix P with ``(x @ P)[:, j] = x[:, (j + d/2)
    % d]``: a head's two halves trade places on the MXU, exactly (one
    product a column). Slicing the halves and concatenating them swapped
    goes through the lane-rotate unit instead, which the softmax's row
    reductions already keep busy: 2.6 ms a recipe step slower (PERF.md
    section 6, PR 37)."""
    h = d // 2
    row = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    return (row == jnp.where(col < h, col + h, col - h)).astype(dtype)


def _tm_turn(x, rot_ref, swap, back: bool = False):
    """Rotate one head's ``(rows, d)`` tile in VMEM by the rows' angles
    (``rot_ref`` (2, rows, d), :func:`tm_rope_table`; ``swap``,
    :func:`_tm_half_swap`, in the dtype the tile is stored in): float32
    inside, cast back, as ``ops/rope.py`` rotates in HBM. ``back`` turns
    by the transposed rotation, which takes a gradient of the rotated
    tile to one of the tile as loaded; a float32 gradient rounds to the
    stored dtype first, as it does on its way to a rotation in HBM."""
    x = x.astype(swap.dtype)
    swapped = jax.lax.dot_general(
        x, swap, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if x.dtype == jnp.float32 else None),
    )
    side = swapped * rot_ref[1]
    main = x.astype(jnp.float32) * rot_ref[0]
    return (main - side if back else main + side).astype(x.dtype)


def _tm_fwd_kernel(
    *refs,
    S: int,
    H: int,
    save_residuals: bool,
    rope: bool = False,
):
    """Single-pass (full-T) forward over token-major refs, one program
    per (batch row, q block), all H heads in-program.

    refs: q_0..q_{S-1} (bq, H*d) | k_0..k_{S-1} (T, H*d) | v (T, H*dv) |
    bias (bq, T) bf16 | c (BH, S) SMEM [| rot_q (2, bq, d), rot_k (2, T, d)
    float32 when ``rope``: the q and k tiles turn as loaded, :func:`_tm_turn`]
    | out (bq, H*dv) [| oall (H, S, bq, dv), lse (bq, H*S) when
    save_residuals].

    The head dim rides FLATTENED into the lane dim (one lane slice per
    head) because Mosaic rejects sublane-strided stores of converted
    (f32 -> bf16) values — the (bq, H, d) mid-dim form fails with
    "infer-vector-layout: unsupported shape cast" at the output store,
    while lane slicing + a single concatenated store compiles (probed on
    v5e, round 4). The (head, stream) loops are statically unrolled —
    each iteration is a plain (bq, d) x (T, d) attention. K is full-T
    resident and T <= _BIAS_MAX_T, so the softmax needs no online block
    loop: one (bq, T) fp32 score pass per (head, stream). lse packs
    (head, stream) into ITS lane dim too ((bq, H*S), column h*S + s) —
    the (H, bq, S) form pads S=2 lanes to 128 and wastes ~1 MB of VMEM
    per buffer."""
    q_refs, refs = refs[:S], refs[S:]
    k_refs, refs = refs[:S], refs[S:]
    v_ref, bias_ref, c_ref, *outs = refs
    d = q_refs[0].shape[-1] // H
    if rope:
        rot_q, rot_k, *outs = outs
        swap = _tm_half_swap(d, q_refs[0].dtype)
    dv = v_ref.shape[-1] // H
    b = pl.program_id(0)
    scale = 1.0 / math.sqrt(d)
    bias = bias_ref[...].astype(jnp.float32)  # (bq, T)

    out_ref = outs[0]
    out_cols = []
    lse_cols = []
    for h in range(H):
        v_h = v_ref[:, h * dv : (h + 1) * dv]  # (T, dv)
        combined = None
        for s_i in range(S):
            q_h = q_refs[s_i][:, h * d : (h + 1) * d]  # (bq, d)
            k_h = k_refs[s_i][:, h * d : (h + 1) * d]  # (T, d)
            if rope:
                q_h = _tm_turn(q_h, rot_q, swap)
                k_h = _tm_turn(k_h, rot_k, swap)
            sm = jax.lax.dot_general(
                q_h, k_h,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale + bias  # (bq, T) f32
            m = jnp.max(sm, axis=-1, keepdims=True)  # (bq, 1)
            p = jnp.exp(sm - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            l_safe = jnp.maximum(l, 1e-30)
            pv = jax.lax.dot_general(
                p.astype(v_h.dtype), v_h,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (bq, dv)
            o_sh = pv / l_safe
            c_sh = c_ref[b * H + h, s_i]
            combined = (
                o_sh * c_sh if combined is None else combined + o_sh * c_sh
            )
            if save_residuals:
                oall_ref = outs[1]
                oall_ref[h, s_i] = o_sh.astype(oall_ref.dtype)
                lse_cols.append(m + jnp.log(l_safe))  # (bq, 1)
        out_cols.append(combined.astype(out_ref.dtype))
    out_ref[...] = jnp.concatenate(out_cols, axis=1)  # (bq, H*dv)
    if save_residuals:
        lse_ref = outs[2]
        lse_ref[...] = jnp.concatenate(lse_cols, axis=1)  # (bq, H*S) f32


def _tm_rot_specs(rot, block_q: int):
    """(specs, operands) of the rotation table for a forward call: the q
    block's rows and all of K's; none without a table."""
    if rot is None:
        return [], ()
    _, T, d = rot.shape
    return [
        pl.BlockSpec((2, block_q, d), lambda b, i: (0, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((2, T, d), lambda b, i: (0, 0, 0),
                     memory_space=pltpu.VMEM),
    ], (rot, rot)


def _tm_fwd_call(
    qs, ks, v, coeffs, rot, *, H: int, block_q: int, save_residuals: bool,
    interpret: bool
):
    """qs/ks: tuples of S (B, T, H*d) arrays (raw projection outputs);
    v (B, T, H*dv); coeffs (B*H, S) fp32; ``rot`` the rotation table
    (:func:`tm_rope_table`) or None; ``H`` static. Returns
    (out (B, T, H*dv) [, oall (B, H, S, T, dv), lse (B, T, H*S)])."""
    S = len(qs)
    B, T, Hd = qs[0].shape
    d = Hd // H
    dv = v.shape[-1] // H
    BH = B * H
    block_q = _pick_block(block_q, T)
    nq = T // block_q

    qspec = pl.BlockSpec(
        (None, block_q, H * d), lambda b, i: (b, i, 0),
        memory_space=pltpu.VMEM,
    )
    kspec = pl.BlockSpec(
        (None, T, H * d), lambda b, i: (b, 0, 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [qspec] * S + [kspec] * S + [
        pl.BlockSpec(
            (None, T, H * dv), lambda b, i: (b, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec((block_q, T), lambda b, i: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((BH, S), lambda b, i: (0, 0),
                     memory_space=pltpu.SMEM),
    ]
    rot_specs, rot_args = _tm_rot_specs(rot, block_q)
    in_specs += rot_specs
    out_shapes = [jax.ShapeDtypeStruct((B, T, H * dv), qs[0].dtype)]
    out_specs = [
        pl.BlockSpec(
            (None, block_q, H * dv), lambda b, i: (b, i, 0),
            memory_space=pltpu.VMEM,
        ),
    ]
    if save_residuals:
        out_shapes += [
            jax.ShapeDtypeStruct((B, H, S, T, dv), qs[0].dtype),
            jax.ShapeDtypeStruct((B, T, H * S), jnp.float32),
        ]
        out_specs += [
            pl.BlockSpec(
                (None, H, S, block_q, dv),
                lambda b, i: (b, 0, 0, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (None, block_q, H * S), lambda b, i: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
        ]
    results = pl.pallas_call(
        functools.partial(
            _tm_fwd_kernel, S=S, H=H, save_residuals=save_residuals,
            rope=rot is not None,
        ),
        grid=(B, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_TM_VMEM_LIMIT,
        ),
        name=kernel_names.FLASH_FWD_TM,
        interpret=interpret,
    )(*qs, *ks, v, _tm_bias(T), coeffs.astype(jnp.float32), *rot_args)
    if save_residuals:
        return results
    return results[0], None, None


def _tm_bwd_columns(
    q_refs, k_refs, v_ref, g_ref, lse_ref, delta_ref, c_ref, bias,
    *, S: int, H: int, s_list: tuple, out_dtype, rot_ref=None,
):
    """The factored whole-T backward math shared by the per-array and
    packed tm kernels: per (head, listed stream) gradient column groups.
    Returns (dq_cols, dk_cols, dv_cols) — dq_cols[j]/dk_cols[j] are
    h-ordered lists of (T, d) columns for stream s_list[j]; dv_cols is
    the h-ordered list of (T, dv) columns (dV summed over the listed
    streams). g V^T runs once per head and is scaled per stream; each
    stream's softmax recompute (the exp floor) happens exactly once.
    With ``rot_ref`` (2, T, d) the q and k tiles turn as loaded, as the
    forward's did, and dq and dk turn back before the cast, so the
    gradients are those of the tiles in HBM (:func:`_tm_turn`)."""
    d = q_refs[0].shape[-1] // H
    dv = v_ref.shape[-1] // H
    b = pl.program_id(0)
    scale = 1.0 / math.sqrt(d)

    if rot_ref is not None:
        swap = _tm_half_swap(d, out_dtype)

    def store(grad):  # a float32 (T, d) gradient of a head's q or k tile
        if rot_ref is None:
            return grad.astype(out_dtype)
        return _tm_turn(grad, rot_ref, swap, back=True)

    dq_cols = [[] for _ in s_list]
    dk_cols = [[] for _ in s_list]
    dv_cols = []
    for h in range(H):
        v_h = v_ref[:, h * dv : (h + 1) * dv]  # (T, dv)
        g_h = g_ref[:, h * dv : (h + 1) * dv]  # (T, dv)
        gv = jax.lax.dot_general(
            g_h, v_h,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (T, T) f32 — once per head, shared by every listed stream
        dv_h = None
        for j, s_idx in enumerate(s_list):
            col = h * S + s_idx
            lse_h = lse_ref[:, col : col + 1]  # (T, 1) f32
            delta_h = delta_ref[:, col : col + 1]  # (T, 1) f32
            q_h = q_refs[j][:, h * d : (h + 1) * d]  # (T, d)
            k_h = k_refs[j][:, h * d : (h + 1) * d]
            if rot_ref is not None:
                q_h = _tm_turn(q_h, rot_ref, swap)
                k_h = _tm_turn(k_h, rot_ref, swap)
            sm = jax.lax.dot_general(
                q_h, k_h,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale + bias
            p = jnp.exp(sm - lse_h)  # (T, T)
            c_sh = c_ref[b * H + h, s_idx]
            ds = (p * (gv * c_sh - delta_h)).astype(q_h.dtype)
            dq_cols[j].append(
                store(
                    jax.lax.dot_general(
                        ds, k_h,
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale
                )
            )
            dk_cols[j].append(
                store(
                    jax.lax.dot_general(
                        ds, q_h,
                        dimension_numbers=(((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale
                )
            )
            pc = p * c_sh
            dv_h = pc if dv_h is None else dv_h + pc
        dv_cols.append(
            jax.lax.dot_general(
                dv_h.astype(g_h.dtype), g_h,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(out_dtype)
        )
    return dq_cols, dk_cols, dv_cols


def _tm_bwd_rot_specs(rot):
    """(specs, operands) of the rotation table for a whole-T backward
    call; none without a table."""
    if rot is None:
        return [], ()
    return [pl.BlockSpec(rot.shape, lambda b: (0, 0, 0),
                         memory_space=pltpu.VMEM)], (rot,)


def _tm_bwd_kernel(*refs, S: int, H: int, s_list: tuple, rope: bool = False):
    """Whole-T backward for the streams in ``s_list`` over token-major
    refs, one program per batch row — the factored math of
    :func:`_bwd_fused_kernel` (see :func:`_tm_bwd_columns`); outputs are
    stored per-stream as lane concats (see _tm_fwd_kernel on why the
    mid-dim form cannot store).

    refs: q_s (T, H*d) per listed stream | k_s likewise | v (T, H*dv) |
    g (T, H*dv) | lse (T, H*S) | delta (T, H*S) | c (BH, S) SMEM |
    bias (T, T) bf16 [| rot (2, T, d) when ``rope``] | dq_s per stream |
    dk_s per stream | dv (T, H*dv)."""
    ns = len(s_list)
    q_refs, refs = refs[:ns], refs[ns:]
    k_refs, refs = refs[:ns], refs[ns:]
    (v_ref, g_ref, lse_ref, delta_ref, c_ref, bias_ref, *outs) = refs
    rot_ref = None
    if rope:
        rot_ref, *outs = outs
    dq_refs, dk_refs, dv_ref = outs[:ns], outs[ns : 2 * ns], outs[2 * ns]
    dq_cols, dk_cols, dv_cols = _tm_bwd_columns(
        q_refs, k_refs, v_ref, g_ref, lse_ref, delta_ref, c_ref,
        bias_ref[...].astype(jnp.float32),
        S=S, H=H, s_list=s_list, out_dtype=dq_refs[0].dtype, rot_ref=rot_ref,
    )
    for j in range(ns):
        dq_refs[j][...] = jnp.concatenate(dq_cols[j], axis=1)
        dk_refs[j][...] = jnp.concatenate(dk_cols[j], axis=1)
    dv_ref[...] = jnp.concatenate(dv_cols, axis=1)


def _tm_bwd_call(qs, ks, v, g, lse, delta, coeffs, rot, *, H: int,
                 interpret: bool):
    """qs/ks/v/g: flat (B, T, H*width); lse/delta: (B, T, H*S) fp32.
    All streams in ONE pallas call (the g V^T matmul then runs once per
    head): the call raises the kernel's scoped-VMEM budget via
    vmem_limit_bytes — the recipe-shape footprint is ~17-18 MB against
    the 16 MB default (measured round 4), comfortably inside v5e's
    physical VMEM. Returns per-stream flat token-major (dqs, dks, dv)."""
    S = len(qs)
    B, T, Hd = qs[0].shape
    Hdv = v.shape[-1]
    BH = B * H

    qspec = pl.BlockSpec(
        (None, T, Hd), lambda b: (b, 0, 0), memory_space=pltpu.VMEM
    )
    vspec = pl.BlockSpec(
        (None, T, Hdv), lambda b: (b, 0, 0), memory_space=pltpu.VMEM
    )
    stspec = pl.BlockSpec(
        (None, T, H * S), lambda b: (b, 0, 0), memory_space=pltpu.VMEM
    )
    rot_specs, rot_args = _tm_bwd_rot_specs(rot)
    results = pl.pallas_call(
        functools.partial(
            _tm_bwd_kernel, S=S, H=H, s_list=tuple(range(S)),
            rope=rot is not None,
        ),
        grid=(B,),
        in_specs=[qspec] * S + [qspec] * S + [
            vspec, vspec, stspec, stspec,
            pl.BlockSpec((BH, S), lambda b: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((T, T), lambda b: (0, 0),
                         memory_space=pltpu.VMEM),
        ] + rot_specs,
        out_specs=[qspec] * S + [qspec] * S + [vspec],
        out_shape=(
            [jax.ShapeDtypeStruct((B, T, Hd), qs[0].dtype)] * S
            + [jax.ShapeDtypeStruct((B, T, Hd), qs[0].dtype)] * S
            + [jax.ShapeDtypeStruct((B, T, Hdv), v.dtype)]
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_TM_VMEM_LIMIT,
        ),
        name=kernel_names.FLASH_BWD_TM,
        interpret=interpret,
    )(*qs, *ks, v, g, lse, delta, coeffs.astype(jnp.float32), _tm_bias(T),
      *rot_args)
    dqs = tuple(results[:S])
    dks = tuple(results[S : 2 * S])
    return dqs, dks, results[2 * S]


# Scoped-VMEM budget for ALL tm pallas_calls (fwd and bwd, per-array and
# packed): 28 MB, ~1/4 of v5e's 128 MB physical VMEM (the 16 MB default
# is conservative). Defined once because the training q-block size below
# is only compilable under it — deriving one from the other keeps them
# from drifting apart (advisor, round 4).
_TM_VMEM_LIMIT = 28 * 1024 * 1024

# Training-forward q-block rows. The residual-saving forward carries
# oall + lse blocks on top of the compute blocks; at the recipe shape the
# 512-row block needs ~18 MB of scoped VMEM at S<=2 (measured round 4)
# but 32.3 MB at S=4 — over the limit. Rather than raising the limit
# (probed round 5 on v5e, S=4: 28 MB/block-256 = 16.3 ms, 40 MB/block-512
# = 18.0 ms, 48 MB/block-512 = 24.9 ms — extra scoped VMEM *slows* the
# kernel by squeezing pipelining headroom), S>=3 drops to 256-row blocks
# under the unchanged limit, which is also the fastest point. At S<=2,
# 512 stays ~0.5% faster than 256 (fewer programs, one bias stripe).
_TM_TRAIN_BLOCK_Q = 512 if _TM_VMEM_LIMIT >= 20 * 1024 * 1024 else 256


def _tm_train_block_q(S: int) -> int:
    # S>=3 drops to 256-row blocks (the VMEM measurement above), still
    # capped by _TM_TRAIN_BLOCK_Q; S<=2 takes _TM_TRAIN_BLOCK_Q
    # directly. The limit-dependent choice lives in ONE place and cannot
    # drift from a future _TM_VMEM_LIMIT edit (ADVICE r5 finding 3).
    return min(_TM_TRAIN_BLOCK_Q, 256) if S >= 3 else _TM_TRAIN_BLOCK_Q


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_tm(qs, ks, v, coeffs, rot, blocks, interpret):
    H = coeffs.shape[0] // qs[0].shape[0]
    out, _, _ = _tm_fwd_call(
        qs, ks, v, coeffs, rot,
        H=H, block_q=blocks[0], save_residuals=False, interpret=interpret,
    )
    return out


def _flash_tm_fwd(qs, ks, v, coeffs, rot, blocks, interpret):
    H = coeffs.shape[0] // qs[0].shape[0]
    out, o_all, lse = _tm_fwd_call(
        qs, ks, v, coeffs, rot,
        H=H, block_q=blocks[2], save_residuals=True, interpret=interpret,
    )
    return out, (qs, ks, v, coeffs, rot, o_all, lse)


def _flash_tm_bwd(blocks, interpret, res, g):
    qs, ks, v, coeffs, rot, o_all, lse = res
    B, H, S, T, dv = o_all.shape
    g32 = g.astype(jnp.float32).reshape(B, T, H, dv)
    # base[b,t,h,s] = <g_t, O_s,t>; delta_s = c_s * base; dcoeffs = sum_t
    # (see _flash_bwd — identical residual algebra, token-major g and a
    # flat (B, T, H*S) stat layout matching lse, so the kernel reads
    # per-(head, stream) columns without a transpose)
    base = jnp.einsum("bthd,bhstd->bths", g32, o_all.astype(jnp.float32))
    dcoeffs = base.sum(1).reshape(B * H, S)
    delta = (
        base * coeffs.astype(jnp.float32).reshape(B, 1, H, S)
    ).reshape(B, T, H * S)
    dqs, dks, dv_grad = _tm_bwd_call(
        qs, ks, v, g.astype(qs[0].dtype), lse, delta, coeffs, rot,
        H=H, interpret=interpret,
    )
    # the angles are positions, not parameters: no gradient to the table
    return dqs, dks, dv_grad, dcoeffs.astype(coeffs.dtype), None


_flash_tm.defvjp(_flash_tm_fwd, _flash_tm_bwd)


def multi_stream_flash_attention_tm(
    qs, ks, v: jnp.ndarray, coeffs: jnp.ndarray, B: int, H: int,
    *,
    rope=None,
    block_q: Optional[int] = None,
    block_q_train: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Token-major entry: ``qs``/``ks`` are tuples of S ``(B, T, H, d)``
    arrays (each the RESHAPED output of its own projection matmul — no
    transpose anywhere), ``v`` is ``(B, T, H, dv)``; returns
    ``(B, T, H, dv)``. The kernels run on the flat ``(B, T, H*width)``
    forms (all reshapes here are free row-major bitcasts). ``rope``: the
    ``(cos, sin)`` tables of a family that rotates, whose q and k then
    arrive UNROTATED with every head's features in
    ``ops/rope.py:half_split``'s order and turn in VMEM. Callers must
    check :func:`use_tm` first; ineligible configs belong on
    :func:`multi_stream_flash_attention_bh`."""
    if interpret is None:
        interpret = _auto_interpret()
    S = len(qs)
    _, T, _, d = qs[0].shape
    dv = v.shape[-1]
    assert use_tm(S, T, 0.0), (
        f"tm kernels do not cover S={S}, T={T}; dispatch via use_tm"
    )
    dq, _, dqt, _ = default_blocks()
    # the S>=3 clamp is a hard VMEM envelope, applied uniformly: both
    # forward variants keep S full-T k/v arrays resident, and EXPLICIT
    # block picks are clamped the same as defaults (an un-clamped
    # explicit 512 at S=4 is exactly the measured 32.3 MB > 28 MB
    # Mosaic overflow the clamp exists to prevent)
    cap = _tm_train_block_q(S)
    blocks = (
        _pick_block(min(block_q if block_q is not None else dq, cap), T),
        0,
        _pick_block(min(block_q_train if block_q_train is not None else dqt,
                        cap), T),
        0,
    )
    c_r = jnp.broadcast_to(
        coeffs.astype(jnp.float32).T[None], (B, H, S)
    ).reshape(B * H, S)
    out = _flash_tm(
        tuple(q.reshape(B, T, H * d) for q in qs),
        tuple(k.reshape(B, T, H * d) for k in ks),
        v.reshape(B, T, H * dv),
        c_r, None if rope is None else tm_rope_table(*rope, T),
        blocks, interpret,
    )
    return out.reshape(B, T, H, dv)


# ---------------------------------------------------------------------------
# Packed-projection tm variant: q/k/v ride as COLUMN WINDOWS of one
# (B, T, W) array — the raw output of a single fused projection matmul
# x @ [Wq1|..|WqS|Wk1|..|WkS|Wv]. pallas receives the same array once per
# logical operand with window-offset index maps (zero copies), and the
# backward emits ONE packed dproj in the same column order, which is
# exactly the operand the projection's own dx/dW matmuls need — no
# gradient concat materializes either. RoPE families ride it too: their
# q/k windows turn on the tile in VMEM (:func:`_tm_turn`), since rotating
# them in HBM would need slice+concat copies of the packed array.
# ---------------------------------------------------------------------------


# The packed whole-T backward holds the (T, W) dproj block beside every
# window of proj; turning the tiles adds the table and float32 copies of a
# head's q, k, dq and dk. At S = 4 (W = 3840 at recipe widths) that is
# 30.0 MB of scoped VMEM inside the recipe step, 2 over _TM_VMEM_LIMIT;
# S <= 3 compiles at micro-batch 32 and 64 (described v5e, PR 37).
_TM_PACKED_ROPE_MAX_S = 3


def tm_packed_ok(S: int, H: int, d: int, dv: int, rope: bool = False) -> bool:
    """Shape eligibility for the packed tm kernels: the fused (B, T, W)
    projection is windowed with H*d- and H*dv-wide column blocks, so the
    V window offset 2*S*H*d must be a whole number of H*dv blocks (holds
    for every S when dv = 2d, and for S = 1, dv = d — only exotic dv/d
    ratios miss it), and both window widths must be 128-lane multiples —
    a BlockSpec block narrower than the array's last dim must divide
    into lanes (Mosaic lowering rule; narrow test-scale models miss it).
    With ``rope`` (the kernels turn q and k) S may not pass
    ``_TM_PACKED_ROPE_MAX_S``.
    Callers route ineligible shapes to the per-array tm path, whose
    blocks span each array's full last dim and are always legal."""
    Hd, Hdv = H * d, H * dv
    if rope and S > _TM_PACKED_ROPE_MAX_S:
        return False
    return (2 * S * Hd) % Hdv == 0 and Hd % 128 == 0 and Hdv % 128 == 0


def _tm_packed_specs(S, H, d, dv, T, block_q):
    """(in_specs for q_0..q_{S-1}, k_0.., v) over one packed (B, T, W)
    array, W = 2*S*H*d + H*dv. Asserts only the offset-alignment
    invariant (wrong windows = wrong math); the 128-lane width rule in
    tm_packed_ok is a TPU-lowering concern the DISPATCHER enforces —
    direct narrow-shape callers still work in interpret mode."""
    Hd, Hdv = H * d, H * dv
    assert (2 * S * Hd) % Hdv == 0, "packed v window misaligned"
    vcol = 2 * S * Hd // Hdv
    qspecs = [
        pl.BlockSpec(
            (None, block_q, Hd),
            (lambda s: lambda b, i: (b, i, s))(s),
            memory_space=pltpu.VMEM,
        )
        for s in range(S)
    ]
    kspecs = [
        pl.BlockSpec(
            (None, T, Hd),
            (lambda s: lambda b, i: (b, 0, S + s))(s),
            memory_space=pltpu.VMEM,
        )
        for s in range(S)
    ]
    vspec = pl.BlockSpec(
        (None, T, Hdv), lambda b, i: (b, 0, vcol), memory_space=pltpu.VMEM
    )
    return qspecs + kspecs + [vspec]


def _tm_fwd_call_packed(
    proj, coeffs, rot, *, S, H, d, dv, block_q, save_residuals, interpret
):
    """Packed twin of :func:`_tm_fwd_call`: same kernel body, operands
    windowed out of ``proj`` (B, T, W)."""
    B, T, W = proj.shape
    BH = B * H
    block_q = _pick_block(block_q, T)
    nq = T // block_q

    in_specs = _tm_packed_specs(S, H, d, dv, T, block_q) + [
        pl.BlockSpec((block_q, T), lambda b, i: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((BH, S), lambda b, i: (0, 0),
                     memory_space=pltpu.SMEM),
    ]
    rot_specs, rot_args = _tm_rot_specs(rot, block_q)
    in_specs += rot_specs
    out_shapes = [jax.ShapeDtypeStruct((B, T, H * dv), proj.dtype)]
    out_specs = [
        pl.BlockSpec(
            (None, block_q, H * dv), lambda b, i: (b, i, 0),
            memory_space=pltpu.VMEM,
        ),
    ]
    if save_residuals:
        out_shapes += [
            jax.ShapeDtypeStruct((B, H, S, T, dv), proj.dtype),
            jax.ShapeDtypeStruct((B, T, H * S), jnp.float32),
        ]
        out_specs += [
            pl.BlockSpec(
                (None, H, S, block_q, dv),
                lambda b, i: (b, 0, 0, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (None, block_q, H * S), lambda b, i: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
        ]
    results = pl.pallas_call(
        functools.partial(
            _tm_fwd_kernel, S=S, H=H, save_residuals=save_residuals,
            rope=rot is not None,
        ),
        grid=(B, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_TM_VMEM_LIMIT,
        ),
        name=kernel_names.FLASH_FWD_TM_PACKED,
        interpret=interpret,
    )(*([proj] * (2 * S + 1)), _tm_bias(T),
      coeffs.astype(jnp.float32), *rot_args)
    if save_residuals:
        return results
    return results[0], None, None


def _tm_bwd_kernel_packed(*refs, S: int, H: int, rope: bool = False):
    """Packed twin of :func:`_tm_bwd_kernel` (all streams; same shared
    math, :func:`_tm_bwd_columns`): the per-stream dq/dk and dv column
    groups store as ONE (T, W) ref in the packed projection order."""
    q_refs, refs = refs[:S], refs[S:]
    k_refs, refs = refs[:S], refs[S:]
    (v_ref, g_ref, lse_ref, delta_ref, c_ref, bias_ref, *rest) = refs
    rot_ref, dproj_ref = rest if rope else (None, *rest)
    dq_cols, dk_cols, dv_cols = _tm_bwd_columns(
        q_refs, k_refs, v_ref, g_ref, lse_ref, delta_ref, c_ref,
        bias_ref[...].astype(jnp.float32),
        S=S, H=H, s_list=tuple(range(S)), out_dtype=dproj_ref.dtype,
        rot_ref=rot_ref,
    )
    cols = (
        [c for s_i in range(S) for c in dq_cols[s_i]]
        + [c for s_i in range(S) for c in dk_cols[s_i]]
        + dv_cols
    )
    dproj_ref[...] = jnp.concatenate(cols, axis=1)  # (T, W)


def _tm_bwd_call_packed(
    proj, g, lse, delta, coeffs, rot, *, S, H, d, dv, interpret
):
    """Returns dproj (B, T, W) — the single packed gradient the fused
    projection matmul's own backward consumes directly."""
    B, T, W = proj.shape
    BH = B * H
    # packed windows with the whole-T 1-D grid index signature
    vspec = pl.BlockSpec(
        (None, T, H * dv), lambda b: (b, 0, 0), memory_space=pltpu.VMEM
    )
    stspec = pl.BlockSpec(
        (None, T, H * S), lambda b: (b, 0, 0), memory_space=pltpu.VMEM
    )
    Hd, Hdv = H * d, H * dv
    vcol = 2 * S * Hd // Hdv
    qspecs = [
        pl.BlockSpec(
            (None, T, Hd), (lambda s: lambda b: (b, 0, s))(s),
            memory_space=pltpu.VMEM,
        )
        for s in range(S)
    ]
    kspecs = [
        pl.BlockSpec(
            (None, T, Hd), (lambda s: lambda b: (b, 0, S + s))(s),
            memory_space=pltpu.VMEM,
        )
        for s in range(S)
    ]
    pvspec = pl.BlockSpec(
        (None, T, Hdv), lambda b: (b, 0, vcol), memory_space=pltpu.VMEM
    )
    rot_specs, rot_args = _tm_bwd_rot_specs(rot)
    results = pl.pallas_call(
        functools.partial(_tm_bwd_kernel_packed, S=S, H=H,
                          rope=rot is not None),
        grid=(B,),
        in_specs=qspecs + kspecs + [
            pvspec,
            vspec,
            stspec,
            stspec,
            pl.BlockSpec((BH, S), lambda b: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((T, T), lambda b: (0, 0),
                         memory_space=pltpu.VMEM),
        ] + rot_specs,
        out_specs=[
            pl.BlockSpec((None, T, W), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, T, W), proj.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_TM_VMEM_LIMIT,
        ),
        name=kernel_names.FLASH_BWD_TM_PACKED,
        interpret=interpret,
    )(*([proj] * (2 * S + 1)), g, lse, delta,
      coeffs.astype(jnp.float32), _tm_bias(T), *rot_args)
    return results[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_tm_packed(proj, coeffs, rot, S, H, d, dv, blocks, interpret):
    out, _, _ = _tm_fwd_call_packed(
        proj, coeffs, rot, S=S, H=H, d=d, dv=dv,
        block_q=blocks[0], save_residuals=False, interpret=interpret,
    )
    return out


def _flash_tm_packed_fwd(proj, coeffs, rot, S, H, d, dv, blocks, interpret):
    out, o_all, lse = _tm_fwd_call_packed(
        proj, coeffs, rot, S=S, H=H, d=d, dv=dv,
        block_q=blocks[2], save_residuals=True, interpret=interpret,
    )
    return out, (proj, coeffs, rot, o_all, lse)


def _flash_tm_packed_bwd(S, H, d, dv, blocks, interpret, res, g):
    proj, coeffs, rot, o_all, lse = res
    B, _, _, T, _ = o_all.shape
    g32 = g.astype(jnp.float32).reshape(B, T, H, dv)
    base = jnp.einsum("bthd,bhstd->bths", g32, o_all.astype(jnp.float32))
    dcoeffs = base.sum(1).reshape(B * H, S)
    delta = (
        base * coeffs.astype(jnp.float32).reshape(B, 1, H, S)
    ).reshape(B, T, H * S)
    dproj = _tm_bwd_call_packed(
        proj, g.astype(proj.dtype), lse, delta, coeffs, rot,
        S=S, H=H, d=d, dv=dv, interpret=interpret,
    )
    return dproj, dcoeffs.astype(coeffs.dtype), None


_flash_tm_packed.defvjp(_flash_tm_packed_fwd, _flash_tm_packed_bwd)


def multi_stream_flash_attention_tm_packed(
    proj: jnp.ndarray,  # (B, T, 2*S*H*d + H*dv) — [q_0..q_S|k_0..k_S|v]
    coeffs: jnp.ndarray,  # (S, H) float32
    B: int, H: int, S: int, d: int, dv: int,
    *,
    rope=None,
    block_q: Optional[int] = None,
    block_q_train: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Packed-projection token-major entry (see the section comment):
    ``proj`` is the raw output of ONE fused projection matmul; returns
    (B, T, H, dv). ``rope`` as :func:`multi_stream_flash_attention_tm`
    takes it; callers check use_tm."""
    if interpret is None:
        interpret = _auto_interpret()
    T = proj.shape[1]
    assert use_tm(S, T, 0.0), (
        f"tm kernels do not cover S={S}, T={T}; dispatch via use_tm"
    )
    dq, _, dqt, _ = default_blocks()
    # the S>=3 clamp is a hard VMEM envelope, applied uniformly: both
    # forward variants keep S full-T k/v arrays resident, and EXPLICIT
    # block picks are clamped the same as defaults (an un-clamped
    # explicit 512 at S=4 is exactly the measured 32.3 MB > 28 MB
    # Mosaic overflow the clamp exists to prevent)
    cap = _tm_train_block_q(S)
    blocks = (
        _pick_block(min(block_q if block_q is not None else dq, cap), T),
        0,
        _pick_block(min(block_q_train if block_q_train is not None else dqt,
                        cap), T),
        0,
    )
    c_r = jnp.broadcast_to(
        coeffs.astype(jnp.float32).T[None], (B, H, S)
    ).reshape(B * H, S)
    out = _flash_tm_packed(
        proj, c_r, None if rope is None else tm_rope_table(*rope, T),
        S, H, d, dv, blocks, interpret,
    )
    return out.reshape(B, T, H, dv)


def flash_vanilla_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, **kw
) -> jnp.ndarray:
    """Fused drop-in for ops.attention.vanilla_attention (causal, no
    dropout). q/k/v: (B, T, H, d)."""
    return multi_stream_flash_attention(
        q[None], k[None], v, vanilla_coeffs(q.shape[2]), **kw
    )


def flash_diff_attention(
    q1: jnp.ndarray,
    k1: jnp.ndarray,
    q2: jnp.ndarray,
    k2: jnp.ndarray,
    v: jnp.ndarray,
    lam: jnp.ndarray,
    **kw,
) -> jnp.ndarray:
    """Fused drop-in for ops.attention.diff_attention:
    ``att1 - lam*att2`` (diff_transformer.py:70) as coeffs [1, -lam]."""
    qs = jnp.stack([q1, q2])
    ks = jnp.stack([k1, k2])
    return multi_stream_flash_attention(qs, ks, v, diff_coeffs(lam), **kw)


def flash_ndiff_attention(
    qs: jnp.ndarray,
    ks: jnp.ndarray,
    v: jnp.ndarray,
    lams: jnp.ndarray,
    signs: jnp.ndarray,
    **kw,
) -> jnp.ndarray:
    """Fused drop-in for ops.attention.ndiff_attention: coeffs are
    ``sign_s * lambda_{s,h}`` (Ndiff_transformer.py:119-123 — the first
    map is scaled by lambda_0, not 1)."""
    return multi_stream_flash_attention(qs, ks, v, ndiff_coeffs(lams, signs), **kw)

"""The RoPE families' kernel path against their dense reference.

``models/common.py:flash_bh_fn`` with tables re-orders the columns of Wq
and Wk inside each head (``ops/rope.py:half_split``) and turns contiguous
halves: on the tile in VMEM on the token-major branch, in HBM on the
head-major one. The dense reference, the prefill chunk and the decode ring
keep ``apply_rope``'s pairing of 2i with 2i + 1. Both must give one
attention, and one gradient to every weight IN THE PUBLISHED column order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from differential_transformer_replication_tpu.models import common
from differential_transformer_replication_tpu.ops import (
    causal_mask,
    ndiff_attention,
    ndiff_signs,
    vanilla_attention,
)
from differential_transformer_replication_tpu.ops.rope import (
    apply_rope,
    apply_rope_halves,
    half_split,
    rope_cos_sin,
)
from differential_transformer_replication_tpu.ops.streams import (
    ndiff_coeffs,
    vanilla_coeffs,
)

# family: (streams, value width over head width); ndiff3 is ndiff with
# n_terms = 3, the most rotated streams the packed kernels admit
FAMILIES = {"control": (1, 1), "ndiff": (4, 2), "ndiff3": (3, 2)}
# shape: (B, T, E, H, d); the recipe's heads are 8 x 96 at T = 512 for
# control (4 x 96 for ndiff, whose values are 192 wide): those shapes take
# the PACKED token-major kernels up to three streams and the per-array
# ones at four, the small ones the per-array ones
SHAPES = {"small": (2, 64, 32, 2, 16), "recipe": (1, 512, 64, 8, 96)}
LAMS = jnp.array([0.8, 0.35, 0.5, 0.2], jnp.float32)


def _inputs(family, shape):
    S, vmul = FAMILIES[family]
    B, T, E, H, d = SHAPES[shape]
    if family != "control" and shape == "recipe":
        H = 4
    ks = jax.random.split(jax.random.PRNGKey(37), 5)
    x = jax.random.normal(ks[0], (B, T, E), jnp.float32)
    wq = jax.random.normal(ks[1], (S, E, H, d), jnp.float32) * E ** -0.5
    wk = jax.random.normal(ks[2], (S, E, H, d), jnp.float32) * E ** -0.5
    wv = jax.random.normal(ks[3], (E, H, vmul * d), jnp.float32) * E ** -0.5
    probe = jax.random.normal(ks[4], (B, T, H, vmul * d), jnp.float32)
    return x, wq, wk, wv, probe


def _lams(family, H):
    n = FAMILIES[family][0]
    return jnp.broadcast_to(LAMS[:n, None], (n, H)), ndiff_signs(n)


def _coeffs(family, H):
    if family == "control":
        return vanilla_coeffs(H)
    return ndiff_coeffs(*_lams(family, H))


def _dense(family, x, wq, wk, wv, cos, sin):
    """The reference: interleaved rotation of the published projections,
    full (T, T) maps."""
    T = x.shape[1]
    qs = apply_rope(jnp.einsum("bte,sehd->sbthd", x, wq), cos, sin)
    ks = apply_rope(jnp.einsum("bte,sehd->sbthd", x, wk), cos, sin)
    v = jnp.einsum("bte,ehd->bthd", x, wv)
    if family == "control":
        return vanilla_attention(qs[0], ks[0], v, mask=causal_mask(T))
    return ndiff_attention(qs, ks, v, *_lams(family, wq.shape[2]),
                           mask=causal_mask(T))


@pytest.mark.parametrize("branch", ["token_major", "head_major"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_path_matches_dense_reference(family, shape, branch,
                                             monkeypatch):
    from differential_transformer_replication_tpu.ops import flash

    x, wq, wk, wv, probe = _inputs(family, shape)
    H, d = wq.shape[2], wq.shape[3]
    cos, sin = rope_cos_sin(d, x.shape[1])
    coeffs = _coeffs(family, H)
    if branch == "head_major":
        # what dropout or a long T select; neither changes the rotation
        monkeypatch.setattr(flash, "use_tm", lambda S, T, rate: False)
    else:
        assert flash.use_tm(wq.shape[0], x.shape[1], 0.0)
        assert flash.tm_packed_ok(wq.shape[0], H, d, wv.shape[-1], True) == (
            shape == "recipe" and family != "ndiff")

    def kernel_loss(x, wq, wk, wv):
        out = common.flash_bh_fn(x, wq, wk, wv, coeffs, dropout_rate=0.0,
                                 rng=None, cos=cos, sin=sin)()
        return jnp.sum(out * probe), out

    def dense_loss(x, wq, wk, wv):
        out = _dense(family, x, wq, wk, wv, cos, sin)
        return jnp.sum(out * probe), out

    (_, got), grads = jax.value_and_grad(
        kernel_loss, argnums=(0, 1, 2, 3), has_aux=True)(x, wq, wk, wv)
    (_, ref), refs = jax.value_and_grad(
        dense_loss, argnums=(0, 1, 2, 3), has_aux=True)(x, wq, wk, wv)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
    for name, g, r in zip(("x", "wq", "wk", "wv"), grads, refs):
        np.testing.assert_allclose(
            g, r, rtol=2e-4, atol=2e-5 * float(jnp.abs(r).max()) + 1e-6,
            err_msg=f"gradient to {name}")


def test_bfloat16_kernel_path_stays_near_the_float32_reference():
    """bf16 operands take the swap of a head's halves through the MXU in
    bf16 (exact: one product a column) and round q, k and their gradients
    where the rotation in HBM did; held to the float32 reference at bf16's
    resolution."""
    x, wq, wk, wv, probe = _inputs("control", "small")
    H, d = wq.shape[2], wq.shape[3]
    cos, sin = rope_cos_sin(d, x.shape[1])

    def kernel_loss(x, wq, wk, wv):
        out = common.flash_bh_fn(
            x.astype(jnp.bfloat16), wq, wk, wv, vanilla_coeffs(H),
            dropout_rate=0.0, rng=None, cos=cos, sin=sin)()
        return jnp.sum(out.astype(jnp.float32) * probe)

    def dense_loss(x, wq, wk, wv):
        return jnp.sum(_dense("control", x, wq, wk, wv, cos, sin) * probe)

    got = jax.grad(kernel_loss, argnums=(0, 1, 2, 3))(x, wq, wk, wv)
    ref = jax.grad(dense_loss, argnums=(0, 1, 2, 3))(x, wq, wk, wv)
    for name, g, r in zip(("x", "wq", "wk", "wv"), got, ref):
        gap = float(jnp.abs(g.astype(jnp.float32) - r).max())
        assert gap <= 0.04 * float(jnp.abs(r).max()), (name, gap)


@pytest.mark.parametrize("d", [16, 96])
def test_half_rotation_of_reordered_features_is_apply_rope_exactly(d):
    """Rotating the re-ordered vector by halves and rotating the published
    one by pairs are the SAME float32 products and sums, so the results
    are equal bit for bit once one is re-ordered as the other."""
    T = 40
    x = jax.random.normal(jax.random.PRNGKey(5), (3, T, d), jnp.float32)
    cos, sin = rope_cos_sin(d, T + 7)  # tables longer than T are cut to it
    got = apply_rope_halves(half_split(x), cos, sin)
    want = half_split(apply_rope(x, cos, sin, headed=False))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    np.testing.assert_array_equal(np.asarray(half_split(x)),
                                  np.asarray(x)[..., perm])

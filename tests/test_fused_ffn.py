"""Numerics parity + scheduling pins for the fused FFN/norm hot path
(ISSUE 9): ops/fused_ffn.py, ops/fused_norm_residual.py, the ffn_impl
switch through all three model families and decode, the remat-policy
knob, and the overlap-scheduled pure-DP step (parallel/dp_step.py).

The kernels run in interpret mode on the CPU mesh — the same code paths
the TPU compiles — so this is the tier-1 gate for the fused path.
"""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from differential_transformer_replication_tpu.config import (
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from differential_transformer_replication_tpu.models import (
    init_model,
    model_forward,
)
from differential_transformer_replication_tpu.ops import (
    group_layer_norm,
    layer_norm,
    swiglu,
)
from differential_transformer_replication_tpu.ops.fused_ffn import (
    fused_swiglu,
)
from differential_transformer_replication_tpu.ops.fused_norm_residual import (
    fused_add_norm,
    fused_group_norm,
    fused_norm,
)

REPO = Path(__file__).resolve().parents[1]

TINY = dict(vocab_size=61, n_embd=32, n_head=2, n_layer=2, block_size=16,
            dropout=0.0, n_terms=2, compute_dtype="float32")

# fp32: the kernels compute the exact same fp32 chain as the reference
# ops — tight. bf16: identical math, but fp32 reduction ORDER differs
# before the bf16 quantization, so parity is to within bf16 ulps.
TOLS = {
    jnp.float32: dict(rtol=2e-5, atol=2e-6),
    jnp.bfloat16: dict(rtol=3e-2, atol=3e-2),
}
GRAD_TOLS = {
    jnp.float32: dict(rtol=2e-4, atol=2e-5),
    jnp.bfloat16: dict(rtol=6e-2, atol=6e-2),
}


def _close(got, want, tols):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tols
    )


def _norm_inputs(dtype, E=48, rows=24):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (3, rows // 3, E), dtype)
    d = jax.random.normal(ks[1], (3, rows // 3, E), dtype)
    w = jax.random.normal(ks[2], (E,)) * 0.2 + 1.0
    b = jax.random.normal(ks[3], (E,)) * 0.2
    return x, d, w, b


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
class TestNormResidualKernels:
    def test_fused_norm_matches_layer_norm(self, dtype):
        x, _, w, b = _norm_inputs(dtype)
        _close(fused_norm(x, w, b), layer_norm(x, w, b), TOLS[dtype])

    def test_group_alias_matches_group_layer_norm(self, dtype):
        x, _, w, b = _norm_inputs(dtype)
        _close(
            fused_group_norm(x, w, b), group_layer_norm(x, w, b), TOLS[dtype]
        )

    def test_fused_add_norm_forward(self, dtype):
        x, d, w, b = _norm_inputs(dtype)
        xnew, normed = fused_add_norm(x, d, w, b)
        # the residual carry is the plain stored-dtype add, bit-exact
        np.testing.assert_array_equal(
            np.asarray(xnew, np.float32), np.asarray(x + d, np.float32)
        )
        _close(normed, layer_norm(x + d, w, b), TOLS[dtype])

    def test_fused_add_norm_grads(self, dtype):
        """Both outputs' cotangents flow: the normed branch through the
        LN backward, the carry branch straight through the add."""
        x, d, w, b = _norm_inputs(dtype)

        def ref(x, d, w, b):
            xn = x + d
            n = layer_norm(xn, w, b)
            return (jnp.sum(jnp.sin(n.astype(jnp.float32)))
                    + jnp.sum(xn.astype(jnp.float32) ** 2))

        def got(x, d, w, b):
            xn, n = fused_add_norm(x, d, w, b)
            return (jnp.sum(jnp.sin(n.astype(jnp.float32)))
                    + jnp.sum(xn.astype(jnp.float32) ** 2))

        g0 = jax.grad(ref, argnums=(0, 1, 2, 3))(x, d, w, b)
        g1 = jax.grad(got, argnums=(0, 1, 2, 3))(x, d, w, b)
        for a, bb in zip(g0, g1):
            _close(bb, a, GRAD_TOLS[dtype])

    def test_fused_norm_grads(self, dtype):
        x, _, w, b = _norm_inputs(dtype)

        def ref(x, w, b):
            return jnp.sum(jnp.sin(layer_norm(x, w, b).astype(jnp.float32)))

        def got(x, w, b):
            return jnp.sum(jnp.sin(fused_norm(x, w, b).astype(jnp.float32)))

        g0 = jax.grad(ref, argnums=(0, 1, 2))(x, w, b)
        g1 = jax.grad(got, argnums=(0, 1, 2))(x, w, b)
        for a, bb in zip(g0, g1):
            _close(bb, a, GRAD_TOLS[dtype])


def _ffn_inputs(dtype, E=32, F=128, rows=24):
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (2, rows // 2, E), dtype)
    lnw = jax.random.normal(ks[1], (E,)) * 0.1 + 1.0
    lnb = jax.random.normal(ks[2], (E,)) * 0.1
    wg = jax.random.normal(ks[3], (E, F)) * 0.05
    bg = jax.random.normal(ks[4], (F,)) * 0.05
    wx = jax.random.normal(ks[5], (E, F)) * 0.05
    bx = jnp.zeros((F,)) + 0.01
    return x, lnw, lnb, wg, bg, wx, bx


def _ref_swiglu(x, wg, bg, wx, bx):
    return swiglu(
        x, wg.astype(x.dtype), bg.astype(x.dtype),
        wx.astype(x.dtype), bx.astype(x.dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
class TestFusedSwiGLU:
    def test_forward_matches_reference(self, dtype):
        x, _, _, wg, bg, wx, bx = _ffn_inputs(dtype)
        _close(
            fused_swiglu(x, wg, bg, wx, bx),
            _ref_swiglu(x, wg, bg, wx, bx), TOLS[dtype],
        )

    def test_block_boundary_composition_matches_reference(self, dtype):
        """The pairing the blocks actually run (apply_block_ffn):
        fused residual-add+LN feeding the fused SwiGLU kernel vs the
        un-fused add -> layer_norm -> swiglu reference chain."""
        x, lnw, lnb, wg, bg, wx, bx = _ffn_inputs(dtype)
        y = jnp.flip(x, axis=1) * 0.5
        carry, normed = fused_add_norm(x, y, lnw, lnb)
        ref_carry = x + y
        _close(carry, ref_carry, TOLS[dtype])
        _close(
            fused_swiglu(normed, wg, bg, wx, bx),
            _ref_swiglu(layer_norm(ref_carry, lnw, lnb), wg, bg, wx, bx),
            TOLS[dtype],
        )

    def test_grads_match_reference(self, dtype):
        x, _, _, wg, bg, wx, bx = _ffn_inputs(dtype)

        def ref(x, wg, bg, wx, bx):
            return jnp.sum(
                jnp.tanh(_ref_swiglu(x, wg, bg, wx, bx).astype(jnp.float32))
            )

        def got(x, wg, bg, wx, bx):
            return jnp.sum(
                jnp.tanh(fused_swiglu(x, wg, bg, wx, bx).astype(jnp.float32))
            )

        g0 = jax.grad(ref, argnums=tuple(range(5)))(x, wg, bg, wx, bx)
        g1 = jax.grad(got, argnums=tuple(range(5)))(x, wg, bg, wx, bx)
        for a, bb in zip(g0, g1):
            _close(bb, a, GRAD_TOLS[dtype])

    def test_block_boundary_composition_grads(self, dtype):
        """Grads through the fused add+LN -> fused SwiGLU pairing match
        the un-fused reference chain (both kernel backwards compose)."""
        x, lnw, lnb, wg, bg, wx, bx = _ffn_inputs(dtype)
        y = jnp.flip(x, axis=1) * 0.5
        args = (x, y, lnw, lnb, wg, bg, wx, bx)

        def ref(x, y, lnw, lnb, wg, bg, wx, bx):
            h = _ref_swiglu(layer_norm(x + y, lnw, lnb), wg, bg, wx, bx)
            return jnp.sum(jnp.tanh(h.astype(jnp.float32)))

        def got(x, y, lnw, lnb, wg, bg, wx, bx):
            _, normed = fused_add_norm(x, y, lnw, lnb)
            h = fused_swiglu(normed, wg, bg, wx, bx)
            return jnp.sum(jnp.tanh(h.astype(jnp.float32)))

        g0 = jax.grad(ref, argnums=tuple(range(8)))(*args)
        g1 = jax.grad(got, argnums=tuple(range(8)))(*args)
        for a, bb in zip(g0, g1):
            _close(bb, a, GRAD_TOLS[dtype])

    def test_odd_tile_shapes(self, dtype):
        """Rows/hidden not divisible by the default tiles: pick_block
        must find exact divisors and the kernel stay correct."""
        x, _, _, wg, bg, wx, bx = _ffn_inputs(dtype, E=24, F=72, rows=18)
        _close(
            fused_swiglu(x, wg, bg, wx, bx, block_m=4, block_f=24),
            _ref_swiglu(x, wg, bg, wx, bx), TOLS[dtype],
        )


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its inner jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _pallas_calls(fn, *args):
    """name -> the pallas_call equations of ``fn``'s jaxpr."""
    calls = {}
    for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if e.primitive.name == "pallas_call":
            calls.setdefault(e.params["name"], []).append(e)
    return calls


def _tanh_loss(x, wg, bg, wx, bx):
    return jnp.sum(
        jnp.tanh(fused_swiglu(x, wg, bg, wx, bx).astype(jnp.float32))
    )


@pytest.mark.parametrize("case", [
    "fwd_rule_h_bitwise-fp32", "fwd_rule_h_bitwise-bf16",
    "primal_is_one_call_one_output", "bwd_kernel_owns_two_products",
    "remat_grads_equal",
])
def test_saved_pre_activations(case):
    """The forward under a gradient hands g and t to the backward; the
    forward without one pays nothing for them (PR 43)."""
    from differential_transformer_replication_tpu import kernel_names
    from differential_transformer_replication_tpu.ops import fused_ffn

    if case.startswith("fwd_rule_h_bitwise"):
        dtype = jnp.float32 if case.endswith("fp32") else jnp.bfloat16
        x, _, _, wg, bg, wx, bx = _ffn_inputs(dtype)
        x2 = x.reshape(-1, x.shape[-1])
        args = (x2, wg.astype(dtype), bg.astype(dtype).reshape(1, -1),
                wx.astype(dtype), bx.astype(dtype).reshape(1, -1),
                8, 64, True)
        h, (_, _, _, g, t) = fused_ffn._swiglu2_fwd(*args)
        np.testing.assert_array_equal(
            np.asarray(h, np.float32),
            np.asarray(fused_ffn._swiglu2(*args), np.float32),
        )
        # the residuals: the pre-activations, in the activation's dtype
        assert g.dtype == t.dtype == dtype and g.shape == t.shape == h.shape
        for got, w, b in ((g, wg, bg), (t, wx, bx)):
            want = x2.astype(jnp.float32) @ w.astype(dtype).astype(
                jnp.float32) + b.astype(dtype).astype(jnp.float32)
            _close(got, want, TOLS[dtype])
        return
    x, _, _, wg, bg, wx, bx = _ffn_inputs(jnp.float32)
    if case == "primal_is_one_call_one_output":
        # serving, evaluation and generation do not pay for the residuals
        calls = _pallas_calls(fused_swiglu, x, wg, bg, wx, bx)
        assert list(calls) == [kernel_names.FUSED_FFN_FWD]
        (call,) = calls[kernel_names.FUSED_FFN_FWD]
        assert len(call.outvars) == 1
    elif case == "bwd_kernel_owns_two_products":
        calls = _pallas_calls(
            jax.grad(_tanh_loss, argnums=tuple(range(5))), x, wg, bg, wx, bx
        )
        (fwd,) = calls[kernel_names.FUSED_FFN_FWD]
        (bwd,) = calls[kernel_names.FUSED_FFN_BWD]
        assert len(fwd.outvars) == 3  # h, g, t
        # x, g, t, gh in: no weight reaches the backward kernel, and it
        # multiplies x with dg and with dt and nothing else
        assert [v.aval.shape for v in bwd.invars] == [
            (24, 32), (24, 128), (24, 128), (24, 128)]
        dots = [e for e in _eqns(bwd.params["jaxpr"])
                if e.primitive.name == "dot_general"]
        assert len(dots) == 2
        # dg over g, dt over t
        assert tuple(bwd.params["input_output_aliases"]) == ((1, 0), (2, 1))
    else:
        assert case == "remat_grads_equal"
        # under remat the forward rule runs inside the backward's replay:
        # the residuals live for one block, the gradients are the same
        g0 = jax.grad(_tanh_loss, argnums=tuple(range(5)))(x, wg, bg, wx, bx)
        g1 = jax.grad(jax.checkpoint(_tanh_loss), argnums=tuple(range(5)))(
            x, wg, bg, wx, bx)
        for a, b in zip(g0, g1):
            _close(b, a, GRAD_TOLS[jnp.float32])
        cfg = ModelConfig(model="control", **TINY).replace(ffn_impl="pallas")
        params = init_model(jax.random.PRNGKey(0), cfg)
        idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 61)

        def loss(p, c):
            return model_forward(p, idx, c, targets=jnp.roll(idx, -1, -1))[1]

        g0 = jax.grad(loss)(params, cfg)
        g1 = jax.grad(loss)(params, cfg.replace(remat=True))
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6
            )


class TestModelParity:
    """ffn_impl='pallas' vs 'xla' through the full forward/backward for
    every family — the switch must be numerically invisible."""

    @pytest.mark.parametrize("kind", ["control", "diff", "ndiff"])
    def test_loss_and_grads_fp32(self, kind):
        cfg = ModelConfig(model=kind, **TINY)
        params = init_model(jax.random.PRNGKey(0), cfg)
        idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 61)
        tgt = jnp.roll(idx, -1, axis=-1)

        def loss(p, impl):
            _, l = model_forward(
                p, idx, cfg.replace(ffn_impl=impl), targets=tgt
            )
            return l

        l0, g0 = jax.value_and_grad(loss)(params, "xla")
        l1, g1 = jax.value_and_grad(loss)(params, "pallas")
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-6
            )

    @pytest.mark.parametrize("kind", ["control", "diff", "ndiff"])
    def test_forward_bf16(self, kind):
        cfg = ModelConfig(model=kind, **{**TINY, "compute_dtype": "bfloat16"})
        params = init_model(jax.random.PRNGKey(0), cfg)
        idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 61)
        tgt = jnp.roll(idx, -1, axis=-1)
        _, l0 = model_forward(params, idx, cfg, targets=tgt)
        _, l1 = model_forward(
            params, idx, cfg.replace(ffn_impl="pallas"), targets=tgt
        )
        np.testing.assert_allclose(float(l1), float(l0), rtol=2e-2)

    def test_fused_path_composes_with_pallas_attention(self):
        """attention_impl and ffn_impl both 'pallas' — the full fused
        hot path bench.py now measures."""
        cfg = ModelConfig(model="diff", **TINY)
        params = init_model(jax.random.PRNGKey(0), cfg)
        idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 61)
        tgt = jnp.roll(idx, -1, axis=-1)
        _, l0 = model_forward(params, idx, cfg, targets=tgt)
        _, l1 = model_forward(
            params, idx,
            cfg.replace(ffn_impl="pallas", attention_impl="pallas"),
            targets=tgt,
        )
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)

    def test_decode_greedy_parity(self):
        """generate_cached fused vs reference: bit-identical greedy
        tokens — the serving decode path (fused_add_norm at every block
        boundary + fused_swiglu + the GLN alias) is loss-free."""
        from differential_transformer_replication_tpu.models.decode import (
            generate_cached,
        )

        for kind in ("control", "diff"):
            cfg = ModelConfig(model=kind, **TINY)
            params = init_model(jax.random.PRNGKey(0), cfg)
            prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 5), 0, 61)
            o0 = generate_cached(
                params, prompt, cfg, 8, jax.random.PRNGKey(4),
                temperature=1e-4,
            )
            o1 = generate_cached(
                params, prompt, cfg.replace(ffn_impl="pallas"), 8,
                jax.random.PRNGKey(4), temperature=1e-4,
            )
            np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))

    def test_ffn_impl_validated(self):
        with pytest.raises(ValueError, match="ffn_impl"):
            ModelConfig(ffn_impl="cuda")


class TestRematPolicies:
    def test_policy_validated(self):
        with pytest.raises(ValueError, match="remat_policy"):
            ModelConfig(remat_policy="sometimes")

    @pytest.mark.parametrize("policy", ["none", "dots", "dots_no_batch",
                                        "nothing", "everything"])
    def test_policies_numerically_invisible(self, policy):
        """Every save policy must give the no-remat loss AND grads on
        the fused path — remat changes memory, never math."""
        cfg = ModelConfig(model="diff", **TINY).replace(ffn_impl="pallas")
        params = init_model(jax.random.PRNGKey(0), cfg)
        idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 61)
        tgt = jnp.roll(idx, -1, axis=-1)

        def loss(p, c):
            _, l = model_forward(p, idx, c, targets=tgt)
            return l

        l0, g0 = jax.value_and_grad(loss)(params, cfg)
        l1, g1 = jax.value_and_grad(loss)(
            params, cfg.replace(remat=True, remat_policy=policy)
        )
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6
            )


def _overlap_cfg(**kw):
    model = ModelConfig(
        model="diff", vocab_size=128, n_embd=32, n_head=2, n_layer=4,
        block_size=16, dropout=0.0, compute_dtype="float32",
    )
    return TrainConfig(
        model=model, mesh=MeshConfig(data=8), vocab_size=128,
        learning_rate=1e-2, min_lr=1e-3, warmup_iters=2, max_iters=100,
        control_head_multiplier=1, **kw,
    )


class TestOverlapDP:
    """The overlap-scheduled pure-DP step (parallel/dp_step.py): bucketed
    pmean-in-backward, single jit, donated state, zero recompiles."""

    def test_eligibility(self):
        from differential_transformer_replication_tpu.parallel.dp_step import (
            overlap_eligible,
        )

        assert overlap_eligible(_overlap_cfg())
        assert not overlap_eligible(_overlap_cfg(dp_overlap=False))
        for mesh in (MeshConfig(data=4, tensor=2), MeshConfig(data=4, fsdp=2),
                     MeshConfig(data=4, sequence=2), MeshConfig(data=1)):
            cfg = _overlap_cfg().replace(mesh=mesh)
            assert not overlap_eligible(cfg), mesh

    def test_parity_and_zero_recompile_pin(self):
        """THE acceptance pin: the overlapped step equals the
        single-device step after one update, and compile_events stays at
        exactly 1 across M further steps on the 8-device mesh (the
        sentinel additionally proves zero backend compiles happen in the
        steady-state window)."""
        from differential_transformer_replication_tpu.analysis.sanitizers import (
            RecompileSentinel,
        )
        from differential_transformer_replication_tpu.parallel import (
            create_mesh,
            make_sharded_train_step,
        )
        from differential_transformer_replication_tpu.parallel.dp_step import (
            create_sharded_train_state,
        )
        from differential_transformer_replication_tpu.train import (
            create_train_state,
            make_train_step,
        )

        cfg = _overlap_cfg(dp_bucket_layers=2)
        mesh = create_mesh(cfg.mesh)
        x = jax.random.randint(jax.random.PRNGKey(1), (1, 8, 16), 0, 128)
        batch = {"x": x, "y": jnp.roll(x, -1, -1)}

        s1, m1 = make_train_step(cfg)(
            create_train_state(jax.random.PRNGKey(0), cfg), batch
        )
        state = create_sharded_train_state(jax.random.PRNGKey(0), cfg, mesh)
        step = make_sharded_train_step(cfg, mesh, state)
        s2, m2 = step(state, batch)
        np.testing.assert_allclose(
            float(m1["loss"]), float(m2["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(s1["params"]), jax.tree.leaves(s2["params"])
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(jax.device_get(b)),
                rtol=2e-4, atol=1e-5,
            )
        with RecompileSentinel(budget=0, name="overlap-steady-state"):
            for _ in range(3):
                s2, m2 = step(s2, batch)
            _ = float(m2["loss"])
        assert int(step._cache_size()) == 1
        assert step._compile_counter_source == "jit-cache"

    def test_grad_accumulation_parity_once_per_step_sync(self):
        """grad_acc_steps > 1 on the overlap path: the microbatch scan
        differentiates the LOCAL loss and one whole-tree pmean runs after
        it (train/step.py grad_sync) — NOT the per-bucket pmeans inside
        every microbatch backward, which would move A x the collective
        volume. Parity with the single-device accumulated step proves
        the once-per-step sync still yields the global mean gradient."""
        from differential_transformer_replication_tpu.parallel import (
            create_mesh,
            make_sharded_train_step,
        )
        from differential_transformer_replication_tpu.parallel.dp_step import (
            create_sharded_train_state,
        )
        from differential_transformer_replication_tpu.train import (
            create_train_state,
            make_train_step,
        )

        cfg = _overlap_cfg(grad_acc_steps=2)
        cfg = cfg.replace(model=cfg.model.replace(ffn_impl="pallas"))
        x = jax.random.randint(jax.random.PRNGKey(5), (2, 8, 16), 0, 128)
        batch = {"x": x, "y": jnp.roll(x, -1, -1)}

        s1, m1 = make_train_step(cfg)(
            create_train_state(jax.random.PRNGKey(0), cfg), batch
        )
        mesh = create_mesh(cfg.mesh)
        state = create_sharded_train_state(jax.random.PRNGKey(0), cfg, mesh)
        step = make_sharded_train_step(cfg, mesh, state)
        s2, m2 = step(state, batch)
        np.testing.assert_allclose(
            float(m1["loss"]), float(m2["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(s1["params"]), jax.tree.leaves(s2["params"])
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(jax.device_get(b)),
                rtol=2e-4, atol=1e-5,
            )

    def test_loss_decreases_with_fused_ffn(self):
        """Overlap + fused kernels together: the full round-6 hot path
        trains."""
        from differential_transformer_replication_tpu.parallel import (
            create_mesh,
            make_sharded_train_step,
        )
        from differential_transformer_replication_tpu.parallel.dp_step import (
            create_sharded_train_state,
        )

        cfg = _overlap_cfg()
        cfg = cfg.replace(model=cfg.model.replace(ffn_impl="pallas"))
        mesh = create_mesh(cfg.mesh)
        state = create_sharded_train_state(jax.random.PRNGKey(0), cfg, mesh)
        step = make_sharded_train_step(cfg, mesh, state)
        x = jax.random.randint(jax.random.PRNGKey(2), (1, 8, 16), 0, 128)
        batch = {"x": x, "y": jnp.roll(x, -1, -1)}
        first = None
        for _ in range(25):
            state, metrics = step(state, batch)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first - 0.5

    def test_bucket_counts(self):
        """One pmean per layer group + embeddings + tail: the bucket
        assignment is the overlap schedule, so pin its shape."""
        from differential_transformer_replication_tpu.parallel.dp_step import (
            make_param_sync,
        )

        calls = []

        def fake_sync_factory(axis):
            def sync(tree):
                calls.append(jax.tree_util.tree_structure(tree))
                return tree
            return sync

        import differential_transformer_replication_tpu.parallel.dp_step as dp

        orig = dp._bucket_sync
        dp._bucket_sync = fake_sync_factory
        try:
            ps = make_param_sync("data", bucket_layers=2)
            cfg = ModelConfig(model="diff", **TINY)  # n_layer=2
            params = init_model(jax.random.PRNGKey(0), cfg)
            out = ps(params)
        finally:
            dp._bucket_sync = orig
        # embed bucket + tail bucket + ceil(2/2)=1 block bucket
        assert len(calls) == 3
        assert jax.tree_util.tree_structure(out) == (
            jax.tree_util.tree_structure(params)
        )


class TestMeshGuardAndShardRng:
    """Fused kernels must never reach a multi-device GSPMD placement as
    bare pallas_calls (models/common.py:use_fused_ffn), and the overlap
    path's replicated dropout key must be folded per shard."""

    def test_use_fused_ffn_matrix(self):
        from differential_transformer_replication_tpu.models import common
        from differential_transformer_replication_tpu.parallel import (
            create_mesh,
        )

        pallas = ModelConfig(model="diff", **TINY).replace(ffn_impl="pallas")
        xla = ModelConfig(model="diff", **TINY)
        multi = create_mesh(MeshConfig(data=8))
        single = create_mesh(MeshConfig(data=1))
        assert common.use_fused_ffn(pallas, None)
        assert common.use_fused_ffn(pallas, single)
        assert not common.use_fused_ffn(pallas, multi)
        assert not common.use_fused_ffn(xla, None)
        assert not common.use_fused_ffn(None, None)

    def test_gspmd_multidevice_falls_back_to_xla(self):
        """On the 8-device GSPMD placement (overlap off) ffn_impl='pallas'
        must compile the same XLA-composition program as 'xla': bit-equal
        loss proves the guard dispatched identically."""
        from differential_transformer_replication_tpu.parallel import (
            create_mesh,
            make_sharded_train_step,
        )
        from differential_transformer_replication_tpu.parallel.dp_step import (
            create_sharded_train_state,
        )

        x = jax.random.randint(jax.random.PRNGKey(3), (1, 8, 16), 0, 128)
        batch = {"x": x, "y": jnp.roll(x, -1, -1)}
        losses = {}
        for impl in ("xla", "pallas"):
            cfg = _overlap_cfg(dp_overlap=False)
            cfg = cfg.replace(model=cfg.model.replace(ffn_impl=impl))
            mesh = create_mesh(cfg.mesh)
            state = create_sharded_train_state(jax.random.PRNGKey(0), cfg, mesh)
            step = make_sharded_train_step(cfg, mesh, state)
            _, m = step(state, batch)
            losses[impl] = float(m["loss"])
        assert losses["pallas"] == losses["xla"]

    def test_overlap_shards_draw_independent_dropout_masks(self):
        """8 shards each holding the SAME example: without the per-shard
        fold_in(axis_index) every shard reuses the single-device key
        chain, making the overlap loss bit-equal to the single-device
        loss on one example — the exact correlated-mask bug."""
        from differential_transformer_replication_tpu.parallel import (
            create_mesh,
            make_sharded_train_step,
        )
        from differential_transformer_replication_tpu.parallel.dp_step import (
            create_sharded_train_state,
        )
        from differential_transformer_replication_tpu.train import (
            create_train_state,
            make_train_step,
        )

        cfg = _overlap_cfg()
        cfg = cfg.replace(model=cfg.model.replace(dropout=0.5))
        rng = jax.random.PRNGKey(7)
        one = jax.random.randint(jax.random.PRNGKey(4), (1, 1, 16), 0, 128)
        single_batch = {"x": one, "y": jnp.roll(one, -1, -1)}
        tiled = jnp.tile(one, (1, 8, 1))
        tiled_batch = {"x": tiled, "y": jnp.roll(tiled, -1, -1)}

        _, m1 = make_train_step(cfg)(
            create_train_state(jax.random.PRNGKey(0), cfg), single_batch, rng
        )
        mesh = create_mesh(cfg.mesh)
        state = create_sharded_train_state(jax.random.PRNGKey(0), cfg, mesh)
        step = make_sharded_train_step(cfg, mesh, state)
        _, m2 = step(state, tiled_batch, rng)
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert np.isfinite(l2)
        assert l1 != l2, "shards reused the replicated dropout key"


class TestCompileCounterFallback:
    """Satellite: jax-version drift removes jit._cache_size -> the
    trainer's compile-event counter must fall back to the backend-
    compile monitoring instead of silently reporting nothing."""

    def test_fallback_attaches_backend_monitor(self, capsys):
        from differential_transformer_replication_tpu.parallel.dp_step import (
            _attach_compile_counter,
        )

        class NoCacheJit:  # a jitted fn on a drifted jax version
            pass

        def step(state, batch, rng=None):
            return state, {}

        out = _attach_compile_counter(step, NoCacheJit(), "drifted")
        assert out._compile_counter_source == "backend-compile-monitor"
        assert isinstance(out._cache_size(), int)
        assert "backend-compile-monitor" in capsys.readouterr().out

    def test_native_source_preferred(self, capsys):
        from differential_transformer_replication_tpu.parallel.dp_step import (
            _attach_compile_counter,
        )

        class WithCache:
            _cache_size = staticmethod(lambda: 1)

        def step(state, batch, rng=None):
            return state, {}

        out = _attach_compile_counter(step, WithCache(), "native")
        assert out._compile_counter_source == "jit-cache"
        assert out._cache_size() == 1
        assert "jit-cache" in capsys.readouterr().out

    def test_fallback_counts_real_compiles(self):
        """The fallback source must actually move when XLA compiles."""
        from differential_transformer_replication_tpu.parallel.dp_step import (
            _attach_compile_counter,
        )

        class NoCacheJit:
            pass

        def step(state, batch, rng=None):
            return state, {}

        out = _attach_compile_counter(step, NoCacheJit(), "live")
        before = out._cache_size()
        _ = jax.jit(lambda v: v * 3.0 + jnp.float32(before))(
            jnp.ones((4,), jnp.float32)
        )
        assert out._cache_size() >= before + 1


class TestToolGates:
    """CI smoke for the new tooling (satellite: ffn_sweep --smoke and
    the machine-readable profile in tier-1)."""

    @pytest.mark.parametrize("tool", ["ffn_sweep"])
    def test_ffn_sweep_smoke(self, tool):
        r = subprocess.run(
            [sys.executable, str(REPO / "tools" / "ffn_sweep.py"),
             "--smoke"],
            capture_output=True, text=True, cwd=str(REPO), timeout=580,
            env=_cpu_env(),
        )
        assert r.returncode == 0, r.stderr[-2000:]
        lines = [json.loads(l) for l in r.stdout.splitlines() if l.strip()]
        cases = {d["case"] for d in lines}
        assert cases == {"ffn_kernel", "ffn_chain", "remat_step"}, cases
        assert {"fwd", "fwd_res", "bwd"} == {
            d["kernel"] for d in lines if d["case"] == "ffn_kernel"
        }
        assert not any("failed" in d for d in lines), lines
        # both impls timed, so before/after deltas are diffable
        assert {"xla", "pallas"} <= {
            d.get("impl") for d in lines if d["case"] == "ffn_chain"
        }

    def test_profile_step_json_line(self, capsys):
        """The capture + report plumbing at toy size, in-process (the
        command itself refuses to run without a TPU, below)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "profile_step", REPO / "tools" / "profile_step.py"
        )
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
        args = tool.build_parser().parse_args(
            ["--json", "--steps", "2", "--micro-batch", "2",
             "--block-size", "16", "--n-embd", "32", "--n-head", "2",
             "--n-layer", "2", "--vocab-size", "64", "--dtype", "float32"]
        )
        out_dir, compiles = tool.capture(args)
        tool.report(out_dir, args.steps, args.top, compiles, args.json)
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert doc["metric"] == "profile_step_breakdown"
        # the capture window ran inside the recompile sentinel — a
        # warmed-up tiny step compiles nothing inside the window
        assert doc["compiles_in_window"] == 0
        # CPU CI has no TPU plane: the breakdown degrades to an explicit
        # error field, never a crash (TPU runs carry groups_ms_per_step)
        assert ("groups_ms_per_step" in doc) or ("error" in doc)

    @pytest.mark.parametrize("tool,argv", [
        ("tools/profile_step.py", ["--json", "--steps", "1"]),
        ("tools/ffn_sweep.py", []),
        ("tools/decode_attn_sweep.py", []),
        ("bench.py", []),
    ])
    def test_measurement_commands_refuse_to_time_the_cpu(self, tool, argv):
        """No chip, no number: outside their --smoke gates the
        measurement scripts exit instead of timing XLA:CPU or the
        Pallas interpreter."""
        r = subprocess.run(
            [sys.executable, str(REPO / tool), *argv],
            capture_output=True, text=True, cwd=str(REPO), timeout=120,
            env=_cpu_env(),
        )
        assert r.returncode != 0
        assert "JAX found no TPU" in r.stderr
        assert r.stdout.strip() == ""


def _cpu_env():
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env

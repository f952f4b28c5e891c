"""The ``nemotron_h`` family (models/nemotron_h.py; the Mamba-2 operators of
ops/ssd.py, the expert layer of models/kimi_linear.py and ops/moe.py with
its latent and its ungated experts, the hybrid loops of models/decode.py)
against the plain reference ``benchmark/reference_nemotron_h.py``, at toy
widths on the CPU with seeded random weights: hidden 64, 8 Mamba-2 heads
of 8 in 2 groups, state 16, sub-chunks of 8, 4 query heads on 1 K/V head,
16 experts of 24 in a latent of 16 of which a token keeps 4, a shared
expert of 48, pattern ``MEM*EME``, a ring of 64. The full forward; prefill
in chunks with a padded tail then decoding through the pool; the chunked
scan against the token-by-token recurrence; the state update's kernel
against its twin; the expert layer against a NumPy transcription; the four
shares of an expert layer against the uncut layer; what the engine admits,
counts and refuses; what the configuration refuses.
"""

import contextlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmark"))

import reference_nemotron_h as reference  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    NEMOTRON_H_FIELDS,
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import (  # noqa: E402
    decode,
    init_model,
    kimi_linear,
    model_forward,
    nemotron_h,
)
from differential_transformer_replication_tpu.ops import moe, ssd  # noqa: E402
from differential_transformer_replication_tpu.serving.engine import (  # noqa: E402
    ServingEngine,
)
from differential_transformer_replication_tpu.serving.migrate import (  # noqa: E402
    MigrateExportError,
)

V = 211
TOY = dict(model="nemotron_h", vocab_size=V, n_embd=64, n_head=4, kv_heads=1,
           n_layer=7, block_size=64, norm_eps=1e-5,
           hybrid_override_pattern="MEM*EME", mamba_num_heads=8,
           mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
           num_experts=16, experts_per_token=4, moe_hidden=24,
           moe_latent_size=16, moe_shared_hidden=48, mlp_act="relu2",
           routed_scaling=5.0, held_experts=[0, 4],
           compute_dtype="float32", param_dtype="float32")
PUBLISHED = dict(model="nemotron_h", vocab_size=32768, n_embd=4096, n_head=32,
                 kv_heads=2, n_layer=11, block_size=8192, norm_eps=1e-5,
                 hybrid_override_pattern="MEMEMEM*EME", mamba_num_heads=128,
                 mamba_head_dim=64, n_groups=8, ssm_state_size=128,
                 chunk_size=128, num_experts=512, experts_per_token=22,
                 moe_hidden=2688, moe_latent_size=1024,
                 moe_shared_hidden=5376, mlp_act="relu2", routed_scaling=5.0,
                 held_experts=[0, 128], param_dtype="bfloat16")
# float32 on both sides; what differs is the order of the sums (the scan in
# sub-chunks against a token at a time, the blocked softmax, grouped experts
# against a loop over all of them), a few 1e-6 on logits of size 4
TOL = dict(atol=5e-4, rtol=5e-4)


def toy(**kw) -> ModelConfig:
    return ModelConfig(**dict(TOY, **kw))


@pytest.fixture(scope="module")
def params():
    return reference.make_params(7, TOY)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(3).integers(0, V, (2, 60)))


@pytest.fixture(scope="module")
def full_logits(params, tokens):
    return reference.forward(params, tokens, TOY)


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


# -- the model against the reference ------------------------------------------


def test_forward_matches_the_reference(params, tokens, full_logits):
    logits, loss = jax.jit(lambda p, i: model_forward(p, i, toy()))(
        params, tokens)
    assert loss is None
    np.testing.assert_allclose(logits, full_logits, **TOL)


@pytest.mark.parametrize("fault", reference.FAULTS[1:])
def test_each_planted_fault_is_another_model(params, tokens, full_logits,
                                             fault):
    """The faults the witness plants move the logits far past the
    tolerance above: each is a model the comparison can tell apart."""
    got = reference.forward(params, tokens, TOY, fault=fault)
    assert float(jnp.abs(got - full_logits).max()) > 0.05


def test_layout_matches_the_reference():
    for model in (TOY, PUBLISHED):
        want = jax.eval_shape(lambda k: init_model(k, ModelConfig(**model)),
                              jax.random.PRNGKey(0))
        spec = reference.param_spec(model)
        got = jax.tree_util.tree_map(
            lambda leaf: tuple(leaf[0]), spec, is_leaf=reference._is_leaf_spec)
        assert got == jax.tree_util.tree_map(lambda a: a.shape, want)


def test_the_family_is_served_not_trained(params, tokens):
    with pytest.raises(ValueError, match="served, not trained"):
        model_forward(params, tokens, toy(), targets=tokens)


# -- prefill in chunks, then decoding through the pool ------------------------------


def _programs(cfg):
    chunk = jax.jit(lambda p, t, pos, c: decode.forward_chunk(p, t, pos, c, cfg))
    tail = jax.jit(lambda p, t, pos, c, v: decode.forward_chunk(
        p, t, pos, c, cfg, valid=v))
    step = jax.jit(lambda p, t, pos, c, a: decode.forward_decode_pool(
        p, t, pos, c, cfg, active=a))
    return chunk, tail, step


@pytest.mark.parametrize("P", [5, 8, 13, 16, 21, 40], ids=[
    "inside_a_sub_chunk", "at_a_sub_chunk_s_edge", "two_chunks_padded_tail",
    "two_whole_chunks", "three_chunks", "five_chunks"])
def test_chunked_prefill_then_pool_decode_matches_the_reference(
        params, tokens, full_logits, P):
    """A prompt of P tokens in prefill chunks of 8 (= the SSD sub-chunk, so
    a boundary falls at a sub-chunk's edge; P = 5, 13, 21 leave a tail
    padded to 8 with ``valid``), then one token a step through the pool."""
    cfg = toy()
    chunk, tail, step = _programs(cfg)
    cache = decode.init_cache(cfg, 2)
    pos = 0
    while P - pos >= 8:
        logits, cache = chunk(params, tokens[:, pos:pos + 8], jnp.int32(pos),
                              cache)
        np.testing.assert_allclose(logits, full_logits[:, pos:pos + 8], **TOL)
        pos += 8
    if pos < P:
        padded = jnp.zeros((2, 8), tokens.dtype).at[:, :P - pos].set(
            tokens[:, pos:P])
        logits, cache = tail(params, padded, jnp.int32(pos), cache,
                             jnp.int32(P - pos))
        np.testing.assert_allclose(logits[:, 0], full_logits[:, P - 1], **TOL)
    for t in range(P, P + 6):
        logits, cache, load = step(params, tokens[:, t],
                                   jnp.full((2,), t, jnp.int32), cache,
                                   jnp.ones((2,), bool))
        np.testing.assert_allclose(logits, full_logits[:, t], **TOL)
        assert load.shape == (3,) and 0 <= int(load[0]) <= 2 * 4 * 3


def test_a_chunk_that_straddles_sub_chunks(params, tokens, full_logits):
    """Chunks of 11 and 13 tokens: their boundaries fall INSIDE sub-chunks
    of 8, and each is padded to whole sub-chunks inside the scan."""
    cfg = toy()
    chunk, _, _ = _programs(cfg)
    cache = decode.init_cache(cfg, 2)
    for lo, hi in ((0, 11), (11, 24), (24, 25)):
        logits, cache = chunk(params, tokens[:, lo:hi], jnp.int32(lo), cache)
        np.testing.assert_allclose(logits, full_logits[:, lo:hi], **TOL)


def test_two_slots_at_different_positions_share_a_step(params, tokens,
                                                       full_logits):
    """Slot 0 holds 20 tokens of row 0, slot 1 nothing live, slot 2 holds 9
    tokens of row 1: one step advances 0 and 2 and leaves every bit of 1."""
    cfg = toy()
    chunk, _, step = _programs(cfg)
    pool = decode.init_cache(cfg, 3)
    marked = [
        {k: leaf.at[1].set(0.5 if leaf.dtype == jnp.float32 else 1)
         for k, leaf in layer.items()} for layer in pool]

    def fill(cache, slot, row, n):
        one = decode.init_cache(cfg, 1)
        _, one = chunk(params, tokens[row:row + 1, :n], jnp.int32(0), one)
        return [{k: leaf.at[(slice(None), slot) if k == "k" else slot].set(
                    new[k][:, 0] if k == "k" else new[k][0])
                 for k, leaf in layer.items()}
                for layer, new in zip(cache, one)]

    cache = fill(fill(marked, 0, 0, 20), 2, 1, 9)
    toks = jnp.asarray([tokens[0, 20], 0, tokens[1, 9]])
    logits, after, _ = step(params, toks, jnp.asarray([20, 0, 9], jnp.int32),
                            cache, jnp.asarray([True, False, True]))
    np.testing.assert_allclose(logits[0], full_logits[0, 20], **TOL)
    np.testing.assert_allclose(logits[2], full_logits[1, 9], **TOL)
    for before, layer in zip(cache, after):
        for k in layer:
            at = (slice(None), 1) if k == "k" else 1
            assert np.array_equal(np.asarray(layer[k][at]),
                                  np.asarray(before[k][at])), k


def test_a_slot_reused_after_reset_starts_a_sequence_anew(params, tokens,
                                                          full_logits):
    cfg = toy()
    chunk, _, _ = _programs(cfg)
    cache = decode.init_cache(cfg, 2)
    _, cache = chunk(params, tokens[:, :16], jnp.int32(0), cache)
    stale, _ = chunk(params, tokens[::-1, :8], jnp.int32(0), cache)
    assert float(jnp.abs(stale - full_logits[::-1, :8]).max()) > 0.05
    for slot in (0, 1):
        cache = jax.jit(decode.reset_slot_state)(cache, jnp.int32(slot))
    fresh, _ = chunk(params, tokens[::-1, :8], jnp.int32(0), cache)
    np.testing.assert_allclose(fresh, full_logits[::-1, :8], **TOL)


def test_the_pattern_gives_kinds_and_cache_leaves():
    cfg = ModelConfig(**PUBLISHED)
    assert cfg.layer_kinds() == tuple(
        {"M": "mamba2", "E": "none", "*": "full"}[c] for c in "MEMEMEM*EME")
    assert cfg.mlp_kinds() == tuple(
        "moe" if c == "E" else "none" for c in "MEMEMEM*EME")
    assert decode.has_recurrent_state(cfg) and cfg.cannot_roll
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 3))
    shapes = [{k: (v.shape, v.dtype.name) for k, v in layer.items()}
              for layer in cache]
    assert shapes[0] == {"ssm": ((3, 128, 8192), "float32"),
                         "conv": ((3, 3, 10240), "bfloat16")}
    assert shapes[1] == {}
    assert shapes[7] == {"k": ((1, 3, 2, 8192, 128), "bfloat16"),
                         "v": ((3, 2, 8192, 128), "bfloat16")}
    assert [sorted(s) for s in shapes] == [
        {"M": ["conv", "ssm"], "E": [], "*": ["k", "v"]}[c]
        for c in "MEMEMEM*EME"]
    # 4.19 MB of state a Mamba-2 layer and slot; 29.7 MB a slot
    per_slot = sum(np.prod(s) * (4 if d == "float32" else 2)
                   for layer in shapes for s, d in layer.values()) / 3
    assert 128 * 8192 * 4 == 4194304 and abs(per_slot - 29.67e6) < 0.01e6


def test_a_chunk_past_the_ring_is_refused(params):
    with pytest.raises(ValueError, match="carry no position"):
        decode.forward_chunk(params, jnp.zeros((1, 8), jnp.int32), 60,
                             decode.init_cache(toy(), 1), toy())


# -- the Mamba-2 operators ------------------------------------------------------------


def _ssd_inputs(B=2, L=27, H=8, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (B, L, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, L, H)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (B, L, G, N)),
            jax.random.normal(k[4], (B, L, G, N)),
            jax.random.normal(k[5], (H,)),
            jax.random.normal(k[6], (B, N, H * P)))


@pytest.mark.parametrize("L, chunk", [(8, 8), (27, 8), (40, 8), (5, 8),
                                      (33, 16), (24, 4)])
def test_chunked_scan_is_the_recurrence(L, chunk):
    """Several sub-chunks with a state handed IN (not zeros) and lengths
    that are no multiple of the sub-chunk: the matrix form gives the
    token-by-token sum, to float32's order of summation (values of size
    30)."""
    args = _ssd_inputs(L=L)
    y0, h0 = ssd.recurrent_scan(*args)
    y1, h1 = jax.jit(lambda *a: ssd.chunk_scan(*a, chunk))(*args)
    np.testing.assert_allclose(y1, y0, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h1, h0, atol=1e-4, rtol=1e-4)


def test_a_step_of_zero_dt_leaves_the_state():
    """What ``valid`` leans on: steps whose dt is 0 neither decay nor add."""
    x, dt, A, Bm, Cm, D, h0 = _ssd_inputs(L=16)
    dt = dt.at[:, 11:].set(0.0)
    _, part = ssd.chunk_scan(x[:, :11], dt[:, :11], A, Bm[:, :11],
                             Cm[:, :11], D, h0, 8)
    _, whole = ssd.chunk_scan(x, dt, A, Bm, Cm, D, h0, 8)
    np.testing.assert_allclose(whole, part, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("active", [
    [False] * 6, [True] * 6, [True, False, True, True, False, False],
    [False, False, False, False, False, True]],
    ids=["none", "all", "some", "last_alone"])
def test_state_update_kernel_is_its_twin_and_spares_inactive_slots(active):
    x, dt, A, Bm, Cm, D, _ = _ssd_inputs(B=1, L=6)
    state = jax.random.normal(jax.random.PRNGKey(9), (6, 16, 64))
    act = jnp.asarray(active)
    args = (state, x[0].reshape(6, 64), dt[0], A, Bm[0], Cm[0], D, act)
    ya, sa = ssd.state_update_xla(*args)
    yb, sb = jax.jit(ssd.state_update)(*args)
    np.testing.assert_allclose(yb, ya, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(sb, sa, atol=1e-6, rtol=1e-6)
    # every BIT of an inactive slot's state, and a y of exactly 0
    quiet = ~np.asarray(act)
    assert np.array_equal(np.asarray(sb)[quiet], np.asarray(state)[quiet])
    assert not np.asarray(yb)[quiet].any()
    # and one step of the recurrence for the active ones
    y0, h0 = ssd.recurrent_scan(x[0][:, None], dt[0][:, None], A,
                                Bm[0][:, None], Cm[0][:, None], D, state)
    np.testing.assert_allclose(np.asarray(sb)[~quiet], np.asarray(h0)[~quiet],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(yb)[~quiet],
                               np.asarray(y0)[:, 0].reshape(6, 64)[~quiet],
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("op", ["chunk", "step"])
def test_heads_of_a_group_share_b_and_c_and_two_groups_do_not(op):
    """Heads 0 and 1 (group 0) given the same x, dt, A and D come out
    alike; head 4 (group 1) given the same does not: it reads other B, C."""
    x, dt, A, Bm, Cm, D, h0 = _ssd_inputs(L=12)
    for h in (1, 4):
        x, dt = x.at[:, :, h].set(x[:, :, 0]), dt.at[:, :, h].set(dt[:, :, 0])
        A, D = A.at[h].set(A[0]), D.at[h].set(D[0])
    h0 = jnp.zeros_like(h0)
    if op == "chunk":
        y, _ = ssd.chunk_scan(x, dt, A, Bm, Cm, D, h0, 8)
        heads = [y[:, :, h] for h in (0, 1, 4)]
    else:
        y, _ = jax.jit(ssd.state_update)(
            h0, x[:, 0].reshape(2, 64), dt[:, 0], A, Bm[:, 0], Cm[:, 0], D,
            jnp.ones((2,), bool))
        heads = [y.reshape(2, 8, 8)[:, h] for h in (0, 1, 4)]
    np.testing.assert_allclose(heads[1], heads[0], atol=1e-6, rtol=1e-6)
    assert float(jnp.abs(heads[2] - heads[0]).max()) > 0.1


def test_mixer_norms_a_group_after_the_gate(params):
    """The gated RMSNorm against a NumPy transcription: gate, THEN norm
    over each group's 32 channels."""
    cfg = toy()
    p = params["blocks"][0]["mamba2"]
    rng = np.random.default_rng(0)
    y = rng.standard_normal((5, 64)).astype(np.float32)
    z = rng.standard_normal((5, 64)).astype(np.float32)
    got = nemotron_h._gate_norm_out(jnp.asarray(y), jnp.asarray(z), p, cfg)
    g = (y * (z / (1 + np.exp(-z)))).reshape(5, 2, 32)
    g = g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
    want = (g.reshape(5, 64) * np.asarray(p["norm"])) @ np.asarray(
        p["out_proj"])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# -- the expert layer ----------------------------------------------------------------


def _numpy_expert_layer(h, p, lo, hi, top, scaling):
    """relu^2, no gate, the latent in and out, the shared expert on the
    hidden state: the published layer in NumPy, float64."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    h = f(h)
    s = 1 / (1 + np.exp(-(h @ f(p["router"]["w"]))))
    order = np.argsort(-(s + f(p["router"]["b"])), axis=-1,
                       kind="stable")[:, :top]
    u = h @ f(p["latent_in"])
    y = np.zeros_like(u)
    fetched = set()
    for t in range(h.shape[0]):
        picked = s[t, order[t]]
        for e, w in zip(order[t], picked / picked.sum() * scaling):
            if lo <= e < hi:
                fetched.add(int(e))
                a = np.maximum(u[t] @ f(p["experts"]["up"][e - lo]), 0.0)
                y[t] += w * ((a * a) @ f(p["experts"]["down"][e - lo]))
    a = np.maximum(h @ f(p["shared"]["up"]["w"]), 0.0)
    return (y @ f(p["latent_out"]) + (a * a) @ f(p["shared"]["down"]["w"]),
            order, fetched)


@pytest.mark.parametrize("model, rows", [(TOY, 37), (dict(
    TOY, num_experts=64, experts_per_token=5, held_experts=[16, 32]), 23)],
    ids=["4_of_16_hold_4", "5_of_64_hold_16"])
def test_expert_layer_is_the_numpy_transcription(model, rows):
    """22-of-512's small twins: the layer through ``moe_mlp`` (the sort,
    the padded grouping, the grouped product) against the transcription."""
    cfg = ModelConfig(**model)
    p = reference.make_params(5, model)["blocks"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(2), (rows, 64))
    y, load = jax.jit(lambda h, p: kimi_linear.moe_mlp(h, p, cfg))(h, p)
    lo, hi = cfg.held_expert_range
    want, order, fetched = _numpy_expert_layer(
        h, p, lo, hi, cfg.experts_per_token, cfg.routed_scaling)
    np.testing.assert_allclose(y, want, atol=2e-4, rtol=2e-4)
    assert np.array_equal(
        load, np.bincount(order.ravel(), minlength=hi)[lo:hi])
    assert set(np.flatnonzero(np.asarray(load)) + lo) == fetched


def test_a_row_none_of_whose_experts_is_held_adds_the_shared_expert_alone(
        params):
    """Rows steered (by the router's bias) onto experts 8-15 meet nothing
    of the share 0-3: the layer's output is the shared expert's, the load
    is zero and the grouped product runs no tile (``used`` 0)."""
    cfg = toy()
    p = dict(params["blocks"][1]["moe"])
    p["router"] = dict(p["router"], b=jnp.where(jnp.arange(16) >= 8, 10.0, 0))
    h = jax.random.normal(jax.random.PRNGKey(4), (9, 64))
    y, load = kimi_linear.moe_mlp(h, p, cfg)
    assert not np.asarray(load).any()
    np.testing.assert_allclose(y, kimi_linear.relu2_mlp(h, p["shared"]),
                               atol=1e-6, rtol=1e-6)


def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The guide's test of the cut: four shares of an expert layer, each
    adding its held experts' terms IN THE LATENT, summed after the
    latent's way out, plus the shared expert counted once, are the uncut
    reference layer; and the reference at a share gives that share's
    part."""
    model = dict(TOY, held_experts=[0, 16])
    uncut = reference.make_params(5, model)["blocks"][1]["moe"]
    s = reference.sizes(model)
    h = jax.random.normal(jax.random.PRNGKey(3), (50, 64))
    want = reference._moe(h, uncut, s, None, None)
    shared = kimi_linear.relu2_mlp(h, uncut["shared"])
    total, loads = jnp.zeros_like(h), []
    for lo in range(0, 16, 4):
        share_model = dict(TOY, held_experts=[lo, lo + 4])
        share = reference.make_params(5, share_model)["blocks"][1]["moe"]
        for leaf in ("up", "down"):  # a slice of the one uncut model
            assert np.array_equal(share["experts"][leaf],
                                  uncut["experts"][leaf][lo:lo + 4])
        cfg = ModelConfig(**share_model)
        y, load = kimi_linear.moe_mlp(h, share, cfg)
        one = reference._moe(h, share, reference.sizes(share_model), None,
                             None)
        np.testing.assert_allclose(y, one, atol=2e-4, rtol=2e-4)
        total = total + (y - shared)
        loads.append(load)
    np.testing.assert_allclose(total + shared, want, atol=2e-4, rtol=2e-4)
    # every token's 4 experts fell on one share, none dropped
    assert int(sum(l.sum() for l in loads)) == 50 * 4


@pytest.mark.parametrize("rows", [1, 7, 64])
def test_ungated_experts_tile_like_the_gated_ones(rows):
    """``ops/moe.py:experts`` with ``up`` in ``gate_up``'s place against a
    dense product a row, and the gated form on the same rows still the
    gated product (the leaves decide)."""
    k = jax.random.split(jax.random.PRNGKey(rows), 5)
    h = jax.random.normal(k[0], (rows, 16))
    chosen = jax.random.randint(k[1], (rows, 3), 0, 8)
    w = jax.random.uniform(k[2], (rows, 3))
    up = jax.random.normal(k[3], (4, 16, 24)) * 0.25
    down = jax.random.normal(k[4], (4, 24, 16)) * 0.2
    y, load = moe.experts(h, chosen, w, {"up": up, "down": down}, 2)
    yg, _ = moe.experts(h, chosen, w, {"gate_up": jnp.concatenate(
        [up, up], -1), "down": down}, 2)
    want, gated = np.zeros((rows, 16)), np.zeros((rows, 16))
    for t in range(rows):
        for e, wt in zip(np.asarray(chosen[t]), np.asarray(w[t])):
            if 2 <= e < 6:
                a = np.asarray(h[t] @ up[e - 2])
                want[t] += wt * ((np.maximum(a, 0) ** 2) @ np.asarray(down[e - 2]))
                gated[t] += wt * ((a / (1 + np.exp(-a)) * a)
                                  @ np.asarray(down[e - 2]))
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(yg, gated, atol=1e-4, rtol=1e-4)
    assert int(load.sum()) == int(((chosen >= 2) & (chosen < 6)).sum())


# -- the engine -----------------------------------------------------------------------


def _engine(params, cfg, tracer=None, **kw):
    return ServingEngine(params, cfg, ServingConfig(
        **dict(dict(num_slots=2, prefill_chunk=8, prefill_budget=16), **kw)),
        tracer=tracer)


def _prompts(n, seed=0, lo=5, hi=52):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, size=int(k)).tolist()
            for k in rng.integers(lo, hi, size=n)]


class _Spans:
    """The tracer's interface, keeping what the engine hands it."""
    path, annotate = None, False

    def __init__(self):
        self.spans = []

    def span(self, name, **args):
        self.spans.append((name, args))
        return contextlib.nullcontext()

    def instant(self, *a, **k): pass
    def counter(self, *a, **k): pass
    def complete(self, *a, **k): pass
    def flush(self): pass
    def close(self): pass


@pytest.mark.parametrize("num_slots", [2, 8], ids=["queued", "at_once"])
def test_engine_serves_the_reference_s_greedy_tokens(params, num_slots):
    """Six requests of 5-51 tokens on two slots (four wait, and enter a
    slot another left, whose state is zeroed first) and on eight: the same
    ``submit``, scheduler, slot pool and sampler as every family."""
    cfg = toy()
    spans = _Spans()
    eng = _engine(params, cfg, tracer=spans, num_slots=num_slots)
    built = eng.compile_stats()
    prompts = _prompts(6)
    outs = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    for p, out in zip(prompts, outs):
        seq = jnp.asarray([list(p) + list(out.tokens)[:-1]])
        want = jnp.argmax(reference.forward(params, seq, TOY)[0, len(p) - 1:], -1)
        assert list(out.tokens) == np.asarray(want).tolist()
    stats = eng.compile_stats()
    assert stats["decode"] - built["decode"] == 1
    assert stats["state_reset"] >= 1 and eng.stats["state_resets"] == 6
    assert stats["prefill"] - built["prefill"] <= 4
    steps = [a for n, a in spans.spans if n == "decode"]
    state = 3 * 16 * 64 * 4  # three Mamba-2 layers' (N, Di) float32 a slot
    assert steps and all(
        a["live_state_bytes"] == a["active"] * state for a in steps)
    assert eng.stats["decode_live_state"] == sum(
        a["live_state_bytes"] for a in steps)
    assert eng.stats["moe_held"] == sum(a["moe"]["held"] for a in steps)
    assert eng.stats["moe_experts_hit"] == sum(
        a["moe"]["experts_hit"] for a in steps)
    assert all(a["moe"]["experts_hit"] <= min(3 * 4, a["moe"]["held"])
               for a in steps)
    # 4 of 16 chosen, 4 held: one assignment a row and expert layer
    per_row = eng.stats["moe_held"] / sum(a["active"] for a in steps) / 3
    assert 0.5 < per_row < 1.5
    text = eng.registry.render()
    for name in ("serving_decode_live_state_bytes_total",
                 "serving_moe_experts_hit_total",
                 "serving_state_resets_total 6"):
        assert name in text
    pool = sum(leaf.nbytes for layer in eng.cache for leaf in layer.values())
    assert pool == num_slots * (3 * (16 * 64 + 3 * 128) + 2 * 64 * 16) * 4
    got = re.search(r"^serving_state_pool_bytes (\S+)$", text, re.M).group(1)
    assert float(got) == num_slots * 3 * (16 * 64 + 3 * 128) * 4


def test_a_slot_reused_by_a_shorter_sequence_serves_what_a_fresh_one_serves(
        params):
    cfg = toy()
    long_one = _prompts(1, seed=11, lo=50, hi=51)[0]
    short = _prompts(1, seed=12, lo=7, hi=8)[0]
    used = _engine(params, cfg, num_slots=1)
    used.generate([long_one], max_new_tokens=12, temperature=0.0)
    again = used.generate([short], max_new_tokens=30, temperature=0.0)[0]
    fresh = _engine(params, cfg, num_slots=1).generate(
        [short], max_new_tokens=30, temperature=0.0)[0]
    assert list(again.tokens) == list(fresh.tokens)


def test_a_request_is_bounded_by_the_ring(params):
    eng = _engine(params, toy())
    with pytest.raises(ValueError, match="carry no position"):
        eng.submit(list(range(50)), max_new_tokens=15)
    out = eng.generate([list(range(50))], max_new_tokens=14,
                       temperature=0.0)[0]
    assert len(out.tokens) == 14


@pytest.mark.parametrize("serving, named", [
    (dict(kv_page_size=16), "paging"),
    (dict(kv_page_size=16, prefix_cache=True), "prefix cache"),
    (dict(spec_mode="ngram"), "multi-token-prediction module"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
    (dict(kv_page_size=16, host_tier_bytes=1 << 20), "host tier"),
])
def test_the_engine_refuses_what_needs_a_snapshot_and_names_its_mixer(
        params, serving, named):
    """The reason is the recurrent state's, and the message names the
    mixer this family has: Mamba-2, neither Mamba nor KDA."""
    with pytest.raises(ValueError) as e:
        _engine(params, toy(), **serving)
    said = str(e.value)
    assert named in said and "nemotron_h" in said and "Mamba-2" in said
    assert "KDA" not in said and "Mamba or" not in said


@pytest.mark.parametrize("family, mixers", [
    ("jamba", "a Mamba layer"), ("kimi_linear", "a KDA layer")])
def test_the_other_recurrent_families_are_told_their_own_mixer(family, mixers):
    from differential_transformer_replication_tpu.serving.engine import (
        _refuse_for_recurrent_state,
    )
    kw = (dict(n_embd=64, n_head=4, n_layer=4, attn_layer_period=2,
               attn_layer_offset=1) if family == "jamba" else
          dict(n_embd=64, n_head=2, n_layer=2, kda_layers=[1],
               full_attn_layers=[2], kda_head_dim=8, num_experts=0,
               first_dense_layers=2))
    cfg = ModelConfig(model=family, **kw)
    with pytest.raises(ValueError) as e:
        _refuse_for_recurrent_state(cfg, ServingConfig(spec_mode="ngram"))
    assert mixers in str(e.value) and "Mamba-2" not in str(e.value)
    assert "multi-token" not in str(e.value)


def test_migration_is_refused_for_the_state(params):
    eng = _engine(params, toy())
    rid = eng.submit(_prompts(1)[0], max_new_tokens=4, temperature=0.0)
    with pytest.raises(MigrateExportError, match="recurrent"):
        eng.export_slot_state(rid)


def test_the_programs_carry_the_new_scopes(params):
    cfg = toy()
    chunk, _, step = _programs(cfg)
    cache = decode.init_cache(cfg, 2)
    toks = jnp.zeros((2, 8), jnp.int32)
    shared = {"ssm", "ssm_conv", "attn", "attn_full", "kv_write", "moe",
              "moe_router", "moe_latent", "moe_experts", "moe_shared"}
    for text, own in (
            (chunk.lower(params, toks, jnp.int32(0), cache).as_text(
                debug_info=True), {"ssm_scan"}),
            (step.lower(params, toks[:, 0], jnp.zeros((2,), jnp.int32), cache,
                        jnp.ones((2,), bool)).as_text(debug_info=True),
             {"ssm_state"})):
        found = set(re.findall(r"[/\"]([a-z_0-9]+)(?=/)", text))
        assert shared | own <= found, (shared | own) - found


# -- the configuration ----------------------------------------------------------------

_OWN = {"hybrid_override_pattern": "ME", "mamba_num_heads": 8,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "chunk_size": 8, "moe_latent_size": 16, "moe_shared_hidden": 48,
        "mlp_act": "relu2", "num_nextn_predict_layers": 1,
        "mtp_hybrid_override_pattern": "*E"}


def test_every_new_field_has_a_refusal_case():
    shared = {"kv_heads", "norm_eps", "mamba_d_conv", "ssm_state_dtype",
              "num_experts", "experts_per_token", "moe_hidden",
              "routed_scaling", "held_experts"}
    assert set(_OWN) == set(NEMOTRON_H_FIELDS) - shared


@pytest.mark.parametrize("family", ["control", "diff", "ndiff", "jamba",
                                    "kimi_linear", "afmoe", "deepseek_v2"])
@pytest.mark.parametrize("field", sorted(_OWN))
def test_another_family_refuses_a_nemotron_h_field_by_name(family, field):
    with pytest.raises(ValueError, match=field):
        ModelConfig(model=family, **{field: _OWN[field]})


@pytest.mark.parametrize("field, value", [
    ("tie_embeddings", True), ("ssm_impl", "pallas"), ("mamba_d_state", 8),
    ("mamba_expand", 4), ("kda_layers", [1]), ("kv_lora_rank", 64),
    ("layer_types", ["full_attention"]), ("sliding_window", 16),
    ("head_dim", 32), ("q_lora_rank", 24), ("n_group", 2),
    ("first_dense_layers", 0), ("ffn_hidden", 96),
])
def test_nemotron_h_refuses_another_family_s_field_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        toy(**{field: value})


@pytest.mark.parametrize("field, value, said", [
    ("num_nextn_predict_layers", 1, "multi-token-prediction"),
    ("mtp_hybrid_override_pattern", "*E", "snapshot"),
    ("attention_impl", "pallas", "attention_impl"),
    ("ffn_impl", "pallas", "ffn_impl"),
    ("decode_attention_impl", "pallas", "ssm_ssd_state_update"),
    ("dropout", 0.1, "dropout"),
    ("ssm_state_dtype", "bfloat16", "float32"),
    ("mlp_act", "gelu", "mlp_act"), ("mlp_act", "silu", "ungated"),
    ("hybrid_override_pattern", "MEM*EM", "hybrid_override_pattern"),
    ("hybrid_override_pattern", "MEM-EME", "hybrid_override_pattern"),
    ("n_groups", 3, "n_groups"), ("mamba_num_heads", 0, "mamba_num_heads"),
    ("chunk_size", 0, "chunk_size"), ("mamba_d_conv", 1, "mamba_d_conv"),
    ("moe_shared_hidden", 0, "moe_shared_hidden"),
    ("kv_heads", 3, "kv_heads"), ("experts_per_token", 17, "num_experts"),
    ("held_experts", [4, 20], "held_experts"), ("moe_hidden", 0, "moe_hidden"),
])
def test_nemotron_h_refuses_what_it_does_not_run_under_its_reason(
        field, value, said):
    with pytest.raises(ValueError, match=said):
        toy(**{field: value})


def test_a_pattern_without_experts_needs_no_expert_fields():
    cfg = ModelConfig(model="nemotron_h", n_embd=64, n_head=4, n_layer=2,
                      hybrid_override_pattern="M*", mamba_num_heads=8,
                      mamba_head_dim=8)
    assert cfg.layer_kinds() == ("mamba2", "full")
    assert cfg.mlp_kinds() == ("none", "none")


def test_the_cut_is_4_648_billion_parameters():
    """ISSUE 42's table, to the fourth digit, from ``init``'s own tree."""
    cfg = ModelConfig(**PUBLISHED)
    tree = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    size = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                         for a in jax.tree_util.tree_leaves(t))
    blocks = tree["blocks"]
    assert round(size(blocks[0]) / 1e6, 2) == 109.64
    assert round(size(blocks[7]) / 1e6, 2) == 35.66
    routed = size(blocks[1]["moe"]["experts"])
    assert round(routed / 128 / 1e6, 3) == 5.505
    assert round((size(blocks[1]) - routed) / 1e6, 2) == 54.53
    assert round(size(blocks[1]) / 1e6, 2) == 759.17
    assert round(size(tree) / 1e6) == 4648
    assert round(2 * size(tree) / 1e9, 2) == 9.30

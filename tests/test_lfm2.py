"""The ``lfm2`` family (models/lfm2.py; the convolution of ops/ssm.py, the
expert layer of models/kimi_linear.py and ops/moe.py without a shared
expert and with the router's epsilon, the hybrid loops and the
``"shortconv"`` record of models/decode.py) against the plain reference
``benchmark/reference_lfm2.py``, at toy widths on the CPU with seeded random
weights: hidden 64, 8 query heads of 64 on 2 K/V heads (4 a K/V head, as
published), three taps, 16 experts of 24 of which a token keeps 4 and the
program holds all, a dense first layer of 96, layers ``conv full_attention
conv conv conv``, a ring of 64. The full forward; prefill in chunks (a chunk
of one token, edges the taps span, a padded tail) then decoding through the
pool; the step against the chunk; the attention layer's order and the
expert layer against NumPy transcriptions; two shares of an expert layer
against the uncut layer; what the engine admits, counts and refuses; what
the configuration refuses.
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

import reference_lfm2 as reference  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    LFM2_FIELDS,
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import (  # noqa: E402
    decode,
    init_model,
    kimi_linear,
    lfm2,
    model_forward,
)
from differential_transformer_replication_tpu.ops import moe  # noqa: E402
from differential_transformer_replication_tpu.serving.engine import (  # noqa: E402
    ServingEngine,
)
from differential_transformer_replication_tpu.serving.migrate import (  # noqa: E402
    MigrateExportError,
)

V = 211
LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]
TOY = dict(model="lfm2", vocab_size=V, n_embd=64, n_head=8, kv_heads=2,
           head_dim=64, n_layer=5, block_size=64, norm_eps=1e-5,
           layer_types=LAYERS, first_dense_layers=1, ffn_hidden=96,
           num_experts=16, experts_per_token=4, moe_hidden=24,
           routed_scaling=1.0, rope_theta=1e6, conv_taps=3, router_eps=1e-6,
           tie_embeddings=True, compute_dtype="float32",
           param_dtype="float32")
PUBLISHED = dict(model="lfm2", vocab_size=65536, n_embd=2048, n_head=32,
                 kv_heads=8, n_layer=5, block_size=8192, norm_eps=1e-5,
                 layer_types=LAYERS, first_dense_layers=1, ffn_hidden=11776,
                 num_experts=64, experts_per_token=4, moe_hidden=1536,
                 routed_scaling=1.0, held_experts=[0, 64], rope_theta=1e6,
                 conv_taps=3, router_eps=1e-6, tie_embeddings=True,
                 compute_dtype="bfloat16", param_dtype="bfloat16")
# float32 on both sides; what differs is the order of the sums (the blocked
# softmax, grouped experts against a loop over all of them), a few 1e-6 on
# logits of size 4
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
    """The faults the witness plants move the logits past the tolerance
    above: each is a model the comparison can tell apart (the bias in the
    weights least of all: 0.02 on scores of 0.9)."""
    got = reference.forward(params, tokens, TOY, fault=fault)
    assert float(jnp.abs(got - full_logits).max()) > 0.01


def test_layout_matches_the_reference():
    for model in (TOY, PUBLISHED, dict(TOY, tie_embeddings=False),
                  dict(TOY, held_experts=[4, 12])):
        want = jax.eval_shape(lambda k: init_model(k, ModelConfig(**model)),
                              jax.random.PRNGKey(0))
        spec = reference.param_spec(model)
        got = jax.tree_util.tree_map(
            lambda leaf: tuple(leaf[0]), spec, is_leaf=reference._is_leaf_spec)
        assert got == jax.tree_util.tree_map(lambda a: a.shape, want)


def test_the_family_is_served_not_trained(params, tokens):
    with pytest.raises(ValueError, match="served, not trained"):
        model_forward(params, tokens, toy(), targets=tokens)


def test_the_taps_are_signed_and_none_is_negligible(params):
    taps = np.abs(np.asarray(params["blocks"][0]["conv"]["conv_w"]))
    signs = np.sign(np.asarray(params["blocks"][0]["conv"]["conv_w"]))
    assert taps.min() > 0.02 and (signs > 0).any() and (signs < 0).any()
    assert np.median(taps.min(axis=0) / taps.max(axis=0)) > 0.3


# -- prefill in chunks, then decoding through the pool ------------------------------


def _programs(cfg):
    chunk = jax.jit(lambda p, t, pos, c: decode.forward_chunk(p, t, pos, c, cfg))
    tail = jax.jit(lambda p, t, pos, c, v: decode.forward_chunk(
        p, t, pos, c, cfg, valid=v))
    step = jax.jit(lambda p, t, pos, c, a: decode.forward_decode_pool(
        p, t, pos, c, cfg, active=a))
    return chunk, tail, step


@pytest.mark.parametrize("P", [1, 2, 5, 8, 9, 10, 13, 16, 21, 40], ids=[
    "one_token_padded", "two_tokens_padded", "inside_a_chunk",
    "one_whole_chunk", "a_tail_of_one_token", "a_tail_of_two_tokens",
    "two_chunks_padded_tail", "two_whole_chunks", "three_chunks",
    "five_chunks"])
def test_chunked_prefill_then_pool_decode_matches_the_reference(
        params, tokens, full_logits, P):
    """A prompt of P tokens in prefill chunks of 8 (P = 9, 10 leave a tail
    of one or two tokens, shorter than the window the taps span; the tail
    is padded to 8 with ``valid``), then one token a step through the pool:
    every edge lies between two tokens the taps span."""
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
        # every assignment is held: 2 rows x 4 experts x 4 expert layers
        assert load.shape == (3,) and int(load[0]) == 2 * 4 * 4


@pytest.mark.parametrize("cuts", [(7, 8, 16, 17, 30), (1, 2, 3, 20),
                                  (2, 3, 11)],
                         ids=["a_chunk_of_one_token", "three_chunks_of_one",
                              "one_token_after_two"])
def test_chunks_of_any_length_hand_the_window_on(params, tokens, full_logits,
                                                 cuts):
    """Unpadded chunks whose edges fall anywhere: a chunk of ONE token
    takes both of its window's inputs from the chunks before it and hands
    on one of them with its own."""
    cfg = toy()
    cache = decode.init_cache(cfg, 2)
    lo = 0
    for hi in cuts:
        logits, cache = decode.forward_chunk(params, tokens[:, lo:hi], lo,
                                             cache, cfg)
        np.testing.assert_allclose(logits, full_logits[:, lo:hi], **TOL)
        lo = hi


def test_a_padded_tail_leaves_the_window_after_the_last_real_token(params,
                                                                   tokens):
    """``valid`` = 3 of 8: the window afterwards holds tokens 1 and 2's
    gated inputs, as an unpadded chunk of 3 leaves it, whatever the
    padding holds."""
    cfg = toy()
    chunk, tail, _ = _programs(cfg)
    _, want = chunk(params, tokens[:, :3], jnp.int32(0),
                    decode.init_cache(cfg, 2))
    padded = jnp.full((2, 8), 5, tokens.dtype).at[:, :3].set(tokens[:, :3])
    _, got = tail(params, padded, jnp.int32(0), decode.init_cache(cfg, 2),
                  jnp.int32(3))
    for a, b, kind in zip(got, want, cfg.layer_kinds()):
        if kind == "shortconv":
            np.testing.assert_allclose(a["conv"], b["conv"], atol=1e-5)


def test_two_slots_at_different_positions_share_a_step(params, tokens,
                                                       full_logits):
    """Slot 0 holds 20 tokens of row 0, slot 1 nothing live, slot 2 holds 9
    tokens of row 1: one step advances 0 and 2 and leaves every bit of 1,
    its windows among them."""
    cfg = toy()
    chunk, _, step = _programs(cfg)
    pool = decode.init_cache(cfg, 3)
    marked = [{k: leaf.at[(slice(None), 1) if k == "k" else 1].set(0.5)
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
    logits, after, load = step(params, toks,
                               jnp.asarray([20, 0, 9], jnp.int32), cache,
                               jnp.asarray([True, False, True]))
    np.testing.assert_allclose(logits[0], full_logits[0, 20], **TOL)
    np.testing.assert_allclose(logits[2], full_logits[1, 9], **TOL)
    assert int(load[0]) == 2 * 4 * 4  # the row that is not live meets none
    for before, layer in zip(cache, after):
        for k in layer:
            at = (slice(None), 1) if k == "k" else 1
            assert np.array_equal(np.asarray(layer[k][at]),
                                  np.asarray(before[k][at])), k


def test_a_slot_reused_after_reset_starts_a_sequence_anew(params, tokens,
                                                          full_logits):
    """Without the reset the new sequence's first two tokens read the last
    one's window; with it they read zeros, a sequence's start."""
    cfg = toy()
    chunk, _, _ = _programs(cfg)
    cache = decode.init_cache(cfg, 2)
    _, cache = chunk(params, tokens[:, :16], jnp.int32(0), cache)
    stale, _ = chunk(params, tokens[::-1, :8], jnp.int32(0), cache)
    off = jnp.abs(stale - full_logits[::-1, :8]).max(axis=(0, 2))
    assert float(off[0]) > 0.05 and float(off[1]) > 0.05
    for slot in (0, 1):
        cache = jax.jit(decode.reset_slot_state)(cache, jnp.int32(slot))
    for layer, kind in zip(cache, cfg.layer_kinds()):
        if kind == "shortconv":
            assert not np.asarray(layer["conv"]).any()
        else:  # a ring is masked by positions, not zeroed
            assert np.asarray(layer["v"]).any()
    fresh, _ = chunk(params, tokens[::-1, :8], jnp.int32(0), cache)
    np.testing.assert_allclose(fresh, full_logits[::-1, :8], **TOL)


def test_layer_types_give_kinds_and_cache_leaves():
    cfg = ModelConfig(**PUBLISHED)
    assert cfg.layer_kinds() == ("shortconv", "full", "shortconv",
                                 "shortconv", "shortconv")
    assert cfg.mlp_kinds() == ("dense", "moe", "moe", "moe", "moe")
    assert decode.has_recurrent_state(cfg) and cfg.cannot_roll
    assert cfg.head_size == 64 and cfg.held_expert_range == (0, 64)
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 3))
    shapes = [{k: (v.shape, v.dtype.name) for k, v in layer.items()}
              for layer in cache]
    # a conv layer's entry holds `conv` ALONE: 2 x 2,048 bfloat16, 8 KB
    assert shapes[0] == {"conv": ((3, 2, 2048), "bfloat16")}
    assert shapes[1] == {"k": ((1, 3, 8, 8192, 64), "bfloat16"),
                         "v": ((3, 8, 8192, 64), "bfloat16")}
    assert [sorted(s) for s in shapes] == [
        ["conv"], ["k", "v"], ["conv"], ["conv"], ["conv"]]
    per_slot = sum(np.prod(s) * 2 for layer in shapes
                   for s, _ in layer.values()) / 3
    assert per_slot == 4 * 8192 + 2 * 8 * 8192 * 64 * 2  # 32 KB + 16.8 MB
    record = decode.KINDS["shortconv"]
    assert record.recurrent and record.state == "conv"
    assert dict(record.leaves) == {"conv": 0} and record.params == "conv"
    assert "conv" in decode.STATE_LEAVES and "conv" in decode.MIXER_LEAVES


def test_a_chunk_past_the_ring_is_refused(params):
    with pytest.raises(ValueError, match="see every earlier position"):
        decode.forward_chunk(params, jnp.zeros((1, 8), jnp.int32), 60,
                             decode.init_cache(toy(), 1), toy())


# -- the gated short convolution ---------------------------------------------------


def test_the_step_is_the_chunk_a_token_at_a_time(params):
    """27 tokens through ``conv_chunk`` at once and through ``conv_step``
    one at a time; a slot that is not active keeps every bit of its
    window."""
    cfg = toy()
    p = params["blocks"][0]["conv"]
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 27, 64))
    window, = lfm2.zero_window(cfg, 3)
    want, last = lfm2.conv_chunk(h, p, cfg, window)
    live = jnp.asarray([True, False, True])
    frozen = window.at[1].set(0.25)
    window = frozen
    for t in range(27):
        out, window = lfm2.conv_step(h[:, t], p, cfg, window, live)
        np.testing.assert_allclose(out[live], want[live, t], atol=1e-5)
        assert np.array_equal(np.asarray(window[1]), np.asarray(frozen[1]))
    np.testing.assert_allclose(window[live], last[live], atol=1e-6)


def test_the_convolution_is_the_numpy_transcription(params):
    """``c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t`` on ``z = B * u``,
    gated by C: no activation, no bias, zeros before the start."""
    cfg = toy()
    p = params["blocks"][0]["conv"]
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 11, 64)),
                   np.float64)
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    B, C, u = np.split(h[0] @ f(p["in_proj"]), 3, axis=-1)
    z = np.concatenate([np.zeros((2, 64)), B * u])
    w = f(p["conv_w"])
    c = w[0] * z[:-2] + w[1] * z[1:-1] + w[2] * z[2:]
    want = (C * c) @ f(p["out_proj"])
    got, window = lfm2.conv_chunk(jnp.asarray(h, jnp.float32), p, cfg,
                                  *lfm2.zero_window(cfg, 1))
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(window[0], (B * u)[-2:], atol=1e-6)


# -- the attention layer -----------------------------------------------------------


def test_attention_norms_a_head_and_then_rotates_it(params):
    """4 query heads on a K/V head, heads of 64, theta 1e6: q and k normed
    over a head's 64 values with their learned scales, THEN rotated
    (dimension i with i + 32) at the token's absolute position, in NumPy."""
    cfg = toy()
    p = params["blocks"][1]["attn"]
    T, H, KV, d = 13, 8, 2, 64
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (T, 64)),
                   np.float64)
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * f(w)

    def rotate(x):  # (T, heads, d)
        inv = 1.0 / 1e6 ** (np.arange(0, d, 2) / d)
        ang = np.arange(T)[:, None] * inv
        cos = np.concatenate([np.cos(ang)] * 2, -1)[:, None]
        sin = np.concatenate([np.sin(ang)] * 2, -1)[:, None]
        turned = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
        return x * cos + turned * sin

    q = rotate(norm(np.einsum("te,ehd->thd", h, f(p["wq"])), p["q_norm"]))
    k = rotate(norm(np.einsum("te,ehd->thd", h, f(p["wk"])), p["k_norm"]))
    v = np.einsum("te,ehd->thd", h, f(p["wv"]))
    out = np.zeros((T, H, d))
    for head in range(H):
        kv = head // (H // KV)
        s = q[:, head] @ k[:, kv].T / 8.0
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, head] = (w / w.sum(-1, keepdims=True)) @ v[:, kv]
    want = out.reshape(T, H * d) @ f(p["out"]["w"])

    hj = jnp.asarray(h, jnp.float32)[None]
    qj, kj, vj = lfm2.normed_rotated_qkv(hj, p, cfg, jnp.arange(T))
    np.testing.assert_allclose(qj[0], q, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(kj[0], k, atol=2e-5, rtol=2e-5)
    # the ring chunk and the step read the same flavour
    cache = decode.init_cache(cfg, 1)[1]
    ring = decode._Ring(jnp.int32(0), None, 64)
    got, cache = decode.KINDS["full"].chunk(hj, params["blocks"][1], cache,
                                            cfg, 0, ring, None)
    np.testing.assert_allclose(got[0], want, atol=5e-5, rtol=5e-5)
    # rotating first is another model
    other = reference._attention(hj[0], p, reference.sizes(TOY), None,
                                 "rope_before_norm")
    assert float(jnp.abs(other - want).max()) > 0.01


@pytest.mark.parametrize("M, pos, live", [
    (1024, [0, 63, 511, 512, 700, 1023], [1, 1, 1, 1, 1, 1]),
    (1024, [900, 5, 513, 30, 1023, 0], [0, 1, 1, 0, 1, 0]),
    (128, [0, 64, 127], [1, 0, 1]),
], ids=["two_blocks_all_live", "two_blocks_some_live", "one_block"])
def test_ring_read_takes_heads_of_64_with_the_ring_on_the_lanes(M, pos, live):
    """``ops/ring_attention.py`` at ``d = 64``: the chip lays such a pool
    out with the ring on the lanes (``position_on_lanes``), and the kernel
    reads the transposed view, a block ``(KV, d, block)``; against
    ``jamba.attend`` under the mask, 4 query heads on each of 2 K/V heads
    (interpret mode: the values; tests/test_tpu_compile.py: the layout)."""
    from differential_transformer_replication_tpu.models import jamba
    from differential_transformer_replication_tpu.ops import (
        kv_write,
        ring_attention,
    )

    assert kv_write.position_on_lanes(M, 64)
    rng = np.random.default_rng(M + len(pos))
    B, H, KV, d = len(pos), 8, 2, 64
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
               for shape in ((B, H, d), (B, KV, M, d), (B, KV, M, d)))
    pos, live = jnp.asarray(pos, jnp.int32), np.asarray(live, bool)
    visible = jnp.arange(M)[None, None, :] <= pos[:, None, None]
    want = jamba.attend(q[:, None], k, v, visible)[:, 0]
    got = jax.jit(lambda *a: ring_attention.ring_decode_attention(*a, M))(
        q, k, v, pos, jnp.asarray(live))
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(got)[~live] == 0)


# -- the expert layer ----------------------------------------------------------------


def _numpy_expert_layer(h, p, lo, hi, top, scaling, eps):
    """The published layer in NumPy, float64: the bias ranks and does not
    weigh, the sum takes the epsilon, no shared expert."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    h = f(h)
    s = 1 / (1 + np.exp(-(h @ f(p["router"]["w"]))))
    order = np.argsort(-(s + f(p["router"]["b"])), axis=-1,
                       kind="stable")[:, :top]
    y = np.zeros_like(h)
    Fm = p["experts"]["down"].shape[1]
    for t in range(h.shape[0]):
        picked = s[t, order[t]]
        for e, w in zip(order[t], picked / (picked.sum() + eps) * scaling):
            if lo <= e < hi:
                gu = h[t] @ f(p["experts"]["gate_up"][e - lo])
                a = gu[:Fm] / (1 + np.exp(-gu[:Fm])) * gu[Fm:]
                y[t] += w * (a @ f(p["experts"]["down"][e - lo]))
    return y, order


@pytest.mark.parametrize("model, rows", [
    (TOY, 37), (dict(TOY, num_experts=64, held_experts=[0, 64]), 64),
    (dict(TOY, held_experts=[4, 12], routed_scaling=2.5), 23)],
    ids=["4_of_16_all_held", "4_of_64_all_held", "a_share_of_8"])
def test_expert_layer_is_the_numpy_transcription(model, rows):
    cfg = ModelConfig(**model)
    p = reference.make_params(5, model)["blocks"][1]["moe"]
    assert "shared" not in p
    h = jax.random.normal(jax.random.PRNGKey(2), (rows, 64))
    y, load = jax.jit(lambda h, p: kimi_linear.moe_mlp(h, p, cfg))(h, p)
    lo, hi = cfg.held_expert_range
    want, order = _numpy_expert_layer(
        h, p, lo, hi, 4, cfg.routed_scaling, 1e-6)
    np.testing.assert_allclose(y, want, atol=2e-4, rtol=2e-4)
    assert np.array_equal(
        load, np.bincount(order.ravel(), minlength=hi)[lo:hi])
    if (lo, hi) == (0, cfg.num_experts):  # every assignment is held
        assert int(load.sum()) == rows * 4


def test_the_bias_changes_who_is_chosen_and_not_the_weights():
    """A bias that lifts expert 3 over all others puts it among every
    row's four; the weight it gets there is its own score over the chosen
    scores' sum (plus 1e-6), as without the bias."""
    p = reference.make_params(5, TOY)["blocks"][1]["moe"]["router"]
    h = jax.random.normal(jax.random.PRNGKey(6), (29, 64))
    lifted = p["b"].at[3].set(2.0)
    chosen, w = moe.route(h, p["w"], lifted, 4, 1.0, 1e-6)
    plain, _ = moe.route(h, p["w"], p["b"], 4, 1.0, 1e-6)
    assert (np.asarray(chosen) == 3).any(axis=1).all()
    assert not (np.asarray(plain) == 3).any(axis=1).all()
    scores = np.asarray(jax.nn.sigmoid(
        jnp.dot(h, p["w"], precision=jax.lax.Precision.HIGHEST)), np.float64)
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=1)
    np.testing.assert_allclose(
        w, picked / (picked.sum(axis=1, keepdims=True) + 1e-6), rtol=1e-6)
    # the epsilon is an argument: at 0 the weights sum to exactly 1
    _, w0 = moe.route(h, p["w"], lifted, 4, 1.0)
    assert float(jnp.abs(w0.sum(axis=1) - 1).max()) < 2e-7
    assert float((1 - w.sum(axis=1)).min()) > 1e-7


def test_the_drawn_bias_changes_the_top_four_of_about_half_the_rows():
    """``ROUTER_BIAS_STD`` at the published router's size (64 experts,
    hidden 2,048, normed rows): the share of rows whose chosen set differs
    from the unbiased ranking's, which ``param_spec`` states."""
    model = dict(PUBLISHED, vocab_size=8, n_layer=2,
                 layer_types=LAYERS[:2], param_dtype="float32")
    p = reference.make_params(3, model)["blocks"][1]["moe"]["router"]
    h = jax.random.normal(jax.random.PRNGKey(8), (4096, 2048))
    with_bias, _ = moe.route(h, p["w"], p["b"], 4, 1.0, 1e-6)
    without, _ = moe.route(h, p["w"], jnp.zeros_like(p["b"]), 4, 1.0, 1e-6)
    changed = np.mean(np.sort(with_bias, axis=1) != np.sort(without, axis=1),
                      axis=1) > 0
    assert 0.25 < changed.mean() < 0.75, changed.mean()


def test_two_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: ``held_experts`` [0, 8) and [8, 16),
    each adding its held experts' terms, sum to the uncut reference layer
    (no shared expert to count once), so the family can be a share later;
    and the reference at a share gives that share's part."""
    model = dict(TOY, held_experts=[0, 16])
    uncut = reference.make_params(5, model)["blocks"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (50, 64))
    want = reference._moe(h, uncut, reference.sizes(model), None, None)
    total, loads = jnp.zeros_like(h), []
    for lo in (0, 8):
        share_model = dict(TOY, held_experts=[lo, lo + 8])
        share = reference.make_params(5, share_model)["blocks"][1]["moe"]
        for leaf in ("gate_up", "down"):  # a slice of the one uncut model
            assert np.array_equal(share["experts"][leaf],
                                  uncut["experts"][leaf][lo:lo + 8])
        y, load = kimi_linear.moe_mlp(h, share, ModelConfig(**share_model))
        one = reference._moe(h, share, reference.sizes(share_model), None,
                             None)
        np.testing.assert_allclose(y, one, atol=2e-4, rtol=2e-4)
        total = total + y
        loads.append(load)
    np.testing.assert_allclose(total, want, atol=2e-4, rtol=2e-4)
    # every token's 4 experts fell on one share or the other, none dropped
    assert int(sum(l.sum() for l in loads)) == 50 * 4


# -- through the engine -----------------------------------------------------------------


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
    slot another left, whose windows are zeroed first) and on eight: the
    same ``submit``, scheduler, slot pool and sampler as every family."""
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
    steps = [a for n, a in spans.spans if n == "decode"]
    window = 4 * 2 * 64 * 4  # four conv layers' two gated inputs a slot
    assert steps and all(
        a["live_state_bytes"] == a["active"] * window for a in steps)
    # every assignment is held: rows x 4 experts x 4 expert layers
    assert all(a["moe"]["held"] == a["active"] * 16 for a in steps)
    assert all(a["moe"]["experts_hit"] <= min(4 * 16, a["moe"]["held"])
               for a in steps)
    assert eng.stats["moe_experts_hit"] == sum(
        a["moe"]["experts_hit"] for a in steps)
    text = eng.registry.render()
    for name in ("serving_decode_live_state_bytes_total",
                 "serving_moe_experts_hit_total",
                 "serving_state_resets_total 6"):
        assert name in text
    pool = sum(leaf.nbytes for layer in eng.cache for leaf in layer.values())
    assert pool == num_slots * (4 * 2 * 64 + 2 * 2 * 64 * 64) * 4
    got = re.search(r"^serving_state_pool_bytes (\S+)$", text, re.M).group(1)
    assert float(got) == num_slots * 4 * 2 * 64 * 4


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


def test_an_engine_that_never_resets_a_window_serves_other_tokens(params):
    """What ``selftest_lfm2.py --broken`` leans on: with the admission's
    reset taken out, a reused slot's first tokens read the last sequence's
    window and the served tokens change."""
    cfg = toy()
    long_one = _prompts(1, seed=11, lo=50, hi=51)[0]
    short = _prompts(1, seed=12, lo=7, hi=8)[0]
    fresh = _engine(params, cfg, num_slots=1).generate(
        [short], max_new_tokens=20, temperature=0.0)[0]
    used = _engine(params, cfg, num_slots=1)
    used._reset_slot_state = lambda slot, iteration: None
    used.generate([long_one], max_new_tokens=12, temperature=0.0)
    again = used.generate([short], max_new_tokens=20, temperature=0.0)[0]
    assert list(again.tokens) != list(fresh.tokens)


def test_a_request_is_bounded_by_the_ring(params):
    eng = _engine(params, toy())
    with pytest.raises(ValueError, match="see every earlier position"):
        eng.submit(list(range(50)), max_new_tokens=15)
    out = eng.generate([list(range(50))], max_new_tokens=14,
                       temperature=0.0)[0]
    assert len(out.tokens) == 14


@pytest.mark.parametrize("serving, named", [
    (dict(kv_page_size=16), "paging"),
    (dict(kv_page_size=16, prefix_cache=True), "prefix cache"),
    (dict(spec_mode="ngram"), "speculation"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
    (dict(kv_page_size=16, host_tier_bytes=1 << 20), "host tier"),
])
def test_the_engine_refuses_what_needs_a_snapshot_and_names_the_conv_layers(
        params, serving, named):
    """The reason is the recurrent state's, from the table's record, and
    the message names the layer this family has."""
    with pytest.raises(ValueError) as e:
        _engine(params, toy(), **serving)
    said = str(e.value)
    assert named in said and "lfm2" in said and "short-convolution" in said
    assert "Mamba" not in said and "KDA" not in said
    assert "multi-token" not in said and "float32" not in said


def test_migration_is_refused_for_the_window(params):
    eng = _engine(params, toy())
    rid = eng.submit(_prompts(1)[0], max_new_tokens=4, temperature=0.0)
    with pytest.raises(MigrateExportError, match="recurrent"):
        eng.export_slot_state(rid)


def test_the_programs_carry_the_new_scopes(params):
    cfg = toy()
    chunk, _, step = _programs(cfg)
    cache = decode.init_cache(cfg, 2)
    toks = jnp.zeros((2, 8), jnp.int32)
    shared = {"conv", "conv_taps", "attn", "attn_full", "kv_write", "moe",
              "moe_router", "moe_experts", "ffn", "lm_head"}
    for text in (
            chunk.lower(params, toks, jnp.int32(0), cache).as_text(
                debug_info=True),
            step.lower(params, toks[:, 0], jnp.zeros((2,), jnp.int32), cache,
                       jnp.ones((2,), bool)).as_text(debug_info=True)):
        found = set(re.findall(r"[/\"]([a-z_0-9]+)(?=/)", text))
        assert shared <= found, shared - found
        assert "moe_shared" not in found and "moe_latent" not in found


# -- the configuration ----------------------------------------------------------------

_OWN = {"conv_taps": 4, "router_eps": 1e-6}


def test_every_new_field_has_a_refusal_case():
    shared = {"ffn_hidden", "kv_heads", "norm_eps", "tie_embeddings",
              "head_dim", "layer_types", "rope_theta", "num_experts",
              "experts_per_token", "moe_hidden", "first_dense_layers",
              "routed_scaling", "held_experts"}
    assert set(_OWN) == set(LFM2_FIELDS) - shared


@pytest.mark.parametrize("family", ["control", "diff", "ndiff", "jamba",
                                    "kimi_linear", "afmoe", "deepseek_v2",
                                    "nemotron_h"])
@pytest.mark.parametrize("field", sorted(_OWN))
def test_another_family_refuses_an_lfm2_field_by_name(family, field):
    with pytest.raises(ValueError, match=field):
        ModelConfig(model=family, **{field: _OWN[field]})


@pytest.mark.parametrize("field, value", [
    ("ssm_impl", "pallas"), ("mamba_d_state", 8), ("mamba_d_conv", 3),
    ("kda_layers", [1]), ("kv_lora_rank", 64), ("sliding_window", 16),
    ("q_lora_rank", 24), ("n_group", 2), ("hybrid_override_pattern", "ME"),
    ("moe_shared_hidden", 48), ("mlp_act", "relu2"), ("moe_latent_size", 16),
])
def test_lfm2_refuses_another_family_s_field_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        toy(**{field: value})


@pytest.mark.parametrize("field, value, said", [
    ("attention_impl", "pallas", "attention_impl"),
    ("ffn_impl", "pallas", "ffn_impl"),
    ("decode_attention_impl", "pallas", "ring_gqa_decode_fwd"),
    ("dropout", 0.1, "dropout"),
    ("layer_types", LAYERS[:4], "layer_types"),
    ("layer_types", ["conv", "sliding_attention", "conv", "conv", "conv"],
     "layer_types"),
    ("kv_heads", 3, "kv_heads"), ("head_dim", 63, "head_dim"),
    ("conv_taps", 1, "conv_taps"), ("router_eps", -1.0, "router_eps"),
    ("experts_per_token", 17, "num_experts"),
    ("held_experts", [4, 20], "held_experts"), ("moe_hidden", 0, "moe_hidden"),
    ("first_dense_layers", 6, "first_dense_layers"),
])
def test_lfm2_refuses_what_it_does_not_run_under_its_reason(field, value,
                                                            said):
    with pytest.raises(ValueError, match=said):
        toy(**{field: value})


def test_the_cut_is_2_7006_billion_parameters():
    """ISSUE 47's table, to the fourth digit, from ``init``'s own tree."""
    cfg = ModelConfig(**PUBLISHED)
    tree = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    size = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                         for a in jax.tree_util.tree_leaves(t))
    blocks = tree["blocks"]
    assert round(size(tree["tok_emb"]) / 1e6, 2) == 134.22
    assert round(size(blocks[0]["conv"]) / 1e6, 2) == 16.78
    assert round(size(blocks[1]["attn"]) / 1e6, 2) == 10.49
    assert round(size(blocks[0]["ffn"]) / 1e6, 2) == 72.35
    assert round(size(blocks[1]["moe"]["router"]) / 1e6, 2) == 0.13
    routed = size(blocks[1]["moe"]["experts"])
    assert round(routed / 64 / 1e6, 3) == 9.437
    assert round(routed / 1e6, 2) == 603.98
    assert round(size(blocks[0]) / 1e6, 1) == 89.1
    assert round(size(blocks[1]) / 1e6, 1) == 614.6
    assert round(size(blocks[2]) / 1e6, 1) == 620.9
    assert "lm_head" not in tree  # the head is the token table's transpose
    # 134.2 + 89.1 + 614.6 + 3 x 620.9 = 2,700.6 of rounded parts; exactly
    # 2,700,654,976
    assert size(tree) == 2_700_654_976 and size(tree) // 10**5 == 27006
    assert round(2 * size(tree) / 1e9, 2) == 5.40

"""The ``deepseek_v2`` family (models/deepseek_v2.py; the MLA mixer and the
experts of models/kimi_linear.py, the hybrid loops of models/decode.py,
ops/mla.py's two blocked reads, ops/moe.py's router limited to groups,
ops/rope.py's YaRN frequencies) against the plain reference
``benchmark/reference_deepseek_v2.py``, at toy widths on the CPU with
seeded random weights: hidden 64, 4 heads, query rank 24, latent 16, nope /
rope / value 8 / 8 / 8, 16 experts in 4 groups of which a token keeps 2
groups and 3 experts, 2 shared experts, a ring of 64 and a YaRN block that
scales from 16 positions, so that a few dozen tokens stand on both sides of
it. The full forward; prefill in chunks with a padded tail then decoding
through the pool; two slots at different positions in one step; the
absorbed reads against the widened one; the router against a NumPy
transcription; the YaRN table against its closed forms; the eight shares
of an expert layer against the uncut layer; what the engine admits, counts
and refuses.
"""

import functools
import math
import re
import sys
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmark"))

import reference_deepseek_v2 as reference  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    DEEPSEEK_V2_FIELDS,
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import (  # noqa: E402
    decode,
    init_model,
    kimi_linear,
    model_forward,
)
from differential_transformer_replication_tpu.models.jamba import (  # noqa: E402
    gated_mlp,
)
from differential_transformer_replication_tpu.ops import mla, moe  # noqa: E402
from differential_transformer_replication_tpu.ops import rope  # noqa: E402
from differential_transformer_replication_tpu.serving.engine import (  # noqa: E402
    ServingEngine,
)
from differential_transformer_replication_tpu.serving.migrate import (  # noqa: E402
    MigrateExportError,
)

YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096}
TOY_YARN = dict(YARN, factor=4, original_max_position_embeddings=16)
TOY = dict(model="deepseek_v2", vocab_size=211, n_embd=64, n_head=4,
           n_layer=3, block_size=64, ffn_hidden=96, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
           v_head_dim=8, rope_theta=100.0, rope_scaling=TOY_YARN,
           num_experts=16, experts_per_token=3, moe_hidden=32, n_group=4,
           topk_group=2, n_shared_experts=2, first_dense_layers=1,
           routed_scaling=4.0, held_experts=[0, 4],
           compute_dtype="float32", param_dtype="float32")
PUBLISHED = dict(model="deepseek_v2", vocab_size=12800, n_embd=5120,
                 n_head=128, n_layer=5, block_size=8192, ffn_hidden=12288,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0,
                 rope_scaling=YARN, num_experts=160, experts_per_token=6,
                 moe_hidden=1536, n_group=8, topk_group=3,
                 n_shared_experts=2, first_dense_layers=1,
                 routed_scaling=16.0, held_experts=[0, 20],
                 param_dtype="bfloat16")
# float32 on both sides; what differs is the order of the sums (absorbed
# products, blocked softmax, grouped experts against full maps and a loop)
TOL = dict(atol=5e-4, rtol=5e-4)


def toy(**kw) -> ModelConfig:
    return ModelConfig(**dict(TOY, **kw))


@pytest.fixture(scope="module")
def params():
    return reference.make_params(7, TOY)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(3).integers(0, 211, (2, 60)))


@pytest.fixture(scope="module")
def full_logits(params, tokens):
    return reference.forward(params, tokens, TOY)


# -- the model against the reference ------------------------------------------


def test_forward_matches_the_reference(params, tokens, full_logits):
    got, loss = model_forward(params, tokens, toy())
    assert loss is None and got.dtype == jnp.float32
    np.testing.assert_allclose(got, full_logits, **TOL)


@pytest.mark.parametrize("fault", reference.FAULTS[1:])
def test_each_planted_fault_is_another_model(params, tokens, full_logits,
                                             fault):
    """The rotation, the scale, the unnormalised weights and every held
    expert are really there: the reference without one is another model."""
    other = reference.forward(params, tokens, TOY, fault=fault)
    assert float(jnp.abs(other - full_logits).max()) > 0.05, fault


def test_layout_matches_the_reference():
    for model in (TOY, dict(TOY, held_experts=[4, 8])):
        got = jax.tree_util.tree_map(
            lambda a: (a.shape, a.dtype), reference.make_params(1, model))
        want = jax.tree_util.tree_map(
            lambda a: (a.shape, a.dtype),
            jax.eval_shape(lambda k: init_model(k, ModelConfig(**model)),
                           jax.random.PRNGKey(0)))
        assert got == want


def test_the_family_is_served_not_trained(params, tokens):
    with pytest.raises(ValueError, match="served, not trained"):
        model_forward(params, tokens, toy(), targets=tokens)


# -- YaRN ----------------------------------------------------------------------


def test_yarn_table_is_the_closed_form_at_the_published_sizes():
    """d(32) = 10.47 and d(1) = 22.51: the pairs below 10 keep their
    frequency, those above 23 are slowed 40 times, between them a linear
    blend; m = 1.2608, the scale times m^2 = 1.5896; cos and sin times
    1."""
    block = {k: v for k, v in YARN.items() if k != "type"}
    d = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(10000))
    assert abs(d(32) - 10.47) < 0.01 and abs(d(1) - 22.51) < 0.01
    assert rope.yarn_correction_range(64, 10000.0, 32, 1, 4096) == (10, 23)
    f, mult = rope.yarn_frequencies(64, 10000.0, block)
    plain = 10000.0 ** (-np.arange(32) / 32)
    g = 1 - np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(f, (1 - g) * plain / 40 + g * plain, rtol=1e-6)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    assert mult == 1.0
    m = rope.yarn_mscale(40, 0.707)
    assert abs(m - 1.2608) < 1e-4 and abs(m * m - 1.5896) < 1e-4
    cfg = ModelConfig(**PUBLISHED)
    assert abs(kimi_linear.mla_scale(cfg) - 192 ** -0.5 * 1.5896) < 1e-5
    # the reference's own table is the same one
    rf, rmult = reference.yarn_frequencies(64, 10000.0, block)
    np.testing.assert_allclose(f, rf, rtol=1e-6)
    assert rmult == 1.0
    # without a block: the plain frequencies, and the reads divide
    f, mult = rope.yarn_frequencies(64, 10000.0, None)
    np.testing.assert_allclose(f, plain, rtol=1e-6)
    assert mult == 1.0 and kimi_linear.mla_scale(
        ModelConfig(**dict(PUBLISHED, rope_scaling={}))) is None


def test_rotation_is_the_reference_s_on_the_published_pairs():
    """``apply_rope_pairs_at`` turns the pair (2i, 2i + 1) and hands the
    result back with the pair at (i, i + d/2): the reference's rotation
    under ``half_split``, a row at its own position as well as a chunk."""
    s = reference.sizes(TOY)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, 40, 8)),
                    jnp.float32)
    want = rope.half_split(reference._rotate(x, s))
    block = {k: v for k, v in TOY_YARN.items() if k != "type"}
    f, mult = rope.yarn_frequencies(8, 100.0, block)
    got = rope.apply_rope_pairs_at(x, jnp.arange(40), f, mult)
    np.testing.assert_allclose(got, want, atol=1e-5)
    one = rope.apply_rope_pairs_at(x[:, 33], jnp.full((3,), 33), f, mult)
    np.testing.assert_allclose(one, want[:, 33], atol=1e-5)
    # q . k is the published sum whatever the order of the pairs
    y = jnp.asarray(np.random.default_rng(2).normal(size=(3, 40, 8)),
                    jnp.float32)
    np.testing.assert_allclose(
        jnp.sum(got * rope.apply_rope_pairs_at(y, jnp.arange(40), f, mult), -1),
        jnp.sum(reference._rotate(x, s) * reference._rotate(y, s), -1),
        atol=1e-5)


# -- prefill in chunks, then the pool -------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    """``(chunk, padded chunk, step)`` jitted once a configuration, the
    position a runtime value as the engine passes it."""
    return (
        jax.jit(lambda p, t, at, c: decode.forward_chunk(p, t, at, c, cfg)),
        jax.jit(lambda p, t, at, c, n: decode.forward_chunk(
            p, t, at, c, cfg, valid=n)),
        jax.jit(lambda p, t, at, c, live: decode.forward_decode_pool(
            p, t, at, c, cfg, active=live)),
    )


def _prefill(params, cfg, cache, idx, P, chunk=8):
    """``idx[:, :P]`` in whole chunks and one padded tail; returns the
    logits of the whole chunks' positions, the last real token's, and the
    cache."""
    whole, tail, _ = _programs(cfg)
    heads, pos = [], 0
    while P - pos >= chunk:
        lg, cache = whole(params, idx[:, pos:pos + chunk], jnp.int32(pos),
                          cache)
        heads.append(lg)
        pos += chunk
    last = heads[-1][:, -1:] if heads else None
    if pos < P:
        shape = 1 << (P - pos - 1).bit_length()
        padded = jnp.zeros((idx.shape[0], shape), idx.dtype).at[
            :, :P - pos].set(idx[:, pos:P])
        last, cache = tail(params, padded, jnp.int32(pos), cache,
                           jnp.int32(P - pos))
    return (jnp.concatenate(heads, axis=1) if heads else None), last, cache


@pytest.mark.parametrize("P", [5, 8, 13, 16, 21, 40], ids=[
    "a_tail_alone", "one_whole_chunk", "a_chunk_and_a_tail",
    "at_the_trained_length", "past_it", "far_past_it"])
def test_chunked_prefill_then_pool_decode_matches_the_reference(
        params, tokens, full_logits, P):
    """A prompt of P tokens in chunks of 8 and a padded tail, then the rest
    of 60 through the pool, the rotation taken from the positions: before
    the YaRN block's trained length of 16, at it and past it."""
    cfg = toy()
    live = np.asarray([0, 2])
    idx = jnp.zeros((3, 60), tokens.dtype).at[live].set(tokens).at[1].set(
        tokens[0])  # slot 1 is never active; its row must move nothing
    heads, last, cache = _prefill(params, cfg, decode.init_cache(cfg, 3),
                                  idx, P)
    whole = P // 8 * 8
    if whole:
        np.testing.assert_allclose(heads[live], full_logits[:, :whole], **TOL)
    np.testing.assert_allclose(last[live, 0], full_logits[:, P - 1], **TOL)
    marked = [{k: v.at[1].set(0.25) for k, v in layer.items()}
              for layer in cache]
    active = jnp.asarray([True, False, True])
    step = _programs(cfg)[2]
    outs, cache = [], marked
    for t in range(P, 60):
        lg, cache, load = step(params, idx[:, t], jnp.full((3,), t), cache,
                               active)
        outs.append(lg[:, None])
        # 2 live rows x 3 experts x 2 expert layers; a row meets a held
        # expert only if it kept the held group
        held, top, hit, reached = (int(v) for v in load)
        assert 0 <= held <= 3 * reached and reached <= 2 * 2
        assert hit <= held and top <= held
    got = jnp.concatenate(outs, axis=1)[live]
    np.testing.assert_allclose(got, full_logits[:, P:], **TOL)
    for layer in cache:
        assert set(layer) == {"latent"}
        assert np.all(np.asarray(layer["latent"][1]) == 0.25)


def test_two_slots_at_different_positions_share_a_step(params, tokens,
                                                       full_logits):
    """Slot 0 stands past the trained length (position 40), slot 1 before
    it (position 9): one decode step turns each row at its own position."""
    cfg = toy()
    one = lambda row, P: _prefill(  # noqa: E731
        params, cfg, decode.init_cache(cfg, 1), tokens[row:row + 1], P)[2]
    a, b = one(0, 40), one(1, 9)
    cache = [{k: jnp.concatenate([x[k], y[k]]) for k in x}
             for x, y in zip(a, b)]
    pos = np.asarray([40, 9])
    for _ in range(12):
        tok = jnp.asarray([tokens[0, pos[0]], tokens[1, pos[1]]])
        lg, cache, _ = _programs(cfg)[2](params, tok, jnp.asarray(pos), cache,
                                         jnp.asarray([True, True]))
        np.testing.assert_allclose(lg[0], full_logits[0, pos[0]], **TOL)
        np.testing.assert_allclose(lg[1], full_logits[1, pos[1]], **TOL)
        pos = pos + 1


def test_a_slot_holds_one_ring_of_latents_a_layer():
    cfg = toy()
    cache = decode.init_cache(cfg, 3)
    assert [set(layer) for layer in cache] == [{"latent"}] * 3
    assert all(layer["latent"].shape == (3, 1, 64, 24) for layer in cache)
    assert cfg.layer_kinds() == ("latent",) * 3 and cfg.cannot_roll
    assert not decode.has_recurrent_state(cfg)


def test_a_chunk_past_the_ring_is_refused(params):
    cfg = toy()
    with pytest.raises(ValueError, match="see every earlier position"):
        decode.forward_chunk(params, jnp.zeros((1, 8), jnp.int32), 60,
                             decode.init_cache(cfg, 1), cfg)


def test_generate_cached_runs_the_family(params):
    cfg = toy()
    idx = jnp.asarray(np.random.default_rng(5).integers(0, 211, (2, 37)))
    out = decode.generate_cached(params, idx, cfg, 6, jax.random.PRNGKey(0),
                                 temperature=1.0, top_k=1)
    logits, _ = model_forward(params, out[:, :-1], cfg)
    assert np.array_equal(np.asarray(out[:, 37:]),
                          np.asarray(jnp.argmax(logits[:, 36:], -1)))
    with pytest.raises(ValueError, match="deepseek_v2 family's cache cannot"):
        decode.generate_cached(params, idx, cfg, 64, jax.random.PRNGKey(0))


# -- the three reads of the ring of latents -------------------------------------

class _Dims(NamedTuple):
    """A layer of kind ``"latent"``: its widths, its softmax scale, the
    gain its random widening is drawn at and what float32 in another
    order of products may differ by."""
    H: int
    rank: int
    rope: int
    nope: int
    vd: int
    scale: float
    gain: float
    tol: float


TOY_DIMS = _Dims(4, 16, 8, 8, 8, 0.3, 1.0, 2e-5)
#: kimi_linear's MLA layer as published (32 heads over a ring of 4,096
#: latents of 512 + 64 values, no rotation), which the two blocked reads
#: serve beside deepseek_v2's; the widening at unit gain, as a trained one
#: (scores of order 1, not 20); a score sums 576 products, a row up to
#: 4,096 weights
KIMI_DIMS = _Dims(32, 512, 64, 128, 128, 192 ** -0.5, 512 ** -0.5, 2e-4)


def _latent_case(M, dims, B, seed):
    """``(latents (B, M, rank + rope), W_kvb, queries (B, 1, H, .))``."""
    rng = np.random.default_rng(seed)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return (arr(B, M, dims.rank + dims.rope),
            arr(dims.rank, dims.H, dims.nope + dims.vd) * dims.gain,
            arr(B, 1, dims.H, dims.nope + dims.rope))


@pytest.mark.parametrize("M, pos, live, dims", [
    (64, [0, 7, 31, 63], [1, 1, 1, 1], TOY_DIMS),
    (64, [5, 40, 63, 12], [0, 1, 0, 1], TOY_DIMS),
    (64, [5, 30, 9, 50], [0, 0, 0, 0], TOY_DIMS),
    (128, [0, 127, 64, 3], [1, 1, 0, 1], TOY_DIMS),
    (2048, [0, 511, 512, 1500, 2047], [1, 1, 1, 1, 1], TOY_DIMS),
    (2048, [2000, 100, 1600, 900, 40], [0, 1, 1, 0, 1], TOY_DIMS),
    (4096, [0, 511, 512, 4095], [1, 1, 1, 1], KIMI_DIMS),
    (4096, [3000, 100, 1600, 40, 352], [0, 1, 1, 0, 1], KIMI_DIMS),
    (4096, [5, 3000, 9], [0, 0, 0], KIMI_DIMS),
], ids=["toy_all_live", "toy_some_live", "toy_none_live", "ring_on_lanes",
        "blocks_all_live", "blocks_some_live", "kimi_all_live",
        "kimi_some_live", "kimi_none_live"])
def test_latent_decode_kernel_reads_a_row_s_live_blocks_alone(M, pos, live,
                                                              dims):
    """``ops/mla.py:latent_decode_attention`` (interpret mode; a ring of
    128 is read as the chip holds it, ring on the lanes, and so is kimi's
    of 4,096 x 576) against the whole ring under a mask
    (``attend_latent``): absorbed on both sides. A row that is not live
    comes out as zeros whatever its ring holds, and the blocks a row is
    told to read are those that hold a visible latent."""
    H, rank, ropew, nope, vd, scale, _, tol = dims
    B = len(pos)
    latent, w, q = _latent_case(M, dims, B, M + len(pos))
    pos, live = np.asarray(pos, np.int32), np.asarray(live, bool)
    visible = jnp.arange(M)[None, None, :] <= jnp.asarray(pos)[:, None, None]
    want = mla.attend_latent(q, latent, w, visible, scale)[:, 0]
    qq = mla.absorb_queries(q[:, 0], w, ropew)
    mixed = jax.jit(lambda *a: mla.latent_decode_attention(
        *a, rank, scale))(qq, latent[:, None], jnp.asarray(pos),
                          jnp.asarray(live))
    got = jnp.einsum("bhr,rhv->bhv", mixed, w[..., nope:]).reshape(B, -1)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=tol, rtol=tol)
    assert np.all(np.asarray(mixed)[~live] == 0)
    KB = mla.key_block(M)
    count = mla.live_blocks(pos, live, M)
    assert np.array_equal(count, np.where(live, pos // KB + 1, 0))
    assert np.array_equal(
        mla.live_blocks(jnp.asarray(pos), jnp.asarray(live), M), count)


@pytest.mark.parametrize("M, L, pos, valid, dims", [
    (64, 8, 0, None, TOY_DIMS), (64, 8, 24, None, TOY_DIMS),
    (64, 16, 48, None, TOY_DIMS), (64, 3, 5, None, TOY_DIMS),
    (2048, 8, 0, None, TOY_DIMS), (2048, 16, 505, None, TOY_DIMS),
    (2048, 64, 1984, None, TOY_DIMS), (2048, 32, 500, 21, TOY_DIMS),
    (4096, 16, 0, None, KIMI_DIMS), (4096, 16, 505, None, KIMI_DIMS),
    (4096, 32, 1000, 21, KIMI_DIMS),
], ids=["ring_start", "mid_ring", "ring_end", "a_chunk_of_three",
        "first_block", "over_a_block_s_edge", "last_block", "a_padded_tail",
        "kimi_first_block", "kimi_over_a_block_s_edge",
        "kimi_a_padded_tail"])
def test_widened_chunk_read_is_the_absorbed_one(M, L, pos, valid, dims):
    """``chunk_attention`` (interpret mode: keys and values a head, the
    ring in blocks up to the chunk's end) against ``attend_latent``
    (absorbed, the ring whole): the same attention, another order of
    products. With ``valid`` the chunk's rows from there on are padding
    (a prompt's tail at a compiled shape, ``forward_chunk``'s ``valid``):
    the latents they wrote lie past the sequence's end, and whatever those
    are, the real rows come out the same, bit for bit."""
    H, rank, ropew, nope, vd, scale, _, tol = dims
    B = 2 if dims is TOY_DIMS else 1  # interpret mode: 256 grid steps a row
    latent, w, _ = _latent_case(M, dims, B, M + pos)
    q = jnp.asarray(np.random.default_rng(L).normal(
        size=(B, L, H, nope + ropew)), jnp.float32)
    visible = decode._ring_visible(pos, L, M, M)
    want = mla.attend_latent(q, latent, w, visible, scale)
    read = jax.jit(lambda ring, at: mla.chunk_attention(q, ring, w, at, scale))
    got = read(latent, jnp.int32(pos))
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    if valid is not None:
        other = latent.at[:, pos + valid:pos + L].set(7.0)
        assert np.array_equal(np.asarray(read(other, jnp.int32(pos)))[:, :valid],
                              np.asarray(got)[:, :valid])


# -- the router -------------------------------------------------------------------


def _np_route(probs, k, scaling, n_group, topk_group):
    """The published selection, transcribed: a group's score is its best
    expert's, the ``topk_group`` best groups (ties to the lower index),
    the ``k`` best experts inside them (likewise), weights ``scaling *
    p``."""
    T, N = probs.shape
    size = N // n_group
    chosen, weights, kept = [], [], []
    for p in probs:
        best = p.reshape(n_group, size).max(-1)
        groups = sorted(np.argsort(-best, kind="stable")[:topk_group])
        inside = [e for g in groups for e in range(g * size, (g + 1) * size)]
        order = sorted(inside, key=lambda e: (-p[e], e))[:k]
        chosen.append(order)
        weights.append([scaling * p[e] for e in order])
        kept.append([g in groups for g in range(n_group)])
    return np.asarray(chosen), np.asarray(weights), np.asarray(kept)


@pytest.mark.parametrize("ties", [False, True], ids=["plain", "with_ties"])
def test_grouped_router_is_the_numpy_transcription(ties):
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    w = rng.normal(size=(64, 16)).astype(np.float32) * 0.3
    if ties:  # experts with the same column score alike on every row
        w[:, 5] = w[:, 1]
        w[:, 9] = w[:, 1]
        w[:, 14] = w[:, 2]
    w = jnp.asarray(w)
    probs = np.asarray(jax.nn.softmax(jnp.dot(
        h, w, precision=jax.lax.Precision.HIGHEST), axis=-1))
    chosen, weights, kept = moe.route_grouped(h, w, 3, 4.0, 4, 2)
    want = _np_route(probs, 3, 4.0, 4, 2)
    assert np.array_equal(chosen, want[0])
    np.testing.assert_allclose(weights, want[1], rtol=1e-6)
    assert np.array_equal(kept, want[2])
    # nothing is renormalised: a row's weights sum to 4 x its 3 probabilities
    assert float(jnp.max(jnp.sum(weights, -1))) < 4.0
    # and the reference's dense form holds the same weights
    dense = reference.route(h, w, reference.sizes(TOY))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(dense), want[0], -1), want[1],
        rtol=1e-5)
    assert np.count_nonzero(np.asarray(dense)) == 40 * 3


def test_a_row_whose_groups_exclude_the_held_one_fetches_no_expert(params):
    """Device-limited routing: a row that did not keep group 0 adds
    nothing here, and a step of such rows reads no expert's weights."""
    cfg = toy()
    p = params["blocks"][1]["moe"]
    h = jnp.asarray(np.random.default_rng(4).normal(size=(64, 64)),
                    jnp.float32)
    chosen, weights, kept = moe.route_grouped(h, p["router"]["w"], 3, 4.0, 4,
                                              2)
    away = np.flatnonzero(~np.asarray(kept)[:, 0])
    assert 8 < len(away) < 56  # half the rows, under even routing
    y, load = moe.experts(h[away], chosen[away], weights[away], p["experts"],
                          0)
    assert int(load.sum()) == 0 and not np.any(np.asarray(y))
    # the layer's own count of the rows that kept the held group
    y, (load, reached) = kimi_linear.moe_mlp(h, p, cfg)
    assert int(reached) == 64 - len(away)
    assert int(load.sum()) == int(np.sum(np.asarray(chosen) < 4))
    live = jnp.arange(64) < 20
    _, (_, reached) = kimi_linear.moe_mlp(h, p, cfg, live)
    assert int(reached) == int(np.sum(np.asarray(kept)[:20, 0]))


def test_eight_shares_and_the_shared_experts_add_up_to_the_uncut_layer():
    """The routed parts of the eight shares, a routing group of four
    experts each, plus the shared experts ONCE, are the layer of the uncut
    model: what a share leaves out is what the other seven add."""
    wide = dict(TOY, num_experts=32, n_group=8, topk_group=3,
                experts_per_token=6)
    uncut = dict(wide, held_experts=[0, 32])
    full = reference.make_params(7, uncut)["blocks"][1]["moe"]
    h = jnp.asarray(np.random.default_rng(8).normal(size=(50, 64)), jnp.float32)
    want = reference._moe(h, full, reference.sizes(uncut), None, None)
    chosen, weights, kept = moe.route_grouped(h, full["router"]["w"], 6, 4.0,
                                              8, 3)
    total = gated_mlp(h, full["shared"])
    assert full["shared"]["gate"]["w"].shape == (64, 2 * 32)
    held = jax.jit(lambda share, lo: moe.experts(h, chosen, weights, share,
                                                 lo))
    loads = []
    for lo in range(0, 32, 4):
        model = dict(wide, held_experts=[lo, lo + 4])
        share = reference.make_params(7, model)["blocks"][1]["moe"]["experts"]
        # a share's experts are the uncut model's, by number
        assert np.array_equal(share["down"], full["experts"]["down"][lo:lo + 4])
        y, load = held(share, jnp.int32(lo))
        if lo < 8:  # and the reference at a share gives that share's part
            one = reference._moe(h, dict(full, experts=share),
                                 reference.sizes(model), None, None)
            np.testing.assert_allclose(
                y + gated_mlp(h, full["shared"]), one, atol=2e-5, rtol=2e-5)
        # a share gets rows only from the tokens that kept its group
        assert int(load.sum()) <= 6 * int(np.sum(np.asarray(kept)[:, lo // 4]))
        total = total + y
        loads.append(load)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # every token's 6 experts fell on one of its 3 groups, none dropped
    assert int(sum(l.sum() for l in loads)) == 50 * 6
    assert np.array_equal(np.concatenate(loads),
                          np.bincount(np.asarray(chosen).ravel(), minlength=32))
    assert np.all(np.asarray(kept).sum(-1) == 3)


# -- the engine -----------------------------------------------------------------------


def _engine(params, cfg, tracer=None, **kw):
    return ServingEngine(params, cfg, ServingConfig(
        **dict(dict(num_slots=2, prefill_chunk=8, prefill_budget=16), **kw)),
        tracer=tracer)


def _prompts(n, seed=0, lo=5, hi=52):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 211, size=int(k)).tolist()
            for k in rng.integers(lo, hi, size=n)]


class _Spans:
    """The tracer's interface, keeping what the engine hands it."""
    path, annotate = None, False

    def __init__(self):
        self.spans = []

    def span(self, name, **args):
        self.spans.append((name, args))
        import contextlib
        return contextlib.nullcontext()

    def instant(self, *a, **k): pass
    def counter(self, *a, **k): pass
    def complete(self, *a, **k): pass
    def flush(self): pass
    def close(self): pass


@pytest.mark.parametrize("num_slots", [2, 8], ids=["queued", "at_once"])
def test_engine_serves_the_reference_s_greedy_tokens(params, num_slots):
    """Six requests of 5-51 tokens on two slots (four wait, and enter a
    slot another left) and on eight: the same ``submit``, scheduler, slot
    pool and sampler as every family."""
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
    assert "state_reset" not in stats and eng.stats["state_resets"] == 0
    assert stats["prefill"] - built["prefill"] <= 4
    steps = [a for n, a in spans.spans if n == "decode"]
    assert steps and all(
        a["active"] <= a["latent_live"] <= 64 * a["active"]
        and 0 <= a["moe"]["rows_in_held_group"] <= 2 * a["active"]
        and a["moe"]["held"] <= 3 * a["moe"]["rows_in_held_group"]
        for a in steps)
    assert eng.stats["decode_live_latent"] == 3 * sum(
        a["latent_live"] for a in steps)
    assert eng.stats["moe_rows_in_held_group"] == sum(
        a["moe"]["rows_in_held_group"] for a in steps)
    rows = sum(a["active"] for a in steps) * 2  # (row, expert layer) pairs
    # 2 of 4 groups kept: half the rows reach the held group, which gives
    # them 3 x (1/2) x ... about 0.75 of their 3 experts here
    assert 0.25 < eng.stats["moe_rows_in_held_group"] / rows < 0.75
    assert 0.3 < eng.stats["moe_held"] / rows < 1.3
    text = eng.registry.render()
    assert "serving_decode_live_latent_positions_total" in text
    assert "serving_moe_rows_in_held_group_total" in text
    pool = sum(leaf.nbytes for layer in eng.cache for leaf in layer.values())
    assert pool == num_slots * 3 * 64 * 24 * 4
    got = re.search(r"^serving_state_pool_bytes (\S+)$", text, re.M).group(1)
    assert float(got) == pool


def test_a_slot_reused_by_a_shorter_sequence_serves_what_a_fresh_one_serves(
        params):
    """A sequence of 50 + 12 tokens leaves the ring full of its latents;
    the 7-token prompt that follows in the same slot sees none of them."""
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
    with pytest.raises(ValueError, match="see every earlier position"):
        eng.submit(list(range(50)), max_new_tokens=15)
    out = eng.generate([list(range(50))], max_new_tokens=14,
                       temperature=0.0)[0]
    assert len(out.tokens) == 14


@pytest.mark.parametrize("serving, named, reason", [
    (dict(kv_page_size=16), "paging", "no `latent` leaf"),
    (dict(kv_page_size=16, prefix_cache=True), "prefix cache",
     "no `latent` leaf"),
    (dict(spec_mode="ngram"), "speculation", "one row a slot"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'",
     "int8 latents do not exist"),
    (dict(kv_page_size=16, host_tier_bytes=1 << 20), "host tier",
     "no paging over latents"),
    (dict(decode_attention_impl="pallas"), "decode_attention_impl",
     "mla_latent_decode_fwd"),
])
def test_the_engine_refuses_each_missing_feature_under_its_true_reason(
        params, serving, named, reason):
    """No recurrent state and one ring length: neither of the other
    hybrid families' reasons holds, and none is given."""
    with pytest.raises(ValueError) as e:
        _engine(params, toy(), **serving)
    said = str(e.value)
    assert named in said and reason in said and "deepseek_v2" in said
    assert "recurrent state" not in said and "two lengths" not in said


def test_migration_needs_the_paged_layout(params):
    eng = _engine(params, toy())
    rid = eng.submit(_prompts(1)[0], max_new_tokens=4, temperature=0.0)
    with pytest.raises(MigrateExportError, match="paged KV layout"):
        eng.export_slot_state(rid)


def test_the_programs_carry_the_new_scopes(params):
    cfg = toy()
    chunk, _, step = _programs(cfg)
    cache = decode.init_cache(cfg, 2)
    toks = jnp.zeros((2, 8), jnp.int32)
    want = {"mla", "mla_q", "mla_latent_write", "mla_attend", "mla_out",
            "moe", "moe_router", "moe_experts", "moe_shared"}
    for text in (
            chunk.lower(params, toks, jnp.int32(0), cache).as_text(
                debug_info=True),
            step.lower(params, toks[:, 0], jnp.zeros((2,), jnp.int32), cache,
                       jnp.ones((2,), bool)).as_text(debug_info=True)):
        found = set(re.findall(r"[/\"]([a-z_]+)(?=/)", text))
        assert want <= found, want - found


# -- the configuration ----------------------------------------------------------------

_OWN = {"q_lora_rank": 24, "rope_scaling": TOY_YARN, "n_group": 4,
        "topk_group": 2, "n_shared_experts": 2}


def test_every_new_field_has_a_refusal_case():
    shared = {"ffn_hidden", "norm_eps", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "rope_theta", "num_experts",
              "experts_per_token", "moe_hidden", "first_dense_layers",
              "routed_scaling", "held_experts"}
    assert set(_OWN) == set(DEEPSEEK_V2_FIELDS) - shared


@pytest.mark.parametrize("family", ["control", "diff", "ndiff", "jamba",
                                    "kimi_linear", "afmoe"])
@pytest.mark.parametrize("field", sorted(_OWN))
def test_another_family_refuses_a_deepseek_v2_field_by_name(family, field):
    with pytest.raises(ValueError, match=field):
        ModelConfig(model=family, **{field: _OWN[field]})


@pytest.mark.parametrize("field, value", [
    ("tie_embeddings", True), ("ssm_impl", "pallas"), ("mamba_d_state", 8),
    ("kda_layers", [1]), ("kv_heads", 2), ("layer_types", ["full_attention"]),
    ("sliding_window", 16), ("head_dim", 32),
])
def test_deepseek_v2_refuses_another_family_s_field_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        toy(**{field: value})


@pytest.mark.parametrize("field, value", [
    ("attention_impl", "pallas"), ("ffn_impl", "pallas"),
    ("decode_attention_impl", "pallas"), ("dropout", 0.1),
    ("q_lora_rank", 0), ("qk_rope_head_dim", 7),
    ("rope_scaling", {"type": "linear", "factor": 2}),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("n_group", 3), ("topk_group", 5), ("topk_group", 0),
    ("experts_per_token", 9), ("held_experts", [2, 6]),
    ("held_experts", [0, 6]), ("first_dense_layers", 9), ("moe_hidden", 0),
    ("n_shared_experts", 0),
])
def test_deepseek_v2_refuses_what_it_does_not_run_by_name(field, value):
    names = {"topk_group": "topk_group", "n_group": "n_group",
             "experts_per_token": "experts_per_token"}
    with pytest.raises(ValueError, match=names.get(field,
                                                   field.split("_")[0])):
        toy(**{field: value})


def test_the_yarn_block_is_a_jit_key_whatever_it_came_as():
    a, b = toy(), toy(rope_scaling=tuple(sorted(TOY_YARN.items())))
    assert a == b and hash(a) == hash(b)
    assert a.yarn["factor"] == 4 and a.replace(block_size=32).yarn == a.yarn
    assert toy(rope_scaling={}).yarn is None


def test_published_share_is_group_0_of_8_behind_one_dense_layer():
    cfg = ModelConfig(**PUBLISHED)
    assert cfg.layer_kinds() == ("latent",) * 5
    assert cfg.mlp_kinds() == ("dense", "moe", "moe", "moe", "moe")
    assert cfg.held_expert_range == (0, 20) and cfg.expert_group_size == 20
    assert cfg.mla_rotary and not ModelConfig(model="kimi_linear", n_layer=1,
                                              kda_layers=[1]).mla_rotary


def test_the_cut_is_3_145_billion_parameters_and_5_760_bytes_a_position():
    cfg = ModelConfig(**PUBLISHED)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(n - 3.145e9) / 3.145e9 < 0.001, n
    assert {a.dtype for a in leaves} == {jnp.dtype("bfloat16")}
    part = lambda l, k: sum(  # noqa: E731
        int(np.prod(a.shape))
        for a in jax.tree_util.tree_leaves(shapes["blocks"][l][k]))
    assert abs(part(0, "mla") - 149.23e6) < 0.01e6
    assert abs(part(0, "ffn") - 188.74e6) < 0.01e6
    assert abs(part(1, "moe") - (20 * 23.59e6 + 47.19e6 + 0.82e6)) < 0.1e6
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 1))
    size = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for layer in cache for a in layer.values())
    assert size == 8192 * 5760

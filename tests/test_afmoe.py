"""The ``afmoe`` family (models/afmoe.py; the hybrid loops of
models/decode.py with rings of two lengths a slot) against the plain
reference ``benchmark/reference_afmoe.py``, at toy widths on the CPU with
seeded random weights: window 16, full ring 64, sliding ring 24 (a slack of
8, the prefill chunk), so that a few dozen tokens reach every case: the
full forward; prefill in chunks with a padded tail then decoding through
the pool, at positions before the window, at its edge, past it and past
window + chunk, with multi-token chunks written at rolled positions and
over the ring's end; two slots at different positions in one step; a slot
reused by a shorter sequence; the request bound at the full ring; the
sixteen shares of an expert layer against the uncut layer; what the engine
and the configuration refuse by name.
"""

import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmark"))

import reference_afmoe as reference  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    AFMOE_FIELDS,
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import (  # noqa: E402
    afmoe,
    decode,
    init_model,
    model_forward,
)
from differential_transformer_replication_tpu.ops import (  # noqa: E402
    moe,
    ring_attention,
)
from differential_transformer_replication_tpu.ops.rope import (  # noqa: E402
    apply_rope_half,
)
from differential_transformer_replication_tpu.serving.engine import (  # noqa: E402
    ServingEngine,
)
from differential_transformer_replication_tpu.serving.migrate import (  # noqa: E402
    MigrateExportError,
)

SLIDING, FULL = "sliding_attention", "full_attention"
TOY = dict(model="afmoe", vocab_size=211, n_embd=64, n_head=4, kv_heads=2,
           head_dim=32, n_layer=5, block_size=64, ffn_hidden=96,
           norm_eps=1e-5, layer_types=[SLIDING, SLIDING, FULL, SLIDING,
                                       SLIDING],
           sliding_window=16, sliding_ring=24, rope_theta=10000.0,
           num_experts=16, experts_per_token=4, moe_hidden=32,
           first_dense_layers=1, routed_scaling=2.448, held_experts=[0, 4],
           compute_dtype="float32", param_dtype="float32")
PUBLISHED = dict(model="afmoe", vocab_size=25024, n_embd=3072, n_head=48,
                 kv_heads=8, head_dim=128, n_layer=5, block_size=8192,
                 ffn_hidden=12288, norm_eps=1e-5,
                 layer_types=[SLIDING, SLIDING, FULL, SLIDING, SLIDING],
                 sliding_window=4096, sliding_ring=5120, num_experts=256,
                 experts_per_token=4, moe_hidden=3072, first_dense_layers=1,
                 routed_scaling=2.448, held_experts=[0, 16],
                 param_dtype="bfloat16")
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
    # the band and the rotation are really there: the reference without
    # either is another model
    for fault in reference.FAULTS[1:]:
        other = reference.forward(params, tokens, TOY, fault=fault)
        assert float(jnp.abs(other - full_logits).max()) > 0.1, fault


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


def test_rotate_half_is_the_reference_s_rotation():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, 40, 32)),
                    jnp.float32)
    want = reference._rotate(x, 10000.0)
    got = apply_rope_half(x, jnp.arange(40), 10000.0)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # a row at its own position, as the decode step rotates it
    one = apply_rope_half(x[:, 17], jnp.full((3,), 17), 10000.0)
    np.testing.assert_allclose(one, want[:, 17], atol=1e-5)


def test_sixteen_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The routed parts of the sixteen shares of two experts each, plus
    the shared expert ONCE, are the layer of the uncut model: what a share
    leaves out is what the other fifteen add."""
    wide = dict(TOY, num_experts=32)
    uncut = dict(wide, held_experts=[0, 32])
    full = reference.make_params(7, uncut)["blocks"][1]["moe"]
    h = jnp.asarray(np.random.default_rng(8).normal(size=(50, 64)), jnp.float32)
    want = reference._moe(h, full, reference.sizes(uncut), None, None)
    chosen, weights = moe.route(h, full["router"]["w"], full["router"]["b"],
                                4, 2.448)
    total = afmoe.gated_mlp(h, full["shared"])
    held = jax.jit(lambda share, lo: moe.experts(h, chosen, weights, share,
                                                 lo))
    loads = []
    for lo in range(0, 32, 2):
        model = dict(wide, held_experts=[lo, lo + 2])
        share = reference.make_params(7, model)["blocks"][1]["moe"]["experts"]
        # a share's experts are the uncut model's, by number
        assert np.array_equal(share["down"], full["experts"]["down"][lo:lo + 2])
        y, load = held(share, jnp.int32(lo))
        if lo < 4:  # and the reference at a share gives that share's part
            one = reference._moe(h, dict(full, experts=share),
                                 reference.sizes(model), None, None)
            np.testing.assert_allclose(
                y + afmoe.gated_mlp(h, full["shared"]), one, atol=2e-5,
                rtol=2e-5)
        total = total + y
        loads.append(load)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # every token's 4 experts fell on one share or another, none dropped
    assert int(sum(l.sum() for l in loads)) == 50 * 4
    assert np.array_equal(np.concatenate(loads),
                          np.bincount(np.asarray(chosen).ravel(), minlength=32))


# -- prefill in chunks, then the pool ---------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    """``(chunk, padded chunk, step)`` jitted once a configuration, the
    position a runtime value as the engine passes it (the concrete-position
    refusals have a test of their own); a shape compiles once for every
    test of this file."""
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


@pytest.mark.parametrize("P", [10, 16, 21, 29, 45], ids=[
    "before_the_window", "at_its_edge", "past_it", "past_window_and_chunk",
    "twice_round_the_ring"])
def test_chunked_prefill_then_pool_decode_matches_the_reference(
        params, tokens, full_logits, P):
    """A prompt of P tokens in chunks of 8 (the sliding ring's slack) and a
    padded tail, then the rest of 60 through the pool. From P = 29 on a
    whole chunk is written at a rolled position of the sliding ring of 24
    (positions 24-31 over slots 0-7), at 45 also the padded tail."""
    cfg = toy()
    live = np.asarray([0, 2])
    idx = jnp.zeros((3, 60), tokens.dtype).at[live].set(tokens).at[1].set(
        tokens[0])  # slot 1 is never active; its row must move nothing
    heads, last, cache = _prefill(params, cfg, decode.init_cache(cfg, 3),
                                  idx, P)
    whole = P // 8 * 8
    np.testing.assert_allclose(heads[live], full_logits[:, :whole], **TOL)
    np.testing.assert_allclose(last[live, 0], full_logits[:, P - 1], **TOL)
    marked = [{k: v.at[(slice(None),) * decode.KV_CACHE_BATCH_AXIS[k] + (1,)
                       ].set(0.25) for k, v in layer.items()}
              for layer in cache]
    active = jnp.asarray([True, False, True])
    step = _programs(cfg)[2]
    outs, cache = [], marked
    for t in range(P, 60):
        lg, cache, load = step(params, idx[:, t], jnp.full((3,), t), cache,
                               active)
        outs.append(lg[:, None])
        # 2 live rows x 4 experts x 4 expert layers, a quarter held
        assert 0 <= int(load[0]) <= 32 and int(load[2]) <= int(load[0])
    got = jnp.concatenate(outs, axis=1)[live]
    np.testing.assert_allclose(got, full_logits[:, P:], **TOL)
    for layer in cache:
        for key, leaf in layer.items():
            at = (slice(None),) * decode.KV_CACHE_BATCH_AXIS[key] + (1,)
            assert np.all(np.asarray(leaf[at]) == 0.25), key


def test_a_chunk_runs_over_the_sliding_ring_s_end(params, tokens, full_logits):
    """Chunks that are not aligned to the ring: 4, then 8s; the one at 20
    lies in slots 20-23 and 0-3 of the ring of 24."""
    cfg = toy()
    cache = decode.init_cache(cfg, 2)
    heads = []
    for pos, size in ((0, 4), (4, 8), (12, 8), (20, 8), (28, 8), (36, 8)):
        lg, cache = _programs(cfg)[0](params, tokens[:, pos:pos + size],
                                      jnp.int32(pos), cache)
        heads.append(lg)
    np.testing.assert_allclose(jnp.concatenate(heads, axis=1),
                               full_logits[:, :44], **TOL)


def test_two_slots_at_different_positions_share_a_step(params, tokens,
                                                       full_logits):
    """Slot 0 has rolled its sliding rings (position 40), slot 1 has not
    (position 9): one decode step serves both."""
    cfg = toy()
    one = lambda row, P: _prefill(  # noqa: E731
        params, cfg, decode.init_cache(cfg, 1), tokens[row:row + 1], P)[2]
    a, b = one(0, 40), one(1, 9)
    cache = [{k: jnp.concatenate([x[k], y[k]],
                                 axis=decode.KV_CACHE_BATCH_AXIS[k])
              for k in x} for x, y in zip(a, b)]
    pos = np.asarray([40, 9])
    for _ in range(12):
        tok = jnp.asarray([tokens[0, pos[0]], tokens[1, pos[1]]])
        lg, cache, _ = _programs(cfg)[2](params, tok, jnp.asarray(pos), cache,
                                         jnp.asarray([True, True]))
        np.testing.assert_allclose(lg[0], full_logits[0, pos[0]], **TOL)
        np.testing.assert_allclose(lg[1], full_logits[1, pos[1]], **TOL)
        pos = pos + 1


def test_a_slot_holds_rings_of_two_lengths():
    cfg = toy()
    cache = decode.init_cache(cfg, 3)
    assert [layer["v"].shape for layer in cache] == [
        (3, 2, 24, 32), (3, 2, 24, 32), (3, 2, 64, 32), (3, 2, 24, 32),
        (3, 2, 24, 32)]
    assert [layer["k"].shape[-2] for layer in cache] == [24, 24, 64, 24, 24]
    assert cfg.ring_slack == 8 and cfg.cannot_roll
    assert not decode.has_recurrent_state(cfg)
    # left unsaid, a sliding ring is two windows long, inside the full ring
    assert toy(sliding_ring=0).ring_len("window") == 32
    assert toy(sliding_ring=0, sliding_window=40).ring_len("window") == 64


@pytest.mark.parametrize("pos, size, named", [
    (60, 8, "exceeds block_size 64"),
    (24, 16, "longer than its slack"),
    (16, 16, "longer than its slack"),
])
def test_a_chunk_that_cannot_be_represented_is_refused(params, pos, size,
                                                       named):
    cfg = toy()
    with pytest.raises(ValueError, match=named):
        decode.forward_chunk(params, jnp.zeros((1, size), jnp.int32), pos,
                             decode.init_cache(cfg, 1), cfg)


def test_a_long_chunk_is_taken_while_the_ring_has_not_rolled(params, tokens,
                                                             full_logits):
    cfg = toy()
    lg, _ = jax.jit(lambda t, c: decode.forward_chunk(params, t, 0, c, cfg))(
        tokens[:, :24], decode.init_cache(cfg, 2))
    np.testing.assert_allclose(lg, full_logits[:, :24], **TOL)


def test_generate_cached_runs_the_family(params):
    cfg = toy()
    idx = jnp.asarray(np.random.default_rng(5).integers(0, 211, (2, 37)))
    out = decode.generate_cached(params, idx, cfg, 6, jax.random.PRNGKey(0),
                                 temperature=1.0, top_k=1)
    logits, _ = model_forward(params, out[:, :-1], cfg)
    assert np.array_equal(np.asarray(out[:, 37:]),
                          np.asarray(jnp.argmax(logits[:, 36:], -1)))
    with pytest.raises(ValueError, match="afmoe family's cache cannot"):
        decode.generate_cached(params, idx, cfg, 64, jax.random.PRNGKey(0))


# -- the decode step's ring read --------------------------------------------------


@pytest.mark.parametrize("M, W, pos, live", [
    (24, 16, [0, 7, 15, 16, 23, 24, 40, 71], [1, 1, 1, 1, 1, 1, 1, 1]),
    (24, 16, [0, 7, 15, 16, 23, 24, 40, 71], [0, 1, 0, 0, 1, 1, 0, 1]),
    (24, 16, [5, 30, 9, 50], [0, 0, 0, 0]),
    (64, 64, [0, 1, 31, 32, 62, 63], [1, 1, 1, 0, 1, 1]),
    (2048, 1024, [0, 511, 512, 1500, 2047, 2048, 5000], [1, 1, 1, 1, 1, 1, 1]),
    (2048, 1024, [3000, 100, 1600, 900, 40, 2100, 700], [0, 1, 1, 0, 1, 1, 0]),
], ids=["toy_all_live", "toy_some_live", "toy_none_live", "full_ring",
        "blocks_all_live", "blocks_some_live"])
def test_ring_decode_kernel_reads_a_row_s_live_blocks_alone(M, W, pos, live):
    """``ops/ring_attention.py`` (interpret mode) against ``jamba.attend``
    under the rolled ring's mask: rows before the window, at its edge, past
    it, a lap and three laps round the ring; a row that is not live comes
    out as zeros whatever its ring holds; and the blocks it is told to read
    are the ones that hold a visible key."""
    rng = np.random.default_rng(M + len(pos))
    B, H, KV, d = len(pos), 4, 2, 32
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
               for shape in ((B, H, d), (B, KV, M, d), (B, KV, M, d)))
    pos, live = np.asarray(pos, np.int32), np.asarray(live, bool)
    visible = jax.vmap(lambda p: decode._ring_visible(p, 1, M, W))(
        jnp.asarray(pos))
    want = afmoe.attend(q[:, None], k, v, visible)[:, 0]
    got = jax.jit(lambda *a: ring_attention.ring_decode_attention(*a, W))(
        q, k, v, jnp.asarray(pos), jnp.asarray(live))
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(got)[~live] == 0)
    first, count = ring_attention.row_blocks(pos, live, M, W)
    KB = ring_attention.ring_block(M)
    for b in range(B):
        held = [j for j in range(M // KB)
                if bool(np.asarray(visible)[b, 0, j * KB:(j + 1) * KB].any())]
        if not live[b]:
            assert count[b] == 0
        elif pos[b] >= M:  # rolled: the whole ring, the window most of it
            assert (first[b], count[b]) == (0, M // KB)
        else:
            assert list(range(first[b], first[b] + count[b])) == held
    traced = ring_attention.row_blocks(jnp.asarray(pos), jnp.asarray(live),
                                       M, W)
    assert np.array_equal(traced[0], first) and np.array_equal(traced[1], count)


def test_live_kv_counts_what_the_active_rows_hold():
    pos = np.asarray([3, 15, 16, 40, 63])
    active = np.asarray([True, True, True, False, True])
    assert decode.live_kv(pos, active, 16) == {
        "live_window": 4 + 16 + 16 + 16, "live_full": 4 + 16 + 17 + 64,
        "rolled": 2}
    assert decode.live_kv(pos, np.zeros(5, bool), 16) == {
        "live_window": 0, "live_full": 0, "rolled": 0}


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
    """Six requests of 5-51 tokens (most roll the sliding rings in
    prefill) on two slots (four wait, and enter a slot another left) and
    on eight."""
    cfg = toy()
    spans = _Spans()
    eng = _engine(params, cfg, tracer=spans, num_slots=num_slots)
    built = eng.compile_stats()
    prompts = _prompts(6)
    assert max(map(len, prompts)) > 24 + 8
    outs = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    for p, out in zip(prompts, outs):
        seq = jnp.asarray([list(p) + list(out.tokens)[:-1]])
        want = jnp.argmax(reference.forward(params, seq, TOY)[0, len(p) - 1:], -1)
        assert list(out.tokens) == np.asarray(want).tolist()
    stats = eng.compile_stats()
    assert stats["decode"] - built["decode"] == 1
    assert "state_reset" not in stats and eng.stats["state_resets"] == 0
    # a prompt's tail is one padded program: at most the ladder's shapes
    assert stats["prefill"] - built["prefill"] <= 4
    steps = [a for n, a in spans.spans if n == "decode"]
    assert steps and all(
        0 < a["kv"]["live_window"] <= min(a["kv"]["live_full"],
                                          16 * a["active"])
        and 0 <= a["kv"]["rolled"] <= a["active"]
        and 0 <= a["moe"]["held"] <= a["active"] * 4 * 4 for a in steps)
    assert any(a["kv"]["rolled"] for a in steps)
    assert eng.stats["decode_live_kv"] == sum(
        4 * a["kv"]["live_window"] + a["kv"]["live_full"] for a in steps)
    # a quarter of 4 experts a row a layer fall on a share of 4 of 16
    per_row = eng.stats["moe_held"] / sum(a["active"] for a in steps) / 4
    assert 0.4 < per_row < 1.6
    text = eng.registry.render()
    assert "serving_decode_live_kv_positions_total" in text
    pool = sum(leaf.nbytes for layer in eng.cache for leaf in layer.values())
    assert pool == num_slots * 2 * 2 * 32 * 4 * (4 * 24 + 64)
    got = re.search(r"^serving_state_pool_bytes (\S+)$", text, re.M).group(1)
    assert float(got) == pool  # rings of both lengths


def test_a_slot_reused_by_a_shorter_sequence_serves_what_a_fresh_one_serves(
        params):
    """A sequence of 50 + 12 tokens leaves every ring full of its keys; the
    7-token prompt that follows in the same slot sees none of them."""
    cfg = toy()
    long_one = _prompts(1, seed=11, lo=50, hi=51)[0]
    short = _prompts(1, seed=12, lo=7, hi=8)[0]
    used = _engine(params, cfg, num_slots=1)
    used.generate([long_one], max_new_tokens=12, temperature=0.0)
    again = used.generate([short], max_new_tokens=30, temperature=0.0)[0]
    fresh = _engine(params, cfg, num_slots=1).generate(
        [short], max_new_tokens=30, temperature=0.0)[0]
    assert list(again.tokens) == list(fresh.tokens)
    seq = jnp.asarray([list(short) + list(fresh.tokens)[:-1]])
    want = jnp.argmax(reference.forward(params, seq, TOY)[0, len(short) - 1:],
                      -1)
    assert list(fresh.tokens) == np.asarray(want).tolist()


def test_a_request_is_bounded_by_the_full_ring(params):
    eng = _engine(params, toy())
    with pytest.raises(ValueError, match="afmoe family's cache cannot"):
        eng.submit(list(range(50)), max_new_tokens=15)
    # up to the full ring it is taken, far past the sliding ring
    out = eng.generate([list(range(50))], max_new_tokens=14,
                       temperature=0.0)[0]
    assert len(out.tokens) == 14


@pytest.mark.parametrize("serving, named", [
    (dict(kv_page_size=16), "paging"),
    (dict(kv_page_size=16, prefix_cache=True), "prefix cache"),
    (dict(spec_mode="ngram"), "speculation"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
    (dict(kv_page_size=16, host_tier_bytes=1 << 20), "host tier"),
    (dict(decode_attention_impl="pallas"), "decode_attention_impl"),
    (dict(prefill_chunk=16), "prefill_chunk"),
])
def test_the_engine_refuses_by_name_what_assumes_one_ring_length(
        params, serving, named):
    with pytest.raises(ValueError) as e:
        _engine(params, toy(), **serving)
    assert named in str(e.value) and "afmoe" in str(e.value)


def test_migration_refuses_by_name(params):
    eng = _engine(params, toy())
    rid = eng.submit(_prompts(1)[0], max_new_tokens=4, temperature=0.0)
    for call in (lambda: eng.export_slot_state(rid),
                 lambda: eng.import_state(b"")):
        with pytest.raises(MigrateExportError, match="rings of two lengths"):
            call()


# -- the configuration ----------------------------------------------------------------

_OWN = {"head_dim": 32, "layer_types": (SLIDING,), "sliding_window": 16,
        "sliding_ring": 24, "rope_theta": 5e5}


def test_every_new_field_has_a_refusal_case():
    shared = {"ffn_hidden", "kv_heads", "norm_eps", "num_experts",
              "experts_per_token", "moe_hidden", "first_dense_layers",
              "routed_scaling", "held_experts"}
    assert set(_OWN) == set(AFMOE_FIELDS) - shared


@pytest.mark.parametrize("family", ["control", "diff", "ndiff", "jamba",
                                    "kimi_linear"])
@pytest.mark.parametrize("field", sorted(_OWN))
def test_another_family_refuses_an_afmoe_field_by_name(family, field):
    with pytest.raises(ValueError, match=field):
        ModelConfig(model=family, **{field: _OWN[field]})


@pytest.mark.parametrize("field, value", [
    ("tie_embeddings", True), ("ssm_impl", "pallas"), ("mamba_d_state", 8),
    ("attn_layer_period", 3), ("kda_layers", [1]), ("kv_lora_rank", 64),
    ("v_head_dim", 32),
])
def test_afmoe_refuses_another_family_s_field_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        toy(**{field: value})


@pytest.mark.parametrize("field, value", [
    ("attention_impl", "pallas"), ("ffn_impl", "pallas"),
    ("decode_attention_impl", "pallas"), ("dropout", 0.1),
    ("layer_types", [SLIDING, FULL]), ("layer_types", ["chunked"] * 5),
    ("sliding_window", 0), ("sliding_window", 32), ("sliding_ring", 128),
    ("kv_heads", 3), ("head_dim", 31), ("held_experts", [8, 20]),
    ("held_experts", [4, 4]), ("experts_per_token", 32),
    ("first_dense_layers", 9), ("moe_hidden", 0),
])
def test_afmoe_refuses_what_it_does_not_run_by_name(field, value):
    with pytest.raises(ValueError, match=field.split("_")[0]):
        toy(**{field: value})


def test_published_list_puts_the_full_layer_third_and_experts_after_layer_1():
    cfg = ModelConfig(**PUBLISHED)
    assert cfg.layer_kinds() == ("window", "window", "full", "window",
                                 "window")
    assert cfg.mlp_kinds() == ("dense", "moe", "moe", "moe", "moe")
    assert cfg.held_expert_range == (0, 16)
    assert (cfg.ring_len("window"), cfg.ring_len("full")) == (5120, 8192)
    assert cfg.ring_slack == 1024 and cfg.head_size == 128


def test_the_cut_is_2_51_billion_parameters_and_117_mb_a_slot():
    cfg = ModelConfig(**PUBLISHED)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(n - 2.51e9) / 2.51e9 < 0.005, n
    assert {a.dtype for a in leaves} == {jnp.dtype("bfloat16")}
    part = lambda l, k: sum(  # noqa: E731
        int(np.prod(a.shape))
        for a in jax.tree_util.tree_leaves(shapes["blocks"][l][k]))
    assert abs(part(0, "attn") - 62.9e6) < 0.1e6
    assert abs(part(0, "ffn") - 113.25e6) < 0.1e6
    assert abs(part(1, "moe") - (16 * 28.31e6 + 29.1e6)) < 0.2e6
    # a slot: K and V of 8 heads x 128 in bfloat16, four sliding rings of
    # 5,120 positions and one full ring of 8,192
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 1))
    size = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for layer in cache for a in layer.values())
    assert size == 4096 * (4 * 5120 + 8192) == 117_440_512

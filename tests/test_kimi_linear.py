"""The ``kimi_linear`` family (models/kimi_linear.py, ops/kda.py, ops/mla.py,
ops/moe.py) against the plain reference ``benchmark/reference_kimi_linear.py``,
at toy widths on the CPU with seeded random weights: the full forward, the
chunkwise delta rule against the token-by-token recurrence, the update
kernel (interpret mode) against its XLA twin, the latent cache against
per-head keys and values, the expert layer's two shares against the uncut
layer, prefill in ladder chunks then decoding through the slot pool against
the full forward, the engine's reset of a reused slot, its expert counters,
and what the engine refuses for a family with a recurrent state.
"""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmark"))

import reference_kimi_linear as reference  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    KIMI_LINEAR_FIELDS,
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import (  # noqa: E402
    decode,
    init_model,
    kimi_linear,
    model_forward,
)
from differential_transformer_replication_tpu.ops import kda, moe  # noqa: E402
from differential_transformer_replication_tpu.serving.engine import (  # noqa: E402
    ServingEngine,
)
from differential_transformer_replication_tpu.serving.migrate import (  # noqa: E402
    MigrateExportError,
)

TOY = dict(model="kimi_linear", vocab_size=211, n_embd=64, n_head=2,
           n_layer=5, block_size=192, ffn_hidden=96, norm_eps=1e-5,
           kda_layers=[1, 2, 3, 5], full_attn_layers=[4], kda_head_dim=16,
           kda_conv=4, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
           experts_per_token=4, moe_hidden=32, first_dense_layers=1,
           routed_scaling=2.446, held_experts=[0, 8],
           compute_dtype="float32", param_dtype="float32")
PUBLISHED = dict(model="kimi_linear", vocab_size=163840, n_embd=2304,
                 n_head=32, n_layer=5, block_size=4096, ffn_hidden=9216,
                 norm_eps=1e-5, kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                 num_experts=256, experts_per_token=8, moe_hidden=1024,
                 routed_scaling=2.446, held_experts=[0, 128],
                 param_dtype="bfloat16")


def toy(**kw) -> ModelConfig:
    return ModelConfig(**dict(TOY, **kw))


@pytest.fixture(scope="module")
def params():
    return reference.make_params(7, TOY)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(3).integers(0, 211, (2, 150)))


@pytest.fixture(scope="module")
def full_logits(params, tokens):
    return reference.forward(params, tokens, TOY)


# -- the model against the reference ------------------------------------------


def test_forward_matches_the_reference(params, tokens, full_logits):
    logits, loss = jax.jit(lambda p, t: model_forward(p, t, toy()))(
        params, tokens)
    assert loss is None
    # the logits have a spread of about 1: a mixer left out moves them by that
    assert float(jnp.std(full_logits)) > 0.3
    np.testing.assert_allclose(logits, full_logits, atol=5e-4, rtol=5e-4)


def test_layout_matches_the_reference():
    shapes = jax.eval_shape(lambda k: init_model(k, toy()),
                            jax.random.PRNGKey(0))
    made = reference.make_params(1, TOY)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), made)
    # the reference names a mixer by its leaf; the program's kind of an
    # ``mla`` leaf is ``"latent"``, the ring it keeps
    assert list(zip(toy().layer_kinds(), toy().mlp_kinds())) == [
        ({"mla": "latent"}.get(mixer, mixer), mlp)
        for mixer, mlp in reference.layer_kinds(TOY)]


def test_the_family_is_served_not_trained(params, tokens):
    with pytest.raises(ValueError, match="served, not trained"):
        model_forward(params, tokens, toy(), targets=tokens)


# -- the delta rule ---------------------------------------------------------------


def _kda_inputs(L, B=2, H=2, d=16, seed=1):
    """Random inputs whose decays run from 1 (g = 0) down to 1e-4 a token."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    g = -rng.uniform(0.0, -np.log(1e-4), size=(B, L, H, d)) * (
        rng.uniform(size=(B, L, H, d)) < 0.5)
    return (unit(f(B, L, H, d)) * d ** -0.5, unit(f(B, L, H, d)),
            f(B, L, H, d), jnp.asarray(g, jnp.float32),
            jnp.asarray(rng.uniform(size=(B, L, H)), jnp.float32),
            f(B, H, d, d))


@pytest.mark.parametrize("L", [1, 3, 64, 65, 150, 256])
def test_chunkwise_form_is_the_recurrence(L):
    *xs, S0 = _kda_inputs(L)
    assert float(jnp.exp(xs[3]).min()) < 2e-4
    o, last = jax.jit(kda.chunk_fwd)(*xs, S0)
    want_o, want_last = kda.recurrence(*xs, S0)
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(last, want_last, atol=2e-5, rtol=2e-5)


def test_chunks_hand_the_state_on_and_padding_leaves_it():
    *xs, S0 = _kda_inputs(100)
    whole_o, whole = kda.chunk_fwd(*xs, S0)
    cut = lambda a, b: tuple(x[:, a:b] for x in xs)  # noqa: E731
    o1, mid = kda.chunk_fwd(*cut(0, 37), S0)
    o2, last = kda.chunk_fwd(*cut(37, 100), mid)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), whole_o,
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(last, whole, atol=2e-5, rtol=2e-5)
    # steps from `valid` on are padding: the state is the one after `valid`
    _, padded = jax.jit(kda.chunk_fwd)(*xs, S0, jnp.int32(37))
    np.testing.assert_allclose(padded, mid, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("active", [
    [True, True, True, True, True], [False, True, False, True, True],
    [False, False, False, False, True], [False] * 5,
], ids=["all", "some", "last", "none"])
@pytest.mark.parametrize("H", [2, 16])
def test_state_update_kernel_matches_its_xla_twin(active, H):
    q, k, v, g, beta, _ = _kda_inputs(1, B=5, H=H, seed=4)
    state = jnp.asarray(np.random.default_rng(5).normal(size=(5, H, 16, 16)),
                        jnp.float32)
    act = jnp.asarray(active)
    args = (state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], act)
    o, new = jax.jit(kda.state_update)(*args)
    want_o, want_new = kda.state_update_xla(*args)
    np.testing.assert_allclose(o, want_o, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(new, want_new, atol=1e-5, rtol=1e-5)
    # a slot that is not active keeps every bit
    idle = ~np.asarray(active)
    assert np.array_equal(np.asarray(new)[idle], np.asarray(state)[idle])
    # and one step of the update is one step of the recurrence
    ref_o, ref_new = kda.recurrence(q, k, v, g, beta, state)
    np.testing.assert_allclose(want_o[act], ref_o[:, 0][act], atol=1e-5)
    np.testing.assert_allclose(want_new[act], ref_new[act], atol=1e-5)


# -- the latent cache --------------------------------------------------------------


def test_latent_attention_is_attention_over_per_head_keys_and_values(params):
    """What the cache holds is (c, k_r); attending to it in the absorbed
    form gives what the reference gets from keys and values a head."""
    p = params["blocks"][3]["mla"]
    h = jnp.asarray(np.random.default_rng(6).normal(size=(2, 40, 64)),
                    jnp.float32)
    cfg = toy()
    latent = kimi_linear.mla_latent(h, p, cfg)
    assert latent.shape == (2, 40, 32 + 8)
    got = kimi_linear.mla_attend(h, p, cfg, latent,
                                 jnp.tril(jnp.ones((40, 40), bool)))
    s = reference.sizes(TOY)
    want = jnp.stack([reference._mla(hb, p, s, None) for hb in h])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    cache = decode.init_cache(cfg, 3)
    assert cache[3]["latent"].shape == (3, 1, 192, 40)
    assert set(cache[0]) == {"kda", "conv"} and cache[0]["kda"].shape == (
        3, 2, 16, 16) and cache[0]["kda"].dtype == jnp.float32


# -- the experts ---------------------------------------------------------------------


def test_two_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The routed parts of the shares 0-7 and 8-15, plus the shared expert
    ONCE, are the layer of the uncut model: what a share leaves out is
    what the other adds."""
    uncut = dict(TOY, held_experts=[0, 16])
    full = reference.make_params(7, uncut)["blocks"][1]["moe"]
    h = jnp.asarray(np.random.default_rng(8).normal(size=(50, 64)), jnp.float32)
    want = reference._moe(h, full, reference.sizes(uncut), None)
    chosen, weights = moe.route(h, full["router"]["w"], full["router"]["b"],
                                4, 2.446)
    total = kimi_linear.gated_mlp(h, full["shared"])
    loads = []
    for lo in (0, 8):
        share = reference.make_params(7, dict(TOY, held_experts=[lo, lo + 8]))[
            "blocks"][1]["moe"]["experts"]
        # a share's experts are the uncut model's, by number
        assert np.array_equal(share["down"], full["experts"]["down"][lo:lo + 8])
        y, load = moe.experts(h, chosen, weights, share, lo)
        # and the reference at that share gives that share's routed part
        one = reference._moe(h, dict(full, experts=share), reference.sizes(
            dict(TOY, held_experts=[lo, lo + 8])), None)
        np.testing.assert_allclose(
            y + kimi_linear.gated_mlp(h, full["shared"]), one, atol=2e-5,
            rtol=2e-5)
        total = total + y
        loads.append(load)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # every token's 4 experts fell on one share or the other, none dropped
    assert int(sum(l.sum() for l in loads)) == 50 * 4
    assert np.array_equal(np.concatenate(loads),
                          np.bincount(np.asarray(chosen).ravel(), minlength=16))


def test_a_row_that_is_not_live_meets_no_expert(params):
    p = params["blocks"][1]["moe"]
    h = jnp.asarray(np.random.default_rng(9).normal(size=(6, 64)), jnp.float32)
    chosen, weights = moe.route(h, p["router"]["w"], p["router"]["b"], 4, 2.446)
    live = jnp.asarray([True, False, True, False, False, True])
    y, load = moe.experts(h, chosen, weights, p["experts"], 0, live)
    y_all, load_all = moe.experts(h, chosen, weights, p["experts"], 0)
    assert np.all(np.asarray(y)[~np.asarray(live)] == 0)
    np.testing.assert_allclose(y[live], y_all[live], atol=1e-6)
    held = (np.asarray(chosen) < 8)
    assert int(load.sum()) == int(held[np.asarray(live)].sum())
    assert int(load_all.sum()) == int(held.sum())


# -- prefill in chunks, then the pool ---------------------------------------------


@pytest.mark.parametrize("free", [2, 0], ids=["last_free", "first_free"])
def test_ladder_prefill_then_pool_decode_matches_the_full_forward(
        params, tokens, full_logits, free):
    """A prompt of 85 tokens in chunks of 64 and a padded tail (21 at the
    shape 32), then 65 tokens through the pool: positions past one chunk of
    the delta rule, through the latent ring. Slot ``free`` is never active:
    it lies behind the live slots or before them (the state kernel's grid
    runs the active slots first, in their order)."""
    cfg = toy()
    live = np.asarray([s for s in range(3) if s != free])
    cache = decode.init_cache(cfg, 3)
    # the free slot's row carries tokens too: they must move nothing
    idx = jnp.zeros((3, tokens.shape[1]), tokens.dtype).at[live].set(
        tokens).at[free].set(tokens[0])
    head, cache = decode.forward_chunk(params, idx[:, :64], 0, cache, cfg)
    np.testing.assert_allclose(head[live], full_logits[:, :64], atol=5e-4,
                               rtol=5e-4)
    padded = jnp.concatenate(
        [idx[:, 64:85], jnp.zeros((3, 11), idx.dtype)], axis=1)
    lg, cache = jax.jit(lambda t, c, n: decode.forward_chunk(
        params, t, 64, c, cfg, valid=n))(padded, cache, jnp.int32(21))
    assert lg.shape == (3, 1, 211)
    np.testing.assert_allclose(lg[live, 0], full_logits[:, 84], atol=5e-4,
                               rtol=5e-4)
    # the free slot holds a state of its own; a step must not move it
    cache = [{k: (v.at[free].set(0.5) if k in decode.STATE_LEAVES else v)
              for k, v in layer.items()} for layer in cache]
    active = jnp.arange(3) != free
    step = jax.jit(lambda t, p, c: decode.forward_decode_pool(
        params, t, p, c, cfg, active=active))
    outs = []
    for t in range(85, idx.shape[1]):
        lg, cache, load = step(idx[:, t], jnp.full((3,), t), cache)
        outs.append(lg[:, None])
        # 2 live rows x 4 experts x 4 expert layers, about half on this share
        assert 0 < int(load[0]) <= 32 and 0 < int(load[1]) <= 8
        assert 0 < int(load[2]) <= int(load[0])
    got = jnp.concatenate(outs, axis=1)[live]
    np.testing.assert_allclose(got, full_logits[:, 85:], atol=5e-4, rtol=5e-4)
    for layer in cache:
        for key in decode.STATE_LEAVES:
            if key in layer:
                assert np.all(np.asarray(layer[key][free]) == 0.5), key


def test_a_padded_tail_leaves_the_states_where_the_exact_tail_leaves_them(
        params, tokens):
    cfg = toy()
    _, cache0 = decode.forward_chunk(params, tokens[:, :64], 0,
                                     decode.init_cache(cfg, 2), cfg)
    exact, pos = cache0, 64
    for size in (16, 4, 1):
        _, exact = decode.forward_chunk(params, tokens[:, pos:pos + size], pos,
                                        exact, cfg)
        pos += size
    padded = jnp.concatenate(
        [tokens[:, 64:85], jnp.zeros((2, 11), tokens.dtype)], axis=1)
    _, cache = decode.forward_chunk(params, padded, 64, cache0, cfg,
                                    valid=jnp.int32(21))
    for got, want in zip(cache, exact):
        for key in decode.STATE_LEAVES:
            if key in got:
                np.testing.assert_allclose(got[key], want[key], atol=2e-5,
                                           rtol=2e-5)
        if "latent" in got:  # the real positions' latents; padding lies past
            np.testing.assert_allclose(got["latent"][:, :, :85],
                                       want["latent"][:, :, :85], atol=2e-5)


def test_generate_cached_runs_the_family(params):
    cfg = toy()
    idx = jnp.asarray(np.random.default_rng(5).integers(0, 211, (2, 9)))
    out = decode.generate_cached(params, idx, cfg, 6, jax.random.PRNGKey(0),
                                 temperature=1.0, top_k=1)
    logits, _ = model_forward(params, out[:, :-1], cfg)
    assert np.array_equal(np.asarray(out[:, 9:]),
                          np.asarray(jnp.argmax(logits[:, 8:], -1)))
    with pytest.raises(ValueError, match="kimi_linear family's cache cannot"):
        decode.generate_cached(params, idx, cfg, 192, jax.random.PRNGKey(0))


# -- the engine -----------------------------------------------------------------------


def _engine(params, cfg, tracer=None, **kw):
    return ServingEngine(params, cfg, ServingConfig(
        **dict(dict(num_slots=2, prefill_chunk=16, prefill_budget=64), **kw)),
        tracer=tracer)


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 211, size=int(k)).tolist()
            for k in rng.integers(5, 60, size=n)]


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
    """Five requests on two slots (three wait, and enter a slot another
    left) and on eight (three slots stay free all along)."""
    cfg = toy()
    spans = _Spans()
    eng = _engine(params, cfg, tracer=spans, prefill_budget=256,
                  num_slots=num_slots)
    built = eng.compile_stats()  # engines of one ModelConfig share their jits
    prompts = _prompts(5)
    outs = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    for p, out in zip(prompts, outs):
        seq = jnp.asarray([list(p) + list(out.tokens)[:-1]])
        want = jnp.argmax(reference.forward(params, seq, TOY)[0, len(p) - 1:], -1)
        assert list(out.tokens) == np.asarray(want).tolist()
    assert eng.stats["state_resets"] == 5
    assert eng.compile_stats()["decode"] - built["decode"] == 1
    assert eng.compile_stats()["state_reset"] - built["state_reset"] == 1
    # the decode span carries the step's expert load, read with its tokens
    steps = [a for n, a in spans.spans if n == "decode"]
    assert steps and all(
        0 < a["moe"]["held"] <= a["active"] * 4 * 4
        and 0 < a["moe"]["max_expert"] <= a["active"] * 4
        and 0 < a["moe"]["experts_hit"] <= min(a["moe"]["held"], 8 * 4)
        for a in steps)
    assert eng.stats["moe_held"] == sum(a["moe"]["held"] for a in steps)
    # about half of 4 experts a row a layer fall on a share of 8 of 16
    per_row = eng.stats["moe_held"] / sum(a["active"] for a in steps) / 4
    assert 1.0 < per_row < 3.0
    resets = [a for n, a in spans.spans if n == "state_reset"]
    assert len(resets) == 5 and all(a["slots"] == 1 for a in resets)
    text = eng.registry.render()
    assert "serving_moe_held_assignments_total" in text
    pool = sum(leaf.nbytes for layer in eng.cache for key, leaf in layer.items())
    got = re.search(r"^serving_state_pool_bytes (\S+)$", text, re.M).group(1)
    assert float(got) == pool  # no K/V ring here: the whole pool is state


def test_the_decode_span_counts_the_latents_the_mla_layer_reads_live(params):
    """The MLA layer is of kind ``"latent"``: the decode step reads a
    row's live latent blocks (``ops/mla.py:latent_decode_attention``), and
    the engine says what the rows hold, ``pos + 1`` a live row, in the
    decode span's ``latent_live`` and, once a layer of that kind (one
    here), in ``serving_decode_live_latent_positions_total``."""
    cfg = toy()
    assert cfg.layer_kinds().count("latent") == 1
    spans = _Spans()
    eng = _engine(params, cfg, tracer=spans, num_slots=3)
    prompt = _prompts(1, seed=4)[0]
    eng.generate([prompt], max_new_tokens=6, temperature=0.0)
    steps = [a for n, a in spans.spans if n == "decode"]
    # one row: the step that feeds the token at position pos holds pos + 1
    assert [a["active"] for a in steps] == [1] * len(steps) and len(steps) >= 5
    assert [a["latent_live"] for a in steps] == [
        len(prompt) + 1 + t for t in range(len(steps))]
    assert eng.stats["decode_live_latent"] == sum(
        a["latent_live"] for a in steps) > 0
    # several rows at once: the sum over the live rows, free slots nothing
    before = len(steps)
    eng.generate(_prompts(2, seed=5), max_new_tokens=4, temperature=0.0)
    steps = [a for n, a in spans.spans if n == "decode"]
    assert len(steps) > before and all(
        a["active"] <= a["latent_live"] <= 192 * a["active"] for a in steps)
    assert eng.stats["decode_live_latent"] == sum(
        a["latent_live"] for a in steps)
    text = eng.registry.render()
    got = re.search(r"^serving_decode_live_latent_positions_total (\S+)$",
                    text, re.M).group(1)
    assert float(got) == eng.stats["decode_live_latent"]


@pytest.mark.parametrize("serving, named", [
    (dict(kv_page_size=16), "paging"),
    (dict(spec_mode="ngram"), "speculation"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
    (dict(kv_page_size=16, host_tier_bytes=1 << 20), "host tier"),
])
def test_mla_layers_alone_refuse_by_name_what_a_ring_of_latents_lacks(
        params, serving, named):
    """Without a KDA layer no recurrent state refuses first: what answers
    is the ring of latents, as for deepseek_v2, and names this family."""
    cfg = toy(kda_layers=[], full_attn_layers=[1, 2, 3, 4, 5])
    with pytest.raises(ValueError) as e:
        _engine(params, cfg, **serving)
    assert named in str(e.value) and "kimi_linear" in str(e.value)
    assert "latent" in str(e.value) and "recurrent" not in str(e.value)


def test_a_reused_slot_serves_what_a_fresh_engine_serves(params):
    cfg = toy()
    first, second = _prompts(2, seed=11)
    used = _engine(params, cfg, num_slots=1)
    used.generate([first], max_new_tokens=12, temperature=0.0)
    again = used.generate([second], max_new_tokens=12, temperature=0.0)[0]
    fresh = _engine(params, cfg, num_slots=1).generate(
        [second], max_new_tokens=12, temperature=0.0)[0]
    assert list(again.tokens) == list(fresh.tokens)
    # and without the reset it does not: the state is really there
    stale = _engine(params, cfg, num_slots=1)
    stale._reset_slot_state = lambda slot, iteration: None
    stale.generate([first], max_new_tokens=12, temperature=0.0)
    kept = stale.generate([second], max_new_tokens=12, temperature=0.0)[0]
    assert list(kept.tokens) != list(fresh.tokens)


def test_a_step_leaves_an_inactive_slot_s_state_bit_identical(params):
    cfg = toy()
    eng = _engine(params, cfg, num_slots=3)
    eng.generate(_prompts(3, seed=2), max_new_tokens=4, temperature=0.0)
    before = jax.tree_util.tree_map(np.asarray, eng.cache)
    eng.submit(_prompts(1, seed=3)[0], max_new_tokens=6, temperature=0.0)
    eng.run()  # one request: it takes one slot, the others stay inactive
    after = jax.tree_util.tree_map(np.asarray, eng.cache)
    moved = [i for i in range(3) if any(
        not np.array_equal(a[k][i], b[k][i])
        for a, b in zip(after, before) for k in a)]
    assert len(moved) == 1, moved


def test_recurrent_state_is_told_by_the_layers_not_the_family():
    assert decode.has_recurrent_state(toy())
    # MLA layers alone keep rings of latents: nothing to zero
    assert not decode.has_recurrent_state(
        toy(kda_layers=[], full_attn_layers=[1, 2, 3, 4, 5]))
    assert not decode.has_recurrent_state(ModelConfig(model="control"))


@pytest.mark.parametrize("serving, named", [
    (dict(kv_page_size=16), "paging"),
    (dict(kv_page_size=16, prefix_cache=True), "prefix cache"),
    (dict(spec_mode="ngram"), "speculation"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
    (dict(kv_page_size=16, host_tier_bytes=1 << 20), "host tier"),
    (dict(decode_attention_impl="pallas"), "decode_attention_impl"),
])
def test_the_engine_refuses_by_name_what_needs_state_by_position(
        params, serving, named):
    with pytest.raises(ValueError) as e:
        _engine(params, toy(), **serving)
    assert named in str(e.value) and "kimi_linear" in str(e.value)


def test_migration_refuses_by_name(params):
    eng = _engine(params, toy())
    rid = eng.submit(_prompts(1)[0], max_new_tokens=4, temperature=0.0)
    for call in (lambda: eng.export_slot_state(rid),
                 lambda: eng.import_state(b"")):
        with pytest.raises(MigrateExportError, match="recurrent state"):
            call()


def test_a_request_past_the_ring_is_refused_at_submit(params):
    eng = _engine(params, toy())
    with pytest.raises(ValueError, match="kimi_linear family's cache cannot"):
        eng.submit(list(range(150)), max_new_tokens=60)


# -- the configuration ----------------------------------------------------------------

_OTHER = {"kda_layers": (1,), "full_attn_layers": (1,), "kda_head_dim": 64,
          "kda_conv": 3, "kv_lora_rank": 64,
          "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
          "num_experts": 8, "experts_per_token": 2, "moe_hidden": 64,
          "first_dense_layers": 0, "routed_scaling": 2.0,
          "held_experts": (0, 4)}


def test_every_new_field_has_a_refusal_case():
    assert set(_OTHER) == set(KIMI_LINEAR_FIELDS) - {"ffn_hidden", "norm_eps"}


@pytest.mark.parametrize("family", ["control", "diff", "ndiff", "jamba"])
@pytest.mark.parametrize("field", sorted(_OTHER))
def test_another_family_refuses_a_kimi_linear_field_by_name(family, field):
    with pytest.raises(ValueError, match=field):
        ModelConfig(model=family, **{field: _OTHER[field]})


@pytest.mark.parametrize("field, value", [
    ("kv_heads", 2), ("tie_embeddings", True), ("ssm_impl", "pallas"),
    ("mamba_d_state", 8), ("attn_layer_period", 3),
])
def test_kimi_linear_refuses_a_jamba_field_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        toy(**{field: value})


@pytest.mark.parametrize("field, value", [
    ("attention_impl", "pallas"), ("ffn_impl", "pallas"),
    ("decode_attention_impl", "pallas"),
    ("kda_conv", 1), ("dropout", 0.1), ("kda_layers", [1, 2, 3]),
    ("full_attn_layers", [3, 4]), ("held_experts", [8, 20]),
    ("held_experts", [4, 4]), ("experts_per_token", 32),
    ("first_dense_layers", 9), ("moe_hidden", 0),
])
def test_kimi_linear_refuses_what_it_does_not_run_by_name(field, value):
    with pytest.raises(ValueError, match=field.split("_")[0]):
        toy(**{field: value})


def test_published_lists_put_mla_at_layer_4_and_experts_after_layer_1():
    cfg = ModelConfig(**PUBLISHED)
    assert cfg.layer_kinds() == ("kda", "kda", "kda", "latent", "kda")
    assert cfg.mlp_kinds() == ("dense", "moe", "moe", "moe", "moe")
    assert cfg.held_expert_range == (0, 128)
    assert ModelConfig(**dict(PUBLISHED, held_experts=[0, 0])
                       ).held_expert_range == (0, 256)


def test_the_cut_is_4_66_billion_parameters_and_13_mb_a_slot():
    cfg = ModelConfig(**PUBLISHED)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(n - 4.66e9) / 4.66e9 < 0.005, n
    assert {a.dtype for a in leaves} == {jnp.dtype("bfloat16")}
    mixer = lambda l, k: sum(  # noqa: E731
        int(np.prod(a.shape))
        for a in jax.tree_util.tree_leaves(shapes["blocks"][l][k]))
    assert abs(mixer(0, "kda") - 39.5e6) < 0.1e6
    assert abs(mixer(3, "mla") - 29.1e6) < 0.1e6
    # a slot: 4 KDA layers x (32 x 128 x 128 float32 + 3 x 12288 bfloat16)
    # and the MLA layer's ring, 4096 x 576 bfloat16
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 1))
    size = lambda keys: sum(  # noqa: E731
        int(np.prod(a.shape)) * a.dtype.itemsize for layer in cache
        for k, a in layer.items() if k in keys)
    assert size(decode.STATE_LEAVES) == 4 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert size(("latent",)) == 4096 * 576 * 2

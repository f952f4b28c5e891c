"""The ``jamba`` family (models/jamba.py, ops/ssm.py) against the plain
reference ``benchmark/reference_jamba.py``, at toy widths on the CPU with
seeded random weights: the full forward, the loss and its gradients, both
Pallas kernels (interpret mode) against their XLA twins, prefill in ladder
chunks then decoding through the slot pool against the full forward, the
engine's reset of a reused slot, and what the engine refuses for a family
with a recurrent state.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmark"))

import reference_jamba as reference  # noqa: E402

from differential_transformer_replication_tpu.config import (  # noqa: E402
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import (  # noqa: E402
    decode,
    init_model,
    jamba,
    model_forward,
)
from differential_transformer_replication_tpu.ops import ssm  # noqa: E402
from differential_transformer_replication_tpu.serving.engine import (  # noqa: E402
    ServingEngine,
)
from differential_transformer_replication_tpu.serving.migrate import (  # noqa: E402
    MigrateExportError,
)

TOY = dict(model="jamba", vocab_size=211, n_embd=64, n_head=4, kv_heads=1,
           n_layer=4, block_size=128, ffn_hidden=96,
           norm_eps=1e-6, tie_embeddings=True,
           attn_layer_period=2, attn_layer_offset=1, mamba_d_state=16,
           mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
           compute_dtype="float32", param_dtype="float32")
PUBLISHED = dict(model="jamba", vocab_size=65536, n_embd=2560, n_head=20,
                 kv_heads=1, n_layer=28, block_size=2048, ffn_hidden=8192,
                 tie_embeddings=True,
                 attn_layer_period=14, attn_layer_offset=7,
                 mamba_dt_rank=160, param_dtype="bfloat16")


def toy(**kw) -> ModelConfig:
    return ModelConfig(**dict(TOY, **kw))


@pytest.fixture(scope="module")
def params():
    return reference.make_params(7, TOY)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(3).integers(0, 211, (2, 100)))


@pytest.fixture(scope="module")
def full_logits(params, tokens):
    return reference.forward(params, tokens, TOY)


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_forward_matches_the_reference(params, tokens, full_logits, impl):
    logits, loss = model_forward(params, tokens, toy(ssm_impl=impl))
    assert loss is None
    np.testing.assert_allclose(logits, full_logits, atol=2e-4, rtol=2e-4)


def test_untied_head_and_layout_match_the_reference():
    model = dict(TOY, tie_embeddings=False, kv_heads=2)
    cfg = ModelConfig(**model)
    p = reference.make_params(5, model)
    want = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)  # noqa: E731
    assert shapes(p) == shapes(want)
    idx = jnp.asarray(np.random.default_rng(1).integers(0, 211, (1, 40)))
    logits, _ = model_forward(p, idx, cfg)
    np.testing.assert_allclose(logits, reference.forward(p, idx, model),
                               atol=2e-4, rtol=2e-4)


def test_loss_and_gradients_match_the_reference(params, tokens):
    x, y = tokens[:, :-1], tokens[:, 1:]
    cfg = toy()
    loss, grads = jax.value_and_grad(
        lambda p: model_forward(p, x, cfg, targets=y)[1])(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss_sum(p, x, y, TOY) / x.size)(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-8
        assert float(jnp.max(jnp.abs(g - r))) / scale < 2e-3, path


def test_loss_chunk_gives_the_dense_loss(params, tokens):
    x, y = tokens[:, :64], tokens[:, 1:65]
    dense = model_forward(params, x, toy(), targets=y)[1]
    logits, chunked = model_forward(params, x, toy(loss_chunk=16), targets=y)
    assert logits is None
    np.testing.assert_allclose(chunked, dense, rtol=1e-5)


def test_pallas_scan_refuses_training_by_name(params, tokens):
    with pytest.raises(ValueError, match="ssm_impl='pallas' is forward only"):
        model_forward(params, tokens, toy(ssm_impl="pallas"), targets=tokens)


def test_multi_query_attention_matches_the_reference(params):
    """One K/V head under four query heads, through the program's grouped
    einsum and through the reference's repeated heads."""
    blk = next(b for b in params["blocks"] if "attn" in b)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 37, 64))
    got = jamba._attn_full(h, blk["attn"])
    want = reference._attention(h, blk["attn"], reference.sizes(TOY), None)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# -- the kernels against their twins --------------------------------------------


def _scan_inputs(L, seed=0, B=2, Di=128, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (B, L, Di)),
            jax.nn.softplus(jax.random.normal(k[1], (B, L, Di)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (Di, N))),
            jax.random.normal(k[3], (B, L, N)),
            jax.random.normal(k[4], (B, L, N)),
            1.0 + 0.3 * jax.random.normal(k[5], (Di,)),
            jax.random.normal(k[6], (B, N, Di)))  # a non-zero initial state


@pytest.mark.parametrize("L, Di", [(1, 128), (3, 128), (64, 128), (200, 128),
                                   (5, 512), (72, 64)])
def test_scan_kernel_matches_its_xla_twin(L, Di):
    """Chunk lengths off and on the time blocks; 512 channels run two lane
    groups a grid step, 64 less than a lane tile."""
    args = _scan_inputs(L, seed=L, Di=Di)
    y, h = ssm.selective_scan_pallas(*args)
    y_ref, h_ref = ssm.selective_scan_xla(*args)
    np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(h, h_ref, atol=1e-5, rtol=1e-5)


def test_scan_in_chunks_is_the_scan_in_one():
    u, delta, A, Bm, Cm, D, h0 = _scan_inputs(96, seed=9)
    y_all, h_all = ssm.selective_scan_xla(u, delta, A, Bm, Cm, D, h0)
    ys, h = [], h0
    for lo, hi in ((0, 64), (64, 65), (65, 96)):
        y, h = ssm.selective_scan_pallas(
            u[:, lo:hi], delta[:, lo:hi], A, Bm[:, lo:hi], Cm[:, lo:hi], D, h)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_all, atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(h, h_all, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("active", [
    [True, False, True, True, False, False, True, False],
    [False] * 8, [True] * 8, [False] * 7 + [True],
])
def test_state_update_kernel_matches_its_xla_twin(active):
    S, Di, N = 8, 256, 16  # two lane groups a slot
    u, delta, A, Bm, Cm, D, state = _scan_inputs(1, seed=2, B=S, Di=Di, N=N)
    act = jnp.asarray(active)
    args = (state, u[:, 0], delta[:, 0], A, Bm[:, 0], Cm[:, 0], D, act)
    y, new = ssm.state_update_pallas(*args)
    y_ref, new_ref = ssm.state_update_xla(*args)
    np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(new, new_ref, atol=1e-5, rtol=1e-5)
    # a slot that is not active keeps every bit of its state
    kept = np.asarray(~act)
    assert np.array_equal(np.asarray(new)[kept], np.asarray(state)[kept])
    assert not np.any(np.asarray(y)[kept])


def test_causal_conv_carries_its_window():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    u = jax.random.normal(k[0], (2, 20, 8))
    w, b = jax.random.normal(k[1], (4, 8)), jax.random.normal(k[2], (8,))
    zero = jnp.zeros((2, 3, 8))
    whole, last = ssm.causal_conv(u, w, b, zero)
    first, win = ssm.causal_conv(u[:, :2], w, b, zero)  # shorter than the window
    rest, win = ssm.causal_conv(u[:, 2:], w, b, win)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), whole,
                               atol=1e-6)
    np.testing.assert_allclose(win, last)
    np.testing.assert_allclose(last, u[:, -3:])
    # padding after step `valid` moves nothing: the window is the one
    # after that step, also where it still reaches into the carried one
    for n in (1, 2, 7, 20):
        _, cut = ssm.causal_conv(u, w, b, zero, valid=jnp.int32(n))
        _, want = ssm.causal_conv(u[:, :n], w, b, zero)
        np.testing.assert_allclose(cut, want)


# -- prefill in chunks, then the pool ---------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ladder_prefill_then_pool_decode_matches_the_full_forward(
        params, tokens, full_logits, impl):
    cfg = toy(ssm_impl=impl)
    cache = decode.init_cache(cfg, 3)  # slot 2 stays free
    idx = jnp.concatenate([tokens, tokens[:1]])  # its row is never active
    outs, pos = [], 0
    for size in (64, 32, 4):
        lg, cache = decode.forward_chunk(params, idx[:, pos:pos + size], pos,
                                         cache, cfg)
        outs.append(lg)
        pos += size
    # the free slot holds a state of its own; a step must not move it
    cache = [{k: (v.at[2].set(0.5) if k in decode.STATE_LEAVES else v)
              for k, v in layer.items()} for layer in cache]
    active = jnp.asarray([True, True, False])
    step = jax.jit(lambda t, p, c: decode.forward_decode_pool(
        params, t, p, c, cfg, active=active))
    for t in range(pos, idx.shape[1]):
        lg, cache = step(idx[:, t], jnp.full((3,), t), cache)
        outs.append(lg[:, None])
    got = jnp.concatenate(outs, axis=1)[:2]
    np.testing.assert_allclose(got, full_logits, atol=5e-4, rtol=5e-4)
    for layer in cache:
        for key in decode.STATE_LEAVES:
            if key in layer:
                assert np.all(np.asarray(layer[key][2]) == 0.5), key


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_padded_tail_chunk_is_the_tail(params, tokens, full_logits, impl):
    """A tail of 21 tokens run once at the ladder's shape 32 (``valid``)
    leaves the states where the digits 16 + 4 + 1 leave them, gives the
    last real token's logits, and the tokens that follow overwrite the
    padding's keys before they attend."""
    cfg = toy(ssm_impl=impl)
    head, cache0 = decode.forward_chunk(params, tokens[:, :64], 0,
                                        decode.init_cache(cfg, 2), cfg)
    exact, pos = cache0, 64
    for size in (16, 4, 1):
        _, exact = decode.forward_chunk(params, tokens[:, pos:pos + size], pos,
                                        exact, cfg)
        pos += size
    padded = jnp.concatenate(
        [tokens[:, 64:85], jnp.zeros((2, 11), tokens.dtype)], axis=1)
    run = jax.jit(lambda t, c, n: decode.forward_chunk(
        params, t, 64, c, cfg, valid=n))
    lg, cache = run(padded, cache0, jnp.int32(21))
    assert lg.shape == (2, 1, 211)
    np.testing.assert_allclose(lg[:, 0], full_logits[:, 84], atol=5e-4,
                               rtol=5e-4)
    for got, want in zip(cache, exact):
        for key in decode.STATE_LEAVES:
            if key in got:
                np.testing.assert_allclose(got[key], want[key], atol=1e-5,
                                           rtol=1e-5)
    outs = []
    for t in range(85, 100):
        step, cache = decode.forward_decode_pool(
            params, tokens[:, t], jnp.full((2,), t), cache, cfg)
        outs.append(step[:, None])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), full_logits[:, 85:],
                               atol=5e-4, rtol=5e-4)
    # a whole chunk is its own padding: valid = L
    whole, _ = run(tokens[:, 64:96], cache0, jnp.int32(32))
    np.testing.assert_allclose(whole[:, 0], full_logits[:, 95], atol=5e-4,
                               rtol=5e-4)


def test_another_family_refuses_a_padded_chunk_by_name():
    cfg = ModelConfig(model="control", vocab_size=61, n_embd=32, n_head=2,
                      n_layer=1, block_size=16)
    with pytest.raises(ValueError, match="families only"):
        decode.forward_chunk(init_model(jax.random.PRNGKey(0), cfg),
                             jnp.zeros((1, 4), jnp.int32), 0,
                             decode.init_cache(cfg, 1), cfg, valid=3)


def test_generate_cached_runs_the_family(params):
    cfg = toy()
    idx = jnp.asarray(np.random.default_rng(5).integers(0, 211, (2, 9)))
    out = decode.generate_cached(params, idx, cfg, 6, jax.random.PRNGKey(0),
                                 temperature=1.0, top_k=1)
    logits, _ = model_forward(params, out[:, :-1], cfg)
    assert np.array_equal(np.asarray(out[:, 9:]),
                          np.asarray(jnp.argmax(logits[:, 8:], -1)))
    with pytest.raises(ValueError, match="cannot roll"):
        decode.generate_cached(params, idx, cfg, 128, jax.random.PRNGKey(0))


# -- the engine -----------------------------------------------------------------------


def _engine(params, cfg, **kw):
    return ServingEngine(params, cfg, ServingConfig(
        **dict(dict(num_slots=2, prefill_chunk=16, prefill_budget=64), **kw)))


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 211, size=int(k)).tolist()
            for k in rng.integers(5, 60, size=n)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_engine_serves_the_reference_s_greedy_tokens(params, impl):
    cfg = toy(ssm_impl=impl)
    eng = _engine(params, cfg, prefill_budget=256)
    prompts = _prompts(5)
    prefill, shapes = eng._prefill_fn, []
    eng._prefill_fn = lambda *a: (shapes.append(a[3].shape[1]), prefill(*a))[1]
    outs = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    eng._prefill_fn = prefill
    for p, out in zip(prompts, outs):
        seq = jnp.asarray([list(p) + list(out.tokens)[:-1]])
        want = jnp.argmax(reference.forward(params, seq, TOY)[0, len(p) - 1:], -1)
        assert list(out.tokens) == np.asarray(want).tolist()
    assert eng.stats["state_resets"] == 5
    # a prompt of p tokens at prefill_chunk 16 is p // 16 whole chunks and
    # one padded tail, not one program a binary digit
    assert len(shapes) == sum(-(-len(p) // 16) for p in prompts)
    assert set(shapes) <= {1, 2, 4, 8, 16}
    assert eng.compile_stats()["decode"] == 1
    assert eng.compile_stats()["state_reset"] == 1


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
    cfg = toy(ssm_impl="pallas")
    eng = _engine(params, cfg, num_slots=3)
    eng.generate(_prompts(3, seed=2), max_new_tokens=4, temperature=0.0)
    before = jax.tree_util.tree_map(np.asarray, eng.cache)
    eng.submit(_prompts(1, seed=3)[0], max_new_tokens=6, temperature=0.0)
    eng.run()  # one request: it takes one slot, the others stay inactive
    after = jax.tree_util.tree_map(np.asarray, eng.cache)
    moved = [i for i in range(3) if any(
        not np.array_equal(a[k][i], b[k][i])
        for a, b in zip(after, before) for k in decode.STATE_LEAVES if k in a)]
    assert len(moved) == 1, moved


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
    assert named in str(e.value) and "jamba" in str(e.value)


def test_migration_refuses_by_name(params):
    eng = _engine(params, toy())
    rid = eng.submit(_prompts(1)[0], max_new_tokens=4, temperature=0.0)
    for call in (lambda: eng.export_slot_state(rid),
                 lambda: eng.import_state(b"")):
        with pytest.raises(MigrateExportError, match="recurrent state"):
            call()


def test_a_request_past_the_ring_is_refused_at_submit(params):
    eng = _engine(params, toy())
    with pytest.raises(ValueError, match="jamba family's cache cannot roll"):
        eng.submit(list(range(100)), max_new_tokens=40)


# -- the configuration ----------------------------------------------------------------


@pytest.mark.parametrize("field, value", [
    ("kv_heads", 2), ("ffn_hidden", 100), ("norm_eps", 1e-6),
    ("tie_embeddings", True), ("mamba_dt_rank", 8), ("ssm_impl", "pallas"),
    ("attn_layer_period", 3), ("mamba_d_state", 8),
])
def test_another_family_refuses_a_jamba_field_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        ModelConfig(model="diff", **{field: value})


@pytest.mark.parametrize("field, value", [
    ("attention_impl", "pallas"), ("ffn_impl", "pallas"),
    ("decode_attention_impl", "pallas"), ("ssm_state_dtype", "bfloat16"),
    ("mamba_d_conv", 1), ("ssm_impl", "cuda"), ("dropout", 0.1),
    ("kv_heads", 3), ("attn_layer_offset", 2), ("ssm_state_dtype", "int8"),
])
def test_jamba_refuses_what_it_does_not_run_by_name(field, value):
    with pytest.raises(ValueError, match=field.split("_")[0]):
        toy(**{field: value})


def test_published_pattern_puts_attention_at_7_and_21_only():
    kinds = ModelConfig(**PUBLISHED).layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("mamba") == 26
    assert reference.layer_kinds(PUBLISHED) == list(kinds)


def test_published_parameter_count_is_3_03_billion():
    cfg = ModelConfig(**PUBLISHED)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(n - 3.03e9) / 3.03e9 < 0.005, n
    assert {a.dtype for a in leaves} == {jnp.dtype("bfloat16")}
    # a slot's state: 26 x (5120 x 16 float32 + 5120 x 3 bfloat16)
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 1))
    state = sum(int(np.prod(a.shape)) * a.dtype.itemsize for layer in cache
                for k, a in layer.items() if k in decode.STATE_LEAVES)
    assert state == 26 * (5120 * 16 * 4 + 5120 * 3 * 2)

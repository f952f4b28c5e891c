"""KV-cache decode parity: the chunked cache path must reproduce the full
forward's logits exactly (same math, different schedule) for all three
model families, in prefill and in token-by-token decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models import (
    generate,
    init_model,
    model_forward,
)
from differential_transformer_replication_tpu.models.decode import (
    forward_chunk,
    forward_decode_pool,
    generate_cached,
    init_cache,
)


def _cfg(kind):
    return ModelConfig(
        model=kind, vocab_size=97, n_embd=32, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
    )


@pytest.mark.parametrize("kind", ["control", "diff", "ndiff"])
def test_prefill_matches_full_forward(kind):
    cfg = _cfg(kind)
    params = init_model(jax.random.PRNGKey(0), cfg)
    idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    ref, _ = model_forward(params, idx, cfg)
    cache = init_cache(cfg, 2)
    got, _ = forward_chunk(params, idx, 0, cache, cfg)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind", ["control", "diff", "ndiff"])
def test_incremental_decode_matches_full_forward(kind):
    """Teacher-forced: prefill 8 tokens, then feed 6 more one at a time;
    at every step the cached logits must equal a from-scratch forward
    over the growing prefix."""
    cfg = _cfg(kind)
    params = init_model(jax.random.PRNGKey(0), cfg)
    seq = jax.random.randint(jax.random.PRNGKey(2), (2, 14), 0, cfg.vocab_size)
    cache = init_cache(cfg, 2)
    logits, cache = forward_chunk(params, seq[:, :8], 0, cache, cfg)
    ref_full, _ = model_forward(params, seq[:, :8], cfg)
    np.testing.assert_allclose(logits[:, -1], ref_full[:, -1], rtol=1e-4, atol=1e-4)
    for t in range(8, 14):
        logits, cache = forward_chunk(params, seq[:, t : t + 1], t, cache, cfg)
        ref_full, _ = model_forward(params, seq[:, : t + 1], cfg)
        np.testing.assert_allclose(
            logits[:, -1], ref_full[:, -1], rtol=1e-4, atol=1e-4,
            err_msg=f"divergence at position {t}",
        )


@pytest.mark.parametrize("kind", ["control", "ndiff"])
def test_served_rope_model_matches_the_kernel_path_forward(kind):
    """The full forward at ``attention_impl='pallas'`` rotates contiguous
    halves of projections whose weights' columns it re-ordered
    (models/common.py:flash_bh_fn); the prefill chunk and the decode step
    rotate pairs 2i, 2i + 1 and keep the K ring in the published order.
    One checkpoint must read the same through both: prefill 8 tokens, then
    decode 6 on the pool, against the kernel path over all 14 (causal, so
    its row t is the prefix's last)."""
    import dataclasses

    cfg = _cfg(kind)
    params = init_model(jax.random.PRNGKey(0), cfg)
    seq = jax.random.randint(jax.random.PRNGKey(2), (2, 14), 0, cfg.vocab_size)
    ref, _ = model_forward(
        params, seq, dataclasses.replace(cfg, attention_impl="pallas"))
    logits, cache = forward_chunk(params, seq[:, :8], 0, init_cache(cfg, 2), cfg)
    np.testing.assert_allclose(logits, ref[:, :8], rtol=1e-4, atol=1e-4)
    for t in range(8, 14):
        logits, cache = forward_decode_pool(
            params, seq[:, t], jnp.full((2,), t, jnp.int32), cache, cfg)
        np.testing.assert_allclose(
            logits, ref[:, t], rtol=1e-4, atol=1e-4,
            err_msg=f"divergence at position {t}",
        )


@pytest.mark.parametrize("kind", ["control", "diff", "ndiff"])
def test_generate_cached_contract(kind):
    cfg = _cfg(kind)
    params = init_model(jax.random.PRNGKey(0), cfg)
    idx = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0, cfg.vocab_size)
    out = generate_cached(params, idx, cfg, 10, jax.random.PRNGKey(4))
    assert out.shape == (2, 15)
    np.testing.assert_array_equal(out[:, :5], idx)  # prompt preserved
    assert int(out.max()) < cfg.vocab_size and int(out.min()) >= 0


def test_generate_cached_rejects_overflow_for_diff_only():
    """The diff family's learned absolute position table cannot roll with
    a KV cache (each window slide re-embeds every cached position), so it
    keeps the hard bound; the RoPE families ride the ring cache past
    block_size (tests below)."""
    cfg = _cfg("diff")
    params = init_model(jax.random.PRNGKey(0), cfg)
    idx = jnp.zeros((1, 30), jnp.int32)
    with pytest.raises(ValueError):
        generate_cached(params, idx, cfg, 10, jax.random.PRNGKey(0))


def test_generate_and_cached_agree_on_argmax_path():
    """With near-deterministic logits the two generators walk the same
    sequence: compare greedy continuations computed from each path's
    logits rather than sampled tokens (sampling consumes rng differently).
    Here: decode 5 steps teacher-forced on generate()'s output and check
    the cached path assigns the same argmax at every position."""
    cfg = _cfg("control")
    params = init_model(jax.random.PRNGKey(0), cfg)
    idx = jax.random.randint(jax.random.PRNGKey(5), (1, 4), 0, cfg.vocab_size)
    full = generate(params, idx, cfg, 5, jax.random.PRNGKey(6))  # (1, 9)
    cache = init_cache(cfg, 1)
    logits_c, cache = forward_chunk(params, full[:, :4], 0, cache, cfg)
    for t in range(4, 9):
        ref_logits, _ = model_forward(params, full[:, : t], cfg)
        np.testing.assert_array_equal(
            jnp.argmax(logits_c[:, -1], -1), jnp.argmax(ref_logits[:, -1], -1)
        )
        if t < 8:
            logits_c, cache = forward_chunk(params, full[:, t : t + 1], t, cache, cfg)


def test_forward_chunk_rejects_invalid_chunks():
    """Concrete positions fail loudly where the cache cannot represent
    them: any past-block_size position for diff (absolute position
    table), and ring-boundary-WRAPPING multi-token chunks for everyone
    (the slice write would clamp); a single token at pos == block_size
    is the valid rolling case for RoPE families."""
    params_d = init_model(jax.random.PRNGKey(0), _cfg("diff"))
    cache_d = init_cache(_cfg("diff"), 1)
    tok = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError):
        forward_chunk(params_d, tok, _cfg("diff").block_size, cache_d, _cfg("diff"))

    cfg = _cfg("control")
    params = init_model(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 1)
    with pytest.raises(ValueError):  # 28+8 wraps the 32-slot ring
        forward_chunk(params, jnp.zeros((1, 8), jnp.int32), 28, cache, cfg)
    # rolling single-token writes are legal past block_size
    logits, _ = forward_chunk(
        params, tok, cfg.block_size, cache, cfg, rope_len=cfg.block_size + 1
    )
    assert bool(jnp.isfinite(logits).all())


def _cfg1(kind):
    """Single-layer variant: the only depth at which the reference's
    crop-recompute and sliding-window caching coincide exactly past the
    block boundary (at depth >= 2 the crop changes every remaining
    position's deep activations each step — Omega(M^2)/token by
    construction, models/decode.py module docstring)."""
    return ModelConfig(
        model=kind, vocab_size=97, n_embd=32, n_head=2, n_layer=1,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
    )


@pytest.mark.parametrize("kind", ["control", "ndiff"])
def test_rolling_decode_matches_windowed_forward_single_layer(kind):
    """Past block_size the ring cache equals the reference's crop
    semantics (control.py:163-171) EXACTLY at depth 1: teacher-force a
    sequence of 2.5x block_size one token at a time and compare every
    step's logits with a from-scratch forward over the cropped last
    block_size tokens. RoPE's relative-position property makes the
    absolute-position cache and the rebased crop mathematically equal."""
    cfg = _cfg1(kind)  # block_size 32
    M = cfg.block_size
    params = init_model(jax.random.PRNGKey(0), cfg)
    total = 2 * M + M // 2
    seq = jax.random.randint(jax.random.PRNGKey(7), (2, total), 0, cfg.vocab_size)
    cache = init_cache(cfg, 2)
    logits, cache = forward_chunk(
        params, seq[:, :8], 0, cache, cfg, rope_len=total
    )
    for t in range(8, total):
        logits, cache = forward_chunk(
            params, seq[:, t : t + 1], t, cache, cfg, rope_len=total
        )
        lo = max(0, t + 1 - M)
        ref_full, _ = model_forward(params, seq[:, lo : t + 1], cfg)
        np.testing.assert_allclose(
            logits[:, -1], ref_full[:, -1], rtol=2e-4, atol=2e-4,
            err_msg=f"divergence at position {t} (window [{lo}, {t}])",
        )


@pytest.mark.parametrize("kind", ["control", "ndiff"])
def test_ring_indexing_matches_append_oracle(kind):
    """Deep-model check of the ring arithmetic itself: an oracle with a
    cache big enough to NEVER wrap (block_size = whole sequence) plus an
    explicit ``window`` visibility clip implements the same
    sliding-window semantics with trivial append indexing; the ring path
    must match it through two full wraps. This isolates slot/mask bugs
    from the (expected, documented) semantic divergence vs the crop
    recompute at depth >= 2."""
    cfg = _cfg(kind)  # 2 layers, block_size 32
    M = cfg.block_size
    params = init_model(jax.random.PRNGKey(0), cfg)
    total = 2 * M + 8
    seq = jax.random.randint(jax.random.PRNGKey(11), (1, total), 0, cfg.vocab_size)

    def run(run_cfg, window):
        cache = init_cache(run_cfg, 1)
        out = []
        logits, cache = forward_chunk(
            params, seq[:, :8], 0, cache, run_cfg, rope_len=total, window=window
        )
        out.append(logits[:, -1])
        for t in range(8, total):
            logits, cache = forward_chunk(
                params, seq[:, t : t + 1], t, cache, run_cfg,
                rope_len=total, window=window,
            )
            out.append(logits[:, -1])
        return out

    ring = run(cfg, 0)  # ring of M slots, default window
    oracle = run(cfg.replace(block_size=total), M)  # append cache + clip
    for i, (r, o) in enumerate(zip(ring, oracle)):
        np.testing.assert_allclose(
            np.asarray(r), np.asarray(o), rtol=1e-5, atol=1e-5,
            err_msg=f"ring/oracle divergence at step {i}",
        )


def test_generate_cached_rolls_past_block_size_greedy_parity():
    """End-to-end at depth 1 (where cache and crop semantics coincide):
    generate_cached past block_size walks the same greedy sequence as the
    windowed generate (which recomputes the cropped O(T^2) forward per
    token), including a prompt longer than block_size (cropped like
    control.py:165)."""
    cfg = _cfg1("control")  # 1 layer, block_size 32
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = jax.random.PRNGKey(8)
    idx = jax.random.randint(jax.random.PRNGKey(9), (2, 6), 0, cfg.vocab_size)
    full = generate(params, idx, cfg, 60, rng, temperature=0.0)
    cached = generate_cached(params, idx, cfg, 60, rng, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))

    # long prompt: both paths crop to the last block_size tokens
    long_idx = jax.random.randint(
        jax.random.PRNGKey(10), (1, 40), 0, cfg.vocab_size
    )
    cropped = generate(
        params, long_idx[:, -cfg.block_size:], cfg, 12, rng, temperature=0.0
    )
    cached_long = generate_cached(params, long_idx, cfg, 12, rng, temperature=0.0)
    np.testing.assert_array_equal(
        np.asarray(cropped[:, -12:]), np.asarray(cached_long[:, -12:])
    )


def test_generate_cached_deep_model_rolls_finite():
    """Depth >= 2 past the boundary: the documented sliding-window
    semantics — outputs finite, prompt preserved, in-vocab, and the
    in-window prefix (where cache == crop exactly) matches the windowed
    generate under greedy decoding."""
    cfg = _cfg("control")  # 2 layers, block_size 32
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = jax.random.PRNGKey(12)
    idx = jax.random.randint(jax.random.PRNGKey(13), (2, 6), 0, cfg.vocab_size)
    out = generate_cached(params, idx, cfg, 50, rng, temperature=0.0)
    assert out.shape == (2, 56)
    np.testing.assert_array_equal(np.asarray(out[:, :6]), np.asarray(idx))
    assert int(out.max()) < cfg.vocab_size and int(out.min()) >= 0
    ref = generate(params, idx, cfg, 50, rng, temperature=0.0)
    # identical while the window still starts at 0 (positions < block_size)
    np.testing.assert_array_equal(
        np.asarray(ref[:, : cfg.block_size]), np.asarray(out[:, : cfg.block_size])
    )


class TestSamplingOptions:
    """temperature/top_k extensions (models/generate.py:sample_token) —
    defaults must be bit-identical to the reference contract."""

    def test_defaults_bit_identical_to_reference_contract(self):
        from differential_transformer_replication_tpu.models.generate import (
            sample_token,
        )

        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 64), jnp.float32)
        key = jax.random.PRNGKey(1)
        ref = jax.random.categorical(key, logits, axis=-1)
        np.testing.assert_array_equal(np.asarray(sample_token(key, logits)),
                                      np.asarray(ref))

    def test_greedy_and_topk(self):
        from differential_transformer_replication_tpu.models.generate import (
            sample_token,
        )

        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 64), jnp.float32)
        key = jax.random.PRNGKey(1)
        # temperature 0 -> argmax; top_k=1 -> argmax regardless of key
        np.testing.assert_array_equal(
            np.asarray(sample_token(key, logits, temperature=0.0)),
            np.asarray(jnp.argmax(logits, -1)),
        )
        np.testing.assert_array_equal(
            np.asarray(sample_token(key, logits, top_k=1)),
            np.asarray(jnp.argmax(logits, -1)),
        )
        # top_k=5: every draw lands in the per-row top-5 set
        topk = jax.lax.top_k(logits, 5)[1]
        for s in range(20):
            draws = sample_token(jax.random.PRNGKey(s), logits, top_k=5)
            for b in range(4):
                assert int(draws[b]) in set(np.asarray(topk[b]).tolist())

    def test_generate_paths_accept_options(self):
        cfg = _cfg("control")
        params = init_model(jax.random.PRNGKey(0), cfg)
        idx = jax.random.randint(jax.random.PRNGKey(5), (2, 4), 0, cfg.vocab_size)
        rng = jax.random.PRNGKey(6)
        g1 = generate(params, idx, cfg, 5, rng, temperature=0.0)
        g2 = generate_cached(params, idx, cfg, 5, rng, temperature=0.0)
        # greedy decode is deterministic, so windowed and cached paths agree
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        g3 = generate(params, idx, cfg, 5, rng, temperature=0.7, top_k=8)
        assert g3.shape == (2, 9)

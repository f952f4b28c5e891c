"""Fused Pallas decode attention + int8 KV cache (ops/decode_attention.py,
models/decode.py pool path, serving wiring).

The load-bearing contracts:

- the fused single-query kernel matches the XLA twin on identical inputs
  for all three combine families, staggered positions, both KV dtypes;
- float-KV greedy decoding through the pallas impl is BIT-IDENTICAL to
  the XLA impl, via ``generate_cached`` AND through the serving engine
  (mixed-length prompts, slot reuse);
- the int8 path is exact between impls on the same quantized cache and
  tolerance-close to the float path; ``quantize_kv`` round-trips within
  half a scale step;
- the engine's zero-recompile pin (decode compiles exactly once) holds
  with the kernel and quantized cache on, across staggered mixed-length
  requests and ring rollover;
- int8 roughly halves KV bytes per slot, asserted via the new
  ``serving_kv_cache_bytes_per_slot`` gauge;
- per-channel int8 weight quantization round-trips within bounds and
  keeps greedy decoding tolerance-close.
"""

import json
import subprocess
import sys
from functools import lru_cache
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from differential_transformer_replication_tpu.config import (
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import (
    generate_cached,
    init_model,
)
from differential_transformer_replication_tpu.models.decode import (
    forward_decode_pool,
    init_cache,
    kv_store_dtype,
)
from differential_transformer_replication_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_reference,
    dequantize_kv,
    quantize_kv,
    quantize_params_int8,
)
from differential_transformer_replication_tpu.serving import ServingEngine

REPO = Path(__file__).resolve().parents[1]
FAMILIES = ("control", "diff", "ndiff")


def _cfg(kind, **kw):
    base = dict(
        model=kind, vocab_size=61, n_embd=32, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


@lru_cache(maxsize=None)
def _setup(kind, **kw):
    cfg = _cfg(kind, **kw)
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _prompts(lens, vocab, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=L).tolist() for L in lens]


def _greedy(params, cfg, prompt, n, **kw):
    out = generate_cached(
        params, jnp.asarray(prompt, jnp.int32)[None], cfg, n,
        jax.random.PRNGKey(0), temperature=0.0, **kw,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# kernel-level parity
# ---------------------------------------------------------------------------


def _rand_case(S, B, H, M, d, dv, kv_dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    qs = jax.random.normal(ks[0], (S, B, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (S, B, H, M, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, M, dv), jnp.float32)
    # staggered positions incl. a partially-filled row and a full ring
    pos = jnp.asarray(
        [(7 * b + 3) % M if b % 2 else M - 1 for b in range(B)], jnp.int32
    )
    coeffs = jax.random.uniform(
        ks[3], (S, H), jnp.float32, minval=-1.0, maxval=1.0
    )
    scales = None
    if kv_dtype == "int8":
        k, ksc = quantize_kv(k)
        v, vsc = quantize_kv(v)
        scales = (ksc, vsc)
    return qs, k, v, pos, coeffs, scales


@pytest.mark.parametrize("kind", FAMILIES)
@pytest.mark.parametrize("kv", ["float", "int8"])
@pytest.mark.parametrize("M", [32, 128], ids=["ring-on-rows", "ring-on-lanes"])
def test_kernel_matches_xla_reference(kind, kv, M):
    """The fused kernel and the materialized-softmax twin agree to fp32
    tile-accumulation noise on identical inputs — per family (S=1/2/N
    combine), staggered per-row positions, both KV dtypes, and both views
    of the pool: a ring of 128 over heads of 16 or 32 is one the chip
    holds with the ring on the lanes (ops/kv_write.py), which the kernel
    reads as (d, M) tiles."""
    S = {"control": 1, "diff": 2, "ndiff": 4}[kind]
    qs, k, v, pos, coeffs, scales = _rand_case(
        S, B=5, H=2, M=M, d=16, dv=16 if kind == "control" else 32,
        kv_dtype=kv,
    )
    if scales is None:
        fused = decode_attention(qs, k, v, pos, coeffs)
        ref = decode_attention_reference(qs, k, v, pos, coeffs)
    else:
        ksc, vsc = scales
        fused = decode_attention(
            qs, k, v, pos, coeffs, k_scale=ksc, v_scale=vsc
        )
        ref = decode_attention_reference(
            qs, dequantize_kv(k, ksc, qs.dtype),
            dequantize_kv(v, vsc, qs.dtype), pos, coeffs,
        )
    assert fused.shape == ref.shape
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_kernel_respects_ring_visibility():
    """A row at position p must ignore cache slots > p: poison the
    invisible tail with huge values and require the output unchanged."""
    qs, k, v, pos, coeffs, _ = _rand_case(
        1, B=1, H=1, M=16, d=8, dv=8, kv_dtype="float"
    )
    pos = jnp.asarray([5], jnp.int32)
    base = decode_attention(qs, k, v, pos, coeffs)
    k_poison = k.at[:, :, :, 6:, :].set(1e4)
    v_poison = v.at[:, :, 6:, :].set(1e4)
    poisoned = decode_attention(qs, k_poison, v_poison, pos, coeffs)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


def test_quantize_kv_roundtrip_bounds():
    """Symmetric per-vector int8: |dequant - x| <= scale/2 elementwise,
    scales carry the vector shape, all-zero vectors stay NaN-free."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 5, 16)) * 7.5
    x = x.at[0, 0, 0].set(0.0)  # all-zero vector must not 0/0
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8
    assert scale.shape == x.shape[:-1]
    back = dequantize_kv(q, scale, jnp.float32)
    assert bool(jnp.isfinite(back).all())
    err = jnp.abs(back - x)
    bound = scale[..., None] * 0.5 + 1e-6
    assert bool((err <= bound).all())
    np.testing.assert_array_equal(np.asarray(back[0, 0, 0]), 0.0)


# ---------------------------------------------------------------------------
# generate_cached parity (pallas vs xla impls)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", FAMILIES)
def test_generate_cached_greedy_bit_parity(kind):
    """Float-KV greedy decoding is bit-identical between the pallas pool
    path and the XLA chunk path for every family (acceptance pin)."""
    cfg, params = _setup(kind)
    prompt = _prompts([9], cfg.vocab_size)[0]
    ref = _greedy(params, cfg, prompt, 8)
    pal = _greedy(
        params, cfg.replace(decode_attention_impl="pallas"), prompt, 8
    )
    assert pal == ref


def test_generate_cached_bf16_greedy_bit_parity():
    """The bf16 storage path ("bf16 stays bit-identical"): same pin at
    bfloat16 compute + forced bf16 KV storage."""
    cfg, params = _setup("control", compute_dtype="bfloat16",
                         kv_cache_dtype="bf16")
    prompt = _prompts([9], cfg.vocab_size)[0]
    ref = _greedy(params, cfg, prompt, 8)
    pal = _greedy(
        params, cfg.replace(decode_attention_impl="pallas"), prompt, 8
    )
    assert pal == ref


@pytest.mark.parametrize("kind", FAMILIES)
def test_generate_cached_int8_parity(kind):
    """int8 KV: both impls read the SAME quantized cache, so greedy
    decoding is bit-identical between them; vs the float cache the
    error is tolerance-bounded — teacher-forced logits stay within the
    quantization noise and greedy trajectories agree for a long prefix
    before (possibly) forking. Token-level agreement AFTER a fork is
    meaningless (a forked sequence diverges everywhere by construction),
    so the gate is (logits tolerance, fork index), not a match
    fraction."""
    from differential_transformer_replication_tpu.models.decode import (
        forward_chunk,
    )

    cfg, params = _setup(kind)
    prompt = _prompts([9], cfg.vocab_size)[0]
    i8 = cfg.replace(kv_cache_dtype="int8")
    ref_i8 = _greedy(params, i8, prompt, 16)
    pal_i8 = _greedy(
        params, i8.replace(decode_attention_impl="pallas"), prompt, 16
    )
    assert pal_i8 == ref_i8
    ref_f = _greedy(params, cfg, prompt, 16)
    first_div = next(
        (i for i, (a, b) in enumerate(zip(ref_i8, ref_f)) if a != b), 16
    )
    assert first_div >= 8, (
        f"int8 forked from float too early: {first_div}"
    )
    ids = jnp.asarray([prompt], jnp.int32)
    l_f, _ = forward_chunk(params, ids, 0, init_cache(cfg, 1), cfg)
    l_q, _ = forward_chunk(params, ids, 0, init_cache(i8, 1), i8)
    np.testing.assert_allclose(
        np.asarray(l_q), np.asarray(l_f), atol=2e-2
    )


def test_ring_rollover_parity_quantized():
    """pos > block_size: the quantized ring cache must roll correctly —
    pallas+int8 bit-matches xla+int8 while the window slides, and the
    fused run stays finite past several rollovers."""
    cfg, params = _setup("control", block_size=16)
    prompt = _prompts([10], cfg.vocab_size)[0]
    n = 30  # 10 + 30 = 2.5x the ring
    i8 = cfg.replace(kv_cache_dtype="int8")
    ref = _greedy(params, i8, prompt, n)
    pal = _greedy(
        params, i8.replace(decode_attention_impl="pallas"), prompt, n
    )
    assert pal == ref
    # and the float pallas path matches the float XLA path out there too
    assert _greedy(
        params, cfg.replace(decode_attention_impl="pallas"), prompt, n
    ) == _greedy(params, cfg, prompt, n)


# ---------------------------------------------------------------------------
# serving engine parity + pins
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", FAMILIES)
def test_engine_greedy_parity_pallas(kind):
    """Mixed-length prompts through a 2-slot pool with the fused kernel
    on produce exactly the tokens the XLA ``generate_cached`` produces —
    the serving-side half of the acceptance pin (slot reuse, queueing,
    per-row positions included)."""
    cfg, params = _setup(kind)
    prompts = _prompts([3, 9, 14, 6, 11], cfg.vocab_size)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=4, prefill_budget=6,
                      decode_attention_impl="pallas"),
    )
    assert eng.cfg.decode_attention_impl == "pallas"  # override applied
    outs = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o.tokens == _greedy(params, cfg, p, 8)
        assert o.finish_reason == "length"


def test_engine_int8_matches_generate_cached_int8():
    """The engine's pallas+int8 decode bit-matches per-request
    ``generate_cached`` under the same quantized-cache config."""
    cfg, params = _setup("diff")
    i8 = cfg.replace(kv_cache_dtype="int8",
                     decode_attention_impl="pallas")
    prompts = _prompts([5, 12, 8], cfg.vocab_size, seed=4)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=4, prefill_budget=8,
                      decode_attention_impl="pallas",
                      kv_cache_dtype="int8"),
    )
    outs = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o.tokens == _greedy(params, i8, p, 8)


def test_engine_decode_compile_pin_pallas_int8():
    """THE zero-recompile pin with the kernel + quantized cache on:
    staggered mixed-length requests (continuous batch composition
    changes every few iterations) compile the decode closure exactly
    once; ring rollover (max_seq_len > block_size) adds no shapes."""
    cfg, params = _setup("control", block_size=16)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(num_slots=3, prefill_chunk=4, prefill_budget=8,
                      max_seq_len=40,
                      decode_attention_impl="pallas",
                      kv_cache_dtype="int8"),
    )
    prompts = _prompts([3, 9, 14, 6, 11, 5], cfg.vocab_size, seed=7)
    done = []
    for i, p in enumerate(prompts):
        # stagger submissions between steps so batch composition churns
        eng.submit(p, max_new_tokens=4 + (i % 3) * 6, temperature=0.0)
        done.extend(eng.step())
    while eng.has_work():
        done.extend(eng.step())
    assert len(done) == len(prompts)
    stats = eng.compile_stats()
    assert stats["decode"] == 1, f"decode recompiled: {stats}"


def test_engine_kv_cache_bytes_gauge_halves_with_int8():
    """The capacity-win assertion: int8 storage (values + fp32 scale
    planes) costs about half the bf16 bytes per slot at real head
    widths, reported through the new gauge; the dtype identity gauge
    names what is active."""
    # d=64 so the fp32 scale plane overhead (4/d) stays small, as at
    # the recipe widths (d=96/128)
    cfg, params = _setup("control", n_embd=128)
    sizes = {}
    for kv in ("bf16", "int8"):
        eng = ServingEngine(
            params, cfg,
            ServingConfig(num_slots=4, kv_cache_dtype=kv),
        )
        g = eng.registry.gauge(
            "serving_kv_cache_bytes_per_slot",
            "HBM bytes of pooled KV-cache state per slot "
            "(includes int8 scale planes when quantized).",
        )
        sizes[kv] = g.value
        # gauge agrees with the actual device buffers
        expect = sum(
            leaf.nbytes for layer in eng.cache for leaf in layer.values()
        ) // 4
        assert sizes[kv] == expect
        dt = eng.registry.gauge(
            "serving_kv_cache_dtype",
            "Active KV-cache storage dtype (constant 1; the identity "
            "rides the label).",
            labelnames=("dtype",),
        )
        assert dt.labels(dtype=kv_store_dtype(eng.cfg)).value == 1
    assert sizes["int8"] <= 0.55 * sizes["bf16"], sizes
    assert sizes["int8"] >= 0.5 * sizes["bf16"]  # scales are not free


def test_forward_decode_pool_matches_per_row_positions():
    """Direct pool-path check: rows at DIFFERENT positions produce the
    same logits as separate forward_chunk calls at those positions."""
    from differential_transformer_replication_tpu.models.decode import (
        forward_chunk,
    )

    cfg, params = _setup("control")
    pal = cfg.replace(decode_attention_impl="pallas")
    B = 3
    rng = np.random.default_rng(9)
    # build per-row caches by prefilling different-length prefixes
    lens = [4, 7, 11]
    pool = init_cache(pal, B)
    toks = np.zeros((B,), np.int32)
    for b, L in enumerate(lens):
        ids = rng.integers(0, cfg.vocab_size, size=L + 1)
        row = init_cache(pal, 1)
        _, row = forward_chunk(
            params, jnp.asarray(ids[None, :L], jnp.int32), 0, row, pal
        )
        for pl_, rl in zip(pool, row):
            for key in pl_:
                axis = 1 if key.startswith("k") else 0
                idx = (slice(None), b) if axis else b
                src = rl[key][:, 0] if axis else rl[key][0]
                pl_[key] = pl_[key].at[idx].set(src)
        toks[b] = ids[L]
    pos = jnp.asarray(lens, jnp.int32)
    logits, _ = jax.jit(forward_decode_pool, static_argnums=(4,))(
        params, jnp.asarray(toks), pos, pool, pal
    )
    for b, L in enumerate(lens):
        rng2 = np.random.default_rng(9)  # regenerate the same ids
        ids = [rng2.integers(0, cfg.vocab_size, size=l + 1)
               for l in lens][b]
        row = init_cache(pal, 1)
        _, row = forward_chunk(
            params, jnp.asarray(ids[None, :L], jnp.int32), 0, row, pal
        )
        ref, _ = forward_chunk(
            params, jnp.asarray([[ids[L]]], jnp.int32), L, row, pal
        )
        np.testing.assert_allclose(
            np.asarray(logits[b]), np.asarray(ref[0, -1]),
            atol=1e-5, rtol=1e-5,
        )


# ---------------------------------------------------------------------------
# int8 weight quantization (load_params_for_inference satellite)
# ---------------------------------------------------------------------------


def test_quantize_params_roundtrip_and_selectivity():
    cfg, params = _setup("diff", n_embd=64)
    q = quantize_params_int8(params)
    # matmul weights changed but stay within half a scale step per
    # output channel; everything else is untouched
    blk = params["blocks"][0]["attn"]
    qblk = q["blocks"][0]["attn"]
    for key in ("wq", "wk", "wv"):
        w, wq = np.asarray(blk[key]), np.asarray(qblk[key])
        assert not np.array_equal(w, wq)
        amax = np.max(np.abs(w), axis=-3, keepdims=True)
        assert np.all(np.abs(w - wq) <= amax / 127.0 * 0.5 + 1e-7)
    w, wq = (np.asarray(params["lm_head"]["w"]),
             np.asarray(q["lm_head"]["w"]))
    amax = np.max(np.abs(w), axis=0, keepdims=True)
    assert np.all(np.abs(w - wq) <= amax / 127.0 * 0.5 + 1e-7)
    np.testing.assert_array_equal(
        np.asarray(params["tok_emb"]), np.asarray(q["tok_emb"])
    )
    np.testing.assert_array_equal(
        np.asarray(params["blocks"][0]["ln1"]["w"]),
        np.asarray(q["blocks"][0]["ln1"]["w"]),
    )
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"]["b"]), np.asarray(q["lm_head"]["b"])
    )


def test_quantized_weights_greedy_tolerance():
    """The --quantize-weights accuracy gate: per-channel int8 weights
    keep greedy decoding near-identical on a small model."""
    cfg, params = _setup("control", n_embd=128)
    q = quantize_params_int8(params)
    prompt = _prompts([9], cfg.vocab_size)[0]
    a = _greedy(params, cfg, prompt, 32)
    b = _greedy(q, cfg, prompt, 32)
    agree = np.mean([x == y for x, y in zip(a, b)])
    assert agree >= 0.9, f"int8 weights drifted too far: {agree}"


def test_load_params_for_inference_quantize_wiring(tmp_path):
    from differential_transformer_replication_tpu.config import TrainConfig
    from differential_transformer_replication_tpu.train.checkpoint import (
        load_params_for_inference,
        save_checkpoint,
    )
    from differential_transformer_replication_tpu.train.step import (
        create_train_state,
    )

    tcfg = TrainConfig(
        model=_cfg("control", vocab_size=31),
        vocab_size=31, control_head_multiplier=1,
    )
    state = create_train_state(jax.random.PRNGKey(0), tcfg)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, state, 1.0, tcfg)
    plain, _, _ = load_params_for_inference(path)
    quant, _, _ = load_params_for_inference(path, quantize="int8")
    assert not np.array_equal(
        np.asarray(plain["lm_head"]["w"]), np.asarray(quant["lm_head"]["w"])
    )
    np.testing.assert_array_equal(
        np.asarray(plain["tok_emb"]), np.asarray(quant["tok_emb"])
    )
    with pytest.raises(ValueError, match="quantization"):
        load_params_for_inference(path, quantize="fp4")


# ---------------------------------------------------------------------------
# config validation + CLI gates
# ---------------------------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError, match="decode_attention_impl"):
        _cfg("control", decode_attention_impl="triton")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _cfg("control", kv_cache_dtype="fp8")
    with pytest.raises(ValueError, match="decode_attention_impl"):
        ServingConfig(decode_attention_impl="triton")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ServingConfig(kv_cache_dtype="fp8")


def test_decode_attn_sweep_smoke():
    """The sweep's --smoke is the tier-1 parity gate for the kernel at
    tiny interpret-mode shapes (one JSON line per case)."""
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "decode_attn_sweep.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 12  # 3 families x 2 dtypes x 2 impls
    assert {ln["impl"] for ln in lines} == {"pallas", "xla"}
    assert all(ln["max_abs_diff"] < 1e-5 for ln in lines)


def test_serve_bench_smoke_fused_int8():
    """serve_bench --smoke with the fused kernel + int8 cache selected:
    completes failure-free, reports the impl/dtype in its JSON line, and
    keeps the measured window recompile-free."""
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "serve_bench.py"),
         "--smoke", "--decode-attention-impl", "pallas",
         "--kv-cache-dtype", "int8"],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[0])
    assert line["decode_attention_impl"] == "pallas"
    assert line["kv_cache_dtype"] == "int8"
    assert line["failed"] == 0
    assert line["compiles_in_window"] == 0

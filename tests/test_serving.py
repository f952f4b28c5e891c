"""Continuous-batching serving engine (serving/).

The load-bearing contract: batched continuous-batching output is
BIT-IDENTICAL to sequential ``generate_cached`` greedy decoding for all
three families on mixed-length prompt sets — the engine is a scheduler
over the same math, never a different model. Plus: slot reuse after
retirement, per-request seed determinism (independent of batch
composition), EOS retirement, scheduler budget/pool invariants, and
jit-stability (no recompilation as requests come and go).
"""

import contextlib
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from functools import lru_cache

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
from differential_transformer_replication_tpu.serving import (
    QueueFullError,
    SamplingParams,
    Scheduler,
    ServingClient,
    ServingEngine,
    serve,
)
from differential_transformer_replication_tpu.serving.scheduler import (
    FREE,
    PREFILL,
)


def _cfg(kind, vocab=61):
    return ModelConfig(
        model=kind, vocab_size=vocab, n_embd=32, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
    )


@lru_cache(maxsize=None)
def _setup(kind, vocab=61):
    cfg = _cfg(kind, vocab)
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _prompts(lens, vocab, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=L).tolist() for L in lens]


def _ref_greedy(params, cfg, prompt, n):
    out = generate_cached(
        params, jnp.asarray(prompt, jnp.int32)[None], cfg, n,
        jax.random.PRNGKey(0), temperature=0.0,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


# one family stays in the quick tier as the representative parity pin;
# the other two ride the full tier (conftest honors explicit slow marks)
@pytest.mark.parametrize("kind", [
    "control",
    pytest.param("diff", marks=pytest.mark.slow),
    pytest.param("ndiff", marks=pytest.mark.slow),
])
def test_batched_greedy_bit_identical_to_generate_cached(kind):
    """Acceptance pin: mixed-length prompts through a 2-slot pool (so
    requests queue and slots are reused) produce exactly the tokens
    sequential per-request generate_cached produces."""
    cfg, params = _setup(kind)
    prompts = _prompts([3, 9, 14, 6, 11], cfg.vocab_size)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(num_slots=2, prefill_chunk=4, prefill_budget=6),
    )
    outs = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o.tokens == _ref_greedy(params, cfg, p, 8)
        assert o.prompt == p  # all in-window: no crop
        assert o.finish_reason == "length"
    # slot reuse + pool invariant: 5 requests through 2 slots
    assert eng.stats["completed"] == 5
    assert eng.scheduler.max_concurrent <= 2
    assert all(s.state == FREE for s in eng.scheduler.slots)


@pytest.mark.parametrize("num_slots", [2, 5], ids=["full_pool", "empty_slots"])
@pytest.mark.parametrize("mixed", [False, True])
def test_a_greedy_batch_skips_the_sort_and_serves_the_same(mixed, num_slots):
    """The sampler sorts the vocabulary only for a batch in which some row
    has a top-k, and draws only where some row has a temperature (a
    ``lax.cond`` each): a greedy request gets the same tokens and log
    probabilities alone (both skipped) and beside a sampling request (both
    run), and the sampling request the same tokens alone and beside it;
    in a pool whose other slots are empty as in one that is full."""
    cfg, params = _setup("control")
    greedy_p, drawn_p = _prompts([7, 5], cfg.vocab_size, seed=8)

    def run(*which):
        eng = ServingEngine(params, cfg, ServingConfig(num_slots=num_slots))
        ids = {}
        if "greedy" in which:
            ids[eng.submit(greedy_p, temperature=0.0, max_new_tokens=6,
                           logprobs=2)] = "greedy"
        if "drawn" in which:
            ids[eng.submit(drawn_p, temperature=0.9, top_k=4, seed=5,
                           max_new_tokens=6)] = "drawn"
        return {ids[o.request_id]: o for o in eng.run()}

    both = run("greedy", "drawn")
    name = "drawn" if mixed else "greedy"
    alone = run(name)[name]
    assert alone.tokens == both[name].tokens
    if not mixed:
        assert alone.tokens == _ref_greedy(params, cfg, greedy_p, 6)
        assert alone.token_logprobs == both[name].token_logprobs
        assert alone.top_logprobs == both[name].top_logprobs


class _SpanArgs:
    """The tracer's interface, keeping every span's name and arguments
    (what a recording tracer is handed; nothing is timed)."""

    def __init__(self):
        self.spans = []

    def span(self, name, **args):
        self.spans.append((name, args))
        return contextlib.nullcontext()

    def instant(self, name, **args):
        pass

    def complete(self, name, t0, t1, **args):
        pass

    def flush(self):
        pass

    def close(self):
        pass

    def sampler_calls(self):
        return [args for name, args in self.spans
                if name == "sample_operands"]


# the 61 tokens as one character each, so a regex over a-f is an FSM over
# six of them
_LETTERS = [chr(ord("a") + i) if i < 26 else "" for i in range(61)]

# what a request may ask of the sampler beyond an argmax, one of each
_ASKERS = {
    "logprobs": dict(temperature=0.0, logprobs=2),
    "drawn": dict(temperature=0.9, top_k=4, seed=5),
    "fsm": dict(temperature=0.0, regex="[a-f]{6}"),
    "penalty": dict(temperature=0.0, repetition_penalty=1.7),
}


def _echo(out):
    return out.tokens, out.token_logprobs, out.top_logprobs


def test_a_plain_batch_in_a_pool_with_empty_slots_takes_the_plain_arm():
    """Two greedy requests that ask nothing else in a pool of six: every
    sampler call, the pool-wide ones with their four empty rows too, is
    reported ``plain``, and the tokens are ``generate_cached``'s."""
    cfg, params = _setup("control")
    prompts = _prompts([7, 5], cfg.vocab_size, seed=8)
    rec = _SpanArgs()
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=6), tracer=rec)
    outs = eng.generate(prompts, max_new_tokens=6, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o.tokens == _ref_greedy(params, cfg, p, 6)
    calls = rec.sampler_calls()
    assert {c["path"] for c in calls} == {"prefill", "decode"}
    assert all(c["rows"] == 6 and c["active"] == 2
               for c in calls if c["path"] == "decode")
    assert all(c["plain"] == 1 and c["asking"] == 0 for c in calls)


def test_an_empty_slot_asks_the_sampler_for_nothing():
    """The packed operand of a pool-wide call: a row the call does not
    name is false under every predicate the program reads (no ``ASK_*``
    bit, no temperature, no top-k), whatever the named rows ask."""
    from differential_transformer_replication_tpu.serving import engine as E

    cfg, params = _setup("control")
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=4))
    eng.submit(_prompts([5], cfg.vocab_size)[0], max_new_tokens=4,
               temperature=0.7, top_k=3, logprobs=1, presence_penalty=0.5)
    eng.step()  # the prompt is in: slot 0 is active, three are empty
    (slot,) = eng.scheduler.active_slots()
    ints, mask, hist = eng._sample_operands([(slot.index, slot)], 4)
    assert ints.shape == (4, 9) and mask is None and hist is not None
    named, rest = ints[slot.index], np.delete(ints, slot.index, axis=0)
    assert named[8] == (E.ASK_PENALTY | E.ASK_LOGPROBS | E.ASK_TEMPERATURE)
    assert named[1] == 3 and named[4:5].view(np.float32)[0] > 0
    assert not rest[:, 8].any() and not rest[:, 1].any()
    assert (rest[:, 4:5].view(np.float32) <= 0).all()


@pytest.mark.parametrize("mixed", [False, True], ids=["plain", "asker"])
@pytest.mark.parametrize("asker", list(_ASKERS))
def test_a_plain_request_and_one_that_asks_serve_the_same_alone_and_together(
        asker, mixed):
    """A request that asks nothing serves the same tokens alone (every
    call the plain arm) and beside one with log probabilities, with a
    temperature and a top-k, with an FSM or with a penalty (the full
    arm); and each of those the same tokens and the same echo alone and
    beside it."""
    cfg, params = _setup("control")
    plain_p, asker_p = _prompts([7, 5], cfg.vocab_size, seed=8)

    def run(*which):
        rec = _SpanArgs()
        eng = ServingEngine(params, cfg, ServingConfig(num_slots=4),
                            vocab=_LETTERS, tracer=rec)
        ids = {}
        if "plain" in which:
            ids[eng.submit(plain_p, temperature=0.0,
                           max_new_tokens=6)] = "plain"
        if "asker" in which:
            ids[eng.submit(asker_p, max_new_tokens=6,
                           **_ASKERS[asker])] = "asker"
        outs = {ids[o.request_id]: o for o in eng.run()}
        return outs, rec.sampler_calls(), eng._sample_fn._cache_size()

    both, calls, programs = run("plain", "asker")
    # the asker's rows make their calls the full arm; the plain request's
    # first token is a call of its own
    assert {c["plain"] for c in calls if c["path"] == "decode"} == {0}
    assert sorted(c["plain"] for c in calls if c["path"] == "prefill") == [
        0, 1]
    name = "asker" if mixed else "plain"
    alone, calls, programs_after = run(name)
    assert {c["plain"] for c in calls} == {0 if mixed else 1}
    # another mix of what is asked is another arm, not another program
    assert programs_after == programs
    assert _echo(alone[name]) == _echo(both[name])
    if not mixed:
        assert alone[name].tokens == _ref_greedy(params, cfg, plain_p, 6)
        assert alone[name].token_logprobs is None
    if asker == "logprobs":
        assert len(both["asker"].token_logprobs) == 6
        assert all(lp < 0 for lp in both["asker"].token_logprobs)
    if asker == "fsm":
        assert set(both["asker"].tokens) <= {
            _LETTERS.index(c) for c in "abcdef"}


def _ref_sampled(params, cfg, prompt, n, seed, temperature, top_k=None):
    """The single-request contract a sampled chain is held to: token t
    drawn by ``sample_token`` with the key ``fold_in(PRNGKey(seed), t)``
    (models/generate.py)."""
    from differential_transformer_replication_tpu.models.decode import (
        forward_chunk,
        init_cache,
    )
    from differential_transformer_replication_tpu.models.generate import (
        sample_token,
    )

    base = jax.random.PRNGKey(seed)
    cache = init_cache(cfg, 1)
    logits, cache = forward_chunk(
        params, jnp.asarray(prompt, jnp.int32)[None], 0, cache, cfg,
        rope_len=cfg.block_size,
    )
    toks = []
    for t in range(n):
        key = jax.random.fold_in(base, t)
        tok = int(sample_token(
            key, logits[:, -1].astype(jnp.float32), temperature, top_k
        )[0])
        toks.append(tok)
        if t < n - 1:
            logits, cache = forward_chunk(
                params, jnp.asarray([[tok]], jnp.int32), len(prompt) + t,
                cache, cfg, rope_len=cfg.block_size,
            )
    return toks


def test_a_temperature_without_logprobs_draws_the_same_and_asks_no_echo():
    """What most deployments send: a temperature and nothing else. The
    tokens are ``sample_token``'s chain (what the sampler served before
    its arms), no call asks for the echo, and a request that does ask
    for it draws the same tokens."""
    cfg, params = _setup("control")
    prompt = _prompts([5], cfg.vocab_size, seed=4)[0]

    def run(**kw):
        rec = _SpanArgs()
        eng = ServingEngine(params, cfg, ServingConfig(num_slots=3),
                            tracer=rec)
        out = eng.generate([prompt], temperature=0.8, seed=11,
                           max_new_tokens=6, **kw)[0]
        return out, rec.sampler_calls()

    out, calls = run()
    assert out.tokens == _ref_sampled(params, cfg, prompt, 6, 11, 0.8)
    assert out.token_logprobs is None
    assert all(c["tempered"] == 1 and c["logprobs"] == 0 and c["plain"] == 0
               for c in calls)
    echoed, calls = run(logprobs=1)
    assert echoed.tokens == out.tokens
    assert all(c["logprobs"] == 1 for c in calls)
    assert all(lp < 0 for lp in echoed.token_logprobs)


@pytest.mark.parametrize("row", ["active", "empty"])
def test_nan_logits_on_an_active_row_raise_through_the_plain_arm(row):
    """The finiteness flag is part of the plain arm's result: NaN logits
    on an active row of a batch that asks nothing still raise the typed
    ``EngineCrashError``; on an empty slot's row they are nobody's and
    the tokens are served."""
    from differential_transformer_replication_tpu.serving import (
        EngineCrashError,
    )

    cfg, params = _setup("control")
    prompt = _prompts([6], cfg.vocab_size, seed=9)[0]
    rec = _SpanArgs()
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=3), tracer=rec)
    eng.submit(prompt, max_new_tokens=5, temperature=0.0)
    eng.step()  # the prompt and its first token
    (slot,) = eng.scheduler.active_slots()
    poisoned = slot.index if row == "active" else (slot.index + 1) % 3
    decode = eng._decode_fn

    def corrupt(*args):
        logits, *rest = decode(*args)
        return (logits.at[poisoned].set(jnp.nan), *rest)

    eng._decode_fn = corrupt
    if row == "active":
        # raised where the corrupted step's row is READ (an iteration
        # after its dispatch), naming slot and request, and nothing of
        # that step delivered: the request holds its first token and
        # the token of the sound step that was in flight, no third
        rid = slot.request.request_id
        with pytest.raises(
            EngineCrashError,
            match=rf"decoding slot\(s\) \[{slot.index}\] "
                  rf"\(request\(s\) \[{rid}\]\)",
        ):
            eng.run()
        assert slot.generated == _ref_greedy(params, cfg, prompt, 2)
        assert eng.take_finished() == []
    else:
        (out,) = eng.run()
        assert out.tokens == _ref_greedy(params, cfg, prompt, 5)
    assert all(c["plain"] == 1 for c in rec.sampler_calls())


@pytest.mark.parametrize("shape", [(6, 61), (16, 1000)])
def test_the_plain_arm_is_the_full_arm_for_rows_that_ask_nothing(shape):
    """The jitted sampler itself on random logits (a NaN, an infinity
    and a tie among them): for operands that ask nothing the plain arm's
    token and finite columns equal the full arm's, which the same rows
    take when one more row of the call asks for its log probabilities;
    the plain arm's echo columns are zeros, the asking row's are not."""
    from differential_transformer_replication_tpu.serving import engine as E

    B, V = shape
    sample = E._build_step_fns(_cfg("control", V), 32)[2]
    rng = np.random.default_rng(B)
    logits = rng.normal(size=(B, V)).astype(np.float32)
    logits[1, 3] = np.nan
    logits[2, 5] = np.inf
    logits[3, [7, 2]] = logits[3].max() + 1.0  # a tie: the lower index
    ints = np.zeros((B, 9), np.int32)
    ints[:, 4:8].view(np.float32)[:, 1] = 1.0  # repetition penalty off
    ints[:-1, 1] = rng.integers(0, 4, size=B - 1)  # inert on a greedy row
    ones, zeros = jnp.ones((B, V), bool), jnp.zeros((B, V), jnp.int32)
    plain = np.asarray(sample(jnp.asarray(ints), jnp.asarray(logits),
                              ones, zeros))
    programs = sample._cache_size()
    asking = ints.copy()
    asking[-1, 8] = E.ASK_LOGPROBS
    full = np.asarray(sample(jnp.asarray(asking), jnp.asarray(logits),
                             ones, zeros))
    assert (plain[:, :2] == full[:, :2]).all()
    assert plain[3, 0] == 2 and plain[2, 0] == 5
    assert plain[:, 1].tolist() == [1, 0, 0] + [1] * (B - 3)
    assert not plain[:, 2:].any()
    assert full[-1, 2:3].view(np.float32)[0] < 0
    # a temperature alone: the full arm draws, and leaves the echo out
    drawn = ints.copy()
    drawn[:, 4:5].view(np.float32)[:] = 0.8
    drawn[:, 8] = E.ASK_TEMPERATURE
    out = np.asarray(sample(jnp.asarray(drawn), jnp.asarray(logits),
                            ones, zeros))
    assert not out[:, 2:].any()
    drawn[0, 8] |= E.ASK_LOGPROBS
    echoed = np.asarray(sample(jnp.asarray(drawn), jnp.asarray(logits),
                               ones, zeros))
    assert (echoed[:, 0] == out[:, 0]).all() and echoed[:, 2:].any()
    assert sample._cache_size() == programs  # one, whatever was asked


@pytest.mark.slow
def test_long_prompt_crop_and_rolling_decode_parity():
    """RoPE families crop prompts > block_size to the last block_size ids
    (the reference's own semantics, control.py:165) and roll the ring
    cache past block_size during decode — both bit-matching
    generate_cached."""
    cfg, params = _setup("control")
    eng = ServingEngine(
        params, cfg,
        ServingConfig(
            num_slots=3, prefill_chunk=8, prefill_budget=16, max_seq_len=64,
        ),
    )
    long_p, short_p = _prompts([40, 5], cfg.vocab_size, seed=2)
    outs = eng.generate([long_p, short_p], max_new_tokens=10, temperature=0.0)
    assert outs[0].tokens == _ref_greedy(params, cfg, long_p, 10)
    assert outs[0].prompt == long_p[-cfg.block_size:]  # cropped echo
    assert outs[1].tokens == _ref_greedy(params, cfg, short_p, 10)


@pytest.mark.slow
def test_per_request_seed_determinism_across_batch_compositions():
    """Sampled output is a function of (params, prompt, sampling params)
    only — the key chain fold_in(PRNGKey(seed), t) must not see slot
    assignment, pool size, or admission order."""
    cfg, params = _setup("control")
    reqs = list(zip(_prompts([4, 9, 6], cfg.vocab_size, seed=3), [7, 7, 99]))

    def run(num_slots, order):
        eng = ServingEngine(
            params, cfg,
            ServingConfig(num_slots=num_slots, prefill_chunk=4,
                          prefill_budget=4),
        )
        ids = {}
        for i in order:
            p, seed = reqs[i]
            ids[eng.submit(p, temperature=1.0, top_k=5, seed=seed,
                           max_new_tokens=6)] = i
        return {ids[o.request_id]: o.tokens for o in eng.run()}

    a = run(1, [0, 1, 2])
    b = run(3, [2, 0, 1])
    assert a == b
    assert all(len(t) == 6 for t in a.values())
    # and every draw is a valid token id
    assert all(0 <= tok < cfg.vocab_size for t in a.values() for tok in t)


@pytest.mark.slow
def test_sampled_chain_matches_sample_token_reference():
    """The engine's batched sampler must be bit-identical, token for
    token, to the single-request sample_token contract with the same
    fold_in key chain (models/generate.py)."""
    cfg, params = _setup("control")
    prompt = _prompts([5], cfg.vocab_size, seed=4)[0]
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=2))
    out = eng.generate(
        [prompt], temperature=1.0, top_k=5, seed=11, max_new_tokens=6
    )[0]
    assert out.tokens == _ref_sampled(params, cfg, prompt, 6, 11, 1.0, 5)


def test_eos_retires_slot_early_without_stalling_batch():
    cfg, params = _setup("control")
    prompts = _prompts([5, 8], cfg.vocab_size, seed=5)
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=2))
    ref = eng.generate(prompts, max_new_tokens=6, temperature=0.0)
    first_tok = ref[0].tokens[0]

    eng2 = ServingEngine(params, cfg, ServingConfig(num_slots=2))
    a = eng2.submit(prompts[0], max_new_tokens=6, temperature=0.0,
                    eos_token_id=first_tok)
    b = eng2.submit(prompts[1], max_new_tokens=6, temperature=0.0)
    outs = {o.request_id: o for o in eng2.run()}
    assert outs[a].tokens == [first_tok]
    assert outs[a].finish_reason == "eos"
    # the other sequence is unaffected by the early retirement
    assert outs[b].tokens == ref[1].tokens
    assert outs[b].finish_reason == "length"


def test_submit_validation():
    cfg, params = _setup("diff")
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=1))
    with pytest.raises(ValueError):  # diff cannot roll past block_size
        eng.submit(list(range(30)), max_new_tokens=10)
    with pytest.raises(ValueError):
        eng.submit([], max_new_tokens=4)
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=0)

    ccfg, cparams = _setup("control")
    ceng = ServingEngine(cparams, ccfg, ServingConfig(num_slots=1))
    with pytest.raises(ValueError):  # past the engine's RoPE table
        ceng.submit(list(range(30)), max_new_tokens=10)


def test_decode_stays_jit_stable_as_requests_come_and_go():
    """Acceptance pin: a first wave compiles everything (decode step,
    prefill ladder, samplers); a second wave with different lengths,
    seeds, sampling params and admission patterns must not add a single
    cache entry, and the decode step must have compiled exactly once."""
    cfg, params = _setup("control", vocab=53)  # fresh compile-cache key
    serving = ServingConfig(num_slots=3, prefill_chunk=8, prefill_budget=8)
    eng = ServingEngine(params, cfg, serving)
    eng.generate(
        _prompts([1, 3, 9, 14], cfg.vocab_size, seed=6),
        max_new_tokens=4, temperature=0.0,
    )
    baseline = eng.compile_stats()
    assert baseline["decode"] == 1
    # ladder {8,4,2,1} -> at most 4 prefill shapes; first-token + pool
    # samplers -> at most 2
    assert baseline["prefill"] <= 4
    assert baseline["sample"] <= 2

    eng2 = ServingEngine(params, cfg, serving)  # same config: shared jits
    outs = eng2.generate(
        _prompts([2, 13, 7, 14, 5, 10, 1], cfg.vocab_size, seed=7),
        max_new_tokens=6, temperature=0.8, top_k=3, seed=42,
    )
    assert len(outs) == 7
    assert eng2.compile_stats() == baseline  # zero new compiles


class TestScheduler:
    """Host-side scheduling policy in isolation (no device work)."""

    def _sched(self, **kw):
        return Scheduler(ServingConfig(**kw))

    def _submit(self, sched, lens):
        from differential_transformer_replication_tpu.serving.request import (
            Request,
        )

        for i, L in enumerate(lens):
            sched.submit(
                Request.make(i, [1] * L), np.ones(L, np.int32), 0.0
            )

    def test_admission_is_fcfs_and_bounded_by_pool(self):
        s = self._sched(num_slots=2, prefill_chunk=8, prefill_budget=64)
        self._submit(s, [4, 4, 4])
        s.plan()
        assert s.occupied() == 2  # third request waits
        assert [sl.request.request_id
                for sl in s.slots if sl.state != FREE] == [0, 1]
        assert s.max_concurrent == 2

    def test_prefill_budget_caps_tokens_per_iteration(self):
        s = self._sched(num_slots=2, prefill_chunk=8, prefill_budget=8)
        self._submit(s, [16, 16])
        chunks = s.plan()
        assert sum(c[2] for c in chunks) <= 8
        assert all(c[0].index == chunks[0][0].index for c in chunks)  # FCFS
        for slot, start, size in chunks:
            slot.filled = start + size
        chunks = s.plan()  # budget renews each iteration
        assert sum(c[2] for c in chunks) <= 8

    def test_chunks_come_from_power_of_two_ladder(self):
        s = self._sched(num_slots=1, prefill_chunk=8, prefill_budget=64)
        self._submit(s, [13])
        sizes = [c[2] for c in s.plan()]
        assert sizes == [8, 4, 1]
        assert all(sz & (sz - 1) == 0 for sz in sizes)

    @pytest.mark.parametrize("length, budget, limit, want", [
        (5, 64, 64, [(0, 5)]),               # the tail in one padded chunk
        (21, 64, 64, [(0, 8), (8, 8), (16, 5)]),  # whole chunks, then the tail
        (16, 64, 64, [(0, 8), (8, 8)]),      # nothing to pad
        (13, 12, 64, [(0, 8), (8, 4)]),      # the padded shape (16) is over budget
        (13, 64, 12, [(0, 8), (8, 4), (12, 1)]),  # ... or past the ring's end
        (13, 64, 0, [(0, 8), (8, 4), (12, 1)]),   # a family that takes none
    ])
    def test_a_tail_is_one_padded_chunk_where_it_fits(self, length, budget,
                                                      limit, want):
        s = Scheduler(ServingConfig(num_slots=1, prefill_chunk=8,
                                    prefill_budget=budget), pad_limit=limit)
        self._submit(s, [length])
        assert [(start, size) for _, start, size in s.plan()] == want

    def test_a_padded_tail_is_charged_its_padded_shape(self):
        s = Scheduler(ServingConfig(num_slots=2, prefill_chunk=8,
                                    prefill_budget=12), pad_limit=64)
        self._submit(s, [5, 5])  # 8 of the budget, then 4 are left
        assert [(c[0].index, c[1], c[2]) for c in s.plan()] == [
            (0, 0, 5), (1, 0, 4)]

    @pytest.mark.parametrize("freed", [0, 2, 4])
    def test_admission_takes_the_lowest_free_slot(self, freed):
        """What the decode attention's bound leans on (models/decode.py
        ``attend_rows``: it reads the pool up to the highest active
        slot): with slots 0..4 of 8 held and one of them freed, the next
        request goes into the freed slot, not past the held ones, so the
        live slots keep to the low indices."""
        s = self._sched(num_slots=8, prefill_chunk=8, prefill_budget=64)
        self._submit(s, [4] * 5)
        s.plan()
        assert [sl.index for sl in s.slots if sl.state != FREE] == [
            0, 1, 2, 3, 4]
        s.retire(s.slots[freed])
        assert s.slots[freed].state == FREE
        from differential_transformer_replication_tpu.serving.request import (
            Request,
        )

        s.submit(Request.make(99, [1] * 4), np.ones(4, np.int32), 0.0)
        s.plan()
        assert s.slots[freed].request.request_id == 99
        assert [sl.index for sl in s.slots if sl.state != FREE] == [
            0, 1, 2, 3, 4]

    def test_retire_frees_slot_for_next_request(self):
        s = self._sched(num_slots=1, prefill_chunk=8, prefill_budget=8)
        self._submit(s, [4, 4])
        s.plan()
        slot = s.slots[0]
        assert slot.state == PREFILL and slot.request.request_id == 0
        s.retire(slot)
        s.plan()
        assert slot.request.request_id == 1
        assert s.max_concurrent == 1

    def test_queue_bound_rejects_fast(self):
        """max_queue_len: the (max+1)-th WAITING request is rejected
        immediately — overload degrades into fast retryable errors, not
        an unbounded queue."""
        s = self._sched(num_slots=1, max_queue_len=2)
        self._submit(s, [4, 4])
        with pytest.raises(QueueFullError, match="admission queue full"):
            self._submit(s, [4])
        # draining the queue re-opens admission
        s.plan()  # admits request 0 into the slot; queue drops to 1
        self._submit(s, [4])
        assert s.queue_len() == 2

    def test_cancel_queued_and_slotted(self):
        s = self._sched(num_slots=1, prefill_chunk=8, prefill_budget=8)
        self._submit(s, [4, 4])
        s.plan()  # req 0 -> slot, req 1 queued
        assert s.cancel(1) is True  # dropped from the queue
        assert s.queue_len() == 0
        assert s.cancel(0) is True  # slot retired back to the pool
        assert s.slots[0].state == FREE
        assert s.cancel(99) is False  # unknown

    def test_unbounded_by_default(self):
        s = self._sched(num_slots=1)
        self._submit(s, [4] * 50)
        assert s.queue_len() == 50


class _StubEngine:
    """Never-finishing engine: requests pile up in a fake queue so the
    runner-level admission bound and cancel plumbing are testable
    without device work."""

    def __init__(self, max_queue_len):
        self.serving = ServingConfig(num_slots=1, max_queue_len=max_queue_len)
        self.queue = []
        self.stats = {"rejected": 0, "cancelled": 0}
        self._next = 0

    def queue_len(self):
        return len(self.queue)

    def has_work(self):
        return bool(self.queue)

    def submit(self, prompt, params=None):
        if (
            self.serving.max_queue_len
            and len(self.queue) >= self.serving.max_queue_len
        ):
            self.stats["rejected"] += 1
            raise QueueFullError("admission queue full")
        rid = self._next
        self._next += 1
        self.queue.append(rid)
        return rid

    def cancel(self, rid):
        if rid in self.queue:
            self.queue.remove(rid)
            self.stats["cancelled"] += 1
            return True
        return False

    def step(self):
        import time as _t

        _t.sleep(0.005)  # never finishes anything; don't spin hot
        return []


class TestRunnerOverloadAndCancel:
    def test_runner_rejects_when_queue_full(self):
        from differential_transformer_replication_tpu.serving.server import (
            EngineRunner,
        )

        runner = EngineRunner(_StubEngine(max_queue_len=2))
        last = None
        try:
            handles = [runner.submit([1], max_new_tokens=4) for _ in range(2)]
            # give the runner time to move them into the engine queue
            deadline = time.time() + 5
            while runner.engine.queue_len() < 2 and time.time() < deadline:
                time.sleep(0.01)
            with pytest.raises(QueueFullError):
                runner.submit([1], max_new_tokens=4)
            assert runner.engine.stats["rejected"] >= 1
            # cancelling a queued request reopens admission
            runner.cancel(handles[0])
            deadline = time.time() + 5
            while runner.engine.queue_len() > 1 and time.time() < deadline:
                time.sleep(0.01)
            last = runner.submit([1], max_new_tokens=4)
        finally:
            # wait for the hand-off deque to flush before clearing the
            # stub queue, or the last submit re-populates it after the
            # clear and close() (which drains) times out on the
            # never-finishing stub
            deadline = time.time() + 10
            while last is not None and last.rid is None \
                    and time.time() < deadline:
                time.sleep(0.01)
            runner.engine.queue.clear()  # let close() drain
            runner.close()

    def test_timeout_cancels_before_engine_admission(self):
        """A request cancelled while still in the hand-off deque never
        reaches the engine at all."""
        from differential_transformer_replication_tpu.serving.server import (
            EngineRunner,
        )

        eng = _StubEngine(max_queue_len=0)
        runner = EngineRunner(eng)
        try:
            blocker = runner.submit([1], max_new_tokens=4)
            with pytest.raises(TimeoutError):
                runner.generate([2], max_new_tokens=4, timeout=0.01)
            # steady state either way: the timed-out request never hit
            # the engine (dropped from the hand-off deque) or was
            # cancelled out of its queue — only the blocker remains
            deadline = time.time() + 10
            while time.time() < deadline and eng.queue != [0]:
                time.sleep(0.01)
            assert eng.queue == [0] and blocker.rid == 0
        finally:
            eng.queue.clear()
            runner.close()


def test_engine_cancel_reclaims_slot_mid_decode():
    """The slot-leak fix at the engine level: cancelling an ACTIVE
    request frees its KV slot for the next admission instead of decoding
    to completion for nobody."""
    cfg, params = _setup("control")
    eng = ServingEngine(
        params, cfg, ServingConfig(num_slots=1, prefill_chunk=8,
                                   prefill_budget=8),
    )
    a = eng.submit(_prompts([5], cfg.vocab_size, seed=9)[0],
                   max_new_tokens=24, temperature=0.0)
    b = eng.submit(_prompts([4], cfg.vocab_size, seed=10)[0],
                   max_new_tokens=4, temperature=0.0)
    for _ in range(3):  # a occupies the only slot and starts decoding
        eng.step()
    assert eng.scheduler.slots[0].request.request_id == a
    assert eng.cancel(a) is True
    assert eng.scheduler.slots[0].state == FREE
    outs = eng.run()  # b admits into the freed slot and completes
    assert [o.request_id for o in outs] == [b]
    assert len(outs[0].tokens) == 4
    assert eng.stats["cancelled"] == 1
    assert eng.cancel(b) is False  # already finished
    # the interrupted slot leaves no residue: a fresh request matches
    # the reference decode bit-for-bit (ring-mask invariant)
    p = _prompts([6], cfg.vocab_size, seed=11)[0]
    out = eng.generate([p], max_new_tokens=6, temperature=0.0)[0]
    assert out.tokens == _ref_greedy(params, cfg, p, 6)


def test_client_timeout_cancels_and_slot_is_reused():
    """End-to-end slot-leak regression: a client timeout cancels the
    request in the engine (KV slot + queue entry reclaimed) and later
    requests still complete on the single slot."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg, ServingConfig(num_slots=1, prefill_chunk=8,
                                   prefill_budget=8),
    ))
    try:
        with pytest.raises(TimeoutError):
            # tiny timeout: compilation alone exceeds it
            client.generate(_prompts([5], cfg.vocab_size, seed=12)[0],
                            max_new_tokens=24, timeout=0.01)
        p = _prompts([4], cfg.vocab_size, seed=13)[0]
        out = client.generate(p, max_new_tokens=4, temperature=0.0,
                              timeout=120)
        assert out.tokens == _ref_greedy(params, cfg, p, 4)
        deadline = time.time() + 30
        while time.time() < deadline and client.runner.engine.has_work():
            time.sleep(0.02)
        stats = client.stats
        assert stats["cancelled"] == 1
        assert not client.runner.engine.has_work()  # nothing decodes for nobody
    finally:
        client.close()


@pytest.mark.slow
def test_http_503_when_admission_queue_full():
    """Overload over HTTP: with a 1-slot pool and max_queue_len=1, a
    burst of 3 concurrent /generate calls gets at least one 503 and the
    accepted requests still complete; the server keeps serving after."""
    cfg, params = _setup("control")
    client = ServingClient(ServingEngine(
        params, cfg,
        ServingConfig(num_slots=1, prefill_chunk=8, prefill_budget=8,
                      max_queue_len=1),
    ))
    httpd = serve(client, port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        codes = []
        lock = threading.Lock()
        # a true simultaneous burst: all three requests hit /generate
        # within ~a millisecond, far faster than one 24-token decode can
        # finish, so the 1-slot + 1-queue server MUST shed at least one
        barrier = threading.Barrier(3)

        def post():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps({
                    "prompt_ids": _prompts([5], cfg.vocab_size, seed=14)[0],
                    "max_new_tokens": 24, "temperature": 0.0,
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            barrier.wait(timeout=30)
            try:
                with urllib.request.urlopen(req, timeout=300) as r:
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            with lock:
                codes.append(code)

        threads = [threading.Thread(target=post) for _ in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        assert codes.count(200) >= 1, codes
        assert codes.count(503) >= 1, codes
        # the server is still healthy after shedding load
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30
        ) as r:
            health = json.load(r)
        assert health["ok"]
        assert health["stats"]["rejected"] >= 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        client.close()


@pytest.mark.slow
def test_serving_client_and_http_server():
    """The concurrency boundary: many caller threads, one engine thread;
    and the stdlib HTTP endpoint end-to-end on an ephemeral port."""
    cfg, params = _setup("control")
    prompts = _prompts([5, 9, 3, 12], cfg.vocab_size, seed=8)
    refs = [_ref_greedy(params, cfg, p, 6) for p in prompts]

    client = ServingClient(ServingEngine(
        params, cfg, ServingConfig(num_slots=2, prefill_chunk=4,
                                   prefill_budget=8),
    ))
    try:
        # concurrent programmatic callers
        outs = client.generate_batch(
            prompts, max_new_tokens=6, temperature=0.0, timeout=120
        )
        assert [o.tokens for o in outs] == refs

        httpd = serve(client, port=0)  # ephemeral port
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps({
                    "prompt_ids": prompts[0], "max_new_tokens": 6,
                    "temperature": 0.0,
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                body = json.load(r)
            assert body["tokens"] == refs[0]
            assert body["finish_reason"] == "length"
            assert body["ttft_ms"] >= 0

            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=30
            ) as r:
                health = json.load(r)
            assert health["ok"] and health["stats"]["completed"] >= 5

            # invalid request -> 400, server stays up
            bad = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate", data=b"{}",
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(bad, timeout=30)
            assert ei.value.code == 400
        finally:
            httpd.shutdown()
            httpd.server_close()
    finally:
        client.close()


def test_serve_bench_smoke():
    """Acceptance pin: the --smoke bench completes with rc=0 under
    JAX_PLATFORMS=cpu and reports req/s, output tok/s and TTFT/ITL
    percentiles as a single JSON line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # no need for the 8-device mesh here
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "tools",
                      "serve_bench.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metric"] == "serving_output_tokens_per_sec"
    assert line["value"] > 0
    assert line["requests_per_sec"] > 0
    assert line["n_requests"] == 8
    for section in ("ttft_ms", "itl_ms"):
        assert line[section]["p50"] is not None
        assert line[section]["p95"] >= line[section]["p50"]
    # error breakdown (serving resilience PR): failures are reported by
    # type instead of silently folded into the latency stats
    assert line["failed"] == 0
    assert line["retries"] == 0
    assert set(line["errors"]) == {
        "queue_full", "engine_crash", "deadline", "timeout",
        "shutting_down", "other",
    }
    assert all(v == 0 for v in line["errors"].values())


# -- the late read (ISSUE 41): the host reads a sampled token one iteration
# -- after the program that made it was dispatched ----------------------------


def _reads_first_always(eng):
    """The engine's own predicate patched so that every iteration reads
    what is in flight before it builds its step (the order of every
    iteration before the late read): no option of the engine does that."""
    eng._reads_first = lambda rows, capturing: "test"
    return eng


def _jamba():
    """A toy of the hybrid family with a recurrent state a slot, with the
    reference's weights (tests/test_jamba.py: with a fixed 0.02 a toy only
    repeats its last token and a state read across requests goes unseen)."""
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import reference_jamba

    toy = dict(model="jamba", vocab_size=61, n_embd=32, n_head=2, kv_heads=1,
               n_layer=2, block_size=32, ffn_hidden=48, norm_eps=1e-6,
               tie_embeddings=True, attn_layer_period=2, attn_layer_offset=1,
               mamba_d_state=8, mamba_d_conv=4, mamba_expand=2,
               mamba_dt_rank=4, compute_dtype="float32",
               param_dtype="float32")
    return ModelConfig(**toy), reference_jamba.make_params(7, toy)


@lru_cache(maxsize=None)
def _family(kind):
    return _jamba() if kind == "jamba" else _setup(kind)


def _pool(pool, **kw):
    return ServingConfig(**dict(
        dict(num_slots=3, prefill_chunk=4, prefill_budget=8), **kw,
        **({"kv_page_size": 8} if pool == "paged" else {})))


# a family with a recurrent state refuses paging by name
_DEPTH_CASES = [("diff", "contiguous"), ("diff", "paged"),
                ("control", "contiguous"), ("control", "paged"),
                ("jamba", "contiguous")]


def _streams(outs):
    return [(o.tokens, o.finish_reason, o.token_logprobs, o.top_logprobs)
            for o in outs]


@pytest.mark.parametrize("sampling", ["greedy", "tempered"])
@pytest.mark.parametrize("family,pool", _DEPTH_CASES)
def test_both_depths_serve_identical_streams(family, pool, sampling):
    """The same requests through the late read and through a read before
    every step give identical tokens, finish reasons and log
    probabilities: more requests than slots (slots are reused), answers
    of 1 and 2 tokens (a row that ends by length with its token in flight
    is known beforehand and left out of the next step), log probabilities
    on some; with a temperature the key chain follows the tokens
    DISPATCHED. The compiled programs of one depth serve the other."""
    cfg, params = _family(family)
    prompts = _prompts([5, 9, 3, 12, 7, 4], cfg.vocab_size, seed=41)
    asks = [SamplingParams(
        max_new_tokens=n, logprobs=lp, seed=100 + i,
        **(dict(temperature=0.0) if sampling == "greedy"
           else dict(temperature=0.9, top_k=7)))
        for i, (n, lp) in enumerate(
            [(7, 0), (1, 2), (9, 0), (2, 0), (6, 2), (8, 0)])]
    late = ServingEngine(params, cfg, _pool(pool))
    first = _reads_first_always(ServingEngine(params, cfg, _pool(pool)))
    got = late.generate(prompts, params=asks)
    # the programs are shared by every engine of this configuration (and
    # by other tests' pools of other sizes): reading first adds none
    compiled = late.compile_stats()
    want = first.generate(prompts, params=asks)
    assert first.compile_stats() == compiled
    assert _streams(got) == _streams(want)
    assert [len(o.tokens) for o in got] == [7, 1, 9, 2, 6, 8]
    if sampling == "greedy" and family != "jamba":
        for p, o in zip(prompts, got):
            assert o.tokens == _ref_greedy(params, cfg, p, len(o.tokens))
    assert late.stats["lookahead_steps"] > 0
    assert late.stats["lookahead_drains"] == 0
    assert first.stats["lookahead_steps"] == 0
    assert first.stats["lookahead_drains"] > 0
    for eng in (late, first):
        assert eng.stats["lookahead_dropped_rows"] == 0
        assert eng.stats["decode_tokens"] == sum(
            a.max_new_tokens - 1 for a in asks)
        assert not eng.has_work()
        assert all(s.state == FREE for s in eng.scheduler.slots)
    if pool == "paged":
        st = late.page_stats()
        assert st["free"] + st["cached"] == st["total"]


@pytest.mark.parametrize("ends_on", ["eos", "stop_sequence"])
@pytest.mark.parametrize("family,pool", [
    ("control", "contiguous"), ("control", "paged"), ("jamba", "contiguous")])
def test_a_row_that_ends_with_a_step_in_flight(family, pool, ends_on):
    """A row that ends on a token the host could not foresee has one step
    too many in flight: nothing is emitted after the ending token, the
    slot (and its pages) is freed once, the row is counted as dropped,
    and the next occupant of the slot, admitted from the queue, serves
    what it serves alone (it reads none of the ended row's K/V or state)."""
    cfg, params = _family(family)
    a_p, b_p, c_p = _prompts([6, 9, 5], cfg.vocab_size, seed=43)
    kw = dict(max_new_tokens=9, temperature=0.0)

    def alone(prompt):
        (out,) = ServingEngine(params, cfg, _pool(pool)).generate(
            [prompt], **kw)
        return out.tokens

    a_ref, b_ref, c_ref = alone(a_p), alone(b_p), alone(c_p)
    # end A on its third token (the first occurrence of that token or of
    # that pair of tokens decides where it really ends)
    if ends_on == "eos":
        ending = dict(eos_token_id=a_ref[2])
        cut = a_ref.index(a_ref[2]) + 1
    else:
        ending = dict(stop=[tuple(a_ref[1:3])])
        cut = next(i + 2 for i in range(len(a_ref) - 1)
                   if a_ref[i:i + 2] == a_ref[1:3])
    eng = ServingEngine(params, cfg, _pool(pool, num_slots=2))
    a = eng.submit(a_p, **kw, **ending)
    b = eng.submit(b_p, **kw)
    c = eng.submit(c_p, **kw)  # waits for the first slot that frees: A's
    outs = {o.request_id: o for o in eng.run()}
    assert outs[a].tokens == a_ref[:cut]
    assert outs[a].finish_reason == ends_on
    assert outs[b].tokens == b_ref and outs[c].tokens == c_ref
    assert eng.stats["lookahead_dropped_rows"] >= 1
    assert eng.stats["completed"] == 3
    assert not eng.has_work()
    assert all(s.state == FREE for s in eng.scheduler.slots)
    if family == "jamba":
        assert eng.stats["state_resets"] == 3
    if pool == "paged":
        st = eng.page_stats()
        assert st["free"] + st["cached"] == st["total"]


@pytest.mark.parametrize("asker", ["masked", "penalized"])
def test_an_asking_row_drains_and_the_batch_returns_to_the_late_read(asker):
    """A constrained or penalised row joining a greedy batch makes every
    step it is live in read first (its FSM cursor or histogram advances on
    the host, token by token), by cause in the ``decode`` span; when it
    leaves the batch returns to the late read. Both requests serve what
    they serve alone."""
    cfg, params = _setup("control")
    vocab = _LETTERS
    long_p, ask_p = _prompts([7, 5], cfg.vocab_size, seed=44)
    long_kw = dict(max_new_tokens=20, temperature=0.0)
    ask_kw = dict(max_new_tokens=6, temperature=0.0, **(
        dict(regex="[a-f]{8,12}") if asker == "masked"
        else dict(repetition_penalty=1.7, presence_penalty=0.4)))

    def alone(prompt, kw):
        (out,) = ServingEngine(params, cfg, ServingConfig(num_slots=2),
                               vocab=vocab).generate([prompt], **kw)
        return out.tokens

    long_ref, ask_ref = alone(long_p, long_kw), alone(ask_p, ask_kw)
    rec = _SpanArgs()
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=2),
                        vocab=vocab, tracer=rec)
    # the programs are shared by every engine of this configuration: the
    # engines above compiled this pool's shapes
    compiled = eng.compile_stats()
    long_id = eng.submit(long_p, **long_kw)
    for _ in range(4):
        eng.step()
    ask_id = eng.submit(ask_p, **ask_kw)
    outs = {o.request_id: o for o in eng.run()}
    assert outs[long_id].tokens == long_ref
    assert outs[ask_id].tokens == ask_ref
    steps = [args for name, args in rec.spans if name == "decode"]
    depth = [s["lookahead"] for s in steps]
    cause = {"masked": "mask", "penalized": "penalty"}[asker]
    assert [s.get("drain") for s in steps if not s["lookahead"]] == (
        [cause] * depth.count(0))
    # late, then reading first while the asking row is live, then late
    first, last = depth.index(0), len(depth) - depth[::-1].index(0)
    assert 0 < first and last < len(depth)
    assert set(depth[first:last]) == {0}
    assert depth.count(0) == ask_kw["max_new_tokens"] - 1
    assert eng.stats["lookahead_drains"] == depth.count(0)
    assert eng.stats["lookahead_steps"] == depth.count(1)
    assert eng.stats["lookahead_dropped_rows"] == 0
    assert all(s.get("inflight_dropped", 0) == 0 for s in steps)
    assert eng.compile_stats() == compiled


def test_spans_are_stamped_with_the_host_s_iteration_and_the_read_says_whose():
    """Every span carries the iteration the HOST is in when it runs (an
    iteration is taken from its first to its last stamped span); the late
    read carries the iteration its work was dispatched in as
    ``of_iteration``, one less; ``first_token`` holds the sampler's
    dispatch and no read."""
    cfg, params = _setup("control")
    rec = _SpanArgs()
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=2), tracer=rec)
    eng.generate(_prompts([5, 8], cfg.vocab_size, seed=45),
                 max_new_tokens=5, temperature=0.0)
    names = [name for name, _ in rec.spans]
    reads = [args for name, args in rec.spans if name == "token_read"]
    assert reads and all(
        r["of_iteration"] == r["iteration"] - 1 for r in reads)
    # a read's first tokens come first (their programs ended a decode
    # step before the step's rows are there), one read for both prompts
    assert [r["path"] for r in reads[:2]] == ["prefill", "decode"]
    assert {r["path"] for r in reads[2:]} == {"decode"}
    stamped = [args["iteration"] for name, args in rec.spans
               if "iteration" in args]
    assert stamped == sorted(stamped)
    # the first token is dispatched, not read, inside `first_token`
    at = names.index("first_token")
    assert names[at + 1:at + 3] == ["sample_operands", "sample_dispatch"]
    assert names[at + 3] != "token_read"


@pytest.mark.parametrize("seed", [0, 1, 41, 2**31 - 1, 2**31, 2**32 - 1,
                                  2**32 + 5, 123456789012])
def test_submit_mints_the_key_jax_would_without_a_program(seed):
    """``submit`` mints a request's key on the host (a program on the chip
    and a blocking read of it would queue behind the step in flight on
    every arrival): word for word ``jax.random.PRNGKey(seed)``."""
    from differential_transformer_replication_tpu.serving import engine as E

    want = np.asarray(jax.random.PRNGKey(seed))
    got = E._prng_key_words(seed)
    assert got.dtype == np.uint32 and got.tolist() == want.tolist()
    cfg, params = _setup("control")
    eng = ServingEngine(params, cfg, ServingConfig(num_slots=1))
    rid = eng.submit([1, 2, 3], max_new_tokens=2, seed=seed)
    assert eng._base_keys[rid].tolist() == want.tolist()

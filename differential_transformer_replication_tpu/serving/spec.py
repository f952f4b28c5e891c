"""Speculative-decoding drafters for the serving engine.

The paper's own experimental design — three interchangeable decoder
families trained on ONE tokenizer (models/control.py, diff.py,
ndiff.py) — is exactly the drafter/verifier pairing speculative
decoding needs (Leviathan et al. 2023, "Fast Inference from
Transformers via Speculative Decoding"): a cheap drafter proposes k
tokens per slot per iteration, and the target model verifies all k in
ONE fused multi-row pool step (models/decode.py:``forward_decode_spec``,
serving/engine.py:``_build_spec_step_fns``) instead of k sequential
decode steps. Every proposal is VERIFIED — an arbitrarily bad (or
poisoned) drafter can only cost throughput, never correctness: greedy
requests accept a draft token iff it equals the target's argmax
(bit-identical to non-spec greedy decoding), sampled requests run the
standard acceptance-ratio test under the existing per-request
``fold_in`` key chains.

Two drafter backends behind one interface:

- :class:`NGramDrafter` — the drafter-free prompt-lookup fallback: a
  host-side suffix map over each request's prompt + emitted tokens
  proposes the continuation that followed the most recent occurrence
  of the current n-gram suffix. Zero device cost; shines on the
  repetitive stretches (code, templated text, self-repeating greedy
  output) where lookups actually hit.
- :class:`ModelDrafter` — a small checkpoint (typically the control
  family beside a diff/ndiff target; any family sharing the tokenizer
  works) run greedily on its OWN contiguous slot-pool KV cache, params
  loaded beside the target's. The drafter pool mirrors the target's
  slot assignment 1:1; per-slot ``_next`` cursors track how far each
  slot's drafter cache is valid, so acceptance/rejection needs no
  explicit rollback — a rejected suffix simply leaves the cursor
  behind, and the next catch-up overwrites it (the same
  position-derived ring semantics the target uses). A poisoned drafter
  pool (the ``spec_drafter_crash`` fault) trips the same finite-logits
  reduction the engine's sampler uses; the drafter then REBUILDS its
  pool from params and returns no proposals, so the engine falls back
  to the non-spec decode step for that iteration — never garbage
  tokens, surfaced via ``serving_spec_drafter_crashes_total``.

Thread-safety: both drafters are lock-owning classes — the engine
thread mutates proposal/cursor/suffix-map state while /health handlers
and the bench read :meth:`stats` concurrently (graftlint GL301/GL6xx
machine-check the discipline, and tests/test_spec.py's mutation test
proves the check is not vacuous). Device work under the lock is fine:
only :meth:`stats` contends, and a scrape blocking for one tiny
drafter step is cheaper than torn counters.
"""

from __future__ import annotations

import threading
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

from differential_transformer_replication_tpu.serving.scheduler import (
    _pow2_chunk,
)


class DraftSlot:
    """One slot's proposal context, passed by the engine each
    iteration: the slot index, the FULL token history (cropped prompt
    + generated so far), the target position P of the last emitted
    token (history[P] is that token), and the per-slot draft cap the
    engine already clamped against max_new_tokens / the ring window /
    the request's own ``draft_len``."""

    __slots__ = ("index", "tokens", "pos", "cap")

    def __init__(self, index: int, tokens: Sequence[int], pos: int,
                 cap: int):
        self.index = index
        self.tokens = tokens
        self.pos = pos
        self.cap = cap


class _DrafterBase:
    """Shared counter surface; see the module docstring for why the
    lock exists (engine thread vs /health readers). Each concrete
    drafter assigns its OWN ``self._lock`` in ``__init__`` — graftlint
    GL301's lock-ownership analysis is per-class, and the machine
    check only guards classes that visibly own their lock."""

    kind = "none"

    def stats(self) -> dict:
        with self._lock:
            return {
                "kind": self.kind,
                "proposed_total": self._proposed,
                "drafter_crashes_total": self._crashes,
            }

    # interface ------------------------------------------------------

    def propose_all(self, slots: List[DraftSlot]) -> Dict[int, List[int]]:
        raise NotImplementedError

    def commit(self, index: int, new_pos: int) -> None:
        """The verify step emitted tokens for this slot; its last
        emitted token now sits at ``new_pos``. Default: nothing (the
        n-gram drafter re-reads history each round)."""

    def release(self, index: int) -> None:
        """The slot retired (finish/deadline/cancel)."""

    def reset(self) -> None:
        """Engine crash recovery: drop everything derived state."""


class NGramDrafter(_DrafterBase):
    """Prompt-lookup speculative decoding (drafter-free fallback).

    Per slot, a suffix map from every n-gram (n = ``max_n`` down to
    ``min_n``) of the request's token history to the position right
    after its most recent occurrence; a proposal is the continuation
    that followed the longest matching suffix of the current history.
    The map is built incrementally (each token indexes ``max_n`` keys),
    so per-iteration cost is O(new tokens), not O(history).
    """

    kind = "ngram"

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if not (1 <= min_n <= max_n):
            raise ValueError(
                f"need 1 <= min_n <= max_n, got {min_n}..{max_n}"
            )
        self._lock = threading.Lock()
        self._proposed = 0
        self._crashes = 0
        self.max_n = max_n
        self.min_n = min_n
        # slot -> ({ngram tuple: (previous end, last end)},
        #           tokens indexed so far). Two ends per key because
        #          the history TAIL always matches itself at
        #          end == len(history) — the useful occurrence is the
        #          one before it.
        self._maps: Dict[int, Tuple[dict, int]] = {}

    def _index_locked(self, index: int, tokens: Sequence[int]):
        entry = self._maps.get(index)
        if entry is None or entry[1] > len(tokens):
            entry = ({}, 0)  # new occupant (slot reuse): fresh map
        smap, done = entry
        first = self.min_n if done == 0 else done + 1
        for end in range(first, len(tokens) + 1):
            for n in range(self.min_n, self.max_n + 1):
                if end - n >= 0:
                    key = tuple(tokens[end - n:end])
                    old = smap.get(key)
                    smap[key] = (old[1] if old else None, end)
        self._maps[index] = (smap, len(tokens))
        return smap

    def propose_all(self, slots: List[DraftSlot]) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        with self._lock:
            for s in slots:
                if s.cap <= 0:
                    continue
                # the engine passes history ending exactly at pos (the
                # common case) — avoid a per-iteration copy then
                hist = (
                    s.tokens if len(s.tokens) == s.pos + 1
                    else list(s.tokens[:s.pos + 1])
                )
                smap = self._index_locked(s.index, hist)
                prop: List[int] = []
                for n in range(min(self.max_n, len(hist)), self.min_n - 1,
                               -1):
                    ends = smap.get(tuple(hist[-n:]))
                    if ends is None:
                        continue
                    # the match ending AT the history tail proposes
                    # nothing (its continuation is the future); fall
                    # back to the occurrence before it
                    at = ends[1] if ends[1] < len(hist) else ends[0]
                    if at is not None:
                        prop = hist[at:at + s.cap]
                        break
                if prop:
                    out[s.index] = prop
                    self._proposed += len(prop)
        return out

    def release(self, index: int) -> None:
        with self._lock:
            self._maps.pop(index, None)

    def reset(self) -> None:
        with self._lock:
            self._maps.clear()


@lru_cache(maxsize=None)
def _drafter_step_fns(cfg, rope_len: int, k: int):
    """Jitted (prefill, k-round-propose) closures for the drafter's
    own slot pool — the drafter-side analog of the engine's
    ``_build_step_fns``, module-cached so drafter rebuilds after a
    crash (or fault) add ZERO recompiles. The propose closure runs ALL
    k greedy rounds as one fused program (k sequential dispatches per
    engine iteration were the dominant model-drafter cost on CPU),
    fusing the whole-pool forwards, the greedy argmaxes AND the
    finite-logits reduction: a poisoned drafter pool surfaces as a
    typed flag through exactly the guard the engine's sampler uses.
    Per-slot round caps ride as a runtime array — a slot whose cap is
    full is no longer ``active`` and its rounds write nothing, so
    varying caps recompile nothing."""
    import jax
    import jax.numpy as jnp

    from differential_transformer_replication_tpu.models.decode import (
        KV_CACHE_BATCH_AXIS,
        forward_chunk,
        forward_decode_pool,
    )

    def _prefill(params, cache, slot, tokens, pos):
        """One prompt/catch-up chunk for one drafter slot, in place in
        the pool (logits discarded — the drafter only needs the K/V)."""
        row = [
            {key: (c[key][:, slot][:, None]
                   if KV_CACHE_BATCH_AXIS[key] else c[key][slot][None])
             for key in c}
            for c in cache
        ]
        _, new_row = forward_chunk(
            params, tokens, pos, row, cfg, rope_len=rope_len
        )
        return [
            {key: (c[key].at[:, slot].set(nr[key][:, 0])
                   if KV_CACHE_BATCH_AXIS[key]
                   else c[key].at[slot].set(nr[key][0]))
             for key in c}
            for c, nr in zip(cache, new_row)
        ]

    def _propose(params, tokens0, pos0, caps, cache):
        """All k greedy propose rounds in one call: feed each slot's
        last token, take the argmax, feed it back — round r active
        for slot b iff r < caps[b]. Returns ((B, k) proposals,
        (B,) finite-ok over active rounds, updated cache)."""
        B = tokens0.shape[0]

        def body(r, carry):
            cache, cur_tok, cur_pos, out, ok = carry
            active = r < caps
            logits, cache = forward_decode_pool(
                params, cur_tok, cur_pos, cache, cfg,
                rope_len=rope_len, active=active,
            )
            lf = logits.astype(jnp.float32)
            nxt = jnp.argmax(lf, axis=-1).astype(jnp.int32)
            ok = ok & jnp.where(
                active, jnp.isfinite(lf).all(axis=-1), True
            )
            out = out.at[:, r].set(jnp.where(active, nxt, 0))
            cur_tok = jnp.where(active, nxt, cur_tok)
            cur_pos = cur_pos + active.astype(jnp.int32)
            return cache, cur_tok, cur_pos, out, ok

        cache, _, _, out, ok = jax.lax.fori_loop(
            0, k, body,
            (cache, jnp.asarray(tokens0, jnp.int32),
             jnp.asarray(pos0, jnp.int32),
             jnp.zeros((B, k), jnp.int32),
             jnp.ones((B,), bool)),
        )
        return out, ok, cache

    return (
        jax.jit(_prefill, donate_argnums=(1,)),
        jax.jit(_propose, donate_argnums=(4,)),
    )


class ModelDrafter(_DrafterBase):
    """A small checkpoint proposing greedily on its own slot pool.

    The drafter's contiguous KV pool mirrors the target's slot
    assignment 1:1. ``_next[i]`` is the first position of slot i whose
    drafter-cache entry is NOT yet valid for the slot's actual token
    history; catch-up (chunked, power-of-two ladder) feeds
    ``tokens[_next..P-1]`` before the k pooled propose rounds feed
    ``tokens[P]`` and then each argmax. :meth:`commit` rewinds the
    cursor past rejected rows — position arithmetic makes the stale
    suffix invisible, exactly like the target's ring.
    """

    kind = "model"

    def __init__(self, params: dict, cfg, num_slots: int, rope_len: int,
                 prefill_chunk: int = 128, draft_len: int = 4):
        import numpy as np

        from differential_transformer_replication_tpu.models.decode import (
            init_cache,
        )

        self._lock = threading.Lock()
        self._proposed = 0
        self._crashes = 0
        self._np = np
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.rope_len = max(rope_len, cfg.block_size)
        self.prefill_chunk = prefill_chunk
        self.draft_len = draft_len
        self._init_cache = lambda: init_cache(cfg, num_slots)
        self._prefill, self._propose = _drafter_step_fns(
            cfg, self.rope_len, draft_len
        )
        with self._lock:
            self.cache = self._init_cache()
            self._next = [0] * num_slots

    # -- drafter window: proposals must stay inside ITS ring too ------

    def window(self) -> int:
        return self.cfg.block_size

    def bytes_total(self) -> int:
        """HBM bytes the drafter pool holds beside the target's — the
        equal-HBM accounting term the README runbook works through."""
        with self._lock:
            return sum(
                leaf.nbytes for layer in self.cache
                for leaf in layer.values()
            )

    def poison(self) -> None:
        """Fault hook (``spec_drafter_crash@N``): NaN-poison the whole
        drafter pool so the next propose round's finite-logits
        reduction trips — proving the fall-back-to-non-spec path."""
        import jax.numpy as jnp

        with self._lock:
            self.cache = [
                {key: (jnp.full_like(leaf, jnp.nan)
                       if jnp.issubdtype(leaf.dtype, jnp.floating)
                       else jnp.zeros_like(leaf))
                 for key, leaf in layer.items()}
                for layer in self.cache
            ]

    def _rebuild_locked(self) -> None:
        self.cache = self._init_cache()  # graftlint: threadsafe (_locked helper: every caller holds self._lock)
        self._next = [0] * self.num_slots  # graftlint: threadsafe (_locked helper: every caller holds self._lock)

    def propose_all(self, slots: List[DraftSlot]) -> Dict[int, List[int]]:
        np = self._np
        import jax.numpy as jnp

        out: Dict[int, List[int]] = {}
        with self._lock:
            # catch-up: feed each slot the history tokens its cache
            # does not yet hold (positions _next..P-1), chunked on the
            # power-of-two ladder so only log2(prefill_chunk)+1 chunk
            # shapes ever compile (the engine's own ladder)
            for s in slots:
                start = self._next[s.index]
                while start < s.pos:
                    # the engine's own prefill ladder (one shared
                    # helper so drafter chunk shapes stay in lockstep
                    # with the scheduler's — the zero-recompile set)
                    size = _pow2_chunk(s.pos - start,
                                       self.prefill_chunk)
                    self.cache = self._prefill(
                        self.params, self.cache, np.int32(s.index),
                        jnp.asarray(
                            [list(s.tokens[start:start + size])],
                            jnp.int32,
                        ),
                        np.int32(start),
                    )
                    start += size
                self._next[s.index] = start
            # all k greedy rounds as ONE fused call (the jitted
            # fori_loop in _drafter_step_fns); per-slot caps ride as a
            # runtime array, so varying caps recompile nothing
            B = self.num_slots
            cur_tok = np.zeros((B,), np.int32)
            cur_pos = np.zeros((B,), np.int32)
            caps = np.zeros((B,), np.int32)
            proposing = []
            for s in slots:
                cap = min(s.cap, self.draft_len,
                          self.window() - s.pos - 1)
                if cap <= 0:
                    continue
                cur_tok[s.index] = s.tokens[s.pos]
                cur_pos[s.index] = s.pos
                caps[s.index] = cap
                proposing.append(s)
            if not proposing:
                return {}
            toks, ok, self.cache = self._propose(
                self.params, cur_tok, cur_pos, jnp.asarray(caps),
                self.cache,
            )
            toks = np.asarray(toks)
            ok = np.asarray(ok)
            if not all(bool(ok[s.index]) for s in proposing):
                # poisoned pool: the same finite-logits guard the
                # engine's sampler uses, surfaced typed — rebuild
                # from params, propose nothing, engine falls back to
                # the non-spec step (never garbage tokens; a drafted
                # garbage token would be rejected by the verify
                # anyway, but a dead drafter must not keep burning a
                # propose round per iteration)
                self._crashes += 1
                self._rebuild_locked()
                return {}
            for s in proposing:
                cap = int(caps[s.index])
                out[s.index] = [int(t) for t in toks[s.index, :cap]]
                self._next[s.index] = s.pos + cap
                self._proposed += cap
        return out

    def commit(self, index: int, new_pos: int) -> None:
        """Rewind the slot's validity cursor past rejected rows: cache
        entries at positions >= new_pos hold rejected draft K/V and
        must be re-fed (the accepted prefix below new_pos is valid by
        construction — the drafter fed exactly those tokens)."""
        with self._lock:
            self._next[index] = min(self._next[index], new_pos)

    def release(self, index: int) -> None:
        with self._lock:
            self._next[index] = 0

    def reset(self) -> None:
        """Engine crash recovery: fresh pool from params (zero
        recompiles — the jitted closures are module-cached)."""
        with self._lock:
            self._rebuild_locked()


def build_drafter(serving, target_cfg, rope_len: int,
                  drafter: Optional[Tuple[dict, object]] = None):
    """Construct the configured drafter for an engine.

    ``drafter`` is an optional pre-loaded ``(params, cfg)`` pair
    (tests, sample.py); otherwise ``spec_mode == "model"`` loads
    ``spec_drafter_ckpt`` through the SAME
    ``load_params_for_inference`` path as the target — manifest
    verification included. A drafter whose vocab differs from the
    target's cannot share the tokenizer and fails loudly."""
    if not serving.spec_enabled():
        return None
    if serving.spec_mode == "ngram":
        return NGramDrafter()
    if drafter is not None:
        d_params, d_cfg = drafter
    else:
        if not serving.spec_drafter_ckpt:
            raise ValueError(
                "spec_mode='model' needs spec_drafter_ckpt (or a "
                "pre-loaded drafter)"
            )
        from differential_transformer_replication_tpu.train.checkpoint import (
            load_params_for_inference,
        )

        d_params, d_cfg, _ = load_params_for_inference(
            serving.spec_drafter_ckpt
        )
    if d_cfg.vocab_size != target_cfg.vocab_size:
        raise ValueError(
            f"drafter vocab ({d_cfg.vocab_size}) != target vocab "
            f"({target_cfg.vocab_size}): drafter and target must share "
            "one tokenizer"
        )
    # the drafter inherits the target's serving-side decode overrides
    # only where they apply to ITS config; its own checkpoint settings
    # otherwise stand (a bf16 drafter beside an int8 target is fine —
    # proposals are token ids, not activations)
    return ModelDrafter(
        d_params, d_cfg, serving.num_slots, rope_len,
        prefill_chunk=serving.prefill_chunk,
        draft_len=serving.spec_draft_len,
    )


def constrain_proposals(props: Dict[int, List[int]],
                        fsms: Dict[int, tuple]) -> Dict[int, List[int]]:
    """Truncate drafter proposals at the first token a slot's
    constraint FSM disallows (serving/constrain.py:TokenFsm).

    ``fsms`` maps slot index -> (fsm, current state) for constrained
    slots; unconstrained slots pass through untouched. A draft the FSM
    rejects outright is dropped (the slot rides the verify step with
    draft length 0 — a runtime array, no recompile). Truncation is an
    OPTIMIZATION, not a correctness requirement: the verify step's
    accept compares each draft token against the argmax/draw of the
    constraint-MASKED target logits, so a disallowed draft token is
    always rejected anyway — pre-truncating just stops the drafter
    from burning verify rows it can never win (Leviathan's
    distribution-preservation is untouched either way)."""
    if not fsms:
        return props
    out: Dict[int, List[int]] = {}
    for i, toks in props.items():
        ent = fsms.get(i)
        if ent is not None:
            fsm, state = ent
            toks = toks[:fsm.prefix_len(toks, state=state)]
        if toks:
            out[i] = toks
    return out

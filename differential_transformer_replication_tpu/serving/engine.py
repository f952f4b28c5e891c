"""Continuous-batching inference engine.

The single-request generators (models/generate.py, models/decode.py:
``generate_cached``) answer one prompt at a time; a serving workload has
many concurrent users with different prompt lengths, arrival times and
sampling params. This engine closes that gap with the two standard
techniques:

- **Slot-pool KV cache**: one fixed ``init_cache(cfg, num_slots)``
  pool holds every in-flight sequence's K/V rings. A request owns one
  slot row from admission to retirement; rows are reused WITHOUT
  clearing because the ring mask derives visibility purely from
  position arithmetic (models/decode.py: ``_attn_chunk``) — a fresh
  prefill at pos=0 makes every stale key invisible by construction.
  With ``ServingConfig.kv_page_size > 0`` the pool is PAGED
  (vLLM-style, serving/pages.py): device KV lives in fixed-size pages
  mapped through per-slot page tables that ride the jitted steps as
  runtime int32 arrays, admission keys on free pages instead of
  slots, and a radix tree shares cached prompt prefixes copy-on-write
  — same ring semantics, same zero-recompile pins.
- **Iteration-level (Orca-style) scheduling**: each :meth:`step` admits
  queued requests into free slots, advances prefill by a bounded token
  budget (serving/scheduler.py), then decodes ALL active slots as one
  batched length-1 ``forward_chunk``. Sequences retire on EOS or
  max-tokens without stalling the rest of the batch; the freed slot is
  refilled on the next iteration.
- **The late read**: sampled tokens pass from step to step ON THE
  DEVICE (the sampler's packed output of step k is an operand of step
  k + 1; a finished prompt's first token is written over its row), the
  next decode step is dispatched BEFORE the last one's tokens are read,
  and the host reads them an iteration late, in one blocking read of
  work the device finished while the host was dispatching
  (:meth:`ServingEngine.step`). Where a step is built from what the
  last token did to the host's state (a row with an FSM mask or a
  penalty, the speculative path, quality telemetry) the same loop reads
  first; no option selects either order.

Everything device-side is shape-static, so continuous batching costs no
recompilation as requests come and go:

- the decode step is one jitted call over the FULL pool — per-slot
  positions/tokens/active-mask are runtime values of ONE packed int32
  operand and of the device's record of sampled rows (inactive rows compute
  garbage and write nothing: the mask goes into the cache write, and the
  step's cache traffic is the rows it writes, in place in the pool);
- prefill chunks come from a power-of-two ladder, so at most
  log2(prefill_chunk)+1 prefill shapes ever compile (a family with a
  recurrent state runs a prompt's tail once, padded to the ladder's next
  shape, where the others run it digit by digit);
- sampling is one jitted batched kernel with per-row temperature/top-k
  ARRAYS (models/generate.py:``sample_token`` bakes them into the trace
  as statics; rows here must differ without recompiling). The greedy and
  default paths are bit-identical to ``sample_token`` — pinned by
  tests/test_serving.py.

The decode step is models/decode.py:``forward_decode_pool``, the one
L = 1 entry point for both pool layouts and both attention impls (a
verify block is ``forward_decode_spec``, which unrolls or batches the
same program): this module never chooses between step functions. Mixed
per-slot positions ride a ``jax.vmap`` over the rows for what is about a
row's own position, its Q/K/V and (on the slot pool under XLA) its
attend over its own ring, each a length-1 ``forward_chunk``'s (each row
carries its own ``pos`` scalar, exactly the traced-position path the
chunked decoder already supports); the write, the norms, the FFN half
and the head run once over the rows as one batch. ``forward_chunk``'s
concrete-position validity guards are enforced host-side at submit
instead. Per-request determinism: the key for the t-th generated token
is ``fold_in(PRNGKey(seed), t)``, a pure function of the request — not
of slot assignment, batch composition, or admission order.

Family limits (models/decode.py module docstring): control/ndiff roll
the ring past block_size up to ``ServingConfig.max_seq_len``; the diff
family's learned absolute position table cannot roll, so its requests
are capped at ``prompt + max_new_tokens <= block_size``.
"""

from __future__ import annotations

import math
import sys
import time
from dataclasses import dataclass, field
from functools import lru_cache
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from differential_transformer_replication_tpu.config import (
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models.decode import (
    KINDS,
    KV_CACHE_BATCH_AXIS,
    apply_logit_pipeline,
    attend_rows,
    copy_cache_pages,
    forward_chunk,
    forward_decode_pool,
    forward_decode_spec,
    HYBRID,
    has_recurrent_state,
    live_kv,
    gather_slot_cache,
    init_cache,
    init_cache_paged,
    reset_slot_state,
    kv_store_dtype,
    own_ring_attend,
    quality_vector,
    scatter_slot_cache,
)
from differential_transformer_replication_tpu.obs.quality import (
    ENTROPY_BINS,
    MARGIN_BINS,
    QualityMonitor,
    build_quality_row,
    load_fingerprint,
)
from differential_transformer_replication_tpu.obs.registry import (
    Registry,
    StatsMap,
)
from differential_transformer_replication_tpu.obs.spans import NOOP_TRACER
from differential_transformer_replication_tpu.obs.trace import (
    TraceContext,
    child_span_args,
    instant_args,
)
from differential_transformer_replication_tpu.serving.constrain import (
    ConstraintCache,
    ConstraintCompileError,
    spec_key,
)
from differential_transformer_replication_tpu.serving.host_tier import (
    TierEntry,
)
from differential_transformer_replication_tpu.serving.migrate import (
    MigrateExportError,
    decode_slot_state,
    encode_slot_state,
    params_from_dict,
    params_to_dict,
)
from differential_transformer_replication_tpu.serving.pages import (
    PagePool,
    PagePoolExhaustedError,
    page_bytes,
)
from differential_transformer_replication_tpu.serving.request import (
    Request,
    RequestOutput,
    SamplingParams,
)
from differential_transformer_replication_tpu.serving.scheduler import (
    ACTIVE,
    FREE,
    Scheduler,
    Slot,
    padded_chunk,
)
from differential_transformer_replication_tpu.utils import faults


# engine.stats keys -> (Prometheus counter name, help). The mapping keys
# are the /health JSON contract (tests/test_serving*.py read them); the
# counter names are the /metrics contract. StatsMap keeps both views over
# ONE set of values so they cannot drift.
_STAT_SPEC = {
    "iterations": (
        "serving_engine_iterations_total",
        "Engine step() iterations executed.",
    ),
    "prefill_tokens": (
        "serving_prefill_tokens_total",
        "Prompt tokens prefilled into KV slots.",
    ),
    "decode_tokens": (
        "serving_decode_tokens_total",
        "Tokens generated by batched decode steps.",
    ),
    "decode_attend_rows": (
        "serving_decode_attend_rows_total",
        "Rows of the slot pool the XLA decode attention read, summed over "
        "decode steps: whole blocks up to the highest active slot "
        "(models/decode.py attend_rows); over iterations x num_slots it "
        "is the share of the pool a step reads. 0 on the paths that "
        "attend the pool another way (pages, the fused kernel, the "
        "hybrid families).",
    ),
    "decode_live_kv": (
        "serving_decode_live_kv_positions_total",
        "Ring positions that hold a live key and value for the rows of "
        "decode steps, summed over the layers (a family whose slots hold "
        "rings of two lengths: min(pos + 1, sliding_window) a sliding "
        "layer, pos + 1 a full one; models/decode.py live_kv). 0 for "
        "the other families.",
    ),
    "decode_live_latent": (
        "serving_decode_live_latent_positions_total",
        "Ring positions that hold a live latent for the rows of decode "
        "steps, pos + 1 a row, summed over the layers that are MLA over "
        "a ring of latents, which the step reads live (every deepseek_v2 "
        "layer, kimi_linear's MLA layers: ops/mla.py "
        "latent_decode_attention). 0 for the other families.",
    ),
    "completed": (
        "serving_requests_completed_total",
        "Requests finished normally (eos or length).",
    ),
    "cancelled": (
        "serving_requests_cancelled_total",
        "Requests abandoned by their caller (timeout/cancel).",
    ),
    "rejected": (
        "serving_requests_rejected_total",
        "Submissions rejected at admission (queue full / invalid).",
    ),
    "deadline_expired": (
        "serving_requests_deadline_expired_total",
        "Requests shed or retired past their server-side deadline.",
    ),
    "engine_restarts": (
        "serving_engine_restarts_total",
        "Slot-pool rebuilds after a crashed engine step.",
    ),
    "state_resets": (
        "serving_state_resets_total",
        "Slots whose recurrent state was zeroed on admission (families "
        "with Mamba, Mamba-2 or KDA layers, or short convolutions whose "
        "window is the whole state; a K/V ring needs none).",
    ),
    "decode_live_state": (
        "serving_decode_live_state_bytes_total",
        "Bytes of recurrent state that decode steps moved: the active "
        "rows times a slot's state leaves (every Mamba, Mamba-2 or KDA "
        "layer's state, read and written once a step by the update "
        "kernels, which touch the active slots alone; a short "
        "convolution's window where that is all the layer keeps). 0 for a "
        "family of rings.",
    ),
    "moe_experts_hit": (
        "serving_moe_experts_hit_total",
        "Held experts that got at least one row in a decode step, summed "
        "over the expert layers and the steps: the experts whose weights "
        "the grouped product had to fetch (families with routed experts).",
    ),
    "moe_held": (
        "serving_moe_held_assignments_total",
        "(row, expert) assignments of decode steps that fell on an expert "
        "this replica holds, over the expert layers (families with routed "
        "experts; the rest of a row's experts_per_token lie on the chips "
        "that hold the other experts).",
    ),
    "moe_rows_in_held_group": (
        "serving_moe_rows_in_held_group_total",
        "(row, expert layer) pairs of decode steps whose router kept a "
        "routing group this replica holds (a router limited to groups: "
        "only such a row can meet a held expert; topk_group / n_group of "
        "the rows under even routing). 0 for a router without groups.",
    ),
    # the late read (ServingEngine.step): how often the loop dispatched a
    # decode step before it had read the one before, how often it had
    # to read first, and what the late read cost in rows
    "lookahead_steps": (
        "serving_lookahead_steps_total",
        "Decode steps dispatched before the tokens of the step before "
        "were read (the host read them an iteration late).",
    ),
    "lookahead_drains": (
        "serving_lookahead_drains_total",
        "Iterations that read everything in flight before building their "
        "decode step (a row with an FSM mask or a penalty, the "
        "speculative path, quality telemetry, a profile capture).",
    ),
    "lookahead_dropped_rows": (
        "serving_lookahead_dropped_rows_total",
        "Rows of a dispatched step whose request had ended by the time "
        "the step was read (an EOS or stop sequence read late, a cancel, "
        "a deadline): the token is dropped, nothing of it is delivered.",
    ),
    "page_shed": (
        "serving_requests_page_shed_total",
        "Requests shed at admission because the KV page pool could "
        "not hold them (typed PagePoolExhaustedError).",
    ),
    # speculative decoding (serving/spec.py): drafted tokens offered
    # to the fused verify step and how many it accepted — the
    # aggregate acceptance-rate series (per-request counts ride
    # RequestOutput.spec_{proposed,accepted})
    "spec_proposed": (
        "serving_spec_proposed_tokens_total",
        "Draft tokens proposed to the speculative verify step.",
    ),
    "spec_accepted": (
        "serving_spec_accepted_tokens_total",
        "Draft tokens the target model accepted.",
    ),
    "spec_drafter_crashes": (
        "serving_spec_drafter_crashes_total",
        "Drafter pools rebuilt after the finite-logits guard tripped "
        "(engine fell back to non-spec decode, never garbage tokens).",
    ),
    # host-tier / preemption (serving/host_tier.py): the graceful-
    # degradation counters — demote/promote traffic, mid-decode
    # preemptions and bit-exact resumes, and the typed fallbacks where
    # a tier transfer degraded to recompute instead of wedging
    "preemptions": (
        "serving_preemptions_total",
        "Mid-decode preemptions: a lower-priority request's KV pages "
        "stashed to the host tier to unblock a higher class.",
    ),
    "resumes": (
        "serving_preempt_resumes_total",
        "Preempted requests swapped back in bit-exact from their "
        "host-tier stash.",
    ),
    "tier_demotions": (
        "serving_host_tier_demotions_total",
        "Evicted radix pages demoted into the host-RAM tier.",
    ),
    "tier_promotions": (
        "serving_host_tier_promotions_total",
        "Host-tier pages promoted back to device at admission "
        "(a copy, never a recompute).",
    ),
    "tier_fallbacks": (
        "serving_host_tier_fallbacks_total",
        "Tier transfers that degraded to recompute or full restart "
        "(failed/corrupt demote, promote, or swap-in) — typed, "
        "counted, never a wedge.",
    ),
    # live migration (serving/migrate.py): slot states exported to /
    # imported from peer replicas, the wire page traffic (shipped vs
    # radix-deduped), and the typed failures that fell back to
    # resume-by-replay instead of wedging or attending garbage KV
    "migrate_exports": (
        "serving_migrate_exports_total",
        "Slot decode states exported to a peer replica (drain path).",
    ),
    "migrate_imports": (
        "serving_migrate_imports_total",
        "Migrated slot states imported and re-admitted bit-exact.",
    ),
    "migrate_pages_shipped": (
        "serving_migrate_pages_shipped_total",
        "KV pages shipped over the wire by slot-state exports.",
    ),
    "migrate_pages_deduped": (
        "serving_migrate_pages_deduped_total",
        "KV pages NOT shipped because the destination's radix tree "
        "already held the prompt-prefix node (copied device-locally).",
    ),
    "migrate_bytes": (
        "serving_migrate_bytes_total",
        "Wire bytes of exported slot states (post-dedup).",
    ),
    "migrate_failed": (
        "serving_migrate_failed_total",
        "Migration imports that failed after admission (bad checksum, "
        "torn payload, injection failure) — typed, counted, degraded "
        "to a bit-exact recompute, never garbage KV.",
    ),
}


class EngineCrashError(RuntimeError):
    """The engine failed mid-flight (device error, corrupt slot pool,
    non-finite logits). Typed and RETRIABLE: the supervised runner
    (serving/server.py) fails in-flight requests with this error,
    rebuilds the slot pool from params, and serves on — a client that
    retries (HTTP 503 + Retry-After) lands on the restarted engine."""

    retriable = True


@dataclass
class _InFlight:
    """One iteration's dispatched work whose outputs the host has not
    read: the first tokens of the prompts that finished there, each its
    (slot, request id, the one-row sampler call's packed output), and the
    decode step, its rows as (slot, request id), the pool-wide sampler
    call's packed output, the expert load where the family has experts
    and the ``moe`` dict of its ``decode`` span, filled when read. A row
    is named by slot AND request: one whose slot no longer holds that
    request when the record is read has ended meanwhile, and its token is
    dropped (``ServingEngine._deliver``)."""

    iteration: int
    firsts: list = field(default_factory=list)
    rows: list = field(default_factory=list)
    out: object = None
    load: object = None
    moe: Optional[dict] = None


# What a row asks of the sampler program beyond an argmax and the
# finiteness check, one bit each in column 8 of its packed operand
# (``_build_step_fns._sample``): the host sets them for the rows a call
# names (``ServingEngine._row_asks``), the program takes its arms by them.
ASK_MASK, ASK_PENALTY, ASK_LOGPROBS, ASK_TEMPERATURE = 1, 2, 4, 8


@lru_cache(maxsize=None)
def _build_step_fns(cfg: ModelConfig, rope_len: int,
                    page_size: int = 0, num_pages: int = 0,
                    lp_k: int = 5, quality: bool = False):
    """Jitted (prefill, decode, sample, page_copy, page_extract,
    page_inject) closures for (cfg, rope_len[, page geometry], logprob
    echo width). The last three are the paged path's page plumbing
    (COW forks + the host tier's demote/promote transfers) and None on
    the contiguous path.

    Cached at module level so engines with the same model/config share
    compile caches (and tests can count compiles across engine
    rebuilds); every argument below is a runtime array, so each closure
    compiles once per distinct input SHAPE only. With ``page_size > 0``
    the prefill/decode closures run the PAGED cache layout
    (models/decode.py): per-slot page tables and write pages ride in as
    runtime int32 arrays, so page allocation/free/share/fork between
    calls compiles nothing new — the same zero-recompile pin as the
    contiguous path. ``page_copy`` is the COW-fork device copy (None on
    the contiguous path). Either decode closure is
    ``forward_decode_pool`` with the arguments its layout has; the
    layout's write and the attention impl are bound inside it
    (models/decode.py:``_pool_seam``), nowhere in this module.

    ``quality`` (a static, like lp_k) appends the in-jit quality
    telemetry tail (models/decode.py:``quality_vector``) to the
    sampler's packed output and widens its int operand by one prev-
    token column; False compiles the EXACT pre-telemetry closure, so
    telemetry-off output is bit-identical by construction.
    """
    if page_size > 0:

        def _decode_paged(params, rows, last_out, cache, page_tables,
                          write_pages):
            """One batched length-1 step over the whole slot pool
            THROUGH the page tables (models/decode.py
            ``forward_decode_pool``, as ``_decode`` below, the rows'
            operand and the merge of their tokens the same): inactive
            rows' writes are redirected to the trash page by
            ``write_pages`` (the contiguous path gives them no write
            target instead)."""
            tokens, pos, _ = _merge_rows(rows, last_out)
            logits, new_cache = forward_decode_pool(
                params, tokens, pos, cache, cfg, rope_len=rope_len,
                page_tables=page_tables, write_pages=write_pages,
            )
            return logits.astype(jnp.float32), new_cache

        def _prefill_paged(params, cache, page_row, tokens, pos):
            """One prompt chunk for one slot through its page-table
            row: gather the slot's contiguous ring view, run the
            SAME forward_chunk the contiguous path runs (bit-parity by
            construction), scatter the pages back. ``page_row`` is a
            runtime int32 array — only the chunk length L distinguishes
            compiles. Written positions always live on pages the slot
            privately owns (serving/pages.py reserves them at
            admission); shared prefix pages are scattered back with
            their own unchanged values."""
            row = gather_slot_cache(cache, page_row)
            logits, new_row = forward_chunk(
                params, tokens, pos, row, cfg, rope_len=rope_len
            )
            new_cache = scatter_slot_cache(cache, new_row, page_row)
            return logits[0, -1].astype(jnp.float32), new_cache

        def _page_copy(cache, src, dst):
            return copy_cache_pages(cache, src, dst)

        def _page_extract(cache, src):
            """One physical page's leaves sliced out of the pool (the
            host-tier demotion/stash capture). A scalar ``src`` take
            REMOVES the page axis, so each leaf is exactly one page's
            K/V image. NOT donated — the pool stays live; the engine
            fetches the result to host numpy."""
            return [
                {key: jnp.take(c[key], src,
                               axis=KV_CACHE_BATCH_AXIS[key])
                 for key in c}
                for c in cache
            ]

        def _page_inject(cache, dst, payload):
            """Write one page image into physical page ``dst`` (the
            host-tier promotion/swap-in). ``dst`` is a runtime scalar,
            so page placement never recompiles — the same contract as
            ``_page_copy``."""
            out = []
            for c, p in zip(cache, payload):
                layer = {}
                for key in c:
                    axis = KV_CACHE_BATCH_AXIS[key]
                    idx = (slice(None),) * axis + (dst,)
                    layer[key] = c[key].at[idx].set(p[key])
                out.append(layer)
            return out

    def _merge_rows(rows, last_out):
        """(tokens, positions, active mask) of a step from its ONE packed
        (B, 4) int32 host operand (``pack_decode_rows``) and the sampler's
        packed output of the step before, still on the device: a row takes
        its token from column 0 of ``last_out`` unless the host names it
        ``from_host`` (it alone knows the token: a resumed, imported or
        replayed slot, a row after a speculative block)."""
        tokens = jnp.where(rows[:, 3] != 0, rows[:, 0], last_out[:, 0])
        return tokens, rows[:, 1], rows[:, 2] != 0

    def _decode(params, rows, last_out, cache):
        """One batched length-1 step over the WHOLE slot pool:
        models/decode.py ``forward_decode_pool``, the one L = 1 entry
        point (a row's Q/K/V a length-1 forward_chunk's under vmap,
        everything else one batch over the rows). Which attention a row
        runs (its own ring under that vmap or, with
        ``decode_attention_impl: pallas``, the fused kernel over every
        row in one (B*H,)-grid call per layer) is bound there, not here.

        ``rows`` is the step's packed host operand, ``last_out`` the
        device's own record of the last sampled tokens (``_merge_rows``):
        ONE program whether the host read those tokens before this call
        or reads them after it. tokens/pos/active: (B,) runtime arrays
        taken from them. Inactive rows run the
        projections, the FFN and the head on garbage inputs (static
        shapes are the point) and their logits mean nothing; the write
        takes ``active`` as its mask, so a mid-prefill or free slot's
        ring is left as it is and the step's cache traffic is the rows
        it writes, in place in the donated pool, and the XLA attention
        takes it as its bound: it reads the pool in blocks of rows up to
        the highest active one (models/decode.py ``attend_rows``, which
        the ``decode`` span carries), the trip count a runtime value of
        this ONE program. A family with
        routed experts (``num_experts``) returns a third item, the step's
        expert load (models/decode.py:``_hybrid_decode``).
        """
        tokens, pos, active = _merge_rows(rows, last_out)
        logits, new_cache, *load = forward_decode_pool(
            params, tokens, pos, cache, cfg, rope_len=rope_len, active=active)
        return (logits.astype(jnp.float32), new_cache, *load)

    def _prefill(params, cache, slot, tokens, pos, valid=None):
        """One prompt chunk for one slot, in place in the pool.

        tokens: (1, L) with L from the power-of-two ladder; slot/pos are
        runtime scalars (dynamic gather/scatter on the pool's batch
        axis), so only L distinguishes compiles. ``valid`` (a runtime
        scalar too; a family with a recurrent state always passes it) is
        how many of the L tokens are the prompt's: a tail that is no
        power of two runs once, padded to the next shape of the ladder
        (``forward_chunk``), and not once a binary digit.
        """
        row = [
            {key: (c[key][:, slot][:, None]
                   if KV_CACHE_BATCH_AXIS[key] else c[key][slot][None])
             for key in c}
            for c in cache
        ]
        logits, new_row = forward_chunk(
            params, tokens, pos, row, cfg, rope_len=rope_len, valid=valid
        )
        new_cache = [
            {key: (c[key].at[:, slot].set(nr[key][:, 0])
                   if KV_CACHE_BATCH_AXIS[key]
                   else c[key].at[slot].set(nr[key][0]))
             for key in c}
            for c, nr in zip(cache, new_row)
        ]
        return logits[0, -1].astype(jnp.float32), new_cache

    @jax.named_scope("sampler")
    def _sample(ints, logits, allowed, counts_v):
        """Batched per-request sampling over (B, V) fp32 logits,
        through the structured-decoding logit pipeline
        (models/decode.py:``apply_logit_pipeline``), computing per call
        only what a row of it ASKED for.

        Every per-row scalar rides ONE packed (B, 9) int32 operand
        (one host->device conversion per call): token count | top_k |
        PRNG base (2 cols, bitcast uint32) | temperature | repetition
        | presence | frequency penalties (bitcast f32) | what the row
        asks (the ``ASK_*`` bits: an FSM mask, a penalty, ``logprobs >
        0``, a temperature); with ``quality`` on, one extra column
        carries the previous emitted token (-1 = none) for the
        repetition flag. A row the call does not name (an empty slot of
        the pool) asks NOTHING: its bits are 0, its temperature and
        top_k 0. ``allowed`` (B, V) bool is the per-row constraint-FSM
        mask row and ``counts_v`` (B, V) int32 the generated-token
        histogram — both runtime arrays (the engine passes cached
        all-ones/zeros constants when no active row needs the
        pipeline), and so are the bits: mixed traffic never recompiles,
        it takes another arm of this one program. The t-th
        token's key is fold_in(base, t); temperature/top-k semantics
        match sample_token row-for-row (<=0 temp = greedy, top_k <= 0
        = off, mask-below-kth-PROCESSED-logit otherwise). Rows with
        the pipeline inert are BIT-IDENTICAL to the pre-pipeline
        sampler (the pipeline's ``where`` passes raw logits through).

        The arms, a ``lax.cond`` each on runtime values of the operand,
        every one returning small arrays only (a branch that handed the
        processed logits on would copy them: 168 MB at 256 x 163,840):
        no bit set in the whole batch -> the PLAIN arm, one argmax over
        the raw logits and the finiteness check, zeros in the echo
        columns. Its tokens are the full arm's bit for bit: with the
        pipeline inert the processed logits ARE the logits, a top-k
        never masks the largest, and a row without a temperature takes
        the argmax. Some bit set -> the full arm, every row through the
        pipeline, and inside it the vocabulary's sort only where some
        row has a top_k, the draw only where some row has a
        temperature, the log-softmax and its top-k only where some row
        has ``ASK_LOGPROBS`` (zeros else). With ``quality`` on the
        telemetry tail reads the sort and the log-softmax of every
        call, so that build is the full arm alone with both run.

        Output is ONE packed (B, 3 + 2*lp_k) int32 array: token |
        finite-ok | chosen-token logprob (bitcast f32) | top-lp_k ids
        | top-lp_k logprobs (bitcast f32); with ``quality`` on, three
        more bitcast-f32 columns append the quality tail (entropy |
        margin | repeat — existing offsets unchanged). Logprobs are
        over the
        distribution actually sampled from — processed logits after
        top-k, divided by the greedy-safe temperature. The finiteness
        flag is over the RAW logits (before the intentional -inf
        masking), in either arm: a corrupt KV slot or numerically
        diverged model yields NaN logits, and serving a garbage argmax
        over them would be a silent wrong answer — the engine turns a
        non-finite ACTIVE row into a typed :class:`EngineCrashError`
        instead (inactive rows compute garbage by design and are
        ignored host-side).
        """
        asks = ints[:, 8]
        B, V = logits.shape

        def _finite():
            with jax.named_scope("sampler_finite"):
                return jnp.isfinite(logits).all(axis=-1)

        def _packed(tokens, ok, chosen, top_ids, top_lp):
            return [
                tokens.astype(jnp.int32)[:, None],
                ok.astype(jnp.int32)[:, None],
                jax.lax.bitcast_convert_type(chosen, jnp.int32),
                top_ids.astype(jnp.int32),
                jax.lax.bitcast_convert_type(top_lp, jnp.int32),
            ]

        def _no_echo(_):
            return (jnp.zeros((B, 1), jnp.float32),
                    jnp.zeros((B, lp_k), jnp.int32),
                    jnp.zeros((B, lp_k), jnp.float32))

        def _plain(_):
            with jax.named_scope("sampler_draw"):
                tokens = jnp.argmax(logits, axis=-1)
            return jnp.concatenate(
                _packed(tokens, _finite(), *_no_echo(None)), axis=1)

        def _full(_):
            counts = ints[:, 0]
            top_k = ints[:, 1]
            bases = jax.lax.bitcast_convert_type(ints[:, 2:4], jnp.uint32)
            f = jax.lax.bitcast_convert_type(ints[:, 4:8], jnp.float32)
            temperature = f[:, 0]
            keys = jax.vmap(jax.random.fold_in)(bases, counts)
            # The sampler's parts each under a scope of its own (metadata
            # only: README, "What a profile calls things"), so a trace
            # says which parts a call ran and what each cost.
            with jax.named_scope("logit_pipeline"):
                proc = apply_logit_pipeline(
                    logits, allowed, counts_v, f[:, 1], f[:, 2], f[:, 3]
                )
            kth = jnp.clip(top_k - 1, 0, V - 1)

            def _kth_largest(_):
                sorted_desc = -jnp.sort(-proc, axis=-1)
                return sorted_desc, jnp.take_along_axis(
                    sorted_desc, kth[:, None], axis=-1)

            with jax.named_scope("sampler_topk"):
                if quality:
                    # the telemetry tail reads the sort's head, so the
                    # sort runs
                    sorted_desc, thresh = _kth_largest(None)
                else:
                    # Only a row with top_k > 0 reads the threshold, and
                    # only a row with a temperature reads the draw: a
                    # batch without one skips the vocabulary's sort and
                    # the noise, the same tokens bit for bit. At 256 x
                    # 65,536 logits the sort alone was 22 ms of a 42 ms
                    # iteration (my chip run, PR 28). An empty slot's
                    # row has neither (``_sample_operands``).
                    thresh = jax.lax.cond(
                        jnp.any(top_k > 0), lambda _: _kth_largest(None)[1],
                        lambda _: jnp.zeros((B, 1), proc.dtype), None)
                masked = jnp.where(
                    (top_k > 0)[:, None] & (proc < thresh), -jnp.inf, proc
                )
            with jax.named_scope("sampler_draw"):
                greedy = jnp.argmax(masked, axis=-1)
                safe_t = jnp.where(
                    temperature > 0, temperature, 1.0)[:, None]
                scaled = masked / safe_t
                drawn = jax.lax.cond(
                    jnp.any(temperature > 0),
                    lambda _: jax.vmap(
                        lambda k, lg: jax.random.categorical(k, lg))(
                            keys, scaled).astype(greedy.dtype),
                    lambda _: jnp.zeros(greedy.shape, greedy.dtype), None)
                tokens = jnp.where(
                    temperature <= 0, greedy, drawn).astype(jnp.int32)

            def _echo(_):
                lp = jax.nn.log_softmax(scaled, axis=-1)
                top_lp, top_ids = jax.lax.top_k(lp, lp_k)
                return lp, (
                    jnp.take_along_axis(lp, tokens[:, None], axis=-1),
                    top_ids.astype(jnp.int32), top_lp)

            with jax.named_scope("sampler_logprobs"):
                if quality:
                    lp, echo = _echo(None)  # the tail reads ``lp``
                else:
                    echo = jax.lax.cond(
                        jnp.any((asks & ASK_LOGPROBS) != 0),
                        lambda _: _echo(None)[1], _no_echo, None)
            cols = _packed(tokens, _finite(), *echo)
            if quality:
                # the telemetry tail rides the SAME packed transfer: the
                # sampled distribution's entropy, the processed-logit
                # margin, and the repeat-of-previous flag per row. The
                # margin reuses sorted_desc's head — the sort already
                # paid for the top-k threshold — so the tail adds no
                # second full-vocab top_k to the fused sampler
                with jax.named_scope("sampler_quality"):
                    qv = quality_vector(
                        lp, proc, tokens, ints[:, 9],
                        top2=sorted_desc[:, :2] if V >= 2 else None,
                    )
                cols.append(jax.lax.bitcast_convert_type(qv, jnp.int32))
            return jnp.concatenate(cols, axis=1)

        if quality:
            return _full(None)
        return jax.lax.cond(jnp.any(asks != 0), _full, _plain, None)

    # Donate the cache pool: the engine always rebinds self.cache to the
    # result, so the old buffers are dead. Donation only ALLOWS an update
    # in place; a program gets one when nothing reads the old pool after
    # the write and the write takes the pool in the layout the chip holds
    # it in (ops/kv_write.py; tests/test_tpu_compile.py pins that the
    # decode program aliases every leaf and allocates no second pool).
    # Every backend donates, the CPU included, so a read of a donated
    # pool fails in the tests and not first on the chip.
    if page_size > 0:
        return (
            jax.jit(_prefill_paged, donate_argnums=(1,)),
            jax.jit(_decode_paged, donate_argnums=(3,)),
            jax.jit(_sample),
            jax.jit(_page_copy, donate_argnums=(0,)),
            jax.jit(_page_extract),  # cache NOT donated: it stays live
            jax.jit(_page_inject, donate_argnums=(0,)),
        )
    return (
        jax.jit(_prefill, donate_argnums=(1,)),
        jax.jit(_decode, donate_argnums=(3,)),
        jax.jit(_sample),
        None,
        None,
        None,
    )


# One slot's recurrent state zeroed in place in the donated pool
# (models/decode.py:reset_slot_state). Model-independent: the slot is a
# runtime scalar and the leaves are told apart by name, so it compiles once
# a pool shape.
_reset_state_fn = jax.jit(reset_slot_state, donate_argnums=(0,))


@jax.jit
def _set_row_fn(last_out, slot, row):
    """A completed prompt's first token (the one-row sampler call's packed
    output) written into the device's record of the last sampled rows, so
    the row joins the next decode step without the host having seen the
    token. NOT donated: the read of the step in flight may still hold the
    old record. ``slot`` is a runtime scalar: one compile a pool shape."""
    return last_out.at[slot].set(row[0])


def pack_decode_rows(tokens, pos, active, from_host=None) -> np.ndarray:
    """The decode step's ONE packed (B, 4) int32 host operand: token |
    position | active | from_host a row (``_build_step_fns._merge_rows``
    takes it apart on the device). ``from_host`` defaults to every row:
    the tokens given are the ones the step runs."""
    tokens = np.asarray(tokens)
    rows = np.zeros((tokens.shape[0], 4), np.int32)
    rows[:, 0] = tokens
    rows[:, 1] = np.asarray(pos)
    rows[:, 2] = np.asarray(active)
    rows[:, 3] = 1 if from_host is None else np.asarray(from_host)
    return rows


def _prng_key_words(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s two uint32 words, minted on the
    host: the default generator pads a 32-bit seed with zeros (``(0,
    seed mod 2**32)``, tests/test_serving.py holds it word for word), so
    a submission costs no program on the chip and no read of one, which
    would queue behind the step in flight. Another generator, 64-bit
    integers or a seed offset take JAX's own path."""
    if (jax.config.jax_default_prng_impl == "threefry2x32"
            and not jax.config.jax_enable_x64
            and not jax.config.jax_random_seed_offset):
        return np.array([0, int(seed) & 0xFFFFFFFF], np.uint32)
    return np.asarray(jax.random.PRNGKey(seed), np.uint32)


def _refuse_for_layer_kinds(cfg: ModelConfig,
                            serving: ServingConfig) -> None:
    """The engine features that a kind of ``cfg``'s layers cannot serve,
    each refused under that kind's own reason (models/decode.py ``KINDS``:
    ``refuses``, in the table's order: a recurrent state before rings of
    two lengths before a ring of latents). A ring that rolls under
    multi-token chunks also bounds ``prefill_chunk``."""
    asked = {"host_tier": serving.host_tier_bytes > 0,
             "spec": serving.spec_enabled(), "paging": serving.paged(),
             "int8": cfg.kv_cache_dtype == "int8"}
    here = {k: r for k, r in KINDS.items() if k in cfg.layer_kinds()}
    mixers = " or ".join(r.name for r in here.values() if r.recurrent)
    for kind, record in here.items():
        for feature, on in asked.items():
            if on and feature in record.refuses:
                raise ValueError(record.refuses[feature].format(
                    model=cfg.model, mixers=mixers, ring=cfg.ring_len(kind),
                    block=cfg.block_size))
        if record.rolls and serving.prefill_chunk > cfg.ring_slack:
            raise ValueError(
                f"prefill_chunk ({serving.prefill_chunk}) exceeds what the "
                f"{cfg.model} family's sliding rings hold past their window "
                f"(sliding_ring {cfg.ring_len(kind)} - sliding_window "
                f"{cfg.sliding_window} = {cfg.ring_slack}): a longer chunk "
                "written at a rolled position would evict keys that its "
                "earlier rows still see (models/decode.py)")


# the name benchmark/configs/nemotron3-super-11l-ep4.json knows it by
_refuse_for_recurrent_state = _refuse_for_layer_kinds


# fold_in salt distinguishing a draft position's ACCEPT-draw key from
# its token key: the t-th token's decisions stay a pure function of
# (request seed, t) — never of slot, batch composition, or how many
# drafts preceded it
_SPEC_ACCEPT_SALT = np.uint32(0x9E3779B9)


@lru_cache(maxsize=None)
def _build_spec_step_fns(cfg: ModelConfig, rope_len: int, draft_len: int,
                         sampled: bool = False, batched: bool = False,
                         page_size: int = 0, num_pages: int = 0,
                         lp_k: int = 5, quality: bool = False):
    """ONE fused jitted verify step for (cfg, rope_len, k rung): the
    L = k+1-row pool forward (models/decode.py:``forward_decode_spec``,
    on either pool layout), the per-row sampling transforms, and the
    accept/reject decision — all under ``lax`` ops with k static.
    Per-slot draft lengths, the reject-storm fault flag, page tables
    and write targets ride as RUNTIME arrays, so mixed spec/non-spec
    traffic and varying per-request draft lengths compile NOTHING
    beyond this one rung (the engine's k ladder is {0 = the plain
    decode step, spec_draft_len = this}). ``page_size`` and
    ``num_pages`` only key the cache, as in ``_build_step_fns``: the
    closure learns its layout from whether it is passed page tables.

    Acceptance semantics (Leviathan et al. 2023, one-hot drafter):
    greedy rows (temperature <= 0) accept draft j iff it equals row
    j-1's argmax — which makes spec-on greedy output BIT-IDENTICAL to
    the non-spec path by induction (row 0's math is exactly
    ``_build_step_fns``'s ``_sample``). Sampled rows accept draft j
    with probability p_j(d_j) under the temperature/top-k-processed
    target distribution (a salted fold_in of the token's own key draws
    the uniform) and on rejection sample the residual — the target
    distribution with the rejected token masked out, renormalized —
    with the token's UNsalted key, so a non-spec row (draft_len 0)
    reduces to ``_sample`` exactly, bit for bit.

    Returns ``(tokens_out (B, L), n_emit (B,), ok (B,), new_cache)``:
    the accepted prefix plus one corrected/bonus token per slot, and
    the finite-logits flags over each slot's USED rows (the guard that
    turns a corrupt pool into a typed EngineCrashError, never a
    garbage token).

    ``sampled=False`` compiles the all-greedy specialization: when no
    active request samples this step, the accept needs NO threefry
    draws and no top-k sort — ``argmax(masked) == argmax(logits)``
    always (the argmax survives its own top-k mask), and the
    rejected-token residual mask never moves a greedy argmax either —
    so the cheap variant is BIT-IDENTICAL to the full one on greedy
    rows while cutting the accept from ~2x the whole L-row forward to
    noise (measured on CPU). The engine picks the variant per step
    from the active slots' temperatures; both are ladder rungs.
    """
    k = draft_len
    L = k + 1

    def _accept(logits, draft, dlen, force_reject, bases, counts,
                temps, topks, rep, pres, freq, allowed, pcounts,
                prev0):
        B, _, V = logits.shape
        # The logit pipeline (models/decode.py:apply_logit_pipeline),
        # applied to EVERY verify row exactly as the L=1 sampler
        # applies it to its one row — the parity that keeps
        # constrained+spec distribution-preserving (Leviathan's test
        # needs identical target processing) and greedy constrained
        # spec bit-identical to non-spec. Row j's histogram counts the
        # draft tokens before it (a cumsum of one-hots, in-kernel);
        # row j's constraint mask is the FSM row for the state reached
        # through drafts 0..j-1, built host-side (all-ones when
        # unconstrained — the pipeline's where passes raw logits
        # through bit-identically).
        # The parts the L=1 sampler has carry its scopes' names
        # (``_build_step_fns._sample``); the accept test itself lies
        # under ``sampler`` alone.
        with jax.named_scope("logit_pipeline"):
            if k > 0:
                oh = jax.nn.one_hot(draft, V, dtype=jnp.int32)
                prefix = jnp.concatenate(
                    [jnp.zeros((B, 1, V), jnp.int32),
                     jnp.cumsum(oh, axis=1)], axis=1,
                )
            else:
                prefix = jnp.zeros((B, L, V), jnp.int32)
            counts3 = pcounts[:, None] + prefix
            proc = apply_logit_pipeline(
                logits.reshape(B * L, V), allowed.reshape(B * L, V),
                counts3.reshape(B * L, V),
                jnp.repeat(rep, L), jnp.repeat(pres, L),
                jnp.repeat(freq, L),
            ).reshape(B, L, V)
        if sampled:
            with jax.named_scope("sampler_topk"):
                kth = jnp.clip(topks - 1, 0, V - 1)
                sorted_desc = -jnp.sort(-proc, axis=-1)
                thresh = jnp.take_along_axis(
                    sorted_desc,
                    jnp.broadcast_to(kth[:, None, None], (B, L, 1)),
                    axis=-1,
                )
                masked = jnp.where(
                    (topks > 0)[:, None, None] & (proc < thresh),
                    -jnp.inf, proc,
                )
        else:
            masked = proc  # greedy: the mask cannot move an argmax
        safe_t = jnp.where(temps > 0, temps, 1.0)
        if k > 0:
            j_idx = jnp.arange(k)[None, :]
            pred = jnp.argmax(masked[:, :k], axis=-1)  # (B, k)
            acc = pred == draft
            if sampled:
                probs = jax.nn.softmax(
                    masked[:, :k] / safe_t[:, None, None], axis=-1
                )
                p_d = jnp.take_along_axis(
                    probs, draft[..., None], axis=-1
                )[..., 0]
                cj = counts[:, None] + j_idx  # token index per row
                tok_keys = jax.vmap(
                    jax.vmap(jax.random.fold_in, in_axes=(None, 0)),
                    in_axes=(0, 0),
                )(bases, cj)  # (B, k, 2)
                u = jax.vmap(jax.vmap(
                    lambda kk: jax.random.uniform(
                        jax.random.fold_in(kk, _SPEC_ACCEPT_SALT)
                    )
                ))(tok_keys)
                acc = jnp.where((temps <= 0)[:, None], acc, u < p_d)
            acc = (
                acc & (j_idx < dlen[:, None])
                & jnp.logical_not(force_reject)
            )
            # accepted prefix length: leading run of accepted rows
            a = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)
            d_rej = jnp.take_along_axis(
                draft, jnp.clip(a, 0, k - 1)[:, None], axis=1
            )[:, 0]
        else:
            a = jnp.zeros_like(counts)
            d_rej = jnp.zeros_like(counts)
        # correction/bonus token from row a: on rejection, the residual
        # distribution (one-hot drafter => target with the rejected
        # token removed, renormalized); greedy argmax is unchanged by
        # that mask (the rejected token was not the argmax)
        with jax.named_scope("sampler_draw"):
            row_logits = jnp.take_along_axis(
                masked, a[:, None, None], axis=1
            )[:, 0]  # (B, V)
            if sampled:
                rejected = a < dlen
                corr_logits = jnp.where(
                    rejected[:, None]
                    & (jnp.arange(V)[None, :] == d_rej[:, None]),
                    -jnp.inf, row_logits,
                )
                corr_keys = jax.vmap(jax.random.fold_in)(
                    bases, counts + a)
                drawn = jax.vmap(
                    lambda kk, lg: jax.random.categorical(kk, lg)
                )(corr_keys, corr_logits / safe_t[:, None])
                corr = jnp.where(
                    temps <= 0, jnp.argmax(corr_logits, axis=-1), drawn
                ).astype(jnp.int32)
            else:
                del d_rej
                corr = jnp.argmax(row_logits, axis=-1).astype(jnp.int32)
        jL = jnp.arange(L)[None, :]
        draft_pad = (
            jnp.pad(draft, ((0, 0), (0, 1))) if k > 0
            else jnp.zeros((B, L), jnp.int32)
        )
        tokens_out = jnp.where(
            jL < a[:, None], draft_pad,
            jnp.where(jL == a[:, None], corr[:, None], 0),
        ).astype(jnp.int32)
        # finite guard over each slot's USED rows only (rows past its
        # draft length computed garbage by design)
        with jax.named_scope("sampler_finite"):
            finite_rows = jnp.isfinite(logits).all(axis=-1)
            ok = (finite_rows | (jL > dlen[:, None])).all(axis=1)
        # per-row logprob echo over the distribution each row's token
        # came from (processed + top-k'd + temperature-scaled, same
        # surface as the L=1 sampler). The greedy rung skips the top-k
        # masking (it cannot move an argmax), so its echo ignores
        # top_k — logprob comparisons on the greedy rung hold with
        # top_k off (documented in the README runbook).
        with jax.named_scope("sampler_logprobs"):
            scaled = masked / safe_t[:, None, None]
            lp = jax.nn.log_softmax(scaled, axis=-1)
            chosen_lp = jnp.take_along_axis(
                lp, tokens_out[..., None], axis=-1
            )[..., 0]  # (B, L)
            top_lp, top_ids = jax.lax.top_k(lp, lp_k)  # (B, L, lp_k)
        qv = None
        if quality:
            # per-row quality tail over the SAME surfaces as the L=1
            # sampler (lp for entropy, proc for margin). Row j's
            # "previous token" is row j-1's emitted token; row 0's is
            # the slot's last emitted token (``prev0``, the verify
            # block's row-0 input). Rows past the accepted prefix
            # compute garbage the host never reads.
            prev_chain = jnp.concatenate(
                [prev0[:, None], tokens_out[:, :-1]], axis=1
            )
            # the sampled rung's top-k threshold sort already ranks
            # proc — reuse its head for the margin (greedy rungs have
            # no sort on hand and fall back to top_k inside)
            with jax.named_scope("sampler_quality"):
                qv = quality_vector(
                    lp, proc, tokens_out, prev_chain,
                    top2=(sorted_desc[..., :2]
                          if sampled and V >= 2 else None),
                )
        return (tokens_out, (a + 1).astype(jnp.int32), ok,
                chosen_lp, top_ids, top_lp, qv)

    # Every per-slot scalar operand rides ONE packed (B, 3L+k+10)
    # int32 array and every host-consumed result ONE stacked
    # (B, 2+2L+2L*lp_k) int32 array: ten separate host->device
    # conversions plus three device->host fetches per iteration were
    # the dominant slice of the spec step's host overhead on CPU
    # (measured ~1.4 ms/iteration — more than the whole fused device
    # program). Column layout (static slices below): tokens |
    # positions | write targets (cache row or physical page) | draft |
    # dlen | counts | topks | PRNG base (2 cols, bitcast uint32) |
    # temperature (bitcast f32) | force-reject flag | repetition |
    # presence | frequency penalties (bitcast f32). The constraint
    # masks (B, L, V) and penalty histograms (B, V) are their own
    # runtime-array operands (cached inert constants when no active
    # slot needs the pipeline).
    def _unpack(ints):
        c = 3 * L + k
        tokens = ints[:, 0:L]
        pos = ints[:, L:2 * L]
        targets = ints[:, 2 * L:3 * L]
        draft = ints[:, 3 * L:c]
        dlen = ints[:, c]
        counts = ints[:, c + 1]
        topks = ints[:, c + 2]
        bases = jax.lax.bitcast_convert_type(
            ints[:, c + 3:c + 5], jnp.uint32
        )
        temps = jax.lax.bitcast_convert_type(
            ints[:, c + 5], jnp.float32
        )
        force_reject = ints[0, c + 6] > 0
        pens = jax.lax.bitcast_convert_type(
            ints[:, c + 7:c + 10], jnp.float32
        )
        return (tokens, pos, targets, draft, dlen, counts, topks,
                bases, temps, force_reject, pens)

    def _pack_out(toks, n_emit, ok, chosen_lp, top_ids, top_lp, qv):
        B = toks.shape[0]
        cols = [
            toks, n_emit[:, None], ok.astype(jnp.int32)[:, None],
            jax.lax.bitcast_convert_type(chosen_lp, jnp.int32),
            top_ids.astype(jnp.int32).reshape(B, L * lp_k),
            jax.lax.bitcast_convert_type(
                top_lp, jnp.int32
            ).reshape(B, L * lp_k),
        ]
        if qv is not None:
            # quality tail appended LAST (every existing echo offset
            # stays valid): entropy | margin | repeat, L columns each
            cols.append(jax.lax.bitcast_convert_type(
                jnp.moveaxis(qv, -1, 1).reshape(B, 3 * L), jnp.int32
            ))
        return jnp.concatenate(cols, axis=1)

    def _spec_step(params, ints, cache, allowed, pcounts, page_tables=None):
        """``targets`` are cache rows on the contiguous pool and, with
        ``page_tables`` (the paged engine passes them last), physical
        write pages."""
        (tokens, pos, targets, draft, dlen, counts, topks,
         bases, temps, force_reject, pens) = _unpack(ints)
        logits, new_cache = forward_decode_spec(
            params, tokens, pos, cache, cfg, targets,
            rope_len=rope_len, batched=batched, page_tables=page_tables,
        )
        with jax.named_scope("sampler"):
            out = _accept(
                logits.astype(jnp.float32), draft, dlen, force_reject,
                bases, counts, temps, topks,
                pens[:, 0], pens[:, 1], pens[:, 2], allowed, pcounts,
                tokens[:, 0],
            )
            return _pack_out(*out), new_cache

    return jax.jit(_spec_step, donate_argnums=(2,))


def _penalties_on(p) -> bool:
    """Whether a request's SamplingParams engage the histogram side of
    the logit pipeline (repetition/presence/frequency)."""
    return (
        p.repetition_penalty != 1.0
        or p.presence_penalty != 0.0
        or p.frequency_penalty != 0.0
    )


class ServingEngine:
    """Continuous-batching engine over one model's params.

    Drive it either synchronously — ``submit()`` then ``run()`` /
    ``generate()`` — or one :meth:`step` at a time (what the background
    thread in serving/server.py does). Not thread-safe by itself; wrap
    it in :class:`serving.server.EngineRunner` for concurrent callers.
    """

    def __init__(self, params: dict, cfg: ModelConfig,
                 serving: Optional[ServingConfig] = None,
                 registry: Optional[Registry] = None,
                 tracer=None, spec_drafter=None,
                 vocab: Optional[Sequence[str]] = None):
        self.params = params
        self.serving = serving or ServingConfig()
        # serving-side overrides: serve a checkpoint with the fused
        # decode kernel / quantized KV without editing its model config
        if self.serving.decode_attention_impl:
            cfg = cfg.replace(
                decode_attention_impl=self.serving.decode_attention_impl
            )
        if self.serving.kv_cache_dtype:
            cfg = cfg.replace(kv_cache_dtype=self.serving.kv_cache_dtype)
        self.cfg = cfg
        # what a kind of this model's layers cannot serve refuses here
        _refuse_for_layer_kinds(cfg, self.serving)
        records = [KINDS[kind] for kind in cfg.layer_kinds()]
        # a sequence's state is overwritten every token in some layers:
        # a slot is zeroed on admission (_run_prefill)
        self._recurrent = has_recurrent_state(cfg)
        # the layers whose ring rolls (afmoe's sliding layers, in a slot of
        # rings of two lengths): the decode span says what the rows hold of
        # each length (models/decode.py live_kv)
        self._window_layers = sum(r.rolls for r in records)
        # the layers that keep a ring of latents, which the decode step
        # reads live (every deepseek_v2 layer, kimi_linear's MLA layers):
        # the decode span says how many latents the rows hold
        self._latent_layers = sum(r.latents for r in records)
        # the hybrid families' prefill program takes a chunk padded to
        # the ladder's next shape (forward_chunk ``valid``): a prompt's
        # tail is one program, not one a binary digit of its length
        self._pads_tail = cfg.model in HYBRID
        self.max_total = self.serving.resolved_max_seq_len(cfg)
        # Paged KV cache (serving/pages.py): device KV lives in fixed
        # pages mapped through per-slot page tables; admission keys on
        # free pages; a radix tree shares cached prompt prefixes.
        self._paged = self.serving.paged()
        self._pages: Optional[PagePool] = None
        # Host-RAM page tier (serving/host_tier.py): evicted full radix
        # pages demote there instead of vanishing; admissions matching
        # a demoted prefix promote it back with a copy, never a
        # recompute. The tier also holds preempted requests' stashes.
        self._tier = None
        # request_id -> host-side decode snapshot of a PREEMPTED
        # request (its KV pages live in a tier stash under the same
        # id); consumed by the bit-exact resume path in _admit_paged
        self._resume: dict = {}
        # (slot, snapshot) pairs resumed by THIS step's admission gate;
        # step() restores their decode state after plan() commits
        self._resumed: list = []
        if self._paged:
            ps = self.serving.kv_page_size
            pool = self.serving.resolved_pool_pages(cfg)  # checks ps | M
            if self.serving.tiered():
                from differential_transformer_replication_tpu.serving.host_tier import (
                    HostTier,
                )

                self._tier = HostTier(
                    budget_bytes=self.serving.host_tier_bytes
                )
            self._pages = PagePool(
                page_size=ps,
                pages_per_slot=cfg.block_size // ps,
                num_slots=self.serving.num_slots,
                total_pages=pool + 1,  # + the reserved trash page
                prefix_cache=self.serving.prefix_cache,
                tier=self._tier,
            )
        # Speculative decoding (serving/spec.py): the drafter proposes
        # up to spec_draft_len tokens per slot per iteration; the
        # target verifies all of them in ONE fused k+1-row pool step.
        # On the contiguous path the pool carries one extra TRASH ROW
        # (batch index num_slots) that absorbs the write-redirected
        # rejected/invalid verify rows — the paged path's trash page
        # already does that job, so its pool shape is unchanged.
        self._spec_k = (
            self.serving.spec_draft_len
            if self.serving.spec_enabled() else 0
        )
        self._rows = self.serving.num_slots + (
            1 if self._spec_k and not self._paged else 0
        )
        # whether the L = 1 step's attention stops at the highest active
        # slot (models/decode.py attend_rows): then its span says so
        self._own_ring_attend = own_ring_attend(cfg, paged=self._paged)
        self._drafter = None
        self._spec_fn = None
        self._spec_window = cfg.block_size
        if self._spec_k:
            from differential_transformer_replication_tpu.serving.spec import (
                build_drafter,
            )

            self._drafter = build_drafter(
                self.serving, cfg, self.max_total, drafter=spec_drafter
            )
            dw = getattr(self._drafter, "window", None)
            if dw is not None:
                self._spec_window = min(self._spec_window, dw())
        # structured decoding (serving/constrain.py): the engine-level
        # compiled-constraint cache, the request_id -> (cache key,
        # token FSM) map of in-flight constrained requests, and the
        # id -> string vocabulary the compiler walks. lp_k is the
        # compile-time logprob echo width — per-request logprobs <= lp_k
        # ride as host-side truncation, never a new trace.
        self._vocab = tuple(vocab) if vocab is not None else None
        self._lp_k = min(self.serving.max_logprobs, cfg.vocab_size)
        # quality telemetry (obs/quality.py): a STATIC of the jitted
        # sampler/verify closures like lp_k — on, they append the
        # in-jit quality tail; off, they compile the exact
        # pre-telemetry trace (bit-identical output by construction)
        self._quality = bool(self.serving.quality_telemetry)
        self._constraint_cache = ConstraintCache(
            self.serving.constraint_cache_entries
        )
        self._constraints: dict = {}
        # inert pipeline operands (all-ones masks / zero histograms) by
        # batch shape, held as device constants so unconstrained
        # traffic pays no per-step (B, V) host build or transfer
        self._inert: dict = {}
        if self._spec_k:
            # both accept variants of the k rung (greedy-specialized /
            # full sampled) — the step picks per iteration from the
            # active slots' temperatures; together with the L=1 step
            # they are the WHOLE fixed compile ladder
            self._spec_fn = {
                s: _build_spec_step_fns(
                    cfg, self.max_total, self._spec_k, sampled=s,
                    batched=self.serving.spec_verify == "batched",
                    page_size=(
                        self.serving.kv_page_size if self._paged else 0
                    ),
                    num_pages=(
                        self._pages.total_pages if self._paged else 0
                    ),
                    lp_k=self._lp_k,
                    quality=self._quality,
                )
                for s in (False, True)
            }
            self._drafter_crashes_seen = 0
        (self._prefill_fn, self._decode_fn, self._sample_fn,
         self._copy_fn, self._extract_fn, self._inject_fn) = _build_step_fns(
            cfg, self.max_total,
            page_size=self.serving.kv_page_size if self._paged else 0,
            num_pages=self._pages.total_pages if self._paged else 0,
            lp_k=self._lp_k,
            quality=self._quality,
        )
        self.cache = (
            init_cache_paged(cfg, self._pages.total_pages,
                             self.serving.kv_page_size)
            if self._paged else init_cache(cfg, self._rows)
        )
        # bytes of recurrent state a slot holds, over the layers (0 for a
        # family of rings): what a decode step moves a live row, one way
        self._state_bytes_per_slot = sum(
            layer[r.state].nbytes // layer[r.state].shape[0]
            for layer, r in zip(self.cache, records) if r.recurrent)
        # The late read (:meth:`step`): the device's own record of the
        # last sampled rows (the newest decode step's packed sampler
        # output, a completed prompt's first token written over its
        # row), which the next step takes its tokens from; the work
        # dispatched an iteration ago whose outputs the host has not
        # read; the first tokens dispatched since.
        self._last_out = self._no_rows_sampled()
        self._inflight: Optional[_InFlight] = None
        self._firsts: list = []
        self.scheduler = Scheduler(
            self.serving,
            on_retire=(
                self._on_retire
                if (self._paged or self._spec_k) else None
            ),
            on_preempt=(
                self._preempt_slot if self._tier is not None else None
            ),
            pad_limit=self.cfg.block_size if self._pads_tail else 0,
        )
        self._next_id = 0
        self._base_keys: dict = {}  # request_id -> np (2,) uint32 PRNG base
        # outputs produced by a step() that later RAISED: the finished/
        # shed requests were already retired from the scheduler, so they
        # would be unreachable after the crash (neither slot-holding nor
        # queued) — the buffer keeps them deliverable (take_finished)
        self._finished_prior: List[RequestOutput] = []
        # Telemetry (obs/): per-engine registry by default so tests and
        # multi-engine processes never cross-contaminate; the serving
        # server exposes it at GET /metrics. The tracer (obs/spans.py)
        # is a shared no-op unless a Chrome-trace path was requested.
        self.registry = registry or Registry()
        self.tracer = tracer or NOOP_TRACER
        # request-lifecycle instrumentation (admit/first_token/finish
        # instants, per-request lifetime spans, trace-context stamping)
        # is gated on a REAL tracer so the tracing-off hot path pays
        # only the existing no-op span calls — nothing per token
        self._tracing = self.tracer is not NOOP_TRACER
        # stats: dict-compatible view over registry counters (the
        # /health JSON keeps its shape; /metrics reads the same values)
        self.stats = StatsMap(self.registry, _STAT_SPEC)
        self._finished_counter = self.registry.counter(
            "serving_requests_finished_total",
            "Retired requests by finish reason.", labelnames=("reason",),
        )
        self._ttft_hist = self.registry.histogram(
            "serving_ttft_seconds",
            "Time from submit to first generated token.",
        )
        self._itl_hist = self.registry.histogram(
            "serving_itl_seconds",
            "Inter-token latency between consecutive generated tokens.",
        )
        self._queue_wait_hist = self.registry.histogram(
            "serving_queue_wait_seconds",
            "Time from submit to first prefill chunk (slot admission).",
        )
        self._step_hist = self.registry.histogram(
            "serving_engine_step_seconds",
            "Wall time of one engine iteration (schedule+prefill+decode).",
        )
        self._slot_gauge = self.registry.gauge(
            "serving_slot_occupancy",
            "KV slots currently held by in-flight requests.",
        )
        self.registry.gauge(
            "serving_slots", "Size of the fixed KV slot pool."
        ).set(self.serving.num_slots)
        self._kv_gauge = self.registry.gauge(
            "serving_kv_utilization",
            "Fraction of pooled KV positions holding live sequence state.",
        )
        self._queue_gauge = self.registry.gauge(
            "serving_queue_depth", "Requests waiting for a slot."
        )
        # priority-class telemetry: per-class queue depths plus the
        # per-class TTFT/ITL series obs/slo.py's per-class objectives
        # evaluate — a saturating batch class cannot hide an
        # interactive-class SLO violation inside an unlabeled series
        self._queue_class_gauge = self.registry.gauge(
            "serving_queue_depth_by_class",
            "Requests waiting for a slot, by priority class.",
            labelnames=("priority",),
        )
        self._class_ttft_hist = self.registry.histogram(
            "serving_class_ttft_seconds",
            "Time from submit to first generated token, by priority "
            "class.",
            labelnames=("priority",),
        )
        self._class_itl_hist = self.registry.histogram(
            "serving_class_itl_seconds",
            "Inter-token latency between consecutive generated tokens, "
            "by priority class.",
            labelnames=("priority",),
        )
        # quantization-aware capacity telemetry: the per-slot HBM cost of
        # KV state (int8 roughly halves it vs bf16 — the dashboards'
        # capacity-win signal) and the active storage dtype as a labeled
        # identity gauge
        self.registry.gauge(
            "serving_state_pool_bytes",
            "HBM bytes of the pool's state that is no K/V ring of "
            "block_size positions: every Mamba, Mamba-2 or KDA layer's recurrent "
            "state and convolution window, every MLA layer's ring of "
            "latents, and, where a slot holds K/V rings of two lengths "
            "(afmoe), the rings of both; 0 for a family of K/V rings of "
            "one length.",
        ).set(
            sum(leaf.nbytes for layer, r in zip(self.cache, records)
                for leaf in layer.values()
                if r.recurrent or r.latents or self._window_layers)
        )
        self.registry.gauge(
            "serving_kv_cache_bytes_per_slot",
            "HBM bytes of pooled KV-cache state per slot "
            "(includes int8 scale planes when quantized).",
        ).set(
            sum(leaf.nbytes for layer in self.cache
                for leaf in layer.values())
            // self._rows
        )
        self.registry.gauge(
            "serving_kv_cache_dtype",
            "Active KV-cache storage dtype (constant 1; the identity "
            "rides the label).",
            labelnames=("dtype",),
        ).set(1, dtype=kv_store_dtype(cfg))
        # paged-pool telemetry (serving/pages.py): point-in-time page
        # gauges plus the monotonic prefix-cache counters, mirrored
        # from the pool's locked host state on every gauge refresh —
        # scraped at /metrics, aggregated fleet-wide at /fleet/metrics,
        # snapshotted into /health as "kv_pages"
        if self._pages is not None:
            st = self._pages.stats()
            self.registry.gauge(
                "serving_kv_pages_total",
                "Physical KV pages in the pool (trash page excluded).",
            ).set(st["total"])
            self._pages_free_gauge = self.registry.gauge(
                "serving_kv_pages_free",
                "KV pages currently unallocated.",
            )
            self._pages_cached_gauge = self.registry.gauge(
                "serving_kv_pages_cached",
                "KV pages held by the radix prefix cache.",
            )
            self._cow_forks_counter = self.registry.counter(
                "serving_kv_pages_cow_forks_total",
                "Copy-on-write forks of shared prefix pages.",
            )
            self._prefix_hits_counter = self.registry.counter(
                "serving_prefix_cache_hits_total",
                "Admissions that reused a cached prompt prefix.",
            )
            self._prefix_misses_counter = self.registry.counter(
                "serving_prefix_cache_misses_total",
                "Admissions with no cached prefix to reuse.",
            )
            self._prefix_evictions_counter = self.registry.counter(
                "serving_prefix_cache_evictions_total",
                "Cached prefix pages LRU-evicted under page pressure.",
            )
            self.registry.gauge(
                "serving_kv_page_bytes",
                "HBM bytes per physical KV page across all layers "
                "(int8-aware: values + fp32 scale planes).",
            ).set(page_bytes(cfg, self.serving.kv_page_size))
            self._tier_prefix_hits_counter = self.registry.counter(
                "serving_host_tier_prefix_hits_total",
                "Admissions whose prefix match extended into the "
                "host tier (promoted, never recomputed).",
            )
        # host-tier telemetry: byte/entry gauges plus the tier's locked
        # counters, mirrored on every gauge refresh (the page-pool
        # pattern) — the "Serving under memory pressure" runbook's
        # dashboard surface
        if self._tier is not None:
            self.registry.gauge(
                "serving_host_tier_budget_bytes",
                "Configured host-RAM byte budget of the KV page tier.",
            ).set(self.serving.host_tier_bytes)
            self._tier_bytes_gauge = self.registry.gauge(
                "serving_host_tier_bytes",
                "Host bytes currently held by the KV page tier "
                "(cached prefixes + pinned preemption stashes).",
            )
            self._tier_entries_gauge = self.registry.gauge(
                "serving_host_tier_entries",
                "Demoted prefix pages currently cached in the host tier.",
            )
            self._tier_stashes_gauge = self.registry.gauge(
                "serving_host_tier_stashes",
                "Preempted requests with KV stashed in the host tier.",
            )
            self._tier_hits_counter = self.registry.counter(
                "serving_host_tier_hits_total",
                "Host-tier prefix lookups that hit a demoted page.",
            )
            self._tier_misses_counter = self.registry.counter(
                "serving_host_tier_misses_total",
                "Host-tier prefix lookups that missed.",
            )
            self._tier_evictions_counter = self.registry.counter(
                "serving_host_tier_evictions_total",
                "Cached tier pages LRU-evicted under the byte budget.",
            )
            self._tier_corrupt_counter = self.registry.counter(
                "serving_host_tier_corrupt_total",
                "Tier page images whose CRC32 verify failed (dropped "
                "and recomputed, never injected).",
            )
        # speculative-decoding telemetry: the aggregate proposed/
        # accepted counters ride _STAT_SPEC (so /health and /metrics
        # can never disagree); the acceptance-rate gauge and the
        # drafter identity/footprint land here. All on /metrics, all
        # summed/labeled through the fleet aggregation like every
        # other serving series.
        self._spec_accept_gauge = None
        if self._spec_k:
            self._spec_accept_gauge = self.registry.gauge(
                "serving_spec_acceptance_rate",
                "Accepted / proposed draft tokens (cumulative).",
            )
            self.registry.gauge(
                "serving_spec_draft_len",
                "Compiled draft-length rung k of the fused verify step.",
            ).set(self._spec_k)
            self.registry.gauge(
                "serving_spec_mode",
                "Active speculative-decoding drafter (constant 1; the "
                "identity rides the label).",
                labelnames=("mode",),
            ).set(1, mode=self.serving.spec_mode)
            drafter_bytes = getattr(self._drafter, "bytes_total", None)
            if drafter_bytes is not None:
                # the model drafter's own KV pool is HBM the operator
                # must account beside the target's pages/slots (README
                # "Speculative decoding" runbook's equal-HBM recipe)
                self.registry.gauge(
                    "serving_spec_drafter_kv_bytes",
                    "HBM bytes held by the drafter's own KV slot pool.",
                ).set(drafter_bytes())
        # structured-decoding telemetry: in-flight constrained requests
        # plus the compile cache's locked counters, mirrored into the
        # registry on every gauge refresh (the page-pool pattern) —
        # scraped at /metrics, aggregated fleet-wide, snapshotted into
        # /health as "constraints"
        self._constrained_gauge = self.registry.gauge(
            "serving_constrained_requests_active",
            "In-flight requests decoding under a compiled constraint.",
        )
        self._ccache_entries_gauge = self.registry.gauge(
            "serving_constraint_cache_entries",
            "Compiled constraint FSMs currently cached.",
        )
        self._ccache_bytes_gauge = self.registry.gauge(
            "serving_constraint_cache_bytes",
            "Host bytes held by cached constraint FSM tables.",
        )
        self._ccache_hits_counter = self.registry.counter(
            "serving_constraint_cache_hits_total",
            "Constraint compiles avoided by the FSM cache.",
        )
        self._ccache_misses_counter = self.registry.counter(
            "serving_constraint_cache_misses_total",
            "Constraint specs compiled from scratch.",
        )
        # model-quality telemetry (obs/quality.py): the in-jit quality
        # tail's host-side aggregation — per-token entropy/margin
        # histograms on the fixed fingerprint bin ladders, per-layer
        # effective-lambda gauges (the paper's central quantity, live
        # from the SERVING params), the PSI drift score against an
        # optional recorded fingerprint, and the constraint-validity
        # rate the canary judge's quality axis reads. The accumulator
        # dict and fault flag exist unconditionally (cheap pops on
        # every retire path); metrics + monitor only when
        # ServingConfig.quality_telemetry is on.
        self._q_acc: dict = {}
        self._q_force_nan = False
        self._q_constraint_total = 0
        self._q_constraint_bad = 0
        self._quality_monitor = None
        self._lambda_gauge = None
        self._lambda_summary: dict = {}
        if self._quality:
            ref = None
            if self.serving.quality_fingerprint:
                # a bad reference path must fail at BUILD, not judge
                # garbage drift at rollout time
                ref = load_fingerprint(self.serving.quality_fingerprint)
            self._quality_monitor = QualityMonitor(reference=ref)
            self._q_entropy_hist = self.registry.histogram(
                "serving_token_entropy",
                "Sampled-distribution entropy (nats) per emitted token.",
                buckets=ENTROPY_BINS,
            )
            self._q_margin_hist = self.registry.histogram(
                "serving_logit_margin",
                "Top-1 vs top-2 processed-logit margin per emitted "
                "token.",
                buckets=MARGIN_BINS,
            )
            self._q_drift_gauge = self.registry.gauge(
                "serving_quality_drift",
                "Max PSI drift of the live entropy/margin sketches vs "
                "the recorded reference fingerprint (0 = no reference, "
                "thin evidence, or no drift).",
            )
            self._q_validity_gauge = self.registry.gauge(
                "serving_constraint_validity_rate",
                "Fraction of finished constrained requests that did "
                "NOT dead-end (1.0 until any constrained request "
                "finishes).",
            )
            self._q_validity_gauge.set(1.0)
            self._lambda_gauge = self.registry.gauge(
                "serving_lambda_mean",
                "Per-layer effective differential-attention lambda of "
                "the serving params (head/term mean; absent for the "
                "control family).",
                labelnames=("layer",),
            )
            self._refresh_lambda_gauges()
        # Continuous on-device profiling (obs/device_profile.py): every
        # profile_every engine iterations, wrap ONE iteration in a
        # jax.profiler capture, parse it off-loop, and publish device_*
        # gauges into this same registry (scraped at /metrics), JSONL
        # rows under the spool, and a stitchable device-lane trace.
        # Uncaptured iterations pay one integer compare; the capture
        # wraps already-compiled steps, so the decode compile count
        # stays pinned at 1 (tests/test_device_profile.py).
        self._device_prof = None
        if self.serving.profile_every > 0:
            from differential_transformer_replication_tpu.obs.device_profile import (
                DeviceProfileSampler,
            )

            self._device_prof = DeviceProfileSampler(
                every=self.serving.profile_every,
                spool_dir=self.serving.profile_dir,
                registry=self.registry,
                tracer=self.tracer,
                process="serving",
            )

    # -- submission ---------------------------------------------------

    def submit(self, prompt: Sequence[int],
               params: Optional[SamplingParams] = None,
               deadline: Optional[float] = None,
               trace: Optional[TraceContext] = None, **kw) -> int:
        """Queue one request; returns its request_id. ``kw`` are
        SamplingParams fields (max_new_tokens, temperature, top_k, seed,
        eos_token_id). ``deadline`` is an ABSOLUTE ``time.perf_counter``
        timestamp after which the engine stops working on the request
        (shed at admission / retired mid-decode, ``finish_reason ==
        "deadline"``); None applies ``ServingConfig.default_deadline_s``
        when set. ``trace`` is the request's cross-process trace
        context (obs/trace.py): host-side only, stamped onto the
        admit/first_token/finish instants and the request-lifetime span
        when tracing is on, echoed as ``RequestOutput.trace_id`` —
        never touching the jitted closures, so tracing costs zero
        recompiles. Raises ValueError when the request cannot fit the
        engine's static shapes (see module docstring on family limits).
        """
        rid = self._next_id
        self._next_id += 1
        req = Request.make(rid, prompt, params, **kw)
        M = self.cfg.block_size
        p = np.asarray(req.prompt, np.int32)
        if self.cfg.cannot_roll:
            if p.shape[0] + req.params.max_new_tokens > M:
                raise ValueError(
                    f"prompt ({p.shape[0]}) + max_new_tokens "
                    f"({req.params.max_new_tokens}) exceeds block_size ({M}) "
                    + ("and the diff family's learned absolute position "
                       "table cannot roll with a KV cache (models/decode.py)"
                       if self.cfg.model == "diff" else
                       f"and the {self.cfg.model} family's cache cannot "
                       "roll: its MLA layers see every earlier position, "
                       "which a rolled ring of latents no longer holds "
                       "(models/decode.py)"
                       if self._latent_layers else
                       f"and the {self.cfg.model} family's cache cannot "
                       "roll: its attention layers see every earlier "
                       "position, which a rolled ring no longer holds "
                       "(models/decode.py)"
                       if self.cfg.full_layers_rotate else
                       f"and the {self.cfg.model} family's cache cannot "
                       "roll: its attention layers (afmoe's full ones) "
                       "carry no position (models/decode.py)")
                )
        else:
            if p.shape[0] > M:
                p = p[-M:]  # the reference's own crop (control.py:165)
            if p.shape[0] + req.params.max_new_tokens > self.max_total:
                raise ValueError(
                    f"cropped prompt ({p.shape[0]}) + max_new_tokens "
                    f"({req.params.max_new_tokens}) exceeds the engine's "
                    f"max_seq_len ({self.max_total}); build the engine with "
                    "a larger ServingConfig.max_seq_len"
                )
        if self._pages is not None:
            # a request whose worst case exceeds the whole pool can
            # NEVER be admitted — fail typed at submit instead of
            # parking it at the queue head forever
            need = self._pages.pages_needed(
                int(p.shape[0]), req.params.max_new_tokens
            )
            if need > self._pages.capacity:
                self.stats.inc("rejected")
                err = PagePoolExhaustedError(
                    f"request needs {need} KV pages but the pool holds "
                    f"{self._pages.capacity}; raise "
                    "ServingConfig.kv_pool_pages or lower "
                    "max_new_tokens"
                )
                err.retriable = False
                raise err
        # structured decoding: compile (or cache-hit) the constraint
        # BEFORE the scheduler sees the request — a malformed spec
        # fails typed (ConstraintCompileError -> HTTP 400) with the
        # engine untouched: no queue entry, no key chain, no slot
        ckey = None
        cfsm = None
        if req.params.constrained:
            eos = (
                req.params.eos_token_id
                if req.params.eos_token_id is not None
                else self.serving.eos_token_id
            )
            ckey = spec_key(req.params, eos)
            if self._vocab is None:
                self.stats.inc("rejected")
                raise ConstraintCompileError(
                    "constrained request but the engine was built "
                    "without a vocabulary (pass vocab= — the id->string "
                    "table the FSM compiler walks)"
                )
            try:
                cfsm = self._constraint_cache.acquire(ckey, self._vocab)
            except ConstraintCompileError:
                self.stats.inc("rejected")
                raise
        now = time.perf_counter()
        if deadline is None and self.serving.default_deadline_s > 0:
            deadline = now + self.serving.default_deadline_s
        # admission bound next (scheduler.submit raises QueueFullError
        # when the wait queue is at ServingConfig.max_queue_len) — a
        # rejected request must leave no key-chain or constraint
        # reference behind
        try:
            self.scheduler.submit(req, p, now, deadline or 0.0,
                                  trace=trace)
        except Exception:
            if ckey is not None:
                self._constraint_cache.release(ckey)
            self.stats.inc("rejected")
            raise
        if ckey is not None:
            self._constraints[rid] = (ckey, cfsm)
        self._base_keys[rid] = _prng_key_words(req.params.seed)
        return rid

    def cancel(self, request_id: int) -> bool:
        """Abandon an in-flight request: dropped from the wait queue, or
        its slot retired so the KV rows return to the pool. Without this
        a caller that times out leaves the engine decoding to completion
        for nobody — the slot leak serving/server.py's timeout path used
        to have. Returns False when the request is unknown or already
        finished (its output was, or is about to be, delivered)."""
        if request_id not in self._base_keys:
            return False
        self.scheduler.cancel(request_id)
        del self._base_keys[request_id]
        self._drop_constraint(request_id)
        self._drop_resume(request_id)
        self._q_acc.pop(request_id, None)
        self.stats.inc("cancelled")
        self._finished_counter.inc(reason="cancelled")
        return True

    def _drop_constraint(self, request_id: int) -> None:
        """Release a request's compiled-FSM reference on EVERY path
        that forgets its key chain (finish, cancel, shed, expire,
        crash) — a leaked reference would pin the cache entry forever."""
        ent = self._constraints.pop(request_id, None)
        if ent is not None:
            self._constraint_cache.release(ent[0])

    # -- one engine iteration -----------------------------------------

    def has_work(self) -> bool:
        """Requests queued or in slots, or a dispatched step whose tokens
        the host has not read: a :meth:`step` with nothing left to
        dispatch reads it, so the last token of the last request is
        delivered without another arrival."""
        return self.scheduler.has_work() or self._inflight is not None

    def _no_rows_sampled(self):
        """The device's record of the last sampled rows before any step
        ran: zeros of the pool-wide sampler call's packed output shape,
        so the decode program sees ONE operand shape from its first call
        (no row reads it: a row is ``from_host`` or was written)."""
        return jnp.zeros(
            (self._rows, 3 + 2 * self._lp_k + (3 if self._quality else 0)),
            jnp.int32)

    def queue_len(self) -> int:
        """Requests waiting for a slot (admission-queue depth)."""
        return self.scheduler.queue_len()

    def step(self) -> List[RequestOutput]:
        """Deadline shed -> admit -> prefill (budgeted) -> batched
        decode, READ AN ITERATION LATE. Returns the requests that
        finished THIS iteration (including ones retired with
        ``finish_reason == "deadline"``).

        The loop's order: (1) schedule and dispatch the admitted
        prompts' chunks, a completed prompt's first token sampled and
        written into the device's record of sampled rows without a read;
        (2) dispatch the decode step for the rows the host KNOWS are live,
        their tokens taken on the device from that record; (3) only then
        read what the iteration BEFORE dispatched (its first tokens,
        emitted at once, then its step's packed rows and its expert
        load: :meth:`_read`), work the device finished while the host was
        dispatching; (4) emit and finish from that read. A step needs the host to have seen the
        last tokens first (``_reads_first``: a live row with an FSM mask
        or a penalty, the speculative path, quality telemetry, a profile
        capture): there the same loop reads what is in flight before it
        builds the step and reads the step right after its dispatch, as
        every iteration did before. A row that ended on a token the host
        could not foresee (EOS, a stop sequence, a cancel, a deadline)
        has one step too many in flight: its token is dropped when read
        (``_deliver``), its K/V write lies in a slot or a page of its own
        that the next occupant overwrites in device order."""
        if not self.has_work():
            out, self._finished_prior = self._finished_prior, []
            return out
        iteration = self.stats["iterations"]
        t_step = time.perf_counter()
        # non-due iterations pay one integer compare; a due one opens a
        # device-profile capture window around exactly this iteration
        capturing = (
            self._device_prof is not None
            and self._device_prof.maybe_begin(iteration)
        )
        faults.serve_fire(iteration)
        if self._quality:
            # chaos drills for the drift detector (utils/faults.py):
            # quality_drift perturbs the live params — logits stay
            # FINITE, so requests keep succeeding and latency is flat;
            # only the quality axis can catch it. quality_nan poisons
            # this iteration's telemetry tail host-side — it must
            # degrade to "no signal", never crash the step or judge.
            if faults.quality_drift_at(iteration):
                self._apply_quality_drift()
            self._q_force_nan = faults.quality_nan_at(iteration)
        # build into the survives-an-exception buffer: a request that
        # finishes (or is deadline-shed) early in this step and is
        # already retired must still reach its caller when a LATER part
        # of the same step crashes (see take_finished)
        finished = self._finished_prior

        if self._pages is not None and faults.page_exhaust_at(iteration):
            # chaos hook: the next admission plan raises the typed
            # PagePoolExhaustedError — proving the 503 shed path
            self._pages.force_exhaust()

        with self.tracer.span("schedule", iteration=iteration):
            # deadline enforcement, both placements, BEFORE device work:
            # expired queue entries never get a slot, expired slots
            # return their KV rows to the pool instead of decoding for
            # nobody
            now = time.perf_counter()
            for req, prompt, t_submit, _dl, trace in (
                self.scheduler.shed_expired(now)
            ):
                finished.append(
                    self._expire_queued(req, prompt, t_submit, now, trace)
                )
            for slot in self.scheduler.expired_slots(now):
                finished.append(self._finish(slot, "deadline", now=now))
            admit = None
            if self._pages is not None:
                # paged admission keys on FREE PAGES, not slots: the
                # gate plans each head-of-line request against the
                # radix cache + page pool (serving/pages.py) before the
                # scheduler commits a slot to it
                admit = (
                    lambda slot, entry: self._admit_paged(
                        slot, entry, iteration, finished
                    )
                )
            chunks = self.scheduler.plan(admit=admit)

        if self._resumed:
            # requests swapped back in by this plan's admission gate:
            # restore the host-side decode state snapshotted at
            # preemption — the device KV was re-injected bit-exact
            # above, so generation continues as if never interrupted
            # (pinned by tests/test_tiering.py). plan() committed the
            # slot as a fresh PREFILL with filled == prompt_len, so no
            # prefill chunks were planned for it.
            for slot, snap in self._resumed:
                slot.generated = list(snap["generated"])
                slot.token_times = list(snap["token_times"])
                slot.first_token_time = snap["first_token_time"]
                slot.filled = snap["filled"]
                slot.cached_len = snap["cached_len"]
                slot.spec_proposed = snap["spec_proposed"]
                slot.spec_accepted = snap["spec_accepted"]
                slot.prompt_ids = snap["prompt_ids"]
                slot.penalty_counts = snap["penalty_counts"]
                slot.token_logprobs = snap["token_logprobs"]
                slot.top_logprobs = snap["top_logprobs"]
                ent = self._constraints.get(slot.request.request_id)
                if ent is not None:
                    # attach the FSM directly — _slot_fsm's lazy path
                    # would RESET the cursor to the FSM's start state
                    slot.constraint = ent[1]
                    slot.fsm_state = snap["fsm_state"]
                # the host alone knows the last token: the next step
                # takes it from the host's operand
                slot.dispatched = len(slot.generated)
                slot.token_on_device = False
                slot.state = ACTIVE
                self._resume.pop(slot.request.request_id, None)
            self._resumed = []

        if chunks:
            with self.tracer.span(
                "prefill", iteration=iteration, chunks=len(chunks)
            ):
                self._run_prefill(chunks, iteration)

        if faults.serve_corrupt_at(iteration):
            self._corrupt_one_slot()
        if self._pages is not None and faults.prefix_corrupt_at(iteration):
            self._corrupt_cached_prefix()

        active = self._live_rows()
        cause = self._reads_first(active, capturing)
        if cause and (self._inflight is not None or self._firsts):
            # this step is built from what the last tokens did to the
            # host's state: read what is in flight before building it
            self._drain(iteration)
            active = self._live_rows()
        if self._constraints:
            if faults.constrain_dead_end_at(iteration):
                # chaos hook: poison the first constrained ACTIVE
                # slot's FSM cursor with the dead-end sentinel — the
                # sweep below must retire it typed, never hang or emit
                # a garbage token (the sweep runs BEFORE decode ever
                # consumes the zeroed mask)
                for s in active:
                    if self._slot_fsm(s) is not None:
                        s.fsm_state = -1
                        break
            swept = False
            for s in active:
                fsm = self._slot_fsm(s)
                if fsm is None:
                    continue
                if s.fsm_state >= 0 and fsm.masks[s.fsm_state].any():
                    continue
                # all-zero mask row: nothing this slot could emit.
                # Accepting state = the structure is complete and no
                # EOS was configured — a normal typed completion.
                # Non-accepting = a true dead end (compiled FSMs prune
                # dead states, so only the fault sentinel reaches
                # here) — typed retriable failure, partial output
                # delivered, slot + pages reclaimed through the
                # standard retire path.
                swept = True
                finished.append(self._finish(
                    s,
                    "constraint_complete"
                    if fsm.is_accepting(s.fsm_state)
                    else "constraint_dead_end",
                ))
            if swept:
                active = self._live_rows()
        proposals = {}
        if active and self._spec_k:
            proposals = self._collect_proposals(active, iteration)
        if active and proposals:
            self._decode_spec(active, proposals, iteration, finished)
        else:
            # the plain L=1 step — also the spec engine's k=0 ladder
            # rung, taken whenever no slot has a proposal this
            # iteration (drafter dry, all slots near their windows,
            # or a rebuilt drafter falling back)
            logits = None
            rec = None
            if active:
                logits, rec = self._dispatch_decode(active, iteration, cause)
            elif self._firsts:
                rec = _InFlight(iteration)
            if rec is not None:
                rec.firsts, self._firsts = self._firsts, []
            # what this iteration reads: under a cause its own work (the
            # next step is built from these tokens), else the work of
            # the iteration before, which the device finished while the
            # host was dispatching; this iteration's stays in flight
            if cause:
                reads = rec
            else:
                reads, self._inflight = self._inflight, rec
            if logits is not None or reads is not None:
                with self.tracer.span("sample", iteration=iteration):
                    if logits is not None:
                        rec.out = self._last_out = self._sample_dispatch(
                            [(s.index, s) for s in active], self._rows,
                            logits, iteration, "decode")
                    if reads is not None:
                        out = self._read(reads, iteration, finished)
            if reads is not None:
                self._deliver(reads, out, finished, iteration)

        # the iteration's book-keeping; it lies AFTER the iteration's
        # last stamped span, so it carries no ``iteration`` (readers
        # take an iteration from its first to its last stamped span)
        with self.tracer.span("step_tail"):
            if capturing:
                # close the window (blocking on a cache leaf so the
                # iteration's device work is inside it) and hand the
                # trace to the off-loop parse worker
                self._device_prof.end(
                    sync=next(iter(self.cache[0].values())))
            self.stats.inc("iterations")
            self._step_hist.observe(time.perf_counter() - t_step)
            self._update_gauges()
        self._finished_prior = []
        return finished

    def _run_prefill(self, chunks, iteration: int) -> None:
        """Execute one iteration's planned prefill chunks (see
        :meth:`Scheduler.plan`); extracted so the step's tracer span
        brackets exactly the prefill device work. Inside it, per chunk:
        ``prefill_call`` (the chunk's transfer and the dispatch of its
        program, which returns before the device is done) and, on the
        chunk that completes a prompt, ``first_token`` (the one-row
        sampler call and the write of its row into the device's record
        of sampled rows, both dispatched and neither read: the row
        joins this iteration's decode step, the host reads the token
        with the next read, :meth:`_deliver`)."""
        for slot, start, size in chunks:
            if start == slot.cached_len:
                # first chunk actually RUN = the request finally got a
                # slot: the submit->admission interval is the
                # queue-wait component of TTFT. With a radix prefix
                # hit, start lands at cached_len, not 0 — the skipped
                # pages are the near-zero-TTFT win.
                self._queue_wait_hist.observe(
                    time.perf_counter() - slot.submit_time
                )
                if self._tracing:
                    self.tracer.instant(
                        "admit", rid=slot.request.request_id,
                        cached=slot.cached_len,
                        **(instant_args(slot.trace)
                           if slot.trace is not None else {}),
                    )
                if self._recurrent:
                    self._reset_slot_state(slot, iteration)
            with self.tracer.span(
                "prefill_call", iteration=iteration, size=size
            ):
                chunk = slot.prompt[start:start + size][None]
                if self._pads_tail:
                    # the ladder's shape that holds the chunk; what is
                    # past ``size`` is padding (Scheduler.plan)
                    tokens = np.zeros((1, padded_chunk(size)), np.int32)
                    tokens[:, :size] = chunk
                    logits, self.cache = self._prefill_fn(
                        self.params, self.cache, np.int32(slot.index),
                        jnp.asarray(tokens), np.int32(start),
                        np.int32(size),
                    )
                elif self._pages is not None:
                    logits, self.cache = self._prefill_fn(
                        self.params, self.cache,
                        jnp.asarray(self._pages.table_row(slot.index)),
                        jnp.asarray(chunk), np.int32(start),
                    )
                else:
                    logits, self.cache = self._prefill_fn(
                        self.params, self.cache, np.int32(slot.index),
                        jnp.asarray(chunk), np.int32(start),
                    )
            slot.filled = start + size
            self.stats.inc("prefill_tokens", size)
            if slot.filled != slot.prompt_len:
                continue
            # prompt complete: the chunk's last-position logits give
            # the first generated token (generate_cached's contract)
            with self.tracer.span("first_token", iteration=iteration):
                out = self._sample_dispatch(
                    [(0, slot)], 1, logits[None], iteration, "prefill")
                self._last_out = _set_row_fn(
                    self._last_out, np.int32(slot.index), out)
            slot.state = ACTIVE
            self._firsts.append((slot, slot.request.request_id, out))

    def _reset_slot_state(self, slot: Slot, iteration: int) -> None:
        """Zero the recurrent state the slot's last sequence left behind,
        before the new one's first chunk (a ring needs none: positions
        mask it). Dispatched like a prefill chunk, in place in the
        donated pool; retirement, cancellation and a deadline leave the
        state where it is, and a slot nobody holds is never read."""
        with self.tracer.span("state_reset", iteration=iteration, slots=1):
            self.cache = _reset_state_fn(self.cache, np.int32(slot.index))
        self.stats.inc("state_resets")

    # -- the late read --------------------------------------------------

    def _live_rows(self) -> List[Slot]:
        """The rows the host KNOWS the next decode step runs: the ACTIVE
        slots but those whose last token is already dispatched (a row that
        reaches ``max_new_tokens`` with the token in flight is known
        beforehand and left out). With everything read that is every
        ACTIVE slot."""
        return [s for s in self.scheduler.active_slots()
                if s.dispatched < s.request.params.max_new_tokens]

    def _reads_first(self, rows: List[Slot], capturing: bool) -> str:
        """Why this iteration's decode step needs the host to have read
        every token before it is built, or "" where it does not and the
        read may come an iteration late. Decided an iteration from what
        the rows ask and the engine's own state, by no option: a live row
        with an FSM mask or a penalty (the cursor and the histogram
        advance on the host, ``_emit``), the speculative path (proposals
        are drafted from the tokens read), quality telemetry (the
        sampler's previous-token column), a profile capture that closes
        on this iteration."""
        if capturing:
            return "profile"
        if self._quality:
            return "quality"
        if self._spec_k:
            return "spec"
        for s in rows:
            asks = self._row_asks(s)
            if asks & ASK_MASK:
                return "mask"
            if asks & ASK_PENALTY:
                return "penalty"
        return ""

    def _owns(self, slot: Slot, rid: int) -> bool:
        """Whether the slot still holds the request a dispatched row was
        built for (request ids are never reused)."""
        return (slot.state != FREE and slot.request is not None
                and slot.request.request_id == rid)

    def _drain(self, iteration: Optional[int] = None) -> None:
        """Read and deliver EVERYTHING in flight, now: before a step that
        is built from the host's state (:meth:`_reads_first`) and before
        anything that reads a slot's host state whole
        (``export_slot_state``, ``_preempt_slot``, ``close``). Outside a
        step what finishes waits in ``_finished_prior`` for the next
        :meth:`step` or :meth:`take_finished`."""
        if self._inflight is None and not self._firsts:
            return
        if iteration is None:
            iteration = self.stats["iterations"]
        rec = self._inflight or _InFlight(iteration)
        rec.firsts = rec.firsts + self._firsts
        self._inflight, self._firsts = None, []
        with self.tracer.span("sample", iteration=iteration):
            out = self._read(rec, iteration, self._finished_prior)
        self._deliver(rec, out, self._finished_prior, iteration)

    def _dispatch_decode(self, active: List[Slot], iteration: int,
                         cause: str):
        """Build and dispatch the L = 1 step for ``active``; returns its
        logits (on the device) and its in-flight record. The rows' host
        operand is ONE packed int32 array (``pack_decode_rows``'s
        layout); a row's token rides in it only where the host alone
        knows it (``Slot.token_on_device`` false), else the program takes
        it from ``_last_out``; positions count the DISPATCHED tokens."""
        with self.tracer.span("decode_inputs", iteration=iteration):
            B = self._rows
            rows = np.zeros((B, 4), np.int32)
            for s in active:
                row = rows[s.index]
                if not s.token_on_device:
                    row[0], row[3] = s.generated[-1], 1
                row[1] = s.prompt_len + s.dispatched - 1
                row[2] = 1
            pos, mask = rows[:, 1], rows[:, 2].astype(bool)
            operands = (rows,)
            if self._pages is not None:
                # page tables + per-row write pages ride the one
                # jitted step as runtime int32 arrays; inactive
                # rows write the trash page (the contiguous path
                # gives them no write target)
                M = self.cfg.block_size
                ps = self.serving.kv_page_size
                tables = self._pages.tables()
                write_pages = np.zeros((B,), np.int32)
                for s in active:
                    write_pages[s.index] = tables[
                        s.index, (pos[s.index] % M) // ps
                    ]
                operands = (rows, tables, write_pages)
        # the decode step is one batched op over every active slot;
        # its span carries the trace ids it advanced so a stitched
        # timeline shows which requests shared each iteration
        decode_args = {"iteration": iteration, "active": len(active),
                       # 1: dispatched before the step before was read
                       "lookahead": int(not cause)}
        if cause:
            decode_args["drain"] = cause
            self.stats.inc("lookahead_drains")
        else:
            self.stats.inc("lookahead_steps")
            # rows of the step in flight (this iteration reads it) whose
            # request has ended since: their tokens will be dropped
            decode_args["inflight_dropped"] = (
                0 if self._inflight is None else sum(
                    1 for s, rid in self._inflight.rows
                    if not self._owns(s, rid)))
        if self._own_ring_attend:
            # what the step's attention reads of the pool: the rule
            # the program runs on this mask, read on the host
            decode_args["attend_rows"] = attend_rows(mask)
            self.stats.inc("decode_attend_rows", decode_args["attend_rows"])
        if self._tracing:
            tids = [
                s.trace.trace_id for s in active
                if s.trace is not None
            ]
            if tids:
                decode_args["trace_ids"] = tids
        if self._window_layers:
            # what the rows hold of their rings of either length:
            # from the positions just built, no device read
            kv = decode_args["kv"] = live_kv(pos, mask,
                                             self.cfg.sliding_window)
            self.stats.inc(
                "decode_live_kv",
                self._window_layers * kv["live_window"]
                + (self.cfg.n_layer - self._window_layers)
                * kv["live_full"])
        if self._state_bytes_per_slot:
            # the recurrent state the step's update kernels move: the
            # active rows' states alone, read and written once
            decode_args["live_state_bytes"] = (
                int(mask.sum()) * self._state_bytes_per_slot)
            self.stats.inc("decode_live_state",
                           decode_args["live_state_bytes"])
        if self._latent_layers:
            # the latents the rows hold live, pos + 1 a row: what the
            # step's attention reads a layer, from the positions
            decode_args["latent_live"] = int(
                (pos[mask].astype(np.int64) + 1).sum())
            self.stats.inc(
                "decode_live_latent",
                self._latent_layers * decode_args["latent_live"])
        rec = _InFlight(
            iteration,
            rows=[(s, s.request.request_id) for s in active])
        if self.cfg.num_experts:
            # filled in when the step's expert load is read (this
            # iteration or the next): the span keeps the dict it was
            # handed
            decode_args["moe"] = rec.moe = {}
        with self.tracer.span("decode", **decode_args):
            # the host's share of the step, taken apart: the
            # operands' transfers, one each, then the call, which
            # returns before the device is done
            with self.tracer.span(
                "decode_h2d", **self._h2d_args(iteration, operands)
            ):
                operands = [jnp.asarray(a) for a in operands]
            with self.tracer.span("decode_dispatch",
                                  iteration=iteration):
                logits, self.cache, *load = self._decode_fn(
                    self.params, operands[0], self._last_out,
                    self.cache, *operands[1:],
                )
        if load:
            rec.load = load[0]
        return logits, rec

    def _read(self, rec: _InFlight, iteration: int,
              finished: List[RequestOutput]):
        """The blocking read of an iteration, inside the caller's
        ``sample`` span, in the order the device made the work: the first
        tokens of the prompts that finished in the record's iteration
        (``token_read`` with ``path: prefill``), EMITTED at once, because
        their programs ended a whole decode step before the step's own
        rows are there and a first token should not wait that step out;
        then the step's packed rows (``token_read`` with ``path:
        decode``: the wait for the device, the copy back, the thread's
        wake-up) and its expert load (``load_read``). The spans are
        stamped with the iteration the HOST is in; ``of_iteration`` is
        the one the work was dispatched in. Returns the step's packed
        rows (None for a record without a step) for :meth:`_deliver`."""
        args = {"iteration": iteration, "of_iteration": rec.iteration}
        if rec.firsts:
            with self.tracer.span("token_read", path="prefill", **args):
                firsts = [np.asarray(o)[0] for _, _, o in rec.firsts]
            self._deliver_firsts(rec, firsts, finished, iteration)
        if rec.out is None:
            return None
        with self.tracer.span("token_read", path="decode", **args):
            out = np.asarray(rec.out)
        if rec.load is not None:
            # the step has finished (its tokens were just read):
            # twelve bytes that are there, no second wait
            with self.tracer.span("load_read", moe=rec.moe, **args):
                held, top, hit, *reached = (
                    int(v) for v in np.asarray(rec.load))
                rec.moe.update(held=held, max_expert=top, experts_hit=hit)
                self.stats.inc("moe_held", held)
                self.stats.inc("moe_experts_hit", hit)
                if reached:  # a router limited to groups
                    rec.moe["rows_in_held_group"] = reached[0]
                    self.stats.inc("moe_rows_in_held_group", reached[0])
        return out

    def _emit_row(self, slot: Slot, row: np.ndarray, now: float,
                  finished: List[RequestOutput]) -> None:
        """One packed sampler row (``_build_step_fns._sample``'s output
        contract) emitted for its slot."""
        self._emit(
            slot, int(row[0]), now, finished,
            lp=self._lp_echo(slot, row),
            q=(self._quality_echo(row) if self._quality else None),
        )

    def _deliver_firsts(self, rec: _InFlight, firsts,
                        finished: List[RequestOutput],
                        iteration: int) -> None:
        """Emit the first tokens a read brought back. A non-finite one of
        a request that still holds its slot raises
        :class:`EngineCrashError` as its turn comes (what finished before
        it is in ``take_finished``); one whose request ended meanwhile (a
        cancel, a deadline) is dropped and counted."""
        dropped = 0
        with self.tracer.span("emit", iteration=iteration):
            now = time.perf_counter()
            for (slot, rid, _), row in zip(rec.firsts, firsts):
                if not self._owns(slot, rid):
                    dropped += 1
                    continue
                if not row[1]:
                    raise EngineCrashError(
                        f"non-finite logits prefilling slot {slot.index} "
                        f"(request {rid}): corrupt "
                        "slot pool or numerically diverged params"
                    )
                self._emit_row(slot, row, now, finished)
        if dropped:
            self.stats.inc("lookahead_dropped_rows", dropped)

    def _deliver(self, rec: _InFlight, out,
                 finished: List[RequestOutput], iteration: int) -> None:
        """Emit a read decode step's token a row (``out`` None: the
        record held first tokens only). A non-finite row of a request
        that still holds its slot raises :class:`EngineCrashError` naming
        slot and request BEFORE any token of the step is emitted. A row
        whose request ended between its dispatch and this read (an EOS or
        a stop sequence read late, its own first token's among them, a
        cancel, a deadline) is dropped and counted: nothing of it
        reaches a caller."""
        if out is None:
            return
        live = [s for s, rid in rec.rows if self._owns(s, rid)]
        bad = [s for s in live if not out[s.index, 1]]
        if bad:
            raise EngineCrashError(
                f"non-finite logits decoding slot(s) "
                f"{[s.index for s in bad]} (request(s) "
                f"{[s.request.request_id for s in bad]}): corrupt "
                "slot pool or numerically diverged params"
            )
        with self.tracer.span("emit", iteration=iteration):
            now = time.perf_counter()
            if live:
                self.stats.inc("decode_tokens", len(live))
            for slot in live:
                self._emit_row(slot, out[slot.index], now, finished)
        if len(live) < len(rec.rows):
            self.stats.inc("lookahead_dropped_rows",
                           len(rec.rows) - len(live))

    # -- speculative decoding (serving/spec.py) ------------------------

    def _collect_proposals(self, active, iteration: int) -> dict:
        """Ask the drafter for up to k tokens per eligible active slot.

        Eligibility clamps each slot's draft cap so the verify block
        can never (a) overrun the request's max_new_tokens budget (the
        corrected token must still fit), (b) write past the ring
        window — the verify writes positions pos..pos+cap, and a
        rolled-over write would evict keys a rejected row still needs
        visible (the ring "rollback" works precisely because rejected
        positions stay IN-window and invisible) — or (c) exceed the
        drafter's own window. Ineligible slots ride the same step with
        draft length 0 (runtime array — no recompile).
        """
        from differential_transformer_replication_tpu.serving.spec import (
            DraftSlot,
            constrain_proposals,
        )

        if faults.spec_drafter_crash_at(iteration):
            poison = getattr(self._drafter, "poison", None)
            if poison is not None:
                poison()
        infos = []
        for s in active:
            p = s.request.params
            cap = self._spec_k
            if p.draft_len is not None:
                cap = min(cap, p.draft_len)
            pos0 = s.prompt_len + len(s.generated) - 1
            cap = min(cap, p.max_new_tokens - len(s.generated) - 1)
            cap = min(cap, self._spec_window - 1 - pos0)
            if cap <= 0:
                continue
            if s.prompt_ids is None:
                # once per admission, not per iteration: per-element
                # int() of the numpy prompt in the decode hot loop is
                # exactly the host cost class this step budgets
                s.prompt_ids = [int(t) for t in s.prompt]
            infos.append(DraftSlot(
                s.index, s.prompt_ids + s.generated, pos0, cap,
            ))
        if not infos:
            return {}
        props = self._drafter.propose_all(infos)
        if props and self._constraints:
            # drop draft suffixes the slot's FSM can never accept —
            # the verify step would reject them row-for-row anyway
            # (serving/spec.py:constrain_proposals)
            fsms = {}
            for s in active:
                fsm = self._slot_fsm(s)
                if fsm is not None:
                    fsms[s.index] = (fsm, s.fsm_state)
            props = constrain_proposals(props, fsms)
        if not props:
            # the no-proposal signature of a tripped drafter: check
            # (and mirror) the crash counter only on this path so the
            # hot loop never takes the drafter lock twice per step
            crashes = self._drafter.stats()["drafter_crashes_total"]
            if crashes > self._drafter_crashes_seen:
                self.stats.inc(
                    "spec_drafter_crashes",
                    crashes - self._drafter_crashes_seen,
                )
                self._drafter_crashes_seen = crashes
                print(
                    "[serving] spec drafter pool tripped the "
                    "finite-logits guard; rebuilt from params, falling "
                    "back to non-spec decode this iteration",
                    file=sys.stderr,
                )
        return props

    def _decode_spec(self, active, proposals: dict, iteration: int,
                     finished: List[RequestOutput]) -> None:
        """One fused k+1-row verify step over the whole pool: build
        the (B, L) token/position block (row 0 = each slot's last
        emitted token, rows 1..dl its draft), write-redirect rows past
        each slot's draft length to the trash row/page, run the jitted
        step (multi-row forward + fused accept/reject), then emit each
        slot's accepted prefix + corrected token host-side."""
        with self.tracer.span("decode_inputs", iteration=iteration):
            B = self.serving.num_slots
            k = self._spec_k
            L = k + 1
            M = self.cfg.block_size
            c = 3 * L + k
            # ONE packed int operand (see _build_spec_step_fns._unpack):
            # tokens | positions | write targets | draft | dlen | counts |
            # topks | PRNG base (bitcast) | temperature (bitcast) |
            # force-reject | penalties (bitcast) — a single host->device
            # conversion per step
            ints = np.zeros((B, c + 10), np.int32)
            tok_blk = ints[:, 0:L]
            pos_blk = ints[:, L:2 * L]
            targets = ints[:, 2 * L:3 * L]
            draft = ints[:, 3 * L:c]
            bases = ints[:, c + 3:c + 5].view(np.uint32)
            temps = ints[:, c + 5].view(np.float32)
            temps[:] = 1.0
            pens = ints[:, c + 7:c + 10].view(np.float32)
            pens[:, 0] = 1.0  # repetition penalty (1 = off)
            need_mask = need_counts = False
            if self._pages is not None:
                tables = self._pages.tables()
                ps = self.serving.kv_page_size
                # targets default to the trash page 0
            else:
                targets[:] = B  # default: the trash row (cache batch B)
            for s in active:
                d = proposals.get(s.index, [])
                dl = len(d)
                p0 = s.prompt_len + len(s.generated) - 1
                prm = s.request.params
                row = ints[s.index]
                tok_blk[s.index, 0] = s.generated[-1]
                pos_blk[s.index, :] = p0  # clamp invalid rows' gathers
                for j, t in enumerate(d):
                    tok_blk[s.index, j + 1] = t
                    draft[s.index, j] = t
                pos_blk[s.index, :dl + 1] = p0 + np.arange(dl + 1)
                row[c] = dl  # dlen
                # counts: key-chain position, replay-offset like the L=1
                # sampler's column 0 (serving/migrate.py key_offset)
                row[c + 1] = prm.key_offset + len(s.generated)
                row[c + 2] = prm.top_k or 0  # topks
                bases[s.index] = self._base_keys[s.request.request_id]
                temps[s.index] = prm.temperature
                pens[s.index, 0] = prm.repetition_penalty
                pens[s.index, 1] = prm.presence_penalty
                pens[s.index, 2] = prm.frequency_penalty
                if self._slot_fsm(s) is not None:
                    need_mask = True
                if _penalties_on(prm):
                    need_counts = True
                if self._pages is not None:
                    for j in range(dl + 1):
                        targets[s.index, j] = tables[
                            s.index, (int(pos_blk[s.index, j]) % M) // ps
                        ]
                else:
                    targets[s.index, :dl + 1] = s.index
            dlen = ints[:, c]
            ints[0, c + 6] = int(faults.spec_reject_storm_at(iteration))
            # the verify pipeline's mask/histogram operands: per verify
            # row j, the FSM row for the state reached through drafts
            # 0..j-1 (walked host-side — table lookups, no device work)
            # and the PRE-BLOCK histogram (the kernel adds the in-block
            # draft cumsum itself). Inert cached constants when no active
            # slot engages the pipeline — the zero-recompile contract's
            # operand side.
            V = self.cfg.vocab_size
            am = cm = None
            if need_mask:
                am = np.ones((B, L, V), bool)
                for s in active:
                    fsm = self._slot_fsm(s)
                    if fsm is None:
                        continue
                    st = s.fsm_state
                    am[s.index, 0] = fsm.allowed_row(st)
                    for j, t in enumerate(proposals.get(s.index, [])):
                        st = fsm.advance(st, int(t))
                        am[s.index, j + 1] = fsm.allowed_row(st)
            if need_counts:
                cm = np.zeros((B, V), np.int32)
                for s in active:
                    if _penalties_on(s.request.params):
                        cm[s.index] = self._slot_counts(s)
        # accept-variant pick: all-greedy steps run the threefry-free
        # specialization (bit-identical on greedy rows)
        spec_fn = self._spec_fn[
            any(s.request.params.temperature > 0 for s in active)
        ]
        decode_args = {"iteration": iteration, "active": len(active)}
        if self._tracing:
            tids = [
                s.trace.trace_id for s in active if s.trace is not None
            ]
            if tids:
                decode_args["trace_ids"] = tids
        operands = [a for a in (
            ints, am, cm, None if self._pages is None else tables,
        ) if a is not None]
        with self.tracer.span("decode", **decode_args):
            with self.tracer.span(
                "decode_h2d", **self._h2d_args(iteration, operands)
            ):
                allowed3, pcounts = self._inert_ops(("spec", B), (B, L))
                if am is not None:
                    allowed3 = jnp.asarray(am)
                if cm is not None:
                    pcounts = jnp.asarray(cm)
                ints_d = jnp.asarray(ints)
                tables_d = (None if self._pages is None
                            else jnp.asarray(tables))
            with self.tracer.span("decode_dispatch", iteration=iteration):
                out, self.cache = spec_fn(
                    self.params, ints_d, self.cache, allowed3, pcounts,
                    tables_d,
                )
        # one transfer for all three host-consumed outputs: the wait for
        # the step, the copy back and the thread's wake-up
        with self.tracer.span("token_read", iteration=iteration,
                              path="decode"):
            out = np.asarray(out)
        toks = out[:, :L]
        n_emit = out[:, L]
        ok = out[:, L + 1].astype(bool)
        bad = [s for s in active if not ok[s.index]]
        if bad:
            raise EngineCrashError(
                f"non-finite logits verifying slot(s) "
                f"{[s.index for s in bad]} (request(s) "
                f"{[s.request.request_id for s in bad]}): corrupt "
                "slot pool or numerically diverged params"
            )
        with self.tracer.span("emit", iteration=iteration):
            now = time.perf_counter()
            emitted = 0
            for s in active:
                dl = int(dlen[s.index])
                n = int(n_emit[s.index])
                if s.constraint is not None:
                    # a constraint can CLOSE mid-verify-window: every
                    # later row's mask is all-zero, so its "greedy
                    # correction" is argmax(-inf) garbage. Truncate at
                    # the first token produced by a zeroed row — the
                    # next step's sweep retires the slot typed, exactly
                    # like the non-spec path (which never consumes a
                    # zero mask because the sweep runs before decode).
                    st, keep = s.fsm_state, 0
                    for j in range(n):
                        if st < 0 or not s.constraint.masks[st].any():
                            break
                        st = s.constraint.advance(
                            st, int(toks[s.index, j])
                        )
                        keep += 1
                    n = keep
                p0 = s.prompt_len + len(s.generated) - 1
                if dl:
                    s.spec_proposed += dl
                    s.spec_accepted += n - 1
                    self.stats.inc("spec_proposed", dl)
                    self.stats.inc("spec_accepted", n - 1)
                # the drafter's validity cursor follows the ACCEPTED
                # prefix; rejected drafter-cache entries past it are
                # rewound (re-fed next round)
                self._drafter.commit(s.index, p0 + n)
                for j in range(n):
                    emitted += 1
                    self._emit(
                        s, int(toks[s.index, j]), now, finished,
                        lp=self._spec_lp_echo(s, out[s.index], j, L),
                        q=(self._spec_quality_echo(out[s.index], j, L)
                           if self._quality else None),
                    )
                    if s.state == FREE:
                        break  # EOS/stop/length retired the slot mid-block
                # the block's tokens were read as they were made: the
                # host alone knows the last one
                s.dispatched = len(s.generated)
                s.token_on_device = False
            self.stats.inc("decode_tokens", emitted)

    def spec_stats(self) -> Optional[dict]:
        """Point-in-time speculative-decoding snapshot for /health
        (None when spec is off): mode, compiled draft rung, aggregate
        proposed/accepted/crash counters and the cumulative acceptance
        rate, plus the drafter's own locked counters."""
        if not self._spec_k:
            return None
        proposed = self.stats["spec_proposed"]
        accepted = self.stats["spec_accepted"]
        out = {
            "mode": self.serving.spec_mode,
            "verify": self.serving.spec_verify,
            "draft_len": self._spec_k,
            "proposed": proposed,
            "accepted": accepted,
            "acceptance_rate": (
                round(accepted / proposed, 4) if proposed else None
            ),
            "drafter_crashes": self.stats["spec_drafter_crashes"],
        }
        out["drafter"] = self._drafter.stats()
        return out

    def _update_gauges(self) -> None:
        """Refresh the point-in-time gauges (/metrics): slot occupancy,
        admission-queue depth, and the fraction of pooled KV positions
        holding live sequence state. Paged mode also mirrors the page
        pool's locked host counters into the registry."""
        occupied = self.scheduler.occupied()
        self._slot_gauge.set(occupied)
        self._queue_gauge.set(self.scheduler.queue_len())
        for cls, depth in self.scheduler.queue_depths().items():
            self._queue_class_gauge.set(depth, priority=cls)
        # structured-decoding mirror (BOTH cache layouts — keep it
        # ahead of the paged early-return below)
        self._constrained_gauge.set(len(self._constraints))
        cst = self._constraint_cache.stats()
        self._ccache_entries_gauge.set(cst["entries"])
        self._ccache_bytes_gauge.set(cst["bytes"])
        self._ccache_hits_counter.set(cst["hits_total"])
        self._ccache_misses_counter.set(cst["misses_total"])
        if self._spec_accept_gauge is not None:
            proposed = self.stats["spec_proposed"]
            self._spec_accept_gauge.set(
                self.stats["spec_accepted"] / proposed if proposed
                else 0.0
            )
        if self._quality_monitor is not None:
            # quality mirror (BOTH cache layouts — ahead of the paged
            # early-return below): the drift score is O(bins) host
            # math over the live sketches, nothing device-side
            self._q_drift_gauge.set(self._quality_monitor.drift())
            if self._q_constraint_total:
                self._q_validity_gauge.set(
                    1.0
                    - self._q_constraint_bad / self._q_constraint_total
                )
        if self._pages is not None:
            st = self._pages.stats()
            self._pages_free_gauge.set(st["free"])
            self._pages_cached_gauge.set(st["cached"])
            self._cow_forks_counter.set(st["cow_forks_total"])
            self._prefix_hits_counter.set(st["hits_total"])
            self._prefix_misses_counter.set(st["misses_total"])
            self._prefix_evictions_counter.set(st["evictions_total"])
            self._tier_prefix_hits_counter.set(st["tier_hits_total"])
            if self._tier is not None:
                ts = self._tier.stats()
                self._tier_bytes_gauge.set(ts["bytes"])
                self._tier_entries_gauge.set(ts["entries"])
                self._tier_stashes_gauge.set(ts["stashes"])
                self._tier_hits_counter.set(ts["hits_total"])
                self._tier_misses_counter.set(ts["misses_total"])
                self._tier_evictions_counter.set(ts["evictions_total"])
                self._tier_corrupt_counter.set(ts["corrupt_total"])
            held = sum(
                min(s.filled + len(s.generated), self.cfg.block_size)
                for s in self.scheduler.slots if s.state != FREE
            )
            self._kv_gauge.set(
                held / (st["total"] * self.serving.kv_page_size)
            )
            return
        held = sum(
            min(s.filled + len(s.generated), self.max_total)
            for s in self.scheduler.slots if s.state != FREE
        )
        self._kv_gauge.set(
            held / (self.serving.num_slots * self.max_total)
        )

    def page_stats(self) -> Optional[dict]:
        """Point-in-time page-pool snapshot for /health (None on the
        contiguous path): total/free/cached pages plus the monotonic
        prefix-cache counters (serving/pages.py:PagePool.stats)."""
        return None if self._pages is None else self._pages.stats()

    def tier_stats(self) -> Optional[dict]:
        """Point-in-time host-tier snapshot for /health (None when the
        tier is off): byte budget/usage, cached entries and pinned
        stashes, the tier's locked hit/miss/eviction/corrupt/rejected
        counters (serving/host_tier.py:HostTier.stats), plus the
        engine-side demote/promote/preempt/resume/fallback totals."""
        if self._tier is None:
            return None
        out = dict(self._tier.stats())
        out["demotions"] = self.stats["tier_demotions"]
        out["promotions"] = self.stats["tier_promotions"]
        out["fallbacks"] = self.stats["tier_fallbacks"]
        out["preemptions"] = self.stats["preemptions"]
        out["resumes"] = self.stats["resumes"]
        return out

    def queue_depths(self) -> dict:
        """Admission-queue depth by priority class (every class
        present, zero-filled) — the /health per-class view."""
        return self.scheduler.queue_depths()

    def constrain_stats(self) -> dict:
        """Point-in-time structured-decoding snapshot for /health:
        in-flight constrained requests plus the compile cache's locked
        counters (serving/constrain.py:ConstraintCache.stats)."""
        out = dict(self._constraint_cache.stats())
        out["active"] = len(self._constraints)
        return out

    # -- model-quality observability (obs/quality.py) ------------------

    def quality_stats(self) -> Optional[dict]:
        """Point-in-time quality snapshot for /health and serve_bench
        (None when quality telemetry is off): live sketch means, token
        counts, skipped ("no signal") observations, the PSI drift
        score, the constraint-validity rate, the cumulative spec
        acceptance when spec is on, and the per-layer lambda summary
        the gauges mirror."""
        if self._quality_monitor is None:
            return None
        out = self._quality_monitor.stats()
        out["constraint_validity_rate"] = (
            1.0 - self._q_constraint_bad / self._q_constraint_total
            if self._q_constraint_total else 1.0
        )
        proposed = self.stats["spec_proposed"]
        if proposed:
            out["spec_acceptance_rate"] = round(
                self.stats["spec_accepted"] / proposed, 4
            )
        out.update(self._lambda_summary)
        return out

    def quality_fingerprint(self,
                            meta: Optional[dict] = None) -> Optional[dict]:
        """The live sketches as a serializable reference fingerprint —
        ``--quality-record``'s payload (obs/quality.py:
        ``save_fingerprint`` writes it atomically at drain). None when
        telemetry is off."""
        if self._quality_monitor is None:
            return None
        return self._quality_monitor.fingerprint(meta=meta)

    def quality_row(self) -> Optional[dict]:
        """One ``{"record": "quality"}`` JSONL row (the serving twin
        of the trainer's introspection records), carrying the
        ``lambda_l<k>`` keys ``tools/lambda_report.py --serving``
        renders beside training rows. None when telemetry is off."""
        if self._quality_monitor is None:
            return None
        return build_quality_row(
            self._quality_monitor, self.stats["iterations"],
            lambdas=self._lambda_summary,
        )

    def _refresh_lambda_gauges(self) -> None:
        """Mirror the SERVING params' per-layer effective lambdas into
        ``serving_lambda_mean{layer=}`` — obs/introspect.py walks the
        same ops/lambdas.py path the trainer logs, so ROADMAP item 6's
        diff-vs-control comparison reads straight off a live fleet.
        Called at build and after any params rebind (the quality_drift
        fault), never per step: the summary fetches device scalars."""
        if self._lambda_gauge is None:
            return
        from differential_transformer_replication_tpu.obs.introspect import (
            serving_lambda_summary,
        )

        self._lambda_summary = serving_lambda_summary(
            self.params, self.cfg
        )
        for key, val in self._lambda_summary.items():
            if "_t" in key:
                continue  # per-term ndiff detail rides quality_row only
            self._lambda_gauge.set(val, layer=key[len("lambda_l"):])

    def _apply_quality_drift(self) -> None:
        """Fault-injection helper (``quality_drift@N``): perturb the
        live params so generated DISTRIBUTIONS shift while every logit
        stays finite — requests keep succeeding and latency stays
        flat, so only the drift detector can catch it (the canary
        chaos drill's point). Every family gets lm_head scaled by
        0.25: the sampled distribution flattens (entropy up, margin
        down) while the greedy argmax is bit-unchanged — on control,
        greedy traffic's tokens are untouched and only the
        fingerprint convicts. diff/ndiff additionally get +2.0 on BOTH
        lambda_q[0] and lambda_k[0] of layer 1 — λ rides exp(lq·lk)
        and the reference initializes those vectors to zero, so one
        side alone is a no-op; shifting both moves term 0's
        exponential by ~exp(4) (bounded, finite), which the
        ``serving_lambda_mean`` gauges surface as the fault's visible
        signature. Params are never donated by the jitted steps, so
        rebinding a shallow-copied tree is safe; the lambda gauges
        refresh to show the perturbed values."""
        params = dict(self.params)
        if self.cfg.model in ("diff", "ndiff"):
            blocks = list(params["blocks"])
            blk = dict(blocks[0])
            attn = dict(blk["attn"])
            for name in ("lambda_q", "lambda_k"):
                vec = attn[name]
                attn[name] = vec.at[0].add(2.0)
            blk["attn"] = attn
            blocks[0] = blk
            params["blocks"] = blocks
        params["lm_head"] = jax.tree_util.tree_map(
            lambda a: a * 0.25, params["lm_head"]
        )
        self.params = params
        self._refresh_lambda_gauges()

    def take_finished(self) -> List[RequestOutput]:
        """Outputs accumulated by a :meth:`step` that raised partway
        through. Those requests were already retired (slot freed / shed
        from the queue), so after a crash they are invisible to both
        :meth:`reset_after_crash`'s lost-list and the preserved queue —
        the supervisor (serving/server.py) must drain this buffer and
        deliver them, or their callers would hang forever."""
        out, self._finished_prior = self._finished_prior, []
        return out

    def run(self) -> List[RequestOutput]:
        """Drain the queue; returns every output, in completion order."""
        outs: List[RequestOutput] = []
        while self.has_work():
            outs.extend(self.step())
        return outs

    def generate(self, prompts: Sequence[Sequence[int]],
                 params: Optional[Sequence[SamplingParams]] = None,
                 **kw) -> List[RequestOutput]:
        """Submit-all + drain convenience; outputs in submission order.
        ``params`` gives per-request SamplingParams; otherwise ``kw``
        build one shared SamplingParams."""
        shared = SamplingParams(**kw) if params is None else None
        ids = []
        try:
            for i, p in enumerate(prompts):
                ids.append(self.submit(p, params=shared if shared else params[i]))
        except Exception:
            # mid-batch rejection (max_queue_len): the prompts already
            # queued would otherwise sit in the scheduler and burn a
            # later run()'s decode iterations for nobody
            for rid in ids:
                self.cancel(rid)
            raise
        by_id = {o.request_id: o for o in self.run()}
        return [by_id[i] for i in ids]

    def close(self) -> None:
        """Release host-side resources: drain the device-profile
        sampler (its queued parse must land before the process exits).
        Idempotent; called by EngineRunner's shutdown paths. What is
        still in flight is read first, so no program outlives the
        engine (nobody is left to deliver its tokens to: a crash in it
        is the shutdown's, not a caller's)."""
        try:
            self._drain()
        except Exception:
            self._inflight, self._firsts = None, []
        if self._device_prof is not None:
            self._device_prof.close()

    def compile_stats(self) -> dict:
        """Compile-cache sizes of the engine's jitted closures. Pinned by
        tests/test_serving.py: decode must stay at 1 entry no matter how
        requests come and go. NOTE the closures are shared across engines
        with identical (cfg, max_seq_len) — counts are per-config, not
        per-instance."""
        out = {
            "prefill": self._prefill_fn._cache_size(),
            "decode": self._decode_fn._cache_size(),
            "sample": self._sample_fn._cache_size(),
        }
        if self._recurrent:
            out["state_reset"] = _reset_state_fn._cache_size()
        if self._copy_fn is not None:
            out["page_copy"] = self._copy_fn._cache_size()
        if self._extract_fn is not None:
            # the host tier's transfer closures: scalar page indices
            # ride as runtime arrays, so demote/promote/preempt/resume
            # churn pins each at 1 entry (tests/test_tiering.py)
            out["page_extract"] = self._extract_fn._cache_size()
            out["page_inject"] = self._inject_fn._cache_size()
        if self._spec_fn is not None:
            # the k rung of the verify ladder (both accept variants);
            # "decode" above is the k=0 rung — together they are THE
            # fixed-ladder compile budget the spec tests pin
            out["spec_decode"] = sum(
                fn._cache_size() for fn in self._spec_fn.values()
            )
        return out

    def _on_retire(self, slot: Slot) -> None:
        """Scheduler retirement hook (every retire path: finish,
        deadline, cancel): return the slot's KV pages (paged) and drop
        its drafter-side state (spec)."""
        if self._pages is not None:
            self._release_slot_pages(slot)
        if self._drafter is not None:
            self._drafter.release(slot.index)

    # -- paged admission / release (serving/pages.py) ------------------

    def _admit_paged(self, slot: Slot, entry, iteration: int,
                     finished: List[RequestOutput]) -> Optional[int]:
        """Scheduler admission gate: plan the selected request against
        the radix cache + page pool (and, when tiered, the host tier).
        Returns the cached/restored prefix length to skip (>= 0), None
        to keep it queued (transient page shortage — the scheduler may
        preempt a lower class on this verdict and retry), or -1 after
        shedding it with the typed :class:`PagePoolExhaustedError`
        output."""
        request, prompt, t_submit, _deadline, trace = entry
        if request.request_id in self._resume:
            verdict = self._try_resume(slot, entry, iteration)
            if verdict == "wait":
                return None
            if verdict == "ok":
                # the full KV image (prompt AND generated) was
                # re-injected: nothing to prefill
                return int(prompt.shape[0])
            # "restart": the stash was unusable — fall through to a
            # fresh admission; fold_in(key, t) token keys make the
            # recomputed output bit-identical to the uninterrupted run
        try:
            adm = self._pages.plan_admission(
                slot.index, [int(t) for t in prompt],
                request.params.max_new_tokens,
            )
        except PagePoolExhaustedError:
            self._drain_demotions(iteration)
            finished.append(
                self._shed_page_exhausted(request, prompt, t_submit,
                                          trace)
            )
            return -1
        # demotion plans from this planning call's evictions MUST be
        # captured before any copy/promote/prefill could overwrite the
        # freed physical pages (serving/pages.py:take_demotions)
        self._drain_demotions(iteration)
        if adm is None:
            return None
        cached = adm.cached_len
        if adm.promotes:
            cached = self._apply_promotes(adm, iteration)
        for src, dst in adm.copies:
            # COW fork: the shared page's prefix K/V lands on a page
            # this slot privately owns; applied BEFORE any further
            # pool call (the pool's eviction invariant)
            self.cache = self._copy_fn(
                self.cache, np.int32(src), np.int32(dst)
            )
        return cached

    # -- host tier: demote / promote / preempt / resume ----------------
    # (serving/host_tier.py; all single-engine-thread, pool lock ->
    # tier lock order per GL601)

    def _extract_page(self, page: int) -> list:
        """One physical page's device bytes as OWNED, writable host
        numpy (per-layer leaf dicts) — the capture side of demotion
        and preemption stashing. ``np.array`` (not ``asarray``): the
        tier checksums the buffer and the swap-corrupt fault flips a
        byte in place, so the copy must not alias device memory."""
        out = self._extract_fn(self.cache, np.int32(page))
        return [
            {key: np.array(leaf) for key, leaf in layer.items()}
            for layer in out
        ]

    def _inject_page(self, page: int, payload) -> bool:
        """Write one host page image into physical page ``page`` (the
        promote/swap-in transfer), retried with a short backoff —
        a transient device_put failure degrades to recompute at the
        caller, never a wedge."""
        for attempt in range(3):
            try:
                self.cache = self._inject_fn(
                    self.cache, np.int32(page), payload
                )
                return True
            except Exception:
                if attempt == 2:
                    return False
                time.sleep(0.005 * (attempt + 1))
        return False

    def _drain_demotions(self, iteration: int) -> None:
        """Capture the pool's pending demotion plans into the host
        tier. Runs immediately after EVERY pool planning call (success
        or not): the freed pages' device bytes are still the evicted
        prefix until a later planning call hands them back out. A
        failed capture (the ``page_demote_fail`` fault) just skips the
        tier — the prefix degrades to recompute, typed and counted."""
        if self._tier is None:
            return
        plans = self._pages.take_demotions()
        if not plans:
            return
        if faults.page_demote_fail_at(iteration):
            self.stats.inc("tier_fallbacks", len(plans))
            return
        for prefix, page in plans:
            if self._tier.put(prefix, self._extract_page(page)):
                self.stats.inc("tier_demotions")

    def _apply_promotes(self, adm, iteration: int) -> int:
        """Stage an admission's host-tier pages back onto the device
        (a copy, never a recompute). Pages apply in prompt order; the
        first failed verify/inject truncates the restored prefix there
        — the remainder simply prefills. The ``page_promote_hang``
        fault stalls (DTX_TIER_HANG_S) then fails every promote."""
        ps = self.serving.kv_page_size
        ok_pages = 0
        if not faults.page_promote_hang_at(iteration):
            for dst, ent in adm.promotes:
                if not ent.verify():
                    self._tier.note_corrupt()
                    break
                if not self._inject_page(int(dst), ent.payload):
                    break
                ok_pages += 1
        if ok_pages:
            self.stats.inc("tier_promotions", ok_pages)
        if ok_pages < len(adm.promotes):
            self.stats.inc(
                "tier_fallbacks", len(adm.promotes) - ok_pages
            )
        return adm.device_cached + ok_pages * ps

    def _preempt_slot(self, slot: Slot) -> None:
        """Scheduler preemption hook (plan()'s blocked-admission path):
        stash an ACTIVE lower-priority slot's live KV pages and host
        decode state to the tier, free its pages, and REQUEUE it with
        its ORIGINAL submit_time so anti-starvation aging keeps
        accruing. The later swap-in (:meth:`_try_resume`) is bit-exact
        — no recompute, no recompile. The snapshot is of the slot's host
        state whole, so what is in flight is read first; a victim that
        read ends is not preempted (its pages are free already)."""
        self._drain()
        if slot.state != ACTIVE:
            return
        rid = slot.request.request_id
        ps = self.serving.kv_page_size
        # pages actually written so far: after emitting g tokens the
        # device KV covers positions 0..P+g-2 (the last token's KV is
        # written by its NEXT step); ceil((P+g)/ps) over-covers that
        # and never exceeds the slot's allocation
        pos = slot.prompt_len + len(slot.generated)
        n_live = min(-(-pos // ps), self._pages.pages_per_slot)
        row = self._pages.table_row(slot.index)
        payloads = [
            self._extract_page(int(row[j])) for j in range(n_live)
        ]
        self._tier.stash(rid, payloads)
        self._resume[rid] = {
            "n_live": n_live,
            "generated": list(slot.generated),
            "token_times": list(slot.token_times),
            "first_token_time": slot.first_token_time,
            "filled": slot.filled,
            "cached_len": slot.cached_len,
            "spec_proposed": slot.spec_proposed,
            "spec_accepted": slot.spec_accepted,
            "prompt_ids": slot.prompt_ids,
            "penalty_counts": slot.penalty_counts,
            "token_logprobs": slot.token_logprobs,
            "top_logprobs": slot.top_logprobs,
            "fsm_state": slot.fsm_state,
        }
        self.scheduler.queue.append(
            (slot.request, slot.prompt, slot.submit_time,
             slot.deadline, slot.trace)
        )
        self._pages.release(slot.index, [], False)
        if self._drafter is not None:
            self._drafter.release(slot.index)
        self.stats.inc("preemptions")
        # reset directly, NOT scheduler.retire: the retire hook would
        # release the slot's pages a second time
        slot.reset()

    def _try_resume(self, slot: Slot, entry, iteration: int) -> str:
        """Swap a preempted request back in: reserve private pages for
        its FULL KV image and inject the stash, checksum-verified.
        Returns "wait" (pool cannot free enough yet — the scheduler
        may preempt for it), "ok" (resumed bit-exact; step() restores
        the host state after plan() commits), or "restart" (stash
        unusable — degrade to a bit-exact full recompute, typed and
        counted)."""
        request, prompt, _t_submit, _deadline, _trace = entry
        rid = request.request_id
        snap = self._resume[rid]
        pages = self._pages.plan_resume(
            slot.index,
            self._pages.pages_needed(
                int(prompt.shape[0]), request.params.max_new_tokens
            ),
        )
        self._drain_demotions(iteration)
        if pages is None:
            return "wait"
        # a snapshot carrying its own page images came over the WIRE
        # (a migrated slot state, serving/migrate.py:import_state) —
        # inject from it instead of the host-tier stash; everything
        # downstream (verify, inject, restore) is shared machinery
        migrated = "pages" in snap
        ents = snap["pages"] if migrated else self._tier.unstash(rid)
        ok = ents is not None
        if ok and faults.page_swap_corrupt_at(iteration):
            # flip one byte of the first payload leaf in place: the
            # CRC verify below must catch it and degrade to restart
            layer0 = ents[0].payload[0]
            leaf = layer0[next(iter(layer0))]
            leaf.reshape(-1).view(np.uint8)[0] ^= 0xFF
        if ok:
            for pg, ent in zip(pages, ents):
                if not ent.verify():
                    if self._tier is not None:
                        self._tier.note_corrupt()
                    ok = False
                    break
                if not self._inject_page(int(pg), ent.payload):
                    ok = False
                    break
        if not ok:
            self._pages.release(slot.index, [], False)
            self._resume.pop(rid, None)
            if migrated:
                # fresh admission below recomputes the whole image;
                # fold_in(key, t) keys make the regenerated stream
                # bit-identical, so the import degrades, never lies
                self.stats.inc("migrate_failed")
            else:
                self._tier.drop_stash(rid)
                self.stats.inc("tier_fallbacks")
            # the bit-exact recompute re-emits every token: reset the
            # per-request quality accumulator so means are not doubled
            self._q_acc.pop(rid, None)
            return "restart"
        self._resumed.append((slot, snap))
        self.stats.inc("resumes")
        return "ok"

    def _drop_resume(self, request_id: int) -> None:
        """Forget a preempted request's swap-in state on every path
        that forgets its key chain (cancel, expire, shed, crash loss)
        — a leaked stash would pin host-tier bytes forever."""
        self._resume.pop(request_id, None)
        if self._tier is not None:
            self._tier.drop_stash(request_id)

    # -- live migration (serving/migrate.py) ---------------------------
    # Engine-thread only, like every other device-touching method: the
    # runner (serving/server.py) executes these between steps.

    def _slot_for(self, request_id: int) -> Optional[Slot]:
        return next(
            (s for s in self.scheduler.slots
             if s.state != FREE and s.request is not None
             and s.request.request_id == request_id),
            None,
        )

    def _refuse_migration(self) -> None:
        if self._recurrent:
            raise MigrateExportError(
                f"live migration is not available for the {self.cfg.model} "
                "family: the wire image ships K/V pages by position, and a "
                "layer's recurrent state has no page to ship "
                "(it needs a snapshot of the state) — fall back to replay"
            )
        if self._window_layers:
            raise MigrateExportError(
                f"live migration is not available for the {self.cfg.model} "
                "family: the wire image ships K/V pages of ONE ring length "
                "a slot, and this family's slots hold rings of two lengths "
                "— fall back to replay"
            )

    def export_slot_state(self, request_id: int,
                          dedup_pages: int = 0) -> bytes:
        """Capture one ACTIVE slot's full decode state as a wire image
        WITHOUT disturbing it — the slot keeps decoding until the
        destination ACKs and :meth:`release_migrated` retires it, so a
        failed transfer costs nothing. ``dedup_pages`` is the
        destination's radix-probe answer (PagePool.probe_prefix):
        that many leading full prompt pages ship as holes the importer
        copies device-locally. Raises the typed
        :class:`MigrateExportError` when there is nothing exportable
        (contiguous layout, request queued/prefilling/finished)."""
        self._refuse_migration()
        if self._pages is None or self._extract_fn is None:
            raise MigrateExportError(
                "live migration needs the paged KV layout "
                "(ServingConfig.kv_page_size > 0) — fall back to replay"
            )
        # the wire image is the slot's host state whole: read what is in
        # flight first (the tokens may also end the request)
        self._drain()
        slot = self._slot_for(request_id)
        if slot is None or slot.state != ACTIVE or not slot.generated:
            raise MigrateExportError(
                f"request {request_id} holds no ACTIVE slot (queued, "
                "prefilling, or already finished) — nothing to "
                "migrate; replay or plain retry covers it",
                code="migrate_not_active",
            )
        faults.stall("migrate_hang")
        ps = self.serving.kv_page_size
        p = slot.request.params
        # live pages: same arithmetic as _preempt_slot — after g
        # emitted tokens the device KV covers positions 0..P+g-2
        pos = slot.prompt_len + len(slot.generated)
        n_live = min(-(-pos // ps), self._pages.pages_per_slot)
        # dedup can only cover FULL pages of the PROMPT (generated
        # tokens never live in a radix tree), and the radix match is
        # capped at prompt_len - 1
        dedup = max(0, min(
            int(dedup_pages), n_live,
            (slot.prompt_len - 1) // ps if slot.prompt_len else 0,
        ))
        row = self._pages.table_row(slot.index)
        payloads: List[Optional[list]] = [
            None if j < dedup else self._extract_page(int(row[j]))
            for j in range(n_live)
        ]
        now = time.perf_counter()
        meta = {
            "prompt": [int(t) for t in slot.prompt],
            "params": params_to_dict(p),
            "generated": list(slot.generated),
            "n_live": n_live,
            "dedup_pages": dedup,
            "page_size": ps,
            "model": self.cfg.model,
            "block_size": self.cfg.block_size,
            "filled": slot.filled,
            "cached_len": slot.cached_len,
            "spec_proposed": slot.spec_proposed,
            "spec_accepted": slot.spec_accepted,
            "fsm_state": slot.fsm_state,
            "token_logprobs": slot.token_logprobs,
            "top_logprobs": slot.top_logprobs,
            "deadline_left_s": (
                max(0.0, slot.deadline - now) if slot.deadline else 0.0
            ),
        }
        blob = encode_slot_state(meta, payloads)
        if payloads and faults.consume("migrate_corrupt"):
            # chaos drill: flip one byte AFTER the per-page CRCs were
            # stamped — the import side's decode must convict the
            # transfer (MigratePayloadError), and the drain path falls
            # back to replay; garbage KV is never attended
            torn = bytearray(blob)
            torn[-1] ^= 0xFF
            blob = bytes(torn)
        self.stats.inc("migrate_exports")
        self.stats.inc("migrate_pages_shipped", n_live - dedup)
        self.stats.inc("migrate_pages_deduped", dedup)
        self.stats.inc("migrate_bytes", len(blob))
        return blob

    def release_migrated(self, request_id: int) -> bool:
        """Retire a slot whose decode state now lives on the
        destination replica (the import was ACKed). Same engine thread
        as the export, so the slot cannot have stepped in between.
        Returns False when the request is unknown/finished — the local
        output wins and the caller abandons the migration."""
        slot = self._slot_for(request_id)
        if slot is None:
            return False
        self._base_keys.pop(request_id, None)
        self._drop_constraint(request_id)
        self._drop_resume(request_id)
        self._q_acc.pop(request_id, None)
        self._finished_counter.inc(reason="migrated")
        if self._tracing:
            self.tracer.instant(
                "finish", rid=request_id, reason="migrated",
                **(instant_args(slot.trace)
                   if slot.trace is not None else {}),
            )
        # standard retire path: pages dereferenced (prompt prefix
        # donated to the radix cache when trustworthy) + drafter state
        # dropped — the SOURCE keeps serving the prefix to new traffic
        self.scheduler.retire(slot)
        return True

    def import_state(self, blob: bytes) -> int:
        """Re-admit a migrated slot state: decode + checksum-verify the
        wire image (serving/migrate.py — a flipped byte is convicted
        HERE, before anything reaches the device), resolve dedup holes
        from the local radix tree, then ride the SAME zero-recompile
        swap-in machinery as host-tier resume: submit() mints a fresh
        request id (key chain, constraint compile, deadline from the
        shipped remainder) and the registered ``self._resume`` snapshot
        makes the paged admission gate inject the pages bit-exact
        (:meth:`_try_resume`). Returns the minted request id. Raises
        :class:`MigratePayloadError` (corrupt/torn) or
        :class:`MigrateExportError` (geometry mismatch, dedup miss,
        contiguous layout) — both typed, both leave the engine clean."""
        self._refuse_migration()
        if self._pages is None or self._inject_fn is None:
            raise MigrateExportError(
                "live migration needs the paged KV layout "
                "(ServingConfig.kv_page_size > 0)"
            )
        meta, payloads = decode_slot_state(blob)
        if (meta.get("page_size") != self.serving.kv_page_size
                or meta.get("model") != self.cfg.model
                or meta.get("block_size") != self.cfg.block_size):
            raise MigrateExportError(
                f"geometry mismatch: wire (model={meta.get('model')}, "
                f"block={meta.get('block_size')}, "
                f"page={meta.get('page_size')}) vs engine "
                f"(model={self.cfg.model}, block={self.cfg.block_size},"
                f" page={self.serving.kv_page_size})",
                code="migrate_geometry",
            )
        prompt = [int(t) for t in meta["prompt"]]
        dedup = int(meta.get("dedup_pages", 0))
        if dedup:
            # resolve the holes from the local radix tree NOW (same
            # engine thread, no planning call until submit below, so
            # the chain cannot be evicted under us); a miss — evicted
            # since the probe — fails typed and the source keeps the
            # request untouched
            chain = self._pages.chain_pages(prompt, dedup)
            if chain is None:
                self.stats.inc("migrate_failed")
                raise MigrateExportError(
                    f"dedup chain ({dedup} pages) no longer cached — "
                    "evicted between probe and import; source retries "
                    "without dedup or falls back to replay",
                    code="migrate_dedup_miss",
                )
            for j, pg in enumerate(chain):
                payloads[j] = self._extract_page(int(pg))
        params = params_from_dict(meta["params"])
        left = float(meta.get("deadline_left_s") or 0.0)
        rid = self.submit(
            prompt, params=params,
            deadline=(time.perf_counter() + left) if left else None,
        )
        self._resume[rid] = {
            "n_live": int(meta["n_live"]),
            "generated": [int(t) for t in meta["generated"]],
            # host timestamps do not survive the process hop: token
            # times restart on the destination clock (ITL histograms
            # skip the splice point; finish_time stays monotonic)
            "token_times": [],
            "first_token_time": time.perf_counter(),
            "filled": int(meta["filled"]),
            "cached_len": int(meta["cached_len"]),
            "spec_proposed": int(meta.get("spec_proposed", 0)),
            "spec_accepted": int(meta.get("spec_accepted", 0)),
            "prompt_ids": None,
            "penalty_counts": None,  # _slot_counts rebuilds lazily
            "token_logprobs": meta.get("token_logprobs"),
            "top_logprobs": (
                [[(int(i), float(v)) for i, v in alts]
                 for alts in meta["top_logprobs"]]
                if meta.get("top_logprobs") is not None else None
            ),
            "fsm_state": int(meta.get("fsm_state", 0)),
            # wire-borne page images: _try_resume injects these instead
            # of a host-tier stash (checksums re-verified at injection)
            "pages": [TierEntry(p) for p in payloads],
        }
        self.stats.inc("migrate_imports")
        return rid

    def progress_snapshot(self) -> List[dict]:
        """Per-in-flight-request emitted-token progress — the
        ``GET /inflight`` body the router harvests into its replay
        journal (serving/migrate.py:ReplayJournal). Engine thread
        (published by the runner between steps); the journal only
        needs a PREFIX of the truly-emitted tokens, so lagging a step
        is correct by construction."""
        out = []
        for s in self.scheduler.slots:
            if s.state == FREE or s.request is None:
                continue
            out.append({
                "request_id": s.request.request_id,
                "prompt_len": s.prompt_len,
                "tokens": list(s.generated),
            })
        for req, prompt, _t, _dl, _tr in list(self.scheduler.queue):
            out.append({
                "request_id": req.request_id,
                "prompt_len": int(prompt.shape[0]),
                "tokens": [],
            })
        return out

    def _release_slot_pages(self, slot: Slot) -> None:
        """Scheduler retirement hook (every retire path: finish,
        deadline, cancel): dereference shared pages and donate the
        prompt's pages to the radix cache when they are trustworthy —
        prompt fully prefilled and the ring never rolled over them."""
        prompt = [] if slot.prompt is None else [int(t) for t in slot.prompt]
        cacheable = (
            slot.prompt_len > 0
            and slot.filled == slot.prompt_len
            # what the device wrote counts DISPATCHED tokens: a row that
            # ended with a step in flight has one position more
            and slot.prompt_len + max(slot.dispatched, len(slot.generated))
            <= self.cfg.block_size
        )
        self._pages.release(slot.index, prompt, cacheable)

    def _shed_page_exhausted(self, request, prompt, submit_time: float,
                             trace=None) -> RequestOutput:
        """A request the page pool refused (never fits, or the
        ``page_exhaust`` fault): shed at admission with a typed output
        the server maps to the 503 shed path — it never touches the
        device."""
        self._base_keys.pop(request.request_id, None)
        self._drop_constraint(request.request_id)
        self._drop_resume(request.request_id)
        self._q_acc.pop(request.request_id, None)
        self.stats.inc("page_shed")
        self._finished_counter.inc(reason="page_exhausted")
        if self._tracing:
            self.tracer.instant(
                "finish", rid=request.request_id,
                reason="page_exhausted",
                **(instant_args(trace) if trace is not None else {}),
            )
        # Retry-After from the pool's OBSERVED drain rate: seconds
        # until enough pages free for THIS request at the recent
        # eviction/release throughput, instead of a static guess —
        # serving/retry.py honors it as the client backoff floor and
        # the server echoes it in the 503's Retry-After header
        retry_after = self._pages.estimated_drain_s(
            self._pages.pages_needed(
                len(prompt), request.params.max_new_tokens
            )
        )
        return RequestOutput(
            request_id=request.request_id,
            prompt=[int(t) for t in prompt],
            tokens=[],
            finish_reason="page_exhausted",
            submit_time=submit_time,
            first_token_time=0.0,
            finish_time=time.perf_counter(),
            token_times=[],
            trace_id=trace.trace_id if trace is not None else None,
            retry_after=retry_after,
        )

    def _corrupt_cached_prefix(self) -> None:
        """Fault-injection helper (``prefix_corrupt@N``): NaN-poison
        one radix-cached page, preferring one currently shared with an
        occupied slot so the very next decode trips the finite-logits
        guard — the supervised restart then rebuilds the pool and the
        poisoned prefix is evicted wholesale instead of ever serving
        garbage tokens (serving/pages.py:PagePool.reset)."""
        cached = set(self._pages.cached_pages())
        if not cached:
            return
        tables = self._pages.tables()
        target = None
        for s in self.scheduler.slots:
            if s.state == FREE:
                continue
            for pg in tables[s.index]:
                if int(pg) in cached:
                    target = int(pg)
                    break
            if target is not None:
                break
        if target is None:
            target = next(iter(cached))
        self.cache = self._poison_pages([target])

    def _poison_pages(self, pages: List[int]) -> list:
        """NaN-poison the given physical pages across every layer/leaf
        (int8 values zero while their fp32 scales go NaN, so every
        dequantized read is NaN — same trick as _corrupt_one_slot)."""
        idx = np.asarray(pages, np.int32)

        def _poison(key, arr):
            ix = (
                (slice(None), idx) if KV_CACHE_BATCH_AXIS[key] else idx
            )
            if jnp.issubdtype(arr.dtype, jnp.floating):
                return arr.at[ix].set(jnp.nan)
            return arr.at[ix].set(0)

        return [
            {key: _poison(key, c[key]) for key in c} for c in self.cache
        ]

    # -- internals ----------------------------------------------------

    def _slot_fsm(self, s: Slot):
        """The slot's compiled token FSM, attached lazily (admission
        happens inside the scheduler, which knows nothing of
        constraints; the engine-side map is keyed by request_id). None
        for unconstrained requests."""
        if s.constraint is None:
            ent = self._constraints.get(s.request.request_id)
            if ent is None:
                return None
            s.constraint = ent[1]
            s.fsm_state = ent[1].start
            ko = s.request.params.key_offset
            if ko:
                # replayed continuation (serving/migrate.py): the dead
                # attempt's FSM already consumed the tokens now riding
                # the prompt tail — walk the fresh cursor over them so
                # masks continue from the same state
                P = s.prompt_len
                st = s.fsm_state
                for t in s.prompt[max(0, P - ko):P]:
                    if st < 0:
                        break
                    st = ent[1].advance(st, int(t))
                s.fsm_state = st
        return s.constraint

    def _slot_counts(self, s: Slot) -> np.ndarray:
        """The slot's generated-token histogram — built once at the
        first penalized sample, then incremented per emitted token
        (_emit); rebuilding the (V,) array per iteration would be the
        exact host cost class the packed operands exist to avoid."""
        if s.penalty_counts is None:
            h = np.zeros((self.cfg.vocab_size,), np.int32)
            ko = s.request.params.key_offset
            if ko:
                # replayed continuation: the dead attempt's emitted
                # tokens (now the prompt tail) were penalized then, so
                # they seed the histogram here — same distribution as
                # the uninterrupted run
                P = s.prompt_len
                for t in s.prompt[max(0, P - ko):P]:
                    h[int(t)] += 1
            for t in s.generated:
                h[t] += 1
            s.penalty_counts = h
        return s.penalty_counts

    def _inert_ops(self, key, shape):
        """Cached all-ones mask + zero-histogram DEVICE constants for
        a pipeline call with no constrained/penalized active rows:
        the common case pays no per-step (B, V) host build or
        transfer, and the pipeline's ``where`` passes raw logits
        through bit-identically."""
        ops = self._inert.get(key)
        if ops is None:
            V = self.cfg.vocab_size
            ops = (
                jnp.ones(shape + (V,), bool),
                jnp.zeros((shape[0], V), jnp.int32),
            )
            self._inert[key] = ops
        return ops

    def _h2d_args(self, iteration: int, operands) -> dict:
        """A ``decode_h2d`` span's args: how many host arrays the step's
        operands are, one transfer each, and their bytes (summed only
        for a tracer that records them)."""
        args = {"iteration": iteration}
        if self._tracing:
            args.update(arrays=len(operands),
                        bytes=sum(a.nbytes for a in operands))
        return args

    def _row_asks(self, s: Slot) -> int:
        """What the slot's request asks of the sampler beyond an argmax,
        as ``ASK_*`` bits: an FSM mask, a penalty on, ``logprobs > 0``, a
        temperature. The ONE rule: ``_sample_operands`` writes it into
        the operand the program takes its arms by, ``_sampler_use``
        counts it for a trace. (A top-k has no bit: the row that reads
        it has a temperature or ``logprobs``, and a greedy row's argmax
        is the same with it and without.)"""
        p = s.request.params
        return ((ASK_MASK if self._slot_fsm(s) is not None else 0)
                | (ASK_PENALTY if _penalties_on(p) else 0)
                | (ASK_LOGPROBS if p.logprobs > 0 else 0)
                | (ASK_TEMPERATURE if p.temperature > 0 else 0))

    def _sampler_use(self, rows) -> dict:
        """What the (row index, slot) assignment asks of the sampler
        beyond an argmax, as counts of rows: with an FSM, with a penalty
        on, with ``logprobs > 0``, with a temperature, and with any of
        the four; and ``plain``, 1 where no row asks anything, which is
        the call that takes the program's plain arm (the telemetry build
        has none). The ``sample_operands`` span's args, so a trace sets
        what the sampler costs beside the rows that wanted it; a pass of
        its own over the rows, made only for a tracer that records."""
        asks = [self._row_asks(s) for _, s in rows]
        use = {name: sum(1 for a in asks if a & bit) for name, bit in (
            ("masked", ASK_MASK), ("penalized", ASK_PENALTY),
            ("logprobs", ASK_LOGPROBS), ("tempered", ASK_TEMPERATURE))}
        use["asking"] = sum(1 for a in asks if a)
        use["plain"] = int(not use["asking"] and not self._quality)
        return use

    def _sample_operands(self, rows, B):
        """Packed (B, 9) int32 sampler operand plus the pipeline's
        allowed/counts HOST arrays for a (row index, slot) assignment
        (see _build_step_fns._sample for the column layout; quality
        telemetry widens it by one previous-token column). A row not
        named (an empty slot of the pool) asks the program for NOTHING:
        no ``ASK_*`` bit, temperature and top_k 0 (a greedy row), so no
        predicate of the program is true for its sake; beside that the
        inert defaults (penalties off, mask all-ones, no previous
        token). A mask or histogram no row needs is None, for which the
        caller passes ``_inert_ops``'s cached device constant."""
        ints = np.zeros((B, 10 if self._quality else 9), np.int32)
        f = ints[:, 4:8].view(np.float32)
        f[:, 1] = 1.0  # repetition penalty (1 = off)
        if self._quality:
            ints[:, 9] = -1  # no previous token (repeat flag stays 0)
        for i, s in rows:
            p = s.request.params
            # key-chain position: a replayed continuation (key_offset >
            # 0, serving/migrate.py) samples token t with the key the
            # DEAD attempt would have used at global position
            # key_offset + t — bit-identical streams across failover
            # and the request's tokens DISPATCHED so far, read or not
            ints[i, 0] = p.key_offset + s.dispatched
            ints[i, 1] = p.top_k or 0
            ints[i, 2:4].view(np.uint32)[:] = (
                self._base_keys[s.request.request_id]
            )
            f[i, 0] = p.temperature
            f[i, 1] = p.repetition_penalty
            f[i, 2] = p.presence_penalty
            f[i, 3] = p.frequency_penalty
            ints[i, 8] = self._row_asks(s)
            if self._quality:
                # the token the sampled one would repeat: the last
                # emitted, or (first sample, at prefill completion)
                # the last prompt token
                if s.generated:
                    ints[i, 9] = s.generated[-1]
                elif s.prompt_len:
                    ints[i, 9] = int(s.prompt[s.prompt_len - 1])
        asked = int(np.bitwise_or.reduce(ints[:, 8]))  # by any row
        am = cm = None
        if asked & ASK_MASK:
            am = np.ones((B, self.cfg.vocab_size), bool)
            for i, s in rows:
                fsm = self._slot_fsm(s)
                if fsm is not None:
                    am[i] = fsm.allowed_row(s.fsm_state)
        if asked & ASK_PENALTY:
            cm = np.zeros((B, self.cfg.vocab_size), np.int32)
            for i, s in rows:
                if _penalties_on(s.request.params):
                    cm[i] = self._slot_counts(s)
        return ints, am, cm

    def _sample_dispatch(self, rows, B: int, logits, iteration: int,
                         path: str):
        """Dispatch one sampler call over a (row index, slot) assignment
        of B rows, the host's share of it in two spans inside the
        caller's ``sample`` / ``first_token``: ``sample_operands`` (the
        packed rows, a mask or histogram where a row needs one) and
        ``sample_dispatch`` (their transfers and the call, which returns
        before the device is done). ``path`` says which caller:
        ``decode`` or ``prefill``. Returns the packed output ON THE
        DEVICE (the layout is _build_step_fns._sample's output contract);
        :meth:`_read` brings it back, this iteration or the next. Each
        named row has one more token dispatched, which the caller keeps
        in the device's record of sampled rows (``_last_out``)."""
        args = {"iteration": iteration, "path": path}
        use = self._sampler_use(rows) if self._tracing else {}
        with self.tracer.span("sample_operands", rows=B,
                              active=len(rows), **args, **use):
            ints, mask, hist = self._sample_operands(rows, B)
        with self.tracer.span("sample_dispatch", **args):
            allowed, counts = self._inert_ops(B, (B,))
            if mask is not None:
                allowed = jnp.asarray(mask)
            if hist is not None:
                counts = jnp.asarray(hist)
            out = self._sample_fn(
                jnp.asarray(ints), logits, allowed, counts
            )
        for _, s in rows:
            s.dispatched += 1
            s.token_on_device = True
        return out

    def _lp_echo(self, s: Slot, row: np.ndarray):
        """Decode one sampler echo row into the (chosen logprob,
        [(token id, logprob)] top list) pair _emit accumulates — None
        when the request asked for none (``params.logprobs == 0``).
        Per-request widths <= the compiled lp_k are host-side
        truncation, never a new trace."""
        n = s.request.params.logprobs
        if not n:
            return None
        K = self._lp_k
        chosen = float(row[2:3].view(np.float32)[0])
        k = min(n, K)
        ids = row[3:3 + k]
        lps = row[3 + K:3 + K + k].view(np.float32)
        return chosen, [
            (int(i), float(v)) for i, v in zip(ids, lps)
        ]

    def _spec_lp_echo(self, s: Slot, row: np.ndarray, j: int, L: int):
        """Per-row logprob echo from the spec verify step's packed
        output (see _build_spec_step_fns._pack_out): verify row j's
        chosen-token logprob + top list for the same request surface
        as :meth:`_lp_echo`."""
        n = s.request.params.logprobs
        if not n:
            return None
        K = self._lp_k
        base = L + 2
        chosen = float(row[base + j:base + j + 1].view(np.float32)[0])
        k = min(n, K)
        o = base + L + j * K
        ids = row[o:o + k]
        lps = row[o + L * K:o + L * K + k].view(np.float32)
        return chosen, [
            (int(i), float(v)) for i, v in zip(ids, lps)
        ]

    def _quality_echo(self, row: np.ndarray):
        """The L=1 sampler's appended quality tail as host floats —
        (entropy, margin, repeat flag), the bitcast twin of
        :meth:`_lp_echo` (see _build_step_fns._sample's layout)."""
        base = 3 + 2 * self._lp_k
        q = row[base:base + 3].view(np.float32)
        return float(q[0]), float(q[1]), float(q[2])

    def _spec_quality_echo(self, row: np.ndarray, j: int, L: int):
        """Verify row j's quality tail from the spec step's packed
        output: ent | margin | rep blocks of L columns each, appended
        after the logprob echo (_build_spec_step_fns._pack_out)."""
        base = 2 + 2 * L + 2 * L * self._lp_k
        ent = row[base + j:base + j + 1].view(np.float32)[0]
        margin = row[base + L + j:base + L + j + 1].view(np.float32)[0]
        rep = row[base + 2 * L + j:base + 2 * L + j + 1].view(
            np.float32
        )[0]
        return float(ent), float(margin), float(rep)

    def _q_observe(self, rid: int, q) -> None:
        """Fold one emitted token's quality tail into the histograms,
        the drift monitor's sketches, and the per-request accumulator
        (keyed by request id, so preempt/resume carries it for free).
        The ``quality_nan`` fault poisons the values HERE: non-finite
        signals are skipped everywhere downstream — "no signal", never
        a crash, never a poisoned fingerprint."""
        ent, margin, rep = q
        if self._q_force_nan:
            ent = margin = float("nan")
        if math.isfinite(ent):
            self._q_entropy_hist.observe(ent)
        if math.isfinite(margin):
            self._q_margin_hist.observe(margin)
        self._quality_monitor.observe(ent, margin)
        acc = self._q_acc.get(rid)
        if acc is None:
            # ent_sum, ent_n, margin_sum, margin_n, rep_run, rep_max
            acc = self._q_acc[rid] = [0.0, 0, 0.0, 0, 0, 0]
        if math.isfinite(ent):
            acc[0] += ent
            acc[1] += 1
        if math.isfinite(margin):
            acc[2] += margin
            acc[3] += 1
        if rep > 0.5:
            acc[4] += 1
            acc[5] = max(acc[5], acc[4])
        else:
            acc[4] = 0

    def _emit(self, slot: Slot, token: int, now: float,
              finished: List[RequestOutput], lp=None, q=None) -> None:
        prev_token_t = slot.token_times[-1] if slot.token_times else None
        slot.generated.append(token)
        slot.token_times.append(now)
        if lp is not None:
            if slot.token_logprobs is None:
                slot.token_logprobs = []
                slot.top_logprobs = []
            slot.token_logprobs.append(lp[0])
            slot.top_logprobs.append(lp[1])
        if q is not None:
            self._q_observe(slot.request.request_id, q)
        if len(slot.generated) == 1:
            slot.first_token_time = now
            slot.state = ACTIVE
            self._ttft_hist.observe(now - slot.submit_time)
            self._class_ttft_hist.observe(
                now - slot.submit_time,
                priority=slot.request.params.priority,
            )
            if self._tracing:
                self.tracer.instant(
                    "first_token", rid=slot.request.request_id,
                    **(instant_args(slot.trace)
                       if slot.trace is not None else {}),
                )
        elif prev_token_t is not None:
            self._itl_hist.observe(now - prev_token_t)
            self._class_itl_hist.observe(
                now - prev_token_t,
                priority=slot.request.params.priority,
            )
        p = slot.request.params
        eos = (
            p.eos_token_id
            if p.eos_token_id is not None
            else self.serving.eos_token_id
        )
        hit_eos = eos is not None and token == eos
        stop_hit = False
        if not hit_eos and p.stop:
            g = slot.generated
            for seq in p.stop:
                n = len(seq)
                tail = g
                if len(g) < n and p.key_offset:
                    # replayed continuation: a stop sequence may span
                    # the prompt/generated boundary (its head was
                    # emitted by the dead attempt and rides the prompt
                    # tail) — match it exactly like the uninterrupted
                    # run would have
                    borrow = min(n - len(g), p.key_offset,
                                 slot.prompt_len)
                    P = slot.prompt_len
                    tail = [
                        int(t) for t in slot.prompt[P - borrow:P]
                    ] + g
                if len(tail) >= n and tuple(tail[-n:]) == seq:
                    stop_hit = True
                    break
        if hit_eos or stop_hit or len(slot.generated) >= p.max_new_tokens:
            finished.append(self._finish(
                slot,
                "eos" if hit_eos
                else ("stop_sequence" if stop_hit else "length"),
            ))
            return
        # the slot decodes on: keep its pipeline state current. The
        # histogram only exists once a penalized sample built it; the
        # FSM cursor follows every emitted token (the next step's mask
        # row — and the zero-row sweep — read it).
        if slot.penalty_counts is not None:
            slot.penalty_counts[token] += 1
        if slot.constraint is not None:
            slot.fsm_state = slot.constraint.advance(slot.fsm_state, token)

    def _finish(self, slot: Slot, reason: str,
                now: Optional[float] = None) -> RequestOutput:
        rid = slot.request.request_id
        quality = None
        if self._quality:
            acc = self._q_acc.pop(rid, None)
            quality = {
                "entropy_mean": (
                    round(acc[0] / acc[1], 6)
                    if acc and acc[1] else None
                ),
                "margin_mean": (
                    round(acc[2] / acc[3], 6)
                    if acc and acc[3] else None
                ),
                "tokens_observed": acc[1] if acc else 0,
                "rep_run_max": acc[5] if acc else 0,
            }
            if slot.spec_proposed:
                quality["spec_acceptance"] = round(
                    slot.spec_accepted / slot.spec_proposed, 4
                )
            if slot.request.params.constrained:
                # the validity rate the canary judge's quality axis
                # compares across arms: a dead end is the constrained
                # path's "wrong answer"
                self._q_constraint_total += 1
                if reason == "constraint_dead_end":
                    self._q_constraint_bad += 1
        out = RequestOutput(
            request_id=rid,
            prompt=[int(t) for t in slot.prompt],
            tokens=list(slot.generated),
            finish_reason=reason,
            submit_time=slot.submit_time,
            first_token_time=slot.first_token_time,
            # a slot retired at its deadline may not have produced a
            # single token yet (still prefilling)
            finish_time=(
                slot.token_times[-1] if slot.token_times
                else (now if now is not None else time.perf_counter())
            ),
            token_times=list(slot.token_times),
            trace_id=(
                slot.trace.trace_id if slot.trace is not None else None
            ),
            spec_proposed=slot.spec_proposed,
            spec_accepted=slot.spec_accepted,
            token_logprobs=(
                list(slot.token_logprobs)
                if slot.token_logprobs is not None else None
            ),
            top_logprobs=(
                list(slot.top_logprobs)
                if slot.top_logprobs is not None else None
            ),
            quality=quality,
        )
        if self._tracing:
            targs = (
                instant_args(slot.trace) if slot.trace is not None
                else {}
            )
            self.tracer.instant("finish", rid=out.request_id,
                                reason=reason, **targs)
            # the request's whole submit->finish lifetime as ONE span,
            # parented to the caller's traceparent hop — what the
            # stitched timeline lines up under the router's forward span
            sargs = (
                child_span_args(slot.trace) if slot.trace is not None
                else {}
            )
            self.tracer.complete(
                "request", slot.submit_time, out.finish_time,
                rid=out.request_id, reason=reason, **sargs,
            )
        del self._base_keys[slot.request.request_id]
        self._drop_constraint(slot.request.request_id)
        if reason == "deadline":
            self.stats.inc("deadline_expired")
        elif reason != "constraint_dead_end":
            # a dead end is a typed FAILURE delivery (HTTP 400 with
            # partial output), not a completion — it rides only the
            # labeled finished counter
            self.stats.inc("completed")
        self._finished_counter.inc(reason=reason)
        self.scheduler.retire(slot)
        return out

    def _expire_queued(self, request, prompt, submit_time: float,
                       now: float, trace=None) -> RequestOutput:
        """A request whose deadline passed while it waited for a slot:
        it never touches the device; the caller gets a typed error."""
        self._base_keys.pop(request.request_id, None)
        self._drop_constraint(request.request_id)
        self._drop_resume(request.request_id)
        self._q_acc.pop(request.request_id, None)
        self.stats.inc("deadline_expired")
        self._finished_counter.inc(reason="deadline")
        if self._tracing:
            self.tracer.instant(
                "finish", rid=request.request_id, reason="deadline",
                **(instant_args(trace) if trace is not None else {}),
            )
        return RequestOutput(
            request_id=request.request_id,
            prompt=[int(t) for t in prompt],
            tokens=[],
            finish_reason="deadline",
            submit_time=submit_time,
            first_token_time=0.0,
            finish_time=now,
            token_times=[],
            trace_id=trace.trace_id if trace is not None else None,
        )

    def _corrupt_one_slot(self) -> None:
        """Fault-injection helper (``serve_corrupt@N``): NaN-poison one
        occupied slot's KV rows. Prefers an ACTIVE slot — the ring mask
        derives visibility from position arithmetic, so poison in
        not-yet-written positions would stay invisible; an active
        slot's already-written keys are visible and the next decode
        step's logits go NaN, tripping the finite-logits guard."""
        target = next(
            (s for s in self.scheduler.slots if s.state == ACTIVE), None
        ) or next(
            (s for s in self.scheduler.slots
             if s.state != FREE and s.filled > 0), None
        )
        if target is None:
            return
        i = target.index
        if self._pages is not None:
            # paged layout: the slot's KV lives in the pages its table
            # row names, not at batch index i
            row = [
                int(p) for p in self._pages.table_row(i)
                if int(p) != PagePool.TRASH
            ]
            if row:
                self.cache = self._poison_pages(row)
            return

        def _poison(key, arr):
            idx = (slice(None), i) if KV_CACHE_BATCH_AXIS[key] else i
            if jnp.issubdtype(arr.dtype, jnp.floating):
                return arr.at[idx].set(jnp.nan)
            # int8 values cannot hold NaN; zeroing them while the float
            # scale planes go NaN above makes every dequantized read
            # 0 * NaN = NaN, so the finite-logits guard still trips
            return arr.at[idx].set(0)

        self.cache = [
            {key: _poison(key, c[key]) for key in c} for c in self.cache
        ]

    # -- crash recovery (serving/server.py supervision) ----------------

    def reset_after_crash(self) -> List[int]:
        """Rebuild device-side state after a failed :meth:`step`.

        A crashed step leaves the engine untrusted: the jitted calls
        donate the cache pool, so a failure mid-call may have
        invalidated (or poisoned) it. Params are immutable jax arrays —
        never donated, never written — so the pool is rebuilt from
        scratch exactly as ``__init__`` built it, and the jitted
        closures are reused from the module-level cache (a restart adds
        ZERO recompiles; pinned by tests/test_serving_resilience.py).

        Requests that held slots (in-flight) lost device state and are
        FAILED — their request_ids are returned for the supervisor to
        error out with :class:`EngineCrashError`. Requests still in the
        wait queue never touched the device and are preserved verbatim
        (same request_id, prompt, deadline, PRNG base), so they complete
        normally after the restart. Stats survive;
        ``stats["engine_restarts"]`` counts the rebuilds.
        """
        if self._device_prof is not None:
            # a crash mid-capture leaves the profiler window open; the
            # torn trace is dropped (counted) so the rebuilt engine's
            # next due iteration captures normally
            self._device_prof.abort()
        lost: List[int] = []
        for slot in self.scheduler.slots:
            if slot.state != FREE and slot.request is not None:
                rid = slot.request.request_id
                lost.append(rid)
                self._base_keys.pop(rid, None)
                self._drop_constraint(rid)
                self._drop_resume(rid)
                self._q_acc.pop(rid, None)
        preserved = list(self.scheduler.queue)
        self._resumed = []
        # what was in flight belongs to requests failed above, and its
        # device state is as untrusted as the pool: dropped, not read
        self._inflight, self._firsts = None, []
        self._last_out = self._no_rows_sampled()
        if self._tier is not None:
            # host-cached prefixes are as untrusted as the device pool
            # they were captured from (a poisoned page demotes with a
            # VALID checksum — the CRC guards torn transfers, not
            # upstream corruption). Preempted requests' stashes
            # SURVIVE: their owners ride the preserved queue and
            # resume bit-exact on the rebuilt engine.
            self._tier.clear_cache()
        if self._paged:
            # fresh page pool AND an empty radix cache: untrusted KV
            # includes every cached prefix (the poisoned-prefix fault's
            # eviction path), so nothing cached survives a crash
            self._pages.reset()
            self.cache = init_cache_paged(
                self.cfg, self._pages.total_pages,
                self.serving.kv_page_size,
            )
        else:
            self.cache = init_cache(self.cfg, self._rows)
        if self._drafter is not None:
            # fresh drafter pool from params: its KV is as untrusted
            # as the target's after a crash, and the rebuild costs
            # zero recompiles (module-cached closures)
            self._drafter.reset()
        self.scheduler = Scheduler(
            self.serving,
            on_retire=(
                self._on_retire
                if (self._paged or self._spec_k) else None
            ),
            on_preempt=(
                self._preempt_slot if self._tier is not None else None
            ),
            pad_limit=self.cfg.block_size if self._pads_tail else 0,
        )
        self.scheduler.queue.extend(preserved)
        self.stats.inc("engine_restarts")
        # the crashed step never reached its gauge refresh; bring the
        # point-in-time view in line with the rebuilt (empty) slot pool
        self._update_gauges()
        return lost

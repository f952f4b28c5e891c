"""One decode step (PR 30), and what runs under the rows' vmap (PR 29).

``models/decode.py:_decode_step`` is the K/V families' one decode
program: ``forward_decode_pool`` (the L = 1 entry point) and
``forward_decode_spec`` (the verify entry point) bind a pool layout and
an attention to it through ``_pool_seam``'s ``write`` and ``attend``.
Until PR 29 the XLA step kept a whole layer a length-1 ``forward_chunk``
a row under ``jax.vmap``. With ``ffn_impl: pallas`` the vmap prepends the
rows to each kernel's grid: at the recipe's widths and 256 rows the fused
FFN kernel ran at ``grid=(256, 6, 1)``, a one-row matmul a grid step,
12,288 steps a decode step. Now only what is about a row's own position
stays under the vmap (``_chunk_qkv``, and ``_chunk_attend`` where a row
reads its own ring); the norms, the attention's output projection, the
FFN half and the head take ``(N, 1, 1, E)`` through their own
``reshape(-1, E)`` as M = N.

The first test pins that in the jaxpr, abstractly, at the serve cell's
size. The others hold the step to a length-1 ``forward_chunk`` a row, each
run as a program of its OWN at B = 1 (the oracle of
tests/test_decode_write.py vmaps the rows instead, and XLA batches a
vmapped matmul against shared weights into one of M = B: it stays equal
bit for bit, before and after PR 29). Against a real M = 1 program
float32 reassociates in the last bits, at any width, as it did before the
lift; bfloat16 rounds to the same values but for an odd last place. The
seam's cases run every binding of ``write`` and ``attend`` (family x pool
layout x rows a slot x attention) against that oracle, and the last test
pins that the step stays written once.
"""

import ast
import contextlib
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
from differential_transformer_replication_tpu.kernel_names import (
    FUSED_ADD_NORM_FWD,
    FUSED_FFN_FWD,
    KV_ROW_WRITE,
)
from differential_transformer_replication_tpu.models import init_model
from differential_transformer_replication_tpu.models.decode import (
    ATTEND_BLOCK_ROWS,
    KV_CACHE_BATCH_AXIS,
    attend_rows,
    forward_chunk,
    forward_decode_pool,
    forward_decode_spec,
    gather_slot_cache,
    init_cache,
    init_cache_paged,
    scatter_slot_cache,
)
from differential_transformer_replication_tpu.serving.engine import (
    ServingEngine,
    _build_step_fns,
    pack_decode_rows,
)

FAMILIES = ["control", "diff", "ndiff"]


def _step(decode, params, tokens, pos, active, pool):
    """The engine's decode program on tokens the host gives: every row
    of its one packed operand says ``from_host``, so the device's record
    of sampled rows (zeros here) is read by none."""
    rows = jnp.asarray(pack_decode_rows(tokens, pos, active))
    return decode(params, rows, jnp.zeros((rows.shape[0], 1), jnp.int32),
                  pool)


def _pallas_calls(jaxpr, out):
    """``(kernel name, grid, shape of the first operand)`` of every
    ``pallas_call`` of a jaxpr, those inside its calls included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        tuple(eqn.params["grid_mapping"].grid),
                        tuple(eqn.invars[0].aval.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, out)
    return out


@pytest.mark.parametrize("family", FAMILIES)
def test_no_ffn_or_norm_kernel_of_the_decode_program_has_a_row_grid(family):
    """The engine's decode program at the recipe's widths (8 layers of
    768, vocabulary 12,000, bf16, ``ffn_impl: pallas``) and the chat
    cell's 256 rows: traced on shapes alone, nothing runs."""
    rows = 256
    cfg = ModelConfig(
        model=family, vocab_size=12000, n_embd=768,
        n_head=8 if family == "control" else 4, n_layer=8, block_size=512,
        dropout=0.0, n_terms=3, compute_dtype="bfloat16", ffn_impl="pallas",
    )
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: init_cache(cfg, rows))
    # the engine's operands: the rows' one packed int32 array and the
    # device's record of the last sampled rows
    packed = jax.ShapeDtypeStruct((rows, 4), jnp.int32)
    sampled = jax.ShapeDtypeStruct((rows, 13), jnp.int32)
    decode = _build_step_fns(cfg, cfg.block_size)[1]
    calls = _pallas_calls(
        jax.make_jaxpr(decode)(params, packed, sampled, pool).jaxpr, [])

    ffn = [c for c in calls if c[0] == FUSED_FFN_FWD]
    # one row tile (pick_block(256, 256)), the hidden width in 6 tiles of
    # 512: the weights stream once a layer
    assert ffn == [(FUSED_FFN_FWD, (6, 1), (rows, 768))] * cfg.n_layer
    norms = [c for c in calls if c[0] == FUSED_ADD_NORM_FWD]
    # ln1 and add+ln2 a layer, ln_f, and the diff families' group norm
    sites = 2 * cfg.n_layer + 1 + (cfg.n_layer if family != "control" else 0)
    assert norms == [(FUSED_ADD_NORM_FWD, (1,), (rows, 768))] * sites
    # what is left with the rows in its grid is the cache write, which
    # addresses a slot a grid step (ops/kv_write.py)
    assert {c[0] for c in calls if rows in c[1]} == {KV_ROW_WRITE}
    assert len(calls) == len(ffn) + len(norms) + 2 * cfg.n_layer


SLOTS = 6
TOKENS = [7, 3, 250, 11, 99, 0]
POS = [5, 9, 31, 0, 17, 2]
# slots 1 and 4 are free or in mid-prefill: they run the same math and
# keep their rings
ACTIVE = [True, False, True, True, False, True]
# the widest gap a logit or a cached value may show against the M = 1
# programs: float32 reads 4.1e-7 here (reassociation), bfloat16 one last
# place of a value under 1 (2 ** -8), so 5 and 4 times the readings
TOLERANCE = {"float32": 2e-6, "bfloat16": 2.0 ** -6}


def _cfg(family, ffn, dtype):
    return ModelConfig(
        model=family, vocab_size=256, n_embd=128, n_head=2, n_layer=3,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype=dtype,
        ffn_impl=ffn,
    )


def _random_pool(cfg, rows, seed):
    rng = np.random.default_rng(seed)
    return [{key: jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)
             for key, leaf in layer.items()}
            for layer in init_cache(cfg, rows)]


def _slot(pool, b):
    return [{key: jax.lax.slice_in_dim(c[key], b, b + 1,
                                       axis=KV_CACHE_BATCH_AXIS[key])
             for key in c} for c in pool]


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("ffn", ["xla", "pallas"])
@pytest.mark.parametrize("family", FAMILIES)
def test_decode_step_matches_a_length_1_chunk_a_row(family, ffn, dtype):
    cfg = _cfg(family, ffn, dtype)
    params = init_model(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(TOKENS, jnp.int32)
    pos = jnp.asarray(POS, jnp.int32)
    before = _random_pool(cfg, SLOTS, 5)  # the step donates the one it gets
    decode = _build_step_fns(cfg, cfg.block_size)[1]
    logits, pool = _step(decode, params, tokens, pos, jnp.asarray(ACTIVE),
                         _random_pool(cfg, SLOTS, 5))
    chunk = jax.jit(lambda p, t, at, row: forward_chunk(
        p, t, at, row, cfg, rope_len=cfg.block_size))
    for b in range(SLOTS):
        got = _slot(pool, b)
        if not ACTIVE[b]:
            for have, kept in zip(got, _slot(before, b)):
                for key in kept:
                    np.testing.assert_array_equal(_f32(have[key]),
                                                  _f32(kept[key]))
            continue
        want_logits, want = chunk(params, tokens[b][None, None], pos[b],
                                  _slot(before, b))
        want_logits = _f32(want_logits[0, -1])
        assert int(np.argmax(logits[b])) == int(np.argmax(want_logits))
        np.testing.assert_allclose(np.asarray(logits[b]), want_logits,
                                   rtol=0, atol=TOLERANCE[dtype])
        for have, row in zip(got, want):
            for key in row:
                np.testing.assert_allclose(
                    _f32(have[key]), _f32(row[key]), rtol=0,
                    atol=TOLERANCE[dtype], err_msg=key)


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("ffn", ["xla", "pallas"])
def test_exact_verify_sub_step_is_a_plain_step_bit_for_bit(ffn, dtype):
    """Speculation's EXACT verify unrolls the engine's own decode step a
    draft row inside one program, on the pool with its trash row: every
    sub-step's logits and the pool it leaves equal those of the plain
    program run a step at a time, whatever M the lifted half runs at."""
    cfg = _cfg("diff", ffn, dtype)
    params = init_model(jax.random.PRNGKey(0), cfg)
    rows, depth = SLOTS + 1, 3  # the spec engine's pool: a trash row
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (SLOTS, depth)),
                         jnp.int32)
    pos = jnp.asarray(POS, jnp.int32)[:, None] % 16 + jnp.arange(depth)
    # drafts of 2, 0 (a slot that does not run), 1, 2, 0, 1 tokens: a row
    # past its slot's draft goes to the trash row
    drafted = np.asarray([2, -1, 1, 2, -1, 1])
    valid = np.arange(depth)[None, :] <= drafted[:, None]
    target = jnp.asarray(
        np.where(valid, np.arange(SLOTS)[:, None], SLOTS), jnp.int32)

    spec_logits, spec_pool = jax.jit(
        lambda p, t, at, pool, tgt: forward_decode_spec(
            p, t, at, pool, cfg, tgt, rope_len=cfg.block_size)
    )(params, tokens, pos, _random_pool(cfg, rows, 9), target)

    decode = _build_step_fns(cfg, cfg.block_size)[1]
    pool = _random_pool(cfg, rows, 9)

    def padded(column, fill):
        return jnp.concatenate([column, jnp.full((1,), fill, column.dtype)])

    for step in range(depth):
        logits, pool = _step(
            decode, params, padded(tokens[:, step], 0),
            padded(pos[:, step], 0),
            padded(jnp.asarray(valid[:, step]), False), pool)
        np.testing.assert_array_equal(np.asarray(spec_logits[:, step]),
                                      np.asarray(logits[:SLOTS]))
    for have, want in zip(spec_pool, pool):
        for key in want:
            np.testing.assert_array_equal(_f32(have[key]), _f32(want[key]),
                                          err_msg=key)


# ---------------------------------------------------------------------------
# The seam: every binding of ``write`` and ``attend`` against the oracle
# ---------------------------------------------------------------------------

DEPTH = 3  # rows a slot in a verify block: the last token and two drafts
PAGE = 8
# a slot's rows start here; slot 0's cross a page boundary (6, 7 | 8)
STARTS = [6, 9, 28, 0, 17]
# draft tokens a slot verifies; -1: the slot does not run at all (free, or
# in mid-prefill) and must keep its ring. The plain step runs row 0 alone.
DRAFTED = [2, -1, 1, 2, 0]


def _seam_cfg(family, attention="xla"):
    return ModelConfig(
        model=family, vocab_size=61, n_embd=64, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
        decode_attention_impl=attention,
    )


@lru_cache(maxsize=None)
def _seam_params(family):
    return init_model(jax.random.PRNGKey(0), _seam_cfg(family))


def _seam_inputs(cfg):
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, cfg.vocab_size, (len(STARTS), DEPTH))
    pos = np.asarray(STARTS)[:, None] + np.arange(DEPTH)
    return tokens.astype(np.int32), pos.astype(np.int32)


@lru_cache(maxsize=None)
def _chunk_a_row(family):
    """The oracle: slot b's rows one after another, each a length-1
    ``forward_chunk`` of its own (B = 1) on the slot's ring. ``[b][l]``
    is ``(row l's logits, the slot's ring after rows 0..l)``."""
    cfg, params = _seam_cfg(family), _seam_params(family)
    tokens, pos = _seam_inputs(cfg)
    pool = _random_pool(cfg, len(STARTS), 13)
    chunk = jax.jit(lambda t, at, row: forward_chunk(
        params, t, at, row, cfg, rope_len=cfg.block_size))
    out = []
    for b in range(len(STARTS)):
        ring, rows = _slot(pool, b), []
        for l in range(DEPTH):
            lg, ring = chunk(tokens[b, l][None, None], pos[b, l], ring)
            rows.append((np.asarray(lg[0, -1], np.float32), ring))
        out.append(rows)
    return out


def _paged(cfg, pool, tables):
    """``pool``'s rings laid out in pages: slot b's logical page j is
    physical page ``tables[b, j]``; page 0 is the trash page."""
    paged = init_cache_paged(cfg, 1 + tables.size, PAGE)
    for b in range(tables.shape[0]):
        paged = scatter_slot_cache(paged, _slot(pool, b), tables[b])
    return paged


def _with_trash_row(cfg, pool):
    """``pool`` and one more row past its slots, which holds anything."""
    extra = _random_pool(cfg, 1, 17)
    return [{key: jnp.concatenate([c[key], e[key]],
                                  axis=KV_CACHE_BATCH_AXIS[key])
             for key in c} for c, e in zip(pool, extra)]


@pytest.mark.parametrize("attention", ["xla", "pallas"])
@pytest.mark.parametrize("rows", ["step", "exact", "batched"])
@pytest.mark.parametrize("layout", ["slots", "pages"])
@pytest.mark.parametrize("family", FAMILIES)
def test_every_binding_of_the_seam_matches_a_length_1_chunk_a_row(
        family, layout, rows, attention):
    """``step``: the plain L = 1 step; ``exact`` and ``batched``: a verify
    block of ``DEPTH`` rows a slot in either formulation. A row that runs
    equals the oracle's (greedy token, logits, the K/V it wrote); a row
    that does not leaves no trace (its slot's ring bit for bit). An exact
    verify is, besides, the plain step a row at a time, bit for bit."""
    cfg, params = _seam_cfg(family, attention), _seam_params(family)
    slots, M = len(STARTS), cfg.block_size
    tokens, pos = _seam_inputs(cfg)
    depth = 1 if rows == "step" else DEPTH
    tokens, pos = tokens[:, :depth], pos[:, :depth]
    runs = np.arange(depth)[None, :] <= np.asarray(DRAFTED)[:, None]
    before = _random_pool(cfg, slots, 13)

    if layout == "pages":
        # physical pages in no order, so that a gather by the table shows
        tables = 1 + np.random.default_rng(5).permutation(
            slots * (M // PAGE)).reshape(slots, M // PAGE).astype(np.int32)
        pool, trash = _paged(cfg, before, tables), 0
        where = np.where(
            runs, np.take_along_axis(tables, pos % M // PAGE, axis=1), 0)
        paging = dict(page_tables=jnp.asarray(tables))
    else:
        # a verify's slot pool carries a trash row past the slots
        trash = int(depth > 1)
        pool = _with_trash_row(cfg, before) if trash else before
        where = np.where(runs, np.arange(slots)[:, None], slots)
        paging = {}
    where = jnp.asarray(where, jnp.int32)

    def ring_of(pool, b):
        if layout == "pages":
            return gather_slot_cache(pool, tables[b])
        return _slot(pool, b)

    @jax.jit
    def step(tokens, pos, pool, how):
        return forward_decode_pool(params, tokens, pos, pool, cfg,
                                   rope_len=M, **paging, **how)

    def column(l):
        """What the L = 1 entry point takes for column ``l`` of a block:
        the slot pool's trash row is a row that does not run."""
        if layout == "pages":
            how = dict(write_pages=where[:, l])
        else:
            how = dict(active=jnp.pad(jnp.asarray(runs[:, l]), (0, trash)))
        return (jnp.pad(tokens[:, l], (0, trash)),
                jnp.pad(pos[:, l], (0, trash)), how)

    if rows == "step":
        t, at, how = column(0)
        logits, after = step(t, at, pool, how)
        logits = logits[:, None]
    else:
        logits, after = jax.jit(
            lambda t, at, pool, tgt: forward_decode_spec(
                params, t, at, pool, cfg, tgt, rope_len=M,
                batched=rows == "batched", **paging)
        )(tokens, pos, pool, where)
    assert logits.shape == (slots, depth, cfg.vocab_size)
    assert logits.dtype == jnp.float32

    tol = TOLERANCE["float32"]
    for b, oracle in enumerate(_chunk_a_row(family)):
        ran = min(DRAFTED[b] + 1, depth)
        want_ring = oracle[ran - 1][1] if ran else _slot(before, b)
        for have, want in zip(ring_of(after, b), want_ring):
            for key in want:
                # a slot that did not run keeps its ring bit for bit
                np.testing.assert_allclose(
                    _f32(have[key]), _f32(want[key]), rtol=0,
                    atol=tol if ran else 0, err_msg=key)
        for l in range(ran):
            have, want = np.asarray(logits[b, l]), oracle[l][0]
            assert int(np.argmax(have)) == int(np.argmax(want))
            np.testing.assert_allclose(have, want, rtol=0, atol=tol)

    if rows == "exact":
        for l in range(depth):
            t, at, how = column(l)
            lg, pool = step(t, at, pool, how)
            np.testing.assert_array_equal(np.asarray(logits[:, l]),
                                          np.asarray(lg[:slots]))
        for have, want in zip(after, pool):
            for key in want:
                np.testing.assert_array_equal(
                    _f32(have[key]), _f32(want[key]), err_msg=key)


# ---------------------------------------------------------------------------
# The XLA own-ring attend stops at the highest active row (PR 33)
# ---------------------------------------------------------------------------

# two whole blocks of ATTEND_BLOCK_ROWS and six rows more: the last block
# starts early and overlaps the one before it
POOL_ROWS = 2 * ATTEND_BLOCK_ROWS + 6
# mask: (the active rows, the rows the attend reads for them)
MASKS = {
    "none": ([], 0),
    "first": ([0], ATTEND_BLOCK_ROWS),
    "last": ([POOL_ROWS - 1], POOL_ROWS),
    # ends in the middle of the second block
    "scattered": ([1, 5, ATTEND_BLOCK_ROWS + 1, ATTEND_BLOCK_ROWS + 8],
                  2 * ATTEND_BLOCK_ROWS),
    "all": (list(range(POOL_ROWS)), POOL_ROWS),
}
ROPE_LEN = 128
# An int8 store rounds a row's K/V on its way in. The two programs'
# float32 differs in its last place (a block's matmuls run at M = 32, the
# pool's at M = 70: reassociation, as TOLERANCE says), so a value on a
# rounding boundary can land one int8 step apart, and the logits that
# read it move: one row of the 420 here reads 6.3e-5 (ndiff, all rows).
INT8_LOGITS = 5e-4


@lru_cache(maxsize=None)
def _bounded(family, store):
    """``(cfg, params, the engine's decode program, the whole-pool step,
    tokens, positions)``: the two programs differ in ``active`` alone,
    the mask against None."""
    cfg = ModelConfig(
        model=family, vocab_size=67, n_embd=64, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
        kv_cache_dtype=store,
    )
    params = init_model(jax.random.PRNGKey(0), cfg)
    whole = jax.jit(lambda t, at, pool: forward_decode_pool(
        params, t, at, pool, cfg, rope_len=ROPE_LEN))
    rng = np.random.default_rng(21)
    tokens = rng.integers(0, cfg.vocab_size, POOL_ROWS).astype(np.int32)
    # control's rings have rolled (RoPE: positions past the block); diff's
    # learned positions end at the block
    pos = rng.integers(*((40, 100) if family == "control" else (0, 32)),
                       POOL_ROWS).astype(np.int32)
    return (cfg, params, _build_step_fns(cfg, ROPE_LEN)[1], whole,
            jnp.asarray(tokens), jnp.asarray(pos))


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("store", ["auto", "int8"])
@pytest.mark.parametrize("family", FAMILIES)
def test_the_own_ring_attend_stops_at_the_highest_active_row(family, store,
                                                             mask):
    """The engine's step (a mask) against the same step with
    ``active=None`` (the whole-pool ``vmap``, no loop): every ACTIVE row
    has its logits and leaves its ring as there; the rule the program
    runs and the one the engine reads on the host agree on the rows
    read; the rows past them come out finite and the same whatever
    their rings hold (they were not read); and ONE program serves every
    mask."""
    cfg, params, decode, whole, tokens, pos = _bounded(family, store)
    rows, read = MASKS[mask]
    active = np.zeros(POOL_ROWS, bool)
    active[rows] = True
    assert attend_rows(active) == read
    assert int(jax.jit(attend_rows)(jnp.asarray(active))) == read

    logits, pool = _step(decode, params, tokens, pos, jnp.asarray(active),
                         _random_pool(cfg, POOL_ROWS, 23))
    want_logits, want_pool = whole(tokens, pos,
                                   _random_pool(cfg, POOL_ROWS, 23))
    assert decode._cache_size() == 1
    assert np.isfinite(np.asarray(logits)).all()
    tol = TOLERANCE["float32"]
    np.testing.assert_allclose(np.asarray(logits)[rows],
                               np.asarray(want_logits)[rows], rtol=0,
                               atol=INT8_LOGITS if store == "int8" else tol)
    before = _random_pool(cfg, POOL_ROWS, 23)
    for have, want, kept in zip(pool, want_pool, before):
        for key in want:
            axis = KV_CACHE_BATCH_AXIS[key]

            def take(leaf, idx):
                return np.take(_f32(leaf), idx, axis)

            np.testing.assert_allclose(
                take(have[key], rows), take(want[key], rows), rtol=0,
                atol=1 if have[key].dtype == jnp.int8 else tol, err_msg=key)
            idle = np.flatnonzero(~active)
            np.testing.assert_array_equal(take(have[key], idle),
                                          take(kept[key], idle), key)
    if read < POOL_ROWS:
        other, _ = _step(decode, params, tokens, pos, jnp.asarray(active),
                         _random_pool(cfg, POOL_ROWS, 29))
        np.testing.assert_array_equal(np.asarray(other)[read:],
                                      np.asarray(logits)[read:])
        assert not np.array_equal(np.asarray(want_logits)[read:],
                                  np.asarray(logits)[read:])


class _Spans:
    """The tracer's interface, keeping the spans the engine hands it."""
    path, annotate = None, False

    def __init__(self):
        self.spans = []

    def span(self, name, **args):
        self.spans.append((name, args))
        return contextlib.nullcontext()

    def __getattr__(self, name):  # instant, counter, complete, flush, close
        return lambda *a, **k: None


@pytest.mark.parametrize("model, serving, read", [
    ({}, dict(num_slots=40), ATTEND_BLOCK_ROWS),  # three requests: one block
    ({}, dict(num_slots=4), 4),  # a pool smaller than a block: itself
    (dict(decode_attention_impl="pallas"), dict(num_slots=40), None),
    ({}, dict(num_slots=40, kv_page_size=8), None),
], ids=["xla", "xla-small-pool", "pallas", "paged"])
def test_the_decode_span_says_what_the_attend_read(model, serving, read):
    """The engine puts the rule's value for the mask it built on the
    ``decode`` span and sums it in ``decode_attend_rows``; a step whose
    attention is bound another way (the fused kernel, pages) says
    nothing, for it reads the pool another way."""
    cfg = ModelConfig(model="diff", vocab_size=67, n_embd=64, n_head=2,
                      n_layer=2, block_size=32, dropout=0.0,
                      compute_dtype="float32", **model)
    spans = _Spans()
    eng = ServingEngine(
        init_model(jax.random.PRNGKey(0), cfg), cfg,
        ServingConfig(prefill_chunk=8, **serving),
        tracer=spans)
    eng.generate([[1, 2, 3], [4, 5], [6, 7, 8, 9]], max_new_tokens=4,
                 temperature=0.0)
    steps = [a for n, a in spans.spans if n == "decode"]
    assert steps and all(0 < a["active"] <= 3 for a in steps)
    assert [a.get("attend_rows") for a in steps] == [read] * len(steps)
    assert eng.stats["decode_attend_rows"] == (read or 0) * len(steps)
    assert "serving_decode_attend_rows_total" in eng.registry.render()


PACKAGE = Path(__file__).resolve().parents[1] / (
    "differential_transformer_replication_tpu")


def _function_of(tree, node):
    """The name of the innermost function that holds ``node``."""
    holders = [fn for fn in ast.walk(tree)
               if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
               and fn.lineno <= node.lineno <= fn.end_lineno]
    return max(holders, key=lambda fn: fn.lineno).name if holders else None


def test_the_decode_step_stays_written_once():
    """The fork PR 30 closed cannot grow back unseen: in the package,
    outside ``config.py`` (which validates the option), ONE comparison
    reads ``decode_attention_impl``, in ``models/decode.py:_fused_attend``
    (``_pool_seam`` binds by it, and through ``own_ring_attend`` the
    engine learns what its ``decode`` span may say of the attention);
    and ``models/decode.py`` walks ``enumerate(params["blocks"], 1)`` in
    two functions, ``forward_chunk`` and the one decode step (the jamba
    family's loops zip its two kinds of layer and are not counted)."""
    compares, loops = [], []
    for path in sorted(PACKAGE.rglob("*.py")):
        if path.name == "config.py":
            continue
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Compare) and any(
                    isinstance(side, ast.Attribute)
                    and side.attr == "decode_attention_impl"
                    for side in [node.left, *node.comparators]):
                compares.append((path.name, _function_of(tree, node)))
            if (path.name == "decode.py" and isinstance(node, ast.For)
                    and ast.unparse(node.iter).startswith(
                        "enumerate(params['blocks']")):
                loops.append(_function_of(tree, node))
    assert compares == [("decode.py", "_fused_attend")]
    assert sorted(loops) == ["_decode_step", "forward_chunk"]

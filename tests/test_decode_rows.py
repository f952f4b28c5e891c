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
from functools import lru_cache
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.kernel_names import (
    FUSED_ADD_NORM_FWD,
    FUSED_FFN_FWD,
    KV_ROW_WRITE,
)
from differential_transformer_replication_tpu.models import init_model
from differential_transformer_replication_tpu.models.decode import (
    KV_CACHE_BATCH_AXIS,
    forward_chunk,
    forward_decode_pool,
    forward_decode_spec,
    gather_slot_cache,
    init_cache,
    init_cache_paged,
    scatter_slot_cache,
)
from differential_transformer_replication_tpu.serving.engine import (
    _build_step_fns,
)

FAMILIES = ["control", "diff", "ndiff"]


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
    ids = jax.ShapeDtypeStruct((rows,), jnp.int32)
    active = jax.ShapeDtypeStruct((rows,), jnp.bool_)
    decode = _build_step_fns(cfg, cfg.block_size)[1]
    calls = _pallas_calls(
        jax.make_jaxpr(decode)(params, ids, ids, active, pool).jaxpr, [])

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
    logits, pool = decode(params, tokens, pos, jnp.asarray(ACTIVE),
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
        logits, pool = decode(
            params, padded(tokens[:, step], 0), padded(pos[:, step], 0),
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
    reads ``decode_attention_impl``, in ``models/decode.py:_pool_seam``;
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
    assert compares == [("decode.py", "_pool_seam")]
    assert sorted(loops) == ["_decode_step", "forward_chunk"]

"""The XLA decode step runs what is about no ring once over the pool's
rows (PR 29).

Until PR 29 ``forward_decode_rows`` kept a whole layer a length-1
``forward_chunk`` a row under ``jax.vmap``. With ``ffn_impl: pallas``
the vmap prepends the rows to each kernel's grid: at the recipe's widths
and 256 rows the fused FFN kernel ran at ``grid=(256, 6, 1)``, a
one-row matmul a grid step, 12,288 steps a decode step. Now only the
row's own ring stays under the vmap (``_chunk_qkv``, ``_chunk_attend``);
the norms, the attention's output projection, the FFN half and the head
take ``(B, 1, 1, E)`` through their own ``reshape(-1, E)`` as M = B.

The first test pins that in the jaxpr, abstractly, at the serve cell's
size. The others hold the step to a length-1 ``forward_chunk`` a row, each
run as a program of its OWN at B = 1 (the oracle of
tests/test_decode_write.py vmaps the rows instead, and XLA batches a
vmapped matmul against shared weights into one of M = B: it stays equal
bit for bit, before and after this PR). Against a real M = 1 program
float32 reassociates in the last bits, at any width, as it did before the
lift; bfloat16 rounds to the same values but for an odd last place.
"""

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
    forward_decode_spec,
    init_cache,
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

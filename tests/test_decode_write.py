"""The decode step's masked, in-place cache write (PR 25).

Until PR 25 the engine's decode program updated EVERY row of the pool and
then selected, a leaf at a time, between the new pool and the old one
(``merge_cache_update``: ``where(active, new, old)``), which kept both
pools alive and cost the chip a dozen passes over the cache a step. Now
the mask goes into the write (models/decode.py:``_write_targets``,
ops/kv_write.py). These cases keep the OLD rule as their own oracle:
update every row, then ``where(active, new, old)`` a leaf — and hold the
new program to the same pool and the same logits, bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from differential_transformer_replication_tpu.config import ModelConfig
from differential_transformer_replication_tpu.models import init_model
from differential_transformer_replication_tpu.models.decode import (
    KV_CACHE_BATCH_AXIS,
    forward_chunk,
    forward_decode_pool,
    init_cache,
)
from differential_transformer_replication_tpu.ops.kv_write import (
    slot_owners,
    write_rows,
)
from differential_transformer_replication_tpu.serving.engine import (
    _build_step_fns,
    pack_decode_rows,
)

SLOTS = 4
# slot 1 is in mid-prefill (its ring holds a partial prompt, its entry in
# ``pos`` is whatever the host left there), slot 3 is free
MASKS = {
    "all": [True, True, True, True],
    "none": [False, False, False, False],
    "mixed": [True, False, True, False],
}
POS = [5, 9, 31, 0]


def _step(decode, params, tokens, pos, active, pool):
    """The engine's decode program on tokens the host gives: every row
    of its one packed operand says ``from_host``, so the device's record
    of sampled rows (zeros here) is read by none."""
    rows = jnp.asarray(pack_decode_rows(tokens, pos, active))
    return decode(params, rows, jnp.zeros((rows.shape[0], 1), jnp.int32),
                  pool)


def _cfg(family, kv, impl):
    return ModelConfig(
        model=family, vocab_size=61, n_embd=32, n_head=2, n_layer=2,
        block_size=32, dropout=0.0, n_terms=3, compute_dtype="float32",
        kv_cache_dtype=kv, decode_attention_impl=impl,
    )


def _random_pool(cfg, seed):
    """A pool whose every position holds something: any value the step
    must not touch is one it could be caught touching."""
    rng = np.random.default_rng(seed)
    pool = []
    for layer in init_cache(cfg, SLOTS):
        filled = {}
        for key, leaf in layer.items():
            if leaf.dtype == jnp.int8:
                val = rng.integers(-127, 128, leaf.shape)
            elif key.endswith("_scale"):
                val = rng.uniform(0.001, 0.05, leaf.shape)
            else:
                val = rng.normal(size=leaf.shape)
            filled[key] = jnp.asarray(val, leaf.dtype)
        pool.append(filled)
    return pool


def _update_every_row(cfg, params, tokens, pos, pool):
    """The step as it was before the mask moved into the write: the
    vmapped length-1 ``forward_chunk`` a row (``xla``), the fused pool
    step with no mask (``pallas``)."""
    if cfg.decode_attention_impl == "pallas":
        logits, new = forward_decode_pool(params, tokens, pos, pool, cfg,
                                          rope_len=cfg.block_size)
        return logits.astype(jnp.float32), new

    def one_row(token, p, row):
        row = [{k: jnp.expand_dims(c[k], KV_CACHE_BATCH_AXIS[k]) for k in c}
               for c in row]
        logits, new = forward_chunk(params, token[None, None], p, row, cfg,
                                    rope_len=cfg.block_size)
        new = [{k: jnp.squeeze(c[k], KV_CACHE_BATCH_AXIS[k]) for k in c}
               for c in new]
        return logits[0, -1].astype(jnp.float32), new

    axes = [{k: KV_CACHE_BATCH_AXIS[k] for k in c} for c in pool]
    return jax.vmap(one_row, in_axes=(0, 0, axes),
                    out_axes=(0, axes))(tokens, pos, pool)


def _old_rule(cfg, params, tokens, pos, active, pool):
    logits, new = _update_every_row(cfg, params, tokens, pos, pool)
    merged = []
    for nc, oc in zip(new, pool):
        layer = {}
        for key in nc:
            axis = KV_CACHE_BATCH_AXIS[key]
            shape = (1,) * axis + (-1,) + (1,) * (nc[key].ndim - axis - 1)
            layer[key] = jnp.where(active.reshape(shape), nc[key], oc[key])
        merged.append(layer)
    return logits, merged


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("family", ["control", "diff", "ndiff"])
def test_decode_step_leaves_the_pool_the_old_masked_merge_left(
        family, kv, impl, mask):
    cfg = _cfg(family, kv, impl)
    params = init_model(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray([7, 3, 60, 11], jnp.int32)
    pos = jnp.asarray(POS, jnp.int32)
    active = jnp.asarray(MASKS[mask])
    # params ride as an argument, as in the engine: closed over they
    # would be folded at compile time, by another exp than the program's
    want_logits, want_pool = jax.jit(
        lambda *a: _old_rule(cfg, *a)
    )(params, tokens, pos, active, _random_pool(cfg, 5))

    decode = _build_step_fns(cfg, cfg.block_size)[1]
    given = _random_pool(cfg, 5)
    logits, pool = _step(decode, params, tokens, pos, active, given)
    for got, want in zip(pool, want_pool):
        assert sorted(got) == sorted(want)
        for key in want:
            assert got[key].dtype == want[key].dtype
            np.testing.assert_array_equal(
                np.asarray(got[key].astype(jnp.float32)),
                np.asarray(want[key].astype(jnp.float32)), err_msg=key)
    on = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(logits)[on],
                                  np.asarray(want_logits)[on])
    # the argument was donated, and nothing reads it afterwards: the
    # next step runs on the pool that came back
    assert all(leaf.is_deleted() for c in given for leaf in c.values())
    again, pool2 = _step(decode, params, tokens, pos + 1, active, pool)
    assert np.isfinite(np.asarray(again)[on]).all()
    assert all(leaf.is_deleted() for c in pool for leaf in c.values())
    assert len(pool2) == cfg.n_layer


LEAVES = [
    # (leaf shape, pool axis, dtype): K and V with the ring on the
    # sublanes and (M a multiple of 128, features not) on the lanes, the
    # int8 leaves, the scale planes
    ((2, 5, 3, 32, 16), 1, jnp.float32),
    ((5, 3, 32, 24), 0, jnp.bfloat16),
    ((2, 5, 3, 256, 16), 1, jnp.bfloat16),
    ((5, 3, 128, 24), 0, jnp.float32),
    ((1, 5, 2, 128, 24), 1, jnp.int8),
    ((5, 2, 64, 128), 0, jnp.int8),
    ((2, 5, 3, 32), 1, jnp.float32),
    ((5, 3, 128), 0, jnp.float32),
]


# ring positions of five slots; -1 keeps the slot's row, M is the leaf's
# ring length. The kernel's grid step of a kept slot points at its
# owner's block and moves nothing (ops/kv_write.py), so the patterns are
# the places a kept step can stand in: nowhere, everywhere, before the
# first writer, between writers, behind the last
TARGETS = {
    "mixed": lambda M: [3, -1, M - 1, 0, -1],
    "none": lambda M: [-1, -1, -1, -1, -1],
    "last-only": lambda M: [-1, -1, -1, -1, 5],
    "first-only": lambda M: [M - 2, -1, -1, -1, -1],
    "kept-then-writers": lambda M: [-1, -1, 7, -1, M - 1],
    "all": lambda M: [0, M - 1, 9, M // 2, 1],
    # one 128-block (and one sublane tile) index, different slots
    "same-block": lambda M: [-1, 2, -1, 5, -1],
}


@pytest.mark.parametrize("pattern", sorted(TARGETS))
@pytest.mark.parametrize("shape,axis,dtype", LEAVES,
                         ids=[f"{'x'.join(map(str, s))}-{jnp.dtype(d).name}"
                              for s, _, d in LEAVES])
def test_write_rows_puts_one_position_a_slot_and_keeps_the_rest(
        shape, axis, dtype, pattern):
    rng = np.random.default_rng(0)

    def draw(shp):
        if dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shp), dtype)
        return jnp.asarray(rng.normal(size=shp), dtype)

    M = shape[axis + 2]
    leaf = draw(shape)
    rows = draw(shape[:axis + 2] + shape[axis + 3:])
    targets = TARGETS[pattern](M)
    want = np.array(leaf.astype(jnp.float32))
    for b, t in enumerate(targets):
        if t >= 0:
            at = (slice(None),) * axis + (b, slice(None), t)
            want[at] = np.asarray(
                rows.astype(jnp.float32))[(slice(None),) * axis + (b,)]
    got = jax.jit(lambda l, r, t: write_rows(l, r, t, axis))(
        leaf, rows, jnp.asarray(targets, jnp.int32))
    assert got.dtype == leaf.dtype and got.shape == leaf.shape
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), want)


@pytest.mark.parametrize("targets,owners", [
    # a writer owns itself; a kept slot rides on the writer before it
    ([3, -1, 31, 0, -1], [0, 0, 2, 3, 3]),
    # before the first writer: the first writer, whose fetch is needed
    ([-1, -1, 7, -1, 31], [2, 2, 2, 2, 4]),
    ([-1, -1, -1, -1, 5], [4, 4, 4, 4, 4]),
    ([30, -1, -1, -1, -1], [0, 0, 0, 0, 0]),
    # nobody writes: one block, slot 0's, read once and put back
    ([-1, -1, -1, -1, -1], [0, 0, 0, 0, 0]),
    # everybody writes: the identity, the kernel as it was
    ([0, 31, 9, 16, 1], [0, 1, 2, 3, 4]),
    ([4], [0]),
    ([-1], [0]),
], ids=["mixed", "kept-then-writers", "last-only", "first-only", "none",
        "all", "one-writes", "one-keeps"])
def test_slot_owners_names_the_nearest_writer_at_or_before_a_slot(
        targets, owners):
    got = jax.jit(slot_owners)(jnp.asarray(targets, jnp.int32))
    assert got.dtype == jnp.int32
    assert np.asarray(got).tolist() == owners

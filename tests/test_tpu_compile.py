"""Compile the main path's kernels for a TPU v5e that is described, not
attached (``topologies.get_topology_desc("tpu", "v5e:2x2")``).

Interpret mode checks none of what the chip's compiler refuses: block
shapes off the (8, 128) tiling, a matmul Mosaic cannot express, too much
fast memory. These cases hand the real lowering recipe-width shapes, so a
kernel that stops compiling for the chip fails here, at no chip time.
Nothing runs — a compile that passes is not a chip run.

The code under test asks ``auto_interpret()`` and still sees the CPU, so
the fixture steers that one probe to the compiled path. The persistent
compile cache is switched off around the module: such compiles would be
written to it and could not be read back without a chip. libtpu admits
one process at a time: under pytest-xdist the workers that lose its lock
skip these cases (the tier-1 command runs them serially).
"""

import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from differential_transformer_replication_tpu.config import (
    MeshConfig,
    ModelConfig,
    ServingConfig,
    TrainConfig,
)
from differential_transformer_replication_tpu.models.common import (
    apply_block_ffn,
    flash_bh_fn,
)
from differential_transformer_replication_tpu import kernel_names
from differential_transformer_replication_tpu.models.decode import (
    ATTEND_BLOCK_ROWS,
)
from differential_transformer_replication_tpu.ops.rope import rope_cos_sin

# the module, not the function ops/__init__.py re-exports under the name
dattn = importlib.import_module(
    "differential_transformer_replication_tpu.ops.decode_attention"
)

KERNEL_MODULES = (
    "ops.flash", "ops.fused_ffn", "ops.fused_norm_residual",
    "ops.decode_attention", "ops.kv_write", "ops.ssm", "ops.kda", "ops.moe",
    "ops.ring_attention", "ops.mla", "ops.ssd",
)

# the recipe's widths (8L/768d, T=512, vocab 12000); the batch is cut, a
# kernel's tiling does not depend on it
E, T, B = 768, 512, 1
FAMILIES = {
    # family: (streams, heads, d, dv, rope) — control doubles its heads
    "control": (1, 8, 96, 96, True),
    "diff": (2, 4, 96, 192, False),
    "ndiff": (4, 4, 96, 192, True),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here: nothing to ask
        pytest.skip(f"no TPU compiler to describe a v5e to: {e!r}")


@pytest.fixture(autouse=True)
def tpu_lowering(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    for name in KERNEL_MODULES:
        mod = importlib.import_module(
            f"differential_transformer_replication_tpu.{name}"
        )
        for attr in ("auto_interpret", "_auto_interpret"):
            if hasattr(mod, attr):
                monkeypatch.setattr(mod, attr, lambda: False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_for(topo, fn, *shapes, sharding=None):
    """Compile ``fn`` for the described chip; returns the compiled text."""
    sharding = sharding or SingleDeviceSharding(topo.devices[0])
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes,
    )
    return jax.jit(fn).lower(*args).compile().as_text()


def sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _attention_shapes(family):
    S, H, d, dv, _ = FAMILIES[family]
    return (sds((B, T, E)), sds((S, E, H, d), jnp.float32),
            sds((S, E, H, d), jnp.float32), sds((E, H, dv), jnp.float32),
            sds((S, H), jnp.float32))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_flash_attention_fwd_bwd_compiles(topo, family):
    """The head-major flash kernels (the path of dropout > 0 and of long
    T), forward and backward, with each family's stream count."""
    from differential_transformer_replication_tpu.ops.flash import (
        multi_stream_flash_attention,
    )

    S, H, d, dv, _ = FAMILIES[family]

    def loss(qs, ks, v, coeffs):
        out = multi_stream_flash_attention(qs, ks, v, coeffs)
        return out.astype(jnp.float32).sum()

    text = compile_for(
        topo, jax.grad(loss, argnums=(0, 1, 2, 3)),
        sds((S, B, T, H, d)), sds((S, B, T, H, d)), sds((B, T, H, dv)),
        sds((S, H), jnp.float32),
    )
    assert text.count("tpu_custom_call") >= 2  # forward and backward
    names = assert_kernels_named(text, "loss")
    # called bare under jax.grad the instruction is ``jvp_<name>_``
    assert any("flash_fwd" in n for n in names)
    assert any("flash_bwd" in n for n in names)


def _attention_half_text(topo, family, batch=B):
    d, rope = FAMILIES[family][2], FAMILIES[family][4]
    cos, sin = rope_cos_sin(d, T) if rope else (None, None)

    def loss(x, wq, wk, wv, coeffs):
        out = flash_bh_fn(x, wq, wk, wv, coeffs, dropout_rate=0.0, rng=None,
                          cos=cos, sin=sin)()
        return out.astype(jnp.float32).sum()

    _, *weights = _attention_shapes(family)
    return compile_for(topo, jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                       sds((batch, T, E)), *weights)


@pytest.mark.parametrize("family", [
    "diff",  # the flagship, and what chip_smoke.py trains
    pytest.param("control", marks=pytest.mark.slow),  # 10 s
    pytest.param("ndiff", marks=pytest.mark.slow),  # 20 s
])
def test_model_attention_path_compiles(topo, family):
    """The attention path the models take at T=512 without dropout (the
    token-major kernels: control and ndiff hand them the RoPE tables and
    they turn q and k in VMEM; packed but for ndiff, whose four rotated
    streams pass the packed backward's VMEM), projections included,
    forward and backward."""
    text = _attention_half_text(topo, family)
    assert text.count("tpu_custom_call") >= 2
    names = assert_kernels_named(text, "loss")
    # ``jvp_flash_fwd_tm_packed_``
    tail = "_tm" if family == "ndiff" else "_tm_packed"
    assert all(re.search(rf"flash_(fwd|bwd){tail}_*$", n) for n in names), names


def _layout_ops(text):
    """``(op, dtype, dims)`` of every gather, transpose and copy
    instruction of the compiled text."""
    return [
        (op, dtype, [int(n) for n in shape.split(",")])
        for dtype, shape, op in re.findall(
            r"= (\w+)\[([\d,]+)\]\S* (gather|transpose|copy)\(", text)
    ]


@pytest.mark.parametrize("family", [
    "control",  # the cell train-control-recipe
    pytest.param("ndiff", marks=pytest.mark.slow),
    "diff",
])
def test_rotation_leaves_no_layout_op_on_an_activation(topo, family):
    """ISSUE 37: ``apply_rope``'s stride of two along the lanes compiled,
    a layer, to 4 gathers that put the FEATURE first, 16 float32
    transposes and a dozen copies of ``(rows, T, 8, 48)`` arrays: 8.7 GB
    of HBM traffic where the products need 2. With the weights' columns
    re-ordered and the halves turned in VMEM, the attention half of a
    layer, forward and backward, holds NO gather, NO transpose, and no
    copy of an activation (an array with a T axis as large as half of one
    head's q): what is left copies weights and the backward's per-row
    statistics. diff, which rotates nothing, is held to the same, so its
    packed projection is never copied."""
    rows = 8
    ops = _layout_ops(_attention_half_text(topo, family, batch=rows))
    assert ops, "the pattern no longer finds the weights' copies"
    assert not [o for o in ops if o[0] != "copy"], ops
    d = FAMILIES[family][2]
    assert not [o for o in ops
                if T in o[2] and math.prod(o[2]) >= rows * T * d // 2], ops


def test_flash_attention_with_dropout_compiles(topo):
    """Dropout > 0 takes the model off the token-major path, onto the
    head-major kernels with in-kernel masks."""
    def loss(x, wq, wk, wv, coeffs, rng):
        out = flash_bh_fn(x, wq, wk, wv, coeffs, dropout_rate=0.1, rng=rng)()
        return out.astype(jnp.float32).sum()

    text = compile_for(topo, jax.grad(loss, argnums=(0, 1, 2, 3)),
                       *_attention_shapes("diff"), sds((2,), jnp.uint32))
    assert text.count("tpu_custom_call") >= 2
    assert not any("_tm" in n for n in assert_kernels_named(text, "loss"))


def test_fused_ffn_and_add_norm_compile(topo):
    """A block's FFN half on the fused path (ops/fused_norm_residual.py +
    ops/fused_ffn.py), forward and backward."""
    cfg = ModelConfig(model="diff", n_embd=E, ffn_impl="pallas", dropout=0.0)
    blk = {
        "ln2": {"w": sds((E,), jnp.float32), "b": sds((E,), jnp.float32)},
        "ffn": {
            name: {"w": sds(shape, jnp.float32),
                   "b": sds(shape[1:], jnp.float32)}
            for name, shape in (("gate", (E, 4 * E)), ("xform", (E, 4 * E)),
                                ("out", (4 * E, E)))
        },
    }

    def loss(x, attn_out, blk):
        return apply_block_ffn(x, attn_out, blk, cfg).astype(jnp.float32).sum()

    text = compile_for(topo, jax.grad(loss, argnums=(0, 1, 2)),
                       sds((B, T, E)), sds((B, T, E)), blk)
    assert text.count("tpu_custom_call") >= 4  # add+norm and SwiGLU, both ways
    assert assert_kernels_named(text, "loss") == set(
        kernel_names.FUSED_FFN + kernel_names.FUSED_NORM)
    assert {"ffn_norm", "ffn"} <= scopes_in(text)


def test_fused_ffn_gradient_compiles_at_the_cells_rows(topo):
    """The SwiGLU kernels under a gradient at the row count the train
    cells run (64 x 512 = 32,768): the backward takes its tiles and its
    VMEM limit from the shape (ops/fused_ffn.py:_bwd_tiles), and it
    writes dg and dt over the saved pre-activations, so the program
    holds no second pair of (M, 4E) arrays."""
    from differential_transformer_replication_tpu.ops.fused_ffn import (
        fused_swiglu,
    )

    rows, F = 64, 4 * E

    def loss(x, wg, bg, wx, bx):
        return fused_swiglu(x, wg, bg, wx, bx).astype(jnp.float32).sum()

    sharding = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in (((rows, T, E), jnp.bfloat16),
                                 ((E, F), jnp.float32), ((F,), jnp.float32),
                                 ((E, F), jnp.float32), ((F,), jnp.float32))]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    # one forward, one backward (autodiff wraps the instruction's name)
    names = sorted(assert_kernels_named(compiled.as_text(), "loss"))
    assert len(names) == 2, names
    assert kernel_names.FUSED_FFN_BWD in names[1], names
    assert kernel_names.FUSED_FFN_FWD in names[0], names
    # live at once: h's cotangent, g/dg and t/dt, and nothing else of
    # that size (a backward that did not alias would hold five)
    hidden = rows * T * F * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * hidden


# decode attention at the recipe's width over a 32-slot pool; pages of 8
# are what the verify skill and serve_bench --smoke use, 1 is the least
DECODE_CASES = [
    pytest.param(rows, page, kv,
                 id=f"L{rows}-{f'page{page}' if page else 'contiguous'}-{kv}")
    for rows in (1, 5) for page in (0, 8) for kv in ("bf16", "int8")
] + [pytest.param(1, 1, "int8", id="L1-page1-int8")]


@pytest.mark.parametrize("rows,page,kv", DECODE_CASES)
def test_decode_attention_compiles(topo, rows, page, kv):
    """The decode kernel's four entry points (single query and the
    speculative verify's L rows; slot pool and page table), bf16 and
    int8 KV. Every page size that divides block_size is a whole block,
    so the compiler takes it."""
    S, H, d, dv, _ = FAMILIES["diff"]
    # (a pool of one-token pages is large: fewer slots there)
    slots, M, L = 32 if page != 1 else 4, T, rows
    store = jnp.int8 if kv == "int8" else jnp.bfloat16
    # the spec engine's slot pool carries one trash row past the slots
    R = (slots * M // page + 1) if page else slots + (L > 1)
    m = page or M
    shapes = [sds((S, slots, L, H, d)), sds((S, R, H, m, d), store),
              sds((R, H, m, dv), store), sds((slots, L), jnp.int32),
              sds((S, H), jnp.float32)]
    if kv == "int8":
        shapes += [sds((S, R, H, m), jnp.float32), sds((R, H, m), jnp.float32)]
    if page:
        shapes.append(sds((slots, M // page), jnp.int32))

    def fn(qs, k, v, pos, coeffs, *rest):
        scales = dict(zip(("k_scale", "v_scale"), rest[:2])) if kv == "int8" else {}
        if L == 1:  # the single-query entry points
            qs, pos = qs[:, :, 0], pos[:, 0]
        if page:
            call = (dattn.decode_attention_paged if L == 1
                    else dattn.decode_attention_multi_paged)
            return call(qs, k, v, rest[-1], pos, coeffs, **scales)
        call = dattn.decode_attention if L == 1 else dattn.decode_attention_multi
        return call(qs, k, v, pos, coeffs, **scales)

    text = compile_for(topo, fn, *shapes)
    assert text.count("tpu_custom_call") == 1
    assert assert_kernels_named(text, "fn") == {kernel_names.DECODE_ATTENTION}


def test_page_size_is_refused_in_words_when_it_does_not_fit():
    """The kernel path takes every page size that divides block_size
    (down to 1, above), so that is the one condition a page size is held
    to — at engine build, before the first request, in words."""
    model = ModelConfig(model="diff", block_size=512)
    serving = ServingConfig(decode_attention_impl="pallas", kv_page_size=24)
    with pytest.raises(ValueError, match=r"kv_page_size \(24\) must divide "
                                         r"block_size \(512\)"):
        serving.resolved_pool_pages(model)
    for ok in (1, 8, 16, 128):
        assert ServingConfig(
            decode_attention_impl="pallas", kv_page_size=ok, num_slots=2,
        ).resolved_pool_pages(model) == 2 * (512 // ok)


def _recipe(family: str, n_layer: int = 8, **mesh) -> TrainConfig:
    return TrainConfig(
        model=ModelConfig(model=family, n_layer=n_layer,
                          attention_impl="pallas", ffn_impl="pallas"),
        mesh=MeshConfig(**mesh),
        micro_batch_size=32 * max(1, mesh.get("data", 1)),
    )


def _abstract_step_args(cfg: TrainConfig):
    from differential_transformer_replication_tpu.train.step import (
        create_train_state,
    )

    state = jax.eval_shape(
        lambda k: create_train_state(k, cfg), jax.random.PRNGKey(0)
    )
    tokens = sds((1, cfg.micro_batch_size, cfg.model.block_size), jnp.int32)
    return state, {"x": tokens, "y": tokens}


@pytest.fixture(scope="module")
def step_text(topo):
    """``step_text(family, n_layer=8)``: the compiled text of the whole
    jitted recipe step (8L/768d, T=512, micro-batch 32, vocab 12000, bf16,
    Pallas attention and FFN) for one described chip, 30-60 s a family at
    the recipe's depth, compiled once for every test of this module that
    reads it."""
    from differential_transformer_replication_tpu.train.step import make_step_fn

    texts = {}

    def get(family: str, n_layer: int = 8) -> str:
        if (family, n_layer) not in texts:
            cfg = _recipe(family, n_layer)
            state, batch = _abstract_step_args(cfg)
            texts[family, n_layer] = compile_for(
                topo, make_step_fn(cfg), state, batch)
        return texts[family, n_layer]

    return get


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_whole_train_step_compiles_one_chip(step_text, family):
    # diff: 82 kernels in the compiled step (ISSUE 21; the same count ran
    # on the chip in chip_smoke.py)
    assert step_text(family).count("tpu_custom_call") >= 60


# -- names (ISSUE 24): what a profiler trace of the chip will call things --


def kernel_instruction_names(text: str) -> list:
    """The own names (number stripped) of the text's Pallas kernels."""
    names = []
    for line in text.split("\n"):
        if 'custom_call_target="tpu_custom_call"' in line:
            own = line.split(" = ", 1)[0].split("%")[-1]
            names.append(re.sub(r"\.\d+$", "", own))
    return names


def assert_kernels_named(text: str, jit_name: str) -> set:
    names = kernel_instruction_names(text)
    assert names, "no Pallas kernel in the compiled program"
    for own in names:
        assert own not in ("jvp__", "transpose_jvp___", "vmap__", jit_name), own
        assert any(n in own for n in kernel_names.ALL), (
            f"the kernel instruction %{own} holds no name of "
            "kernel_names.py: its pallas_call passes no name=")
    return set(names)


def scopes_in(text: str) -> set:
    """Every path component of every ``op_name`` of the text, with the
    autodiff wrappers taken off (``transpose(jvp(attn))`` -> ``attn``)."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        for part in path.split("/"):
            out.add(re.sub(r"^(?:\w+\()+|\)+$", "", part))
    return out


@pytest.mark.parametrize("family,n_layer", [
    # the names do not depend on the depth: one layer of the flagship in
    # the quick tier, every family at the recipe's depth in the slow one
    pytest.param("diff", 1, id="diff-1layer"),
] + [pytest.param(f, 8, marks=pytest.mark.slow, id=f) for f in sorted(FAMILIES)])
def test_train_step_kernels_and_phases_carry_names(step_text, family, n_layer):
    """Every Pallas kernel of the compiled recipe step is named from the
    table (attention, FFN and norm apart, forward and backward apart),
    and the model's and the step's phases reach ``op_name``."""
    text = step_text(family, n_layer)
    names = assert_kernels_named(text, "step")
    # T=512 without dropout: the token-major kernels, packed up to three
    # rotated streams
    flash = ({kernel_names.FLASH_FWD_TM, kernel_names.FLASH_BWD_TM}
             if family == "ndiff" else
             {kernel_names.FLASH_FWD_TM_PACKED, kernel_names.FLASH_BWD_TM_PACKED})
    assert names == flash | set(kernel_names.FUSED_FFN + kernel_names.FUSED_NORM)
    assert {"embed", "attn_norm", "attn", "ffn_norm", "ffn", "lm_head_loss",
            "grad_norm_clip", "optimizer"} <= scopes_in(text)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_program_keeps_its_name_and_names_its_kernels(topo, impl):
    """The engine's decode program at the recipe's widths (two layers:
    the names do not depend on the depth) over a small pool, on the path
    the serve cell takes (``xla``: forward_chunk under
    vmap, so the fused norm/FFN kernels are ``vmap_<name>_``) and on the
    fused decode-attention path; its module stays ``jit__decode`` (the
    benchmark finds the program by that)."""
    text = _compile_decode(topo, impl, slots=8)[0].as_text()
    assert text.startswith("HloModule jit__decode")
    names = assert_kernels_named(text, "_decode")
    assert any(kernel_names.FUSED_FFN_FWD in n for n in names)
    assert any(kernel_names.FUSED_ADD_NORM_FWD in n for n in names)
    assert (kernel_names.DECODE_ATTENTION in names) == (impl == "pallas")
    assert kernel_names.KV_ROW_WRITE in names
    assert {"attn_norm", "attn", "kv_write", "ffn_norm", "ffn", "lm_head",
            "kv_merge"} <= scopes_in(text)


def _lower_decode(decode, place, params, slots, cache):
    """The engine's decode program lowered with the operands the engine
    hands it: ONE packed (slots, 4) int32 host operand (token | position |
    active | from_host a row) and the device's record of the last sampled
    rows, the sampler's packed output (13 columns at the default echo
    width), which the program takes the tokens from."""
    return decode.lower(params, place(sds((slots, 4), jnp.int32)),
                        place(sds((slots, 13), jnp.int32)), cache)


def _compile_decode(topo, impl, slots, kv="auto"):
    """The engine's decode program of the diff recipe's widths, two
    layers deep, compiled for the described chip: (compiled, the
    abstract cache it was lowered with)."""
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.models.decode import init_cache
    from differential_transformer_replication_tpu.serving.engine import (
        _build_step_fns,
    )

    cfg = ModelConfig(model="diff", n_layer=2, ffn_impl="pallas",
                      decode_attention_impl=impl, kv_cache_dtype=kv)
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots)))
    decode = _build_step_fns(cfg, cfg.block_size)[1]
    return (_lower_decode(decode, place, params, slots, cache).compile(),
            cache)


_HLO_DTYPES = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}


@pytest.mark.parametrize("impl,kv", [("xla", "auto"), ("pallas", "auto"),
                                     ("xla", "int8"), ("pallas", "int8")])
def test_decode_program_updates_the_pool_in_place(topo, impl, kv):
    """What the chip's compiler makes of the decode step at the serve
    cell's pool (256 slots; two layers): every cache leaf is aliased
    input to output; nothing but the write kernel produces a buffer the
    size of a K or V leaf (until PR 25: a ``copy`` of every ring into the
    layout XLA's scatter wants, a second copy back, and a select between
    the new pool and the old one), and the program's temporaries are
    under a quarter of one layer's pool (they held a whole second pool).
    The pool's layout on the chip follows from its shape (the ring on
    the lanes for the recipe's 512 x 96 and 512 x 192), so this is what
    keeps a later change — to the write, to what reads the pool after
    it, or to the shapes — from quietly bringing the copies back. The
    XLA attention reads the pool a block of rows at a time inside a
    ``while`` (PR 33), whose body is held to the same."""
    compiled, cache = _compile_decode(topo, impl, slots=256, kv=kv)
    text = compiled.as_text()
    leaves = jax.tree_util.tree_leaves(cache)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    head = text.split("\n", 1)[0]
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}", head.split(
        "input_output_alias={", 1)[1].split("entry_computation_layout", 1)[0])
    # output 0 is the logits; outputs 1.. are the cache leaves, each fed
    # by a parameter of its own
    assert sorted(int(o) for o, _ in aliased) == list(
        range(1, len(leaves) + 1))
    assert len({p for _, p in aliased}) == len(leaves)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    layer_bytes = pool_bytes // 2
    assert mem.temp_size_in_bytes < layer_bytes // 4, mem.temp_size_in_bytes

    # K and V (the scale planes are 1/96 of them and ride along)
    big = {(_HLO_DTYPES[layer[key].dtype.name], layer[key].size)
           for layer in cache for key in ("k", "v")}
    if impl == "xla":
        # nor one the size of a BLOCK of their rows (PR 33): the attend's
        # loop slices a block of the pool where the score and value
        # fusions read it. Handed the leaf in another layout than the
        # chip's, the loop's body copies every block through VMEM first
        # (models/decode.py:_attend_own_ring)
        big |= {(dtype, size * ATTEND_BLOCK_ROWS // 256)
                for dtype, size in big}
    # an instruction INSIDE a fusion is no buffer; the fusion's own
    # result, in the computation that calls it, is
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    harmless = {"parameter", "bitcast", "get-tuple-element", "tuple"}
    if kv == "int8":
        # a 100 MB int8 leaf fits the chip's 128 MiB of fast memory, and
        # XLA's memory-space assignment prefetches it there for the XLA
        # attention and writes it back, asynchronously: a move between
        # memories, in the pool's own layout
        harmless |= {"copy-start", "copy-done", "slice-start", "slice-done"}
    offenders, inside = [], False
    for line in text.split("\n"):
        head_of = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head_of:
            inside = head_of.group(1) in fused
        m = re.match(
            r"\s+(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(",
            line)
        if inside or not m:
            continue
        own, dtype, dims, opcode = m.groups()
        size = 1
        for n in dims.split(","):
            size *= int(n)
        if (dtype, size) not in big or opcode in harmless:
            continue
        if opcode == "custom-call" and (
                own.startswith(kernel_names.KV_ROW_WRITE)
                or (kv == "int8" and "ConcatBitcast" in line)):
            continue
        offenders.append(f"%{own} = {dtype}[{dims}] {opcode}")
    assert not offenders, offenders


def test_decode_program_takes_its_tokens_on_the_device(topo, monkeypatch):
    """ISSUE 41: the decode program with the merged token operand lowers
    for the chip at the chat cell's pool (256 slots): beside the weights
    and the pool it takes exactly two operands, the ONE packed int32 host
    operand (token | position | active | from_host a row) and the device's
    record of the last sampled rows, and the pool is still updated in
    place. And the engine keeps that ONE compiled program over iterations
    at both depths: the same operand shapes whether the record is the
    step before's output, a first token written over a row, or the zeros
    of a fresh engine (a tiny engine on the CPU; the depth is forced by
    patching the engine's own predicate)."""
    compiled, cache = _compile_decode(topo, "xla", slots=256)
    head = compiled.as_text().split("\n", 1)[0]
    layout = head.split("entry_computation_layout={(", 1)[1].split(")->")[0]
    ints = re.findall(r"s32\[([\d,]*)\]", layout)
    assert sorted(ints) == ["256,13", "256,4"], ints
    assert "pred[" not in layout  # the active mask rides the packed operand
    leaves = jax.tree_util.tree_leaves(cache)
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in leaves)

    from differential_transformer_replication_tpu.config import ServingConfig
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.serving.engine import (
        ServingEngine,
    )

    monkeypatch.undo()  # the engine below RUNS, on the CPU: interpret mode
    cfg = ModelConfig(model="control", vocab_size=67, n_embd=32, n_head=2,
                      n_layer=1, block_size=32, compute_dtype="float32")
    eng = ServingEngine(init_model(jax.random.PRNGKey(0), cfg), cfg,
                        ServingConfig(num_slots=3))
    late = eng._reads_first
    for depth in (late, lambda rows, capturing: "test", late):
        eng._reads_first = depth
        eng.generate([list(range(n, 2 * n)) for n in (4, 7)],
                     max_new_tokens=5, temperature=0.0)
    assert eng.stats["lookahead_steps"] and eng.stats["lookahead_drains"]
    assert eng.compile_stats()["decode"] == 1


# -- the jamba family at the published widths (AI21-Jamba2-3B) ----------------

JAMBA = dict(model="jamba", vocab_size=65536, n_embd=2560, n_head=20,
             kv_heads=1, block_size=2048, ffn_hidden=8192,
             tie_embeddings=True, mamba_dt_rank=160,
             param_dtype="bfloat16", ssm_impl="pallas")


@pytest.mark.parametrize("L", [1, 8, 512])
def test_ssm_scan_kernel_compiles_at_published_widths(topo, L):
    """One sequence's chunk of the prefill ladder through 5120 channels
    of 16 states (a chunk shorter than a time block is padded to one)."""
    from differential_transformer_replication_tpu.ops import ssm

    Di, N = 5120, 16
    text = compile_for(
        topo, ssm.selective_scan_pallas, sds((1, L, Di)),
        sds((1, L, Di), jnp.float32), sds((Di, N), jnp.float32),
        sds((1, L, N), jnp.float32), sds((1, L, N), jnp.float32),
        sds((Di,), jnp.float32), sds((1, N, Di), jnp.float32))
    assert kernel_instruction_names(text) == [kernel_names.SSM_SCAN_FWD]


def _compile_jamba(topo, slots, n_layer=4):
    """The engine's programs for a jamba stack of the published widths,
    ``n_layer`` deep with attention in layer 1 (every kind of layer is
    there): ``(decode, prefill, reset, the abstract cache)``."""
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.models.decode import init_cache
    from differential_transformer_replication_tpu.serving import engine

    cfg = ModelConfig(**JAMBA, n_layer=n_layer, attn_layer_period=n_layer,
                      attn_layer_offset=1)
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots)))
    scalar = place(sds((), jnp.int32))
    prefill, decode = engine._build_step_fns(cfg, cfg.block_size)[:2]
    return (
        _lower_decode(decode, place, params, slots, cache).compile(),
        # the engine always gives this family `valid` (a padded tail)
        prefill.lower(params, cache, scalar, place(sds((1, 64), jnp.int32)),
                      scalar, scalar).compile(),
        engine._reset_state_fn.lower(cache, scalar).compile(),
        cache,
    )


def test_jamba_programs_update_the_state_pool_in_place(topo):
    """What the chip's compiler makes of the jamba family's three
    programs at the serve cell's pool (256 slots, published widths, four
    layers): every cache leaf is aliased input to output in all three;
    the decode program names both of its kernels and all of its phases,
    and nothing in it but the state kernel produces a buffer the size of
    a layer's recurrent state (84 MB; an XLA select over the pool would,
    and a copy into another layout twice)."""
    decode, prefill, reset, cache = _compile_jamba(topo, slots=256)
    leaves = jax.tree_util.tree_leaves(cache)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    for compiled in (decode, prefill, reset):
        assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
    text = decode.as_text()
    assert text.startswith("HloModule jit__decode")
    names = assert_kernels_named(text, "_decode")
    assert names == {kernel_names.SSM_STATE_UPDATE, kernel_names.KV_ROW_WRITE}
    assert {"ssm", "ssm_conv", "ssm_state", "attn_norm", "attn", "kv_write",
            "ffn_norm", "ffn", "lm_head", "kv_merge"} <= scopes_in(text)
    assert {"ssm", "ssm_conv", "ssm_scan"} <= scopes_in(prefill.as_text())
    assert assert_kernels_named(prefill.as_text(), "_prefill") == {
        kernel_names.SSM_SCAN_FWD}
    state = 256 * 16 * 5120
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    offenders, inside = [], False
    for line in text.split("\n"):
        head_of = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head_of:
            inside = head_of.group(1) in fused
        m = re.match(
            r"\s+(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(",
            line)
        if inside or not m:
            continue
        own, dtype, dims, opcode = m.groups()
        size = 1
        for n in dims.split(","):
            size *= int(n)
        if (dtype, size) != ("f32", state) or opcode in (
                "parameter", "bitcast", "get-tuple-element", "tuple"):
            continue
        if opcode == "custom-call" and own.startswith(
                kernel_names.SSM_STATE_UPDATE):
            continue
        offenders.append(f"%{own} = {dtype}[{dims}] {opcode}")
    assert not offenders, offenders
    assert decode.memory_analysis().temp_size_in_bytes < state * 4


# -- the kimi_linear family at the published widths (Kimi-Linear, a share) ----

KIMI = dict(model="kimi_linear", vocab_size=163840, n_embd=2304, n_head=32,
            n_layer=5, block_size=4096, ffn_hidden=9216, norm_eps=1e-5,
            kda_layers=[1, 2, 3, 5], full_attn_layers=[4], num_experts=256,
            experts_per_token=8, moe_hidden=1024, routed_scaling=2.446,
            held_experts=[0, 128], param_dtype="bfloat16")


def test_kimi_linear_programs_update_the_pool_in_place(topo):
    """What the chip's compiler makes of the kimi_linear family's three
    programs at the serve cell's own size (256 slots, the five layers of
    the share at published widths): every cache leaf is aliased input to
    output in all three; the decode program names its four kernels (the
    state update, the latent's write, the live-latent read, the experts'
    grouped product), the prefill program the widened chunk read, and
    both all of their scopes. The decode program's temporaries stay under
    0.1 GB, far under the latent ring (1.21 GB) and a KDA layer's state
    pool (0.54 GB): neither is copied, selected over or laid out anew, and
    no score over slots x ring exists (134 MB in float32 if it did). The
    chip lays the ring of latents (4096 x 576) out with the positions on
    the lanes, which is what ``ops/kv_write.py:position_on_lanes`` says of
    it, the row write writes and the live-latent read takes (handed the
    row-major leaf that read made the compiler copy the pool both ways:
    ``ops/mla.py:latent_decode_attention``)."""
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.models.decode import init_cache
    from differential_transformer_replication_tpu.serving import engine

    cfg, slots = ModelConfig(**KIMI), 256
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots)))
    scalar = place(sds((), jnp.int32))
    prefill, decode = engine._build_step_fns(cfg, cfg.block_size)[:2]
    programs = {
        "decode": _lower_decode(decode, place, params, slots,
                                cache).compile(),
        "prefill": prefill.lower(params, cache, scalar,
                                 place(sds((1, 256), jnp.int32)), scalar,
                                 scalar).compile(),
        "reset": engine._reset_state_fn.lower(cache, scalar).compile(),
    }
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(cache))
    assert pool_bytes == 256 * (4 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
                                + 4096 * 576 * 2)
    for name, compiled in programs.items():
        assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes, name
    text = programs["decode"].as_text()
    assert text.startswith("HloModule jit__decode")
    assert assert_kernels_named(text, "_decode") == {
        kernel_names.KDA_STATE_UPDATE, kernel_names.KV_ROW_WRITE,
        kernel_names.MLA_LATENT_DECODE, kernel_names.MOE_GROUPED_MATMUL}
    assert {"kda", "kda_conv", "kda_state", "mla", "mla_q",
            "mla_latent_write", "mla_attend", "mla_out", "moe", "moe_router",
            "moe_experts", "moe_shared", "ffn_norm", "ffn", "lm_head",
            "kv_merge"} <= scopes_in(text)
    assert {"kda", "kda_conv", "kda_chunk", "mla", "mla_q",
            "mla_latent_write", "mla_attend", "mla_out",
            "moe_experts"} <= scopes_in(programs["prefill"].as_text())
    assert assert_kernels_named(programs["prefill"].as_text(), "_prefill") == {
        kernel_names.MOE_GROUPED_MATMUL, kernel_names.MLA_CHUNK_WIDENED}
    assert programs["decode"].memory_analysis().temp_size_in_bytes < 0.1e9
    assert programs["prefill"].memory_analysis().temp_size_in_bytes < 0.4e9


# -- the afmoe family at the published widths (Trinity-Large, a share) --------

AFMOE = dict(model="afmoe", vocab_size=25024, n_embd=3072, n_head=48,
             kv_heads=8, head_dim=128, n_layer=5, block_size=8192,
             ffn_hidden=12288, norm_eps=1e-5,
             layer_types=["sliding_attention", "sliding_attention",
                          "full_attention", "sliding_attention",
                          "sliding_attention"],
             sliding_window=4096, sliding_ring=5120, num_experts=256,
             experts_per_token=4, moe_hidden=3072, routed_scaling=2.448,
             held_experts=[0, 16], param_dtype="bfloat16")


def test_afmoe_programs_update_the_pool_of_two_ring_lengths_in_place(topo):
    """What the chip's compiler makes of the afmoe family's two programs
    at the serve cell's own size (64 slots of four rings of 5,120 and one
    of 8,192 positions, the five layers of the share at published widths):
    every cache leaf is aliased input to output; the decode program names
    its kernels (the row write into rings of both lengths, the experts'
    grouped product) and its scopes, the ring reads of either kind among
    them; and the temporaries of both stay far under the pool (7.5 GB)
    and under what the chip has left beside pool and weights (3.5 GB): no
    ring is copied or laid out anew, and a prefill chunk's scores exist a
    block of 1,024 ring positions at a time."""
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.models.decode import init_cache
    from differential_transformer_replication_tpu.serving import engine

    cfg, slots = ModelConfig(**AFMOE), 64
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots)))
    scalar = place(sds((), jnp.int32))
    prefill, decode = engine._build_step_fns(cfg, cfg.block_size)[:2]
    programs = {
        "decode": _lower_decode(decode, place, params, slots,
                                cache).compile(),
        "prefill": prefill.lower(params, cache, scalar,
                                 place(sds((1, 1024), jnp.int32)), scalar,
                                 scalar).compile(),
    }
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(cache))
    assert pool_bytes == 64 * 4096 * (4 * 5120 + 8192)
    for name, compiled in programs.items():
        assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes, name
    text = programs["decode"].as_text()
    assert text.startswith("HloModule jit__decode")
    assert assert_kernels_named(text, "_decode") == {
        kernel_names.KV_ROW_WRITE, kernel_names.MOE_GROUPED_MATMUL,
        kernel_names.RING_GQA_DECODE}
    assert {"attn_norm", "attn", "attn_window", "attn_full", "attn_gate",
            "kv_write", "moe", "moe_router", "moe_experts", "moe_shared",
            "ffn_norm", "ffn", "lm_head", "kv_merge"} <= scopes_in(text)
    assert {"attn", "attn_window", "attn_full", "attn_gate", "kv_write",
            "moe_experts"} <= scopes_in(programs["prefill"].as_text())
    assert assert_kernels_named(programs["prefill"].as_text(), "_prefill") == {
        kernel_names.MOE_GROUPED_MATMUL}
    assert programs["decode"].memory_analysis().temp_size_in_bytes < 1.0e9
    assert programs["prefill"].memory_analysis().temp_size_in_bytes < 2.0e9


# -- the deepseek_v2 family at the published widths (DeepSeek-V2, a share) ----

DEEPSEEK_V2 = dict(
    model="deepseek_v2", vocab_size=12800, n_embd=5120, n_head=128,
    n_layer=5, block_size=8192, ffn_hidden=12288, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=10000.0,
    rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096},
    num_experts=160, experts_per_token=6, moe_hidden=1536, n_group=8,
    topk_group=3, n_shared_experts=2, routed_scaling=16.0,
    held_experts=[0, 20], param_dtype="bfloat16")


def test_deepseek_v2_programs_update_the_latent_pool_in_place(topo):
    """What the chip's compiler makes of the deepseek_v2 family's two
    programs at the serve cell's own size (64 slots of five rings of
    8,192 latents of 576 values, the five layers of the share at
    published widths, 128 heads): every cache leaf is aliased input to
    output; the decode program names its kernels (the latent's row write,
    the live-latent read, the experts' grouped product) and its scopes;
    and the temporaries stay far under the pool (3.02 GB): the decode step
    holds no score over slots x ring (268 MB a layer in float32 if it
    did), and a prefill chunk's scores and widened keys stay on the
    chip (``mla_chunk_widened_fwd``)."""
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.models.decode import init_cache
    from differential_transformer_replication_tpu.serving import engine

    cfg, slots = ModelConfig(**DEEPSEEK_V2), 64
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots)))
    scalar = place(sds((), jnp.int32))
    prefill, decode = engine._build_step_fns(cfg, cfg.block_size)[:2]
    programs = {
        "decode": _lower_decode(decode, place, params, slots,
                                cache).compile(),
        "prefill": prefill.lower(params, cache, scalar,
                                 place(sds((1, 1024), jnp.int32)), scalar,
                                 scalar).compile(),
    }
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(cache))
    assert pool_bytes == 64 * 8192 * 5760
    for name, compiled in programs.items():
        assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes, name
    text = programs["decode"].as_text()
    assert text.startswith("HloModule jit__decode")
    assert assert_kernels_named(text, "_decode") == {
        kernel_names.KV_ROW_WRITE, kernel_names.MOE_GROUPED_MATMUL,
        kernel_names.MLA_LATENT_DECODE}
    assert {"mla", "mla_q", "mla_latent_write", "mla_attend", "mla_out",
            "moe", "moe_router", "moe_experts", "moe_shared", "ffn_norm",
            "ffn", "lm_head", "kv_merge"} <= scopes_in(text)
    assert {"mla", "mla_q", "mla_latent_write", "mla_attend", "mla_out",
            "moe_experts"} <= scopes_in(programs["prefill"].as_text())
    assert assert_kernels_named(programs["prefill"].as_text(), "_prefill") == {
        kernel_names.MOE_GROUPED_MATMUL, kernel_names.MLA_CHUNK_WIDENED}
    assert programs["decode"].memory_analysis().temp_size_in_bytes < 0.1e9
    assert programs["prefill"].memory_analysis().temp_size_in_bytes < 1.0e9


def _computations(text: str) -> dict:
    """``{name: body}`` of a compiled module's computations."""
    parts = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)
    return {re.match(r"(?:ENTRY )?(%[\w.\-]+)", p).group(1): p
            for p in parts[1:]}


def _runs(comps: dict, name: str) -> set:
    """``name`` and every computation it calls as a fusion or applies as a
    reduction, transitively: what runs when it runs, but for the branches
    of a ``conditional`` in it."""
    seen, todo = set(), [name]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += re.findall(r"(?:calls|to_apply)=(%[\w.\-]+)", comps[c])
    return seen


def _op_kinds(body: str) -> set:
    return set(re.findall(r" = [^=]*?\b([a-z\-]+)\(", body))


# -- the nemotron_h family at the published widths (Nemotron-3-Super, a share) -

NEMOTRON_H = dict(
    model="nemotron_h", vocab_size=32768, n_embd=4096, n_head=32, kv_heads=2,
    n_layer=11, block_size=8192, norm_eps=1e-5,
    hybrid_override_pattern="MEMEMEM*EME", mamba_num_heads=128,
    mamba_head_dim=64, n_groups=8, ssm_state_size=128, chunk_size=128,
    mamba_d_conv=4, num_experts=512, experts_per_token=22, moe_hidden=2688,
    moe_latent_size=1024, moe_shared_hidden=5376, mlp_act="relu2",
    routed_scaling=5.0, held_experts=[0, 128], param_dtype="bfloat16")


def test_nemotron_h_programs_update_the_state_pool_in_place(topo):
    """What the chip's compiler makes of the nemotron_h family's two
    programs at the serve cell's own size (64 slots; five Mamba-2 states of
    4.19 MB and one K/V ring of 8,192 positions a slot, five expert layers
    that keep nothing; the eleven layers of the share at published widths):
    every cache leaf is aliased input to output; the decode program names
    its kernels (the state update, the row write, the live-block ring
    read, the experts' grouped product) and its scopes; and the
    temporaries of both stay far under the pool (1.9 GB) and under what
    the chip has left beside pool and weights (4.5 GB): no state and no
    ring is copied, and a chunk's scan holds a sub-chunk's decay maps, not
    a state a token."""
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.models.decode import init_cache
    from differential_transformer_replication_tpu.serving import engine

    cfg, slots = ModelConfig(**NEMOTRON_H), 64
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots)))
    assert [sorted(layer) for layer in cache] == [
        {"M": ["conv", "ssm"], "*": ["k", "v"], "E": []}[c]
        for c in cfg.hybrid_override_pattern]
    scalar = place(sds((), jnp.int32))
    prefill, decode = engine._build_step_fns(cfg, cfg.block_size)[:2]
    programs = {
        "decode": _lower_decode(decode, place, params, slots,
                                cache).compile(),
        "prefill": prefill.lower(params, cache, scalar,
                                 place(sds((1, 1024), jnp.int32)), scalar,
                                 scalar).compile(),
    }
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(cache))
    assert pool_bytes == 64 * (5 * (128 * 8192 * 4 + 3 * 10240 * 2)
                               + 2 * 2 * 8192 * 128 * 2)
    for name, compiled in programs.items():
        assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes, name
    text = programs["decode"].as_text()
    assert text.startswith("HloModule jit__decode")
    assert assert_kernels_named(text, "_decode") == {
        kernel_names.SSD_STATE_UPDATE, kernel_names.KV_ROW_WRITE,
        kernel_names.MOE_GROUPED_MATMUL, kernel_names.RING_GQA_DECODE}
    assert {"ssm", "ssm_conv", "ssm_state", "attn_norm", "attn", "attn_full",
            "kv_write", "moe", "moe_router", "moe_latent", "moe_experts",
            "moe_shared", "ffn_norm", "lm_head"} <= scopes_in(text)
    assert {"ssm", "ssm_conv", "ssm_scan", "attn", "attn_full", "kv_write",
            "moe_latent", "moe_experts"} <= scopes_in(
                programs["prefill"].as_text())
    assert assert_kernels_named(programs["prefill"].as_text(), "_prefill") == {
        kernel_names.MOE_GROUPED_MATMUL}
    for name, compiled in programs.items():
        print(name, compiled.memory_analysis())
    assert programs["decode"].memory_analysis().temp_size_in_bytes < 0.5e9
    assert programs["prefill"].memory_analysis().temp_size_in_bytes < 2.0e9


# -- the lfm2 family at the published widths (LFM2-24B-A2B, one stage) --------

LFM2 = dict(
    model="lfm2", vocab_size=65536, n_embd=2048, n_head=32, kv_heads=8,
    n_layer=5, block_size=8192, norm_eps=1e-5,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    first_dense_layers=1, ffn_hidden=11776, num_experts=64,
    experts_per_token=4, moe_hidden=1536, routed_scaling=1.0,
    held_experts=[0, 64], rope_theta=1e6, conv_taps=3, router_eps=1e-6,
    tie_embeddings=True, param_dtype="bfloat16")


def _ring_on_lanes(text: str, shape: str) -> bool:
    """Whether the entry parameter of ``shape`` (``"64,8,8192,64"``) lies
    with its ring axis minor-most (on the lanes), the features next."""
    layout = re.search(
        r"bf16\[" + shape + r"\]\{([\d,]+):[^}]*\} parameter\(", text).group(1)
    order = [int(a) for a in layout.split(",")]
    rank = len(shape.split(","))
    return order[:2] == [rank - 2, rank - 1]


def test_lfm2_programs_update_the_pool_in_place(topo):
    """What the chip's compiler makes of the lfm2 family's two programs at
    the serve cell's own size (64 slots; four windows of 8 KB and one K/V
    ring of 8,192 positions of heads of 64 a slot; the five layers of the
    stage at published widths, all 64 experts held): every cache leaf is
    aliased input to output; the decode program names its kernels and its
    scopes; the chip lays the ring of heads of 64 out WITH THE RING ON THE
    LANES (``position_on_lanes(8192, 64)``), the row write and the ring
    read both take it so, and the decode program's temporaries stay under
    a hundredth of the pool: handed the row-major leaf, the read made the
    compiler copy the K and the V pool there and back every step (2.15 GB
    of temporaries, PR 47)."""
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.models.decode import init_cache
    from differential_transformer_replication_tpu.ops.kv_write import (
        position_on_lanes,
    )
    from differential_transformer_replication_tpu.serving import engine

    cfg, slots = ModelConfig(**LFM2), 64
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots)))
    assert [sorted(layer) for layer in cache] == [
        ["conv"], ["k", "v"], ["conv"], ["conv"], ["conv"]]
    scalar = place(sds((), jnp.int32))
    prefill, decode = engine._build_step_fns(cfg, cfg.block_size)[:2]
    programs = {
        "decode": _lower_decode(decode, place, params, slots,
                                cache).compile(),
        "prefill": prefill.lower(params, cache, scalar,
                                 place(sds((1, 1024), jnp.int32)), scalar,
                                 scalar).compile(),
    }
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(cache))
    assert pool_bytes == 64 * (4 * 2 * 2048 * 2 + 2 * 8 * 8192 * 64 * 2)
    for name, compiled in programs.items():
        assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes, name
    text = programs["decode"].as_text()
    assert text.startswith("HloModule jit__decode")
    assert assert_kernels_named(text, "_decode") == {
        kernel_names.KV_ROW_WRITE, kernel_names.MOE_GROUPED_MATMUL,
        kernel_names.RING_GQA_DECODE}
    assert {"conv", "conv_taps", "attn_norm", "attn", "attn_full", "kv_write",
            "moe", "moe_router", "moe_experts", "ffn_norm", "ffn",
            "lm_head"} <= scopes_in(text)
    assert not {"moe_shared", "moe_latent", "ssm"} & scopes_in(text)
    assert {"conv", "conv_taps", "attn", "attn_full", "kv_write",
            "moe_experts"} <= scopes_in(programs["prefill"].as_text())
    assert assert_kernels_named(programs["prefill"].as_text(), "_prefill") == {
        kernel_names.MOE_GROUPED_MATMUL}
    # the layout the rule foretells is the one the compiler chose, in both
    # programs (the pool is one buffer between them)
    assert position_on_lanes(8192, 64)
    for compiled in programs.values():
        assert _ring_on_lanes(compiled.as_text(), "64,8,8192,64")
        assert _ring_on_lanes(compiled.as_text(), "1,64,8,8192,64")
    for name, compiled in programs.items():
        print(name, compiled.memory_analysis())
    assert programs["decode"].memory_analysis().temp_size_in_bytes < 0.02e9
    assert programs["prefill"].memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("d, lanes", [(64, True), (128, False)])
def test_ring_read_and_row_write_agree_on_the_pools_layout(topo, d, lanes):
    """The K/V write and the ring read at ``KV 8, G 4`` on a pool of 64
    rings of 8,192 positions, one after the other on the donated pool: at
    heads of 64 the ring lies on the lanes and at 128 row-major, and either
    way nothing of a leaf's size is copied between the two kernels."""
    from differential_transformer_replication_tpu.ops.kv_write import (
        position_on_lanes,
        write_rows,
    )
    from differential_transformer_replication_tpu.ops.ring_attention import (
        ring_decode_attention,
    )

    B, KV, G, M = 64, 8, 4, 8192
    assert position_on_lanes(M, d) is lanes

    def step(k, v, k_row, v_row, q, pos, live):
        targets = jnp.where(live, jax.lax.rem(pos, M), -1)
        k = write_rows(k, k_row, targets, 0)
        v = write_rows(v, v_row, targets, 0)
        return ring_decode_attention(q, k, v, pos, live, M), k, v

    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in (sds((B, KV, M, d)), sds((B, KV, M, d)),
                      sds((B, KV, d)), sds((B, KV, d)), sds((B, KV * G, d)),
                      sds((B,), jnp.int32), sds((B,), jnp.bool_))]
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert {kernel_names.KV_ROW_WRITE, kernel_names.RING_GQA_DECODE} <= set(
        kernel_instruction_names(text))
    leaf = B * KV * M * d * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * leaf
    assert memory.temp_size_in_bytes < leaf / 100
    assert _ring_on_lanes(text, f"{B},{KV},{M},{d}") is lanes


def test_short_convolution_step_compiles_at_published_widths(topo):
    """The conv step over 64 slots of 2,048 channels: one projection to
    three parts, the gates, the window's shift under the live mask and the
    three taps, in XLA; the window is updated in place."""
    from differential_transformer_replication_tpu.models import lfm2

    cfg = ModelConfig(**LFM2)
    p = {"in_proj": sds((2048, 6144)), "conv_w": sds((3, 2048)),
         "out_proj": sds((2048, 2048))}
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    compiled = jax.jit(
        lambda h, p, conv, live: lfm2.conv_step(h, p, cfg, conv, live),
        donate_argnums=(2,)).lower(
            *place((sds((64, 2048)), p, sds((64, 2, 2048)),
                    sds((64,), jnp.bool_)))).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == 64 * 2 * 2048 * 2
    assert {"conv_taps"} <= scopes_in(compiled.as_text())


@pytest.mark.parametrize("tokens", [64, 1024], ids=["256_assignments",
                                                    "4096_assignments"])
def test_grouped_product_compiles_with_every_expert_held(topo, tokens):
    """``ops/moe.py:experts`` on 64 held experts of 2,048 x 3,072 and
    1,536 x 2,048 with EVERY assignment held: a decode step over 64 slots
    (256 assignments, tiles of 16 rows) and a prompt chunk of 1,024 (4,096
    assignments, tiles of 64)."""
    from differential_transformer_replication_tpu.ops import moe

    assert moe._row_tile(tokens * 4, 64) == (16 if tokens == 64 else 64)
    p = {"gate_up": sds((64, 2048, 3072)), "down": sds((64, 1536, 2048))}
    text = compile_for(
        topo, lambda h, chosen, w, p: moe.experts(h, chosen, w, p, 0),
        sds((tokens, 2048)), sds((tokens, 4), jnp.int32),
        sds((tokens, 4), jnp.float32), p)
    assert kernel_instruction_names(text).count(
        kernel_names.MOE_GROUPED_MATMUL) == 2


def test_sampler_is_scoped(topo):
    """The engine's jitted sampler (a narrow vocabulary: the sort over
    12,000 takes the compiler 23 s and the scope does not depend on it),
    and what of it a batch that asks nothing executes."""
    from differential_transformer_replication_tpu.serving.engine import (
        _build_step_fns,
    )

    slots, V = 8, 512
    sample = _build_step_fns(ModelConfig(model="diff", vocab_size=V), 512)[2]
    text = compile_for(
        topo, sample, sds((slots, 9), jnp.int32), sds((slots, V), jnp.float32),
        sds((slots, V), jnp.bool_), sds((slots, V), jnp.int32))
    assert text.startswith("HloModule jit__sample")
    assert "sampler" in scopes_in(text)
    # the body sits in a branch a batch that asks nothing skips; inside it
    # the sort, the draw and the echo each in a branch of its own
    assert len(re.findall(r" conditional\(", text)) == 4
    comps = _computations(text)
    (entry,) = (n for n, body in comps.items() if body.startswith("ENTRY "))
    (arms,) = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}",
                         comps[entry])
    plain, full = arms.split(", ")  # a cond's false branch comes first
    assert " conditional(" in comps[full]
    # what a plain batch executes, the ENTRY computation and the plain
    # arm: no vocabulary-wide sort, exponential, logarithm or PRNG round,
    # no pass of the pipeline; over the logits the two reductions alone
    runs = _runs(comps, entry) | _runs(comps, plain)
    kinds = set().union(*(_op_kinds(comps[c]) for c in runs))
    assert not kinds & {"sort", "exponential", "log", "xor", "divide",
                        "shift-right-logical", "rng-bit-generator",
                        "gather", "custom-call"}
    assert {"sort", "exponential", "log", "xor"} <= set().union(
        *(_op_kinds(body) for body in comps.values()))
    over_logits = [c for c in runs
                   if re.search(rf"f32\[{slots},{V}\][^ ]* parameter\(",
                                comps[c]) and c not in (entry, plain)]
    assert sorted(k for c in over_logits for k in _op_kinds(comps[c])
                  if k in ("reduce", "is-finite", "iota")) == [
                      "iota", "is-finite", "reduce", "reduce"]


@pytest.mark.slow
def test_overlapped_dp_step_compiles_four_chips(topo):
    """The sharded step ``--data-parallel 4`` reaches (parallel/dp_step.py)
    compiled for the four described chips: kernels inside shard_map, the
    bucketed gradient all-reduces, state replicated, batch split."""
    from differential_transformer_replication_tpu.parallel import create_mesh
    from differential_transformer_replication_tpu.parallel.dp_step import (
        make_sharded_train_step,
        overlap_eligible,
    )

    cfg = _recipe("diff", data=4)
    assert overlap_eligible(cfg)
    mesh = create_mesh(cfg.mesh, devices=topo.devices)
    assert isinstance(mesh, Mesh) and mesh.devices.size == 4
    state, batch = _abstract_step_args(cfg)
    step = make_sharded_train_step(cfg, mesh, state)
    repl = NamedSharding(mesh, P())
    place = lambda tree, sh: jax.tree_util.tree_map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree)
    compiled = step.jitted.lower(
        place(state, repl),
        place(batch, NamedSharding(mesh, P(None, "data", None))), None,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 60
    assert "all-reduce" in text
    assert_kernels_named(text, "step")  # the names survive shard_map
    x_sharding = compiled.input_shardings[0][1]["x"]
    assert len(x_sharding.device_set) == 4
    assert not x_sharding.is_fully_replicated

"""The table of layer kinds (models/decode.py ``KINDS``): what a layer of a
kind keeps in a slot, what is read off the table, what the engine refuses
for it, and the seam itself: a kind the package does not know, registered by
the test alone, runs through the prefill chunk, the decode step, the reset
and an engine without an edit elsewhere. Over the six hybrid families' toy
configurations (tests/test_<family>.py ``TOY``, the weights of
benchmark/reference_<family>.py).
"""

import importlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmark"))
sys.path.insert(0, str(REPO / "tests"))

from differential_transformer_replication_tpu.config import (  # noqa: E402
    ModelConfig,
    ServingConfig,
)
from differential_transformer_replication_tpu.models import decode  # noqa: E402
from differential_transformer_replication_tpu.serving.engine import (  # noqa: E402
    ServingEngine,
)

FAMILIES = ("jamba", "kimi_linear", "afmoe", "deepseek_v2", "nemotron_h",
            "lfm2")
V = 223  # not the families' own 211: no cached program of theirs is met


def toy_dict(family: str, **kw) -> dict:
    return dict(importlib.import_module("test_" + family).TOY, **kw)


def reference(family: str):
    return importlib.import_module("reference_" + family)


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


# -- (a) what a slot holds -------------------------------------------------------

F32 = "float32"
# init_cache(toy, 3) at the parent commit, a layer kind (B = 3)
SLOT = {
    "jamba": {
        "mamba": {"ssm": ((3, 16, 128), F32), "conv": ((3, 3, 128), F32)},
        "attention": {"k": ((1, 3, 1, 128, 16), F32),
                      "v": ((3, 1, 128, 16), F32)}},
    "kimi_linear": {
        "kda": {"kda": ((3, 2, 16, 16), F32), "conv": ((3, 3, 96), F32)},
        "latent": {"latent": ((3, 1, 192, 40), F32)}},
    "afmoe": {
        "window": {"k": ((1, 3, 2, 24, 32), F32), "v": ((3, 2, 24, 32), F32)},
        "full": {"k": ((1, 3, 2, 64, 32), F32), "v": ((3, 2, 64, 32), F32)}},
    "deepseek_v2": {"latent": {"latent": ((3, 1, 64, 24), F32)}},
    "nemotron_h": {
        "mamba2": {"ssm": ((3, 16, 64), F32), "conv": ((3, 3, 128), F32)},
        "full": {"k": ((1, 3, 1, 64, 16), F32), "v": ((3, 1, 64, 16), F32)},
        "none": {}},
    # PR 47's record: a recurrent kind whose only leaf is its window
    "lfm2": {
        "shortconv": {"conv": ((3, 2, 64), F32)},
        "full": {"k": ((1, 3, 2, 64, 64), F32), "v": ((3, 2, 64, 64), F32)}},
}


def _spec(layer: dict) -> dict:
    return {k: (tuple(a.shape), str(a.dtype)) for k, a in layer.items()}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_slot_holds_what_the_records_say(family):
    cfg = ModelConfig(**toy_dict(family))
    cache = decode.init_cache(cfg, 3)
    assert len(cache) == cfg.n_layer
    assert set(cfg.layer_kinds()) == set(SLOT[family])
    for layer, kind in zip(cache, cfg.layer_kinds()):
        record = decode.KINDS[kind]  # every kind has one
        assert _spec(layer) == SLOT[family][kind]
        assert set(layer) <= set(record.leaves)
        assert _spec(layer) == _spec(jax.eval_shape(
            lambda: record.zeros(cfg, 3, cfg.ring_len(kind))))
        assert all(float(jnp.abs(a).max()) == 0 for a in layer.values())
        assert (record.chunk is None) == (record.params is None) == (
            kind == "none")


@pytest.mark.parametrize("store, leaves", [
    ("auto", {"k": ((2, 3, 2, 32, 16), "bfloat16"),
              "v": ((3, 2, 32, 32), "bfloat16")}),
    ("int8", {"k": ((2, 3, 2, 32, 16), "int8"), "v": ((3, 2, 32, 32), "int8"),
              "k_scale": ((2, 3, 2, 32), F32), "v_scale": ((3, 2, 32), F32)}),
])
def test_the_reference_families_keep_the_attention_record_s_rings(store,
                                                                  leaves):
    cfg = ModelConfig(model="diff", vocab_size=64, n_embd=64, n_head=2,
                      n_layer=2, block_size=32, kv_cache_dtype=store)
    assert cfg.layer_kinds() == ("attention",) * 2
    assert [_spec(layer) for layer in decode.init_cache(cfg, 3)] == [leaves] * 2


# -- (b) what is read off the table ----------------------------------------------------

# literal copies of PR 45's constants (models/decode.py, config.py), with
# what PR 47's record ``"shortconv"`` adds: its weights' leaf ``conv`` (its
# cache leaf ``conv`` was a state leaf already) and itself among the
# recurrent kinds
PARENT = {
    "KV_CACHE_BATCH_AXIS": {"k": 1, "v": 0, "k_scale": 1, "v_scale": 0,
                            "ssm": 0, "conv": 0, "kda": 0, "latent": 0},
    "STATE_LEAVES": ("ssm", "conv", "kda"),
    "MIXER_LEAVES": ("attn", "mamba", "mamba2", "kda", "conv", "mla"),
    "RECURRENT_KINDS": ("mamba", "kda", "mamba2", "shortconv"),
    "BLOCKED_KINDS": ("window", "full", "latent"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_what_is_derived_is_what_the_parent_spelled_out(name):
    if name == "RECURRENT_KINDS":
        got = [k for k, r in decode.KINDS.items() if r.recurrent]
        assert sorted(got) == sorted(PARENT[name])
    elif name == "BLOCKED_KINDS":
        got = [k for k, r in decode.KINDS.items() if r.blocks]
        assert sorted(got) == sorted(PARENT[name])
    else:
        assert getattr(decode, name) == PARENT[name]


def test_the_table_has_the_kinds_a_configuration_can_name():
    assert set(decode.KINDS) == {"attention", "mamba", "mamba2", "kda",
                                 "shortconv", "latent", "window", "full",
                                 "none"}
    assert [k for k, r in decode.KINDS.items() if r.rolls] == ["window"]
    assert [k for k, r in decode.KINDS.items() if r.latents] == ["latent"]


# -- (d) what the engine refuses, letter for letter ---------------------------------------

# literal copies of the parent's messages (serving/engine.py
# _refuse_for_recurrent_state, _refuse_for_two_ring_lengths,
# _refuse_for_latent_ring at PR 44)
_SNAPSHOT = ("the {model} family keeps a recurrent state a {mixer} layer, and "
             "{what} needs a snapshot of that state at a position, which the "
             "engine does not take")
_DRAFTS = {"nemotron_h": "; the published multi-token-prediction module, "
                         "which this family leaves out, would be its draft "
                         "head"}
RECURRENT = {
    "host_tier": _SNAPSHOT.replace(
        "{what}", "the host tier (host_tier_bytes; preemption and resume)"),
    "spec": _SNAPSHOT.replace(
        "{what}", "speculation (spec_mode; rejected drafts roll the cache "
                  "back{drafts})"),
    "paging": _SNAPSHOT.replace(
        "{what}", "paging (kv_page_size > 0; with it the prefix cache, whose "
                  "hits resume a sequence at the shared prefix's end)"),
    "int8": "kv_cache_dtype='int8' is not available for the {model} family: "
            "its attention layers' decode path reads float rings "
            "(grouped-query K/V, or MLA's latents), and a quantized {mixer} "
            "state does not exist yet (the state is float32)",
}
_TWO = ("the {model} family keeps rings of two lengths a slot (a sliding "
        "layer's of 24, a full layer's of 64), and ")
TWO_LENGTHS = {
    "host_tier": _TWO + "the host tier (host_tier_bytes) stashes and restores "
                        "a slot as pages of one page table",
    "spec": _TWO + "speculation (spec_mode) verifies several rows a slot in "
                   "one step, whose writes into a rolled sliding ring would "
                   "evict keys that the step's earlier rows still see, and "
                   "whose rejected rows cannot be rolled back there",
    "paging": _TWO + "paging (kv_page_size > 0; with it the prefix cache) maps "
                     "every layer's ring through ONE page table a slot, "
                     "block_size long",
    "int8": "kv_cache_dtype='int8' is not available for the {model} family: "
            "its prefill writes a chunk into a rolling ring by a select over "
            "the float ring, and its grouped-query decode path reads float "
            "rings",
    "prefill_chunk": "prefill_chunk (16) exceeds what the {model} family's "
                     "sliding rings hold past their window (sliding_ring 24 - "
                     "sliding_window 16 = 8): a longer chunk written at a "
                     "rolled position would evict keys that its earlier rows "
                     "still see (models/decode.py)",
}
_LATENTS = "the {model} family keeps a ring of latents a slot and MLA layer, and "
LATENTS = {
    "host_tier": _LATENTS + "the host tier (host_tier_bytes) stashes and "
                            "restores a slot as the pages of a page table, "
                            "which this pool does not have (no paging over "
                            "latents yet)",
    "spec": _LATENTS + "speculation (spec_mode) verifies several rows a slot "
                       "in one step: the hybrid decode loop advances one row a "
                       "slot, and the live-latent read (ops/mla.py "
                       "latent_decode_attention) takes one query position a "
                       "slot",
    "paging": _LATENTS + "paging (kv_page_size > 0; with it the prefix cache) "
                         "maps K and V leaves through a page table: "
                         "serving/pages.py and the paged decode programs know "
                         "no `latent` leaf, and the live-latent read takes a "
                         "slot's ring whole, not pages",
    "int8": "kv_cache_dtype='int8' is not available for the {model} family: "
            "int8 latents do not exist yet (quantize_kv scales a K/V head; a "
            "latent is key and value of every head at once, and its shared "
            "key part would need a scale of its own), and the live-latent "
            "read takes float latents",
}
# the first reason a family meets: a recurrent state before rings of two
# lengths before a ring of latents (kimi_linear has KDA and MLA layers)
# a window alone is refused as a state is, and says what it is stored as
WINDOW_ALONE = dict(RECURRENT, int8=RECURRENT["int8"].replace(
    "(the state is float32)",
    "(the state is the convolution's window of conv_taps - 1 gated inputs "
    "in the compute dtype)"))
REASON = {"jamba": (RECURRENT, "Mamba"), "kimi_linear": (RECURRENT, "KDA"),
          "nemotron_h": (RECURRENT, "Mamba-2"), "afmoe": (TWO_LENGTHS, ""),
          "deepseek_v2": (LATENTS, ""),
          "lfm2": (WINDOW_ALONE, "short-convolution")}
ASKING = {"host_tier": dict(kv_page_size=8, host_tier_bytes=1 << 20),
          "spec": dict(spec_mode="ngram"), "paging": dict(kv_page_size=8),
          "int8": dict(kv_cache_dtype="int8"),
          "prefill_chunk": dict(prefill_chunk=16)}


@pytest.mark.parametrize("family, feature", [
    (family, feature) for family in FAMILIES for feature in ASKING
    if feature in REASON[family][0]])
def test_every_refusal_reads_as_it_did(family, feature):
    template, mixer = REASON[family]
    serving = ServingConfig(**dict(dict(num_slots=2, prefill_chunk=8),
                                   **ASKING[feature]))
    with pytest.raises(ValueError) as e:
        ServingEngine({}, ModelConfig(**toy_dict(family)), serving)
    assert str(e.value) == template[feature].format(
        model=family, mixer=mixer, drafts=_DRAFTS.get(family, ""))


# -- (c) a kind the package does not know ------------------------------------------------


def _toy_chunk(h, blk, layer_cache, cfg, pos, ring, valid):
    """s_t = s_{t-1} / 2 + h_t W, out_t = s_t; the state stops at ``valid``."""
    u = h @ blk["toy"]["w"]
    real = jnp.arange(h.shape[1]) < (h.shape[1] if valid is None else valid)

    def one(s, xs):
        u_t, real_t = xs
        new = 0.5 * s + u_t
        return jnp.where(real_t, new, s), new

    last, out = jax.lax.scan(one, layer_cache["toy_sum"],
                             (u.swapaxes(0, 1), real))
    return out.swapaxes(0, 1), {"toy_sum": last}


def _toy_step(h, blk, layer_cache, cfg, live, pos, ring):
    new = 0.5 * layer_cache["toy_sum"] + h @ blk["toy"]["w"]
    return new, {"toy_sum": jnp.where(live[:, None], new,
                                      layer_cache["toy_sum"])}


TOY_KIND = decode.LayerKind(
    params="toy", leaves={"toy_sum": 0}, state="toy_sum", name="Toy",
    scope="toy_norm", chunk=_toy_chunk, step=_toy_step,
    zeros=lambda cfg, rows, M: {
        "toy_sum": jnp.zeros((rows, cfg.n_embd), jnp.float32)},
    refuses={"spec": "the {model} family keeps a {mixers} sum, and "
                     "speculation is no game for it"})


@pytest.fixture
def toy_kind(monkeypatch):
    """The first layer of every configuration is of the kind ``"toy"``:
    one record in the table, and what is read off the table read again."""
    monkeypatch.setitem(decode.KINDS, "toy", TOY_KIND)
    monkeypatch.setitem(decode.KV_CACHE_BATCH_AXIS, "toy_sum", 0)
    monkeypatch.setattr(decode, "STATE_LEAVES",
                        decode.STATE_LEAVES + ("toy_sum",))
    kinds = ModelConfig.layer_kinds
    monkeypatch.setattr(ModelConfig, "layer_kinds",
                        lambda self: ("toy",) + kinds(self)[1:])


def _toy_model(family: str):
    model = toy_dict(family, vocab_size=V)
    params = reference(family).make_params(5, model)
    E = model["n_embd"]
    first = {k: v for k, v in params["blocks"][0].items()
             if k not in decode.MIXER_LEAVES}
    first["toy"] = {"w": jax.random.normal(jax.random.PRNGKey(1), (E, E))
                    * E ** -0.5}
    return ModelConfig(**model), dict(params, blocks=[first]
                                      + list(params["blocks"][1:]))


@pytest.mark.parametrize("family", FAMILIES)
def test_an_unknown_kind_runs_through_the_walk_the_cache_and_the_engine(
        family, toy_kind):
    cfg, params = _toy_model(family)
    assert cfg.layer_kinds()[0] == "toy" and decode.has_recurrent_state(cfg)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, V, (2, 24)))
    chunk = jax.jit(lambda t, pos, c, v=None: decode.forward_chunk(
        params, t, pos, c, cfg, valid=v))
    step = jax.jit(lambda t, pos, c, a: decode.forward_decode_pool(
        params, t, pos, c, cfg, active=a))

    # one chunk of 24 against: a chunk of 8, a tail of 5 padded to 8, then
    # eleven steps through the pool
    whole, _ = chunk(tokens, jnp.int32(0), decode.init_cache(cfg, 2))
    cache = decode.init_cache(cfg, 2)
    assert cache[0]["toy_sum"].shape == (2, cfg.n_embd)
    logits, cache = chunk(tokens[:, :8], jnp.int32(0), cache)
    np.testing.assert_allclose(logits, whole[:, :8], atol=5e-4, rtol=5e-4)
    padded = jnp.zeros((2, 8), tokens.dtype).at[:, :5].set(tokens[:, 8:13])
    logits, cache = chunk(padded, jnp.int32(8), cache, jnp.int32(5))
    np.testing.assert_allclose(logits[:, 0], whole[:, 12], atol=5e-4,
                               rtol=5e-4)
    for t in range(13, 24):
        logits, cache, *_ = step(tokens[:, t], jnp.full((2,), t, jnp.int32),
                                 cache, jnp.ones((2,), bool))
        np.testing.assert_allclose(logits, whole[:, t], atol=5e-4, rtol=5e-4)
    assert float(jnp.abs(cache[0]["toy_sum"]).min()) > 0

    # a row that is not active keeps its state; the reset zeroes one slot's
    kept = step(tokens[:, 0], jnp.full((2,), 24, jnp.int32), cache,
                jnp.asarray([True, False]))[1]
    np.testing.assert_array_equal(kept[0]["toy_sum"][1],
                                  cache[0]["toy_sum"][1])
    reset = jax.jit(decode.reset_slot_state)(cache, jnp.int32(1))
    assert float(jnp.abs(reset[0]["toy_sum"][1]).max()) == 0
    np.testing.assert_array_equal(reset[0]["toy_sum"][0],
                                  cache[0]["toy_sum"][0])

    # the engine admits it: three prompts through one slot, each zeroed
    # first, serve what the chunk program says (one prefill shape: 8, 8 + 8
    # and 5 padded to 8; the reference is the 24-token program again, the
    # sequence padded behind, which no earlier position sees)
    eng = ServingEngine(params, cfg, ServingConfig(
        num_slots=1, prefill_chunk=8, prefill_budget=64))
    prompts = [tokens[0, :8].tolist(), tokens[1, :16].tolist(),
               tokens[0, 3:8].tolist()]
    outs = eng.generate(prompts, max_new_tokens=4, temperature=0.0)
    assert eng.stats["state_resets"] == 3
    for prompt, out in zip(prompts, outs):
        seq = prompt + list(out.tokens)[:-1]
        padded = jnp.asarray([seq + [0] * (24 - len(seq))] * 2)
        logits = chunk(padded, jnp.int32(0), decode.init_cache(cfg, 2))[0]
        want = jnp.argmax(logits[0, len(prompt) - 1:len(seq)], -1)
        assert list(out.tokens) == np.asarray(want).tolist()


def test_an_unknown_kind_s_refusal_reaches_the_operator(toy_kind, monkeypatch):
    monkeypatch.setattr(ModelConfig, "layer_kinds",
                        lambda self: ("toy",) * self.n_layer)
    cfg = ModelConfig(**toy_dict("jamba", vocab_size=V))
    with pytest.raises(ValueError) as e:
        ServingEngine({}, cfg, ServingConfig(spec_mode="ngram"))
    assert str(e.value) == ("the jamba family keeps a Toy sum, and "
                            "speculation is no game for it")
    # what it does not refuse, the engine goes on to build
    ServingEngine({}, cfg, ServingConfig(num_slots=1, kv_cache_dtype="int8"))

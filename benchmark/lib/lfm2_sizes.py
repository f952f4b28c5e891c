"""The sizes of an `lfm2` configuration's ``model`` block and the need
functions of its roofline metrics (``layer_metrics/
lfm2_decode_step_roofline.py``, ``lfm2_moe_experts_roofline.py``,
``lfm2_prefill_moe_experts_roofline.py``, ``lfm2_decode_attn_roofline.py``),
which count the JOB and not the implementation: what a decode step or a
prompt chunk has to read and multiply whatever the program does. Every size
comes from the ``model`` block; the defaults where a key is left out are the
program's (``config.py:ModelConfig``). ``lib/cost.py`` counts `control` and
`diff`, ``lib/jamba_sizes.py`` `jamba`, ``lib/kimi_linear_sizes.py``
`kimi_linear` (its ``expert_load`` reads the engine's spans only and serves
this family too), ``lib/afmoe_sizes.py`` `afmoe`,
``lib/deepseek_v2_sizes.py`` `deepseek_v2`, ``lib/nemotron_h_sizes.py``
`nemotron_h` (its ``state_load`` and ``traced_prefill_calls`` read spans
only and serve this family too).
"""

from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2}


def sizes(model: dict) -> dict:
    if model["model"] != "lfm2":
        raise ValueError(f"benchmark/lib/lfm2_sizes.py counts the `lfm2` "
                         f"family, not {model['model']!r}")
    E, H = model["n_embd"], model["n_head"]
    N = model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    kinds = list(model["layer_types"])
    dense = min(model.get("first_dense_layers", 1), len(kinds))
    return {"E": E, "H": H, "KV": model.get("kv_heads") or H,
            "d": model.get("head_dim") or E // H, "V": model["vocab_size"],
            "K": model.get("conv_taps", 3),
            "F": model.get("ffn_hidden") or 4 * E,
            "N": N, "top": model.get("experts_per_token", 8),
            "Fm": model.get("moe_hidden", 1024),
            "held": (hi or N) - lo, "layers": len(kinds),
            "conv": kinds.count("conv"),
            "attn": kinds.count("full_attention"),
            "dense": dense, "moe": len(kinds) - dense,
            "tied": bool(model.get("tie_embeddings", False))}


def param_parts(model: dict) -> dict:
    """Parameters of each part of the tree of ``models/lfm2.py``: a conv
    mixer (the input projection to three parts, the taps, the output
    projection), an attention mixer (q, k, v, o and the two head norms),
    the dense feed-forward part, an expert layer's router with its bias,
    one routed expert (a SwiGLU: three matrices), a block's two norms, the
    final norm, the token table (the head too where they are tied)."""
    s = sizes(model)
    E, H, KV, d = s["E"], s["H"], s["KV"], s["d"]
    return {
        "conv": E * 3 * E + s["K"] * E + E * E,
        "attn": 2 * E * H * d + 2 * E * KV * d + 2 * d,
        "dense": 3 * E * s["F"],
        "router": E * s["N"] + s["N"],
        "expert": 3 * E * s["Fm"],
        "norms": 2 * E,
        "final_norm": E,
        "embed": s["V"] * E,
    }


def param_count(model: dict) -> int:
    """Every parameter this share holds."""
    s, p = sizes(model), param_parts(model)
    return (s["conv"] * p["conv"] + s["attn"] * p["attn"]
            + s["dense"] * p["dense"]
            + s["moe"] * (p["router"] + s["held"] * p["expert"])
            + s["layers"] * p["norms"] + p["final_norm"]
            + p["embed"] * (1 if s["tied"] else 2))


def window_bytes(model: dict) -> int:
    """A slot's convolution windows over all conv layers, as the pool
    stores them: ``conv_taps - 1`` gated inputs of n_embd channels in the
    compute dtype a layer; what the engine's ``live_state_bytes`` counts a
    live row."""
    s = sizes(model)
    return (s["conv"] * (s["K"] - 1) * s["E"]
            * _BYTES[model.get("compute_dtype", "bfloat16")])


def position_bytes(model: dict) -> int:
    """K and V of one position in one attention layer, as the cache stores
    them (the compute dtype)."""
    s = sizes(model)
    return 2 * s["KV"] * s["d"] * _BYTES[model.get("compute_dtype",
                                                   "bfloat16")]


def slot_bytes(model: dict) -> int:
    """Everything a slot of the pool holds: the windows and the attention
    layers' K/V rings."""
    s = sizes(model)
    return (window_bytes(model)
            + s["attn"] * model["block_size"] * position_bytes(model))


def attn_need(model: dict, live_positions: float) -> dict:
    """The ring reads of one decode step, every attention layer: the K and
    V of the rows' ``live_positions`` (summed over the rows, ``pos + 1``
    each) once, a score and a weighted value a query head and live
    position."""
    s = sizes(model)
    live = s["attn"] * live_positions
    return {"flops": live * s["H"] * 4.0 * s["d"],
            "bytes": float(live * position_bytes(model))}


def experts_need(model: dict, load: dict) -> dict:
    """The routed experts of one decode step, all expert layers, as
    ``lib/kimi_linear_sizes.py:experts_need`` counts: the weights of the
    experts that got a row read once in their stored dtype (18.9 MB each in
    bfloat16), a row of E values in and out an assignment in the compute
    dtype, 2 operations a weight and assignment."""
    s, p = sizes(model), param_parts(model)
    wb = _BYTES[model.get("param_dtype", "float32")]
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    return {"flops": 2.0 * p["expert"] * load["held"],
            "bytes": float(load["experts_hit"] * p["expert"] * wb
                           + load["held"] * 2 * s["E"] * cb)}


def expected_experts_hit(model: dict, tokens: int) -> float:
    """Experts of ONE layer that ``tokens`` tokens hit if every token's
    ``experts_per_token`` fall evenly on the ``num_experts`` (the share of
    them held here): ``held (1 - (1 - 1/N)^(tokens top))``. The engine
    counts the hit experts of decode steps, not of prefill calls; a router
    that falls unevenly hits fewer, by 2% of 64 at 128 tokens and by
    nothing from 512 on (its bias moves a choice within a few
    hundredths)."""
    s = sizes(model)
    return s["held"] * (1.0 - (1.0 - 1.0 / s["N"]) ** (tokens * s["top"]))


def prefill_experts_need(model: dict, tokens: int) -> dict:
    """The routed experts of ONE prefill call that really held ``tokens``
    tokens, all expert layers: the weights of the experts its tokens hit
    (:func:`expected_experts_hit`) read once, a row in and out an
    assignment, 2 operations a weight and assignment of a held expert. The
    padding to the ladder's shape and to whole tiles is the program's
    choice and does not count."""
    s, p = sizes(model), param_parts(model)
    wb = _BYTES[model.get("param_dtype", "float32")]
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    held = tokens * s["top"] * s["held"] / s["N"]  # assignments a layer
    hit = expected_experts_hit(model, tokens)
    return {"flops": s["moe"] * 2.0 * p["expert"] * held,
            "bytes": float(s["moe"] * (hit * p["expert"] * wb
                                       + held * 2 * s["E"] * cb))}


def decode_need(model: dict, load: dict, rows: float,
                live_positions: float) -> dict:
    """One decode step that advances ``rows`` sequences by a token: every
    weight the step must read, once, in its stored dtype (the mixers, the
    dense part, the routers, the norms, the token table as the tied head;
    of the routed experts those that got a row; a row of the table a
    sequence), the live rows' windows there and back, and the K and V of
    their live positions (:func:`attn_need`). 2 operations a weight and
    row, plus the experts' and attention's; the taps' few operations a
    channel are left out."""
    s, p = sizes(model), param_parts(model)
    wb = _BYTES[model.get("param_dtype", "float32")]
    fixed = (s["conv"] * p["conv"] + s["attn"] * p["attn"]
             + s["dense"] * p["dense"] + s["moe"] * p["router"]
             + s["layers"] * p["norms"] + p["final_norm"] + p["embed"])
    routed, rings = experts_need(model, load), attn_need(model,
                                                        live_positions)
    return {"flops": 2.0 * fixed * rows + routed["flops"] + rings["flops"],
            "bytes": float(fixed * wb + rows * s["E"] * wb + routed["bytes"]
                           + rings["bytes"]
                           + rows * 2 * window_bytes(model))}

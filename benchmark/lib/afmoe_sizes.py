"""The sizes of an `afmoe` configuration's ``model`` block and the need
functions of its roofline metrics (``layer_metrics/
afmoe_decode_attn_roofline.py``, ``afmoe_moe_experts_roofline.py``,
``afmoe_decode_step_roofline.py``), which count the JOB and not the
implementation: what a decode step has to read and multiply whatever the
program does (the decode step's ring read, ``ring_gqa_decode_fwd``, fetches
a row's live ring blocks of 512 positions and is held against the live
positions' K and V alone: what the rounding up to blocks costs shows). The
defaults where a key is left out are the program's
(``config.py:ModelConfig``). ``lib/cost.py`` counts
`control` and `diff`, ``lib/jamba_sizes.py`` `jamba`,
``lib/kimi_linear_sizes.py`` `kimi_linear` (its ``expert_load`` reads the
engine's spans only and serves this family too).

Also what the engine's ``decode`` spans say of the rings (:func:`kv_load`):
the span's ``kv`` argument, which the engine fills from the positions it
holds by the rule of ``models/decode.py:live_kv``.
"""

from __future__ import annotations

from typing import Optional

_BYTES = {"float32": 4, "bfloat16": 2}
_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def sizes(model: dict) -> dict:
    if model["model"] != "afmoe":
        raise ValueError(f"benchmark/lib/afmoe_sizes.py counts the `afmoe` "
                         f"family, not {model['model']!r}")
    E, L = model["n_embd"], model["n_layer"]
    N = model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    kinds = [_KINDS[t] for t in model["layer_types"]]
    dense = min(model.get("first_dense_layers", 1), L) if N else L
    return {"E": E, "H": model["n_head"], "V": model["vocab_size"],
            "KV": model.get("kv_heads") or model["n_head"],
            "d": model.get("head_dim") or E // model["n_head"],
            "W": model.get("sliding_window", 0),
            "F": model.get("ffn_hidden") or 4 * E,
            "Fm": model.get("moe_hidden", 1024), "N": N,
            "top": model.get("experts_per_token", 8),
            "held": (hi or N) - lo, "layers": L,
            "window": kinds.count("window"), "full": kinds.count("full"),
            "dense": dense, "moe": L - dense}


def param_parts(model: dict) -> dict:
    """Parameters of each part of the tree of ``models/afmoe.py``: one
    layer's attention (q, k, v, gate and output projections, the two head
    norms, and the block's four norm scales counted with it), one dense
    MLP, one expert layer without its routed experts (shared expert,
    router with its bias), one routed expert, the head with the final
    norm, the token table."""
    s = sizes(model)
    E, H, KV, d = s["E"], s["H"], s["KV"], s["d"]
    return {
        "attn": (E * H * d + 2 * E * KV * d + E * H * d + H * d * E + 2 * d
                 + 4 * E),
        "dense": 3 * E * s["F"],
        "moe_fixed": 3 * E * s["Fm"] + E * s["N"] + s["N"],
        "expert": 3 * E * s["Fm"],
        "head": E * s["V"] + E,
        "embed": s["V"] * E,
    }


def param_count(model: dict) -> int:
    """Every parameter this share holds."""
    s, p = sizes(model), param_parts(model)
    return (s["layers"] * p["attn"] + s["dense"] * p["dense"]
            + s["moe"] * (p["moe_fixed"] + s["held"] * p["expert"])
            + p["head"] + p["embed"])


def kv_load(run) -> Optional[dict]:
    """Means a decode step of the measured window, from the ``decode``
    spans' ``kv`` argument: ``live_window`` and ``live_full``, the ring
    positions a sliding and a full layer hold live, summed over the
    active rows; ``rolled``, the rows past the window; ``active`` rows.
    None where no span carries the argument (a program from before it, or
    a family of one ring length)."""
    if run.spans is None:
        return None
    t0, t1 = run.values["measured_window"]
    mine = [a for n, _, b, a in list(run.spans.spans)
            if n == "decode" and t0 <= b < t1 and (a or {}).get("kv")]
    if not mine:
        return None
    mean = lambda f: sum(f(a) for a in mine) / len(mine)  # noqa: E731
    return {"live_window": mean(lambda a: a["kv"]["live_window"]),
            "live_full": mean(lambda a: a["kv"]["live_full"]),
            "rolled": mean(lambda a: a["kv"]["rolled"]),
            "active": mean(lambda a: a["active"]), "steps": len(mine)}


def position_bytes(model: dict) -> int:
    """K and V of one position in one layer, as the cache stores them
    (the compute dtype)."""
    s = sizes(model)
    return 2 * s["KV"] * s["d"] * _BYTES[model.get("compute_dtype", "bfloat16")]


def live_positions(model: dict, kv: dict) -> float:
    """Live ring positions of a step's rows over all layers."""
    s = sizes(model)
    return s["window"] * kv["live_window"] + s["full"] * kv["live_full"]


def attn_need(model: dict, kv: dict) -> dict:
    """The ring reads of one decode step, all layers: the K and V of the
    rows' live positions once (a sliding layer's at most its window), a
    score and a weighted value a query head and live position."""
    s = sizes(model)
    live = live_positions(model, kv)
    return {"flops": live * s["H"] * 4.0 * s["d"],
            "bytes": float(live * position_bytes(model))}


def experts_need(model: dict, load: dict) -> dict:
    """The routed experts of one decode step, all expert layers, as
    ``lib/kimi_linear_sizes.py:experts_need`` counts: the weights of the
    experts that got a row read once in their stored dtype, a row of E
    values in and out an assignment in the compute dtype, 2 operations a
    weight and assignment."""
    s, p = sizes(model), param_parts(model)
    wb = _BYTES[model.get("param_dtype", "float32")]
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    return {"flops": 2.0 * p["expert"] * load["held"],
            "bytes": float(load["experts_hit"] * p["expert"] * wb
                           + load["held"] * 2 * s["E"] * cb)}


def decode_need(model: dict, load: dict, kv: dict) -> dict:
    """One decode step that advances ``kv["active"]`` sequences by a
    token: every weight the step must read, once, in its stored dtype
    (every layer's attention, the dense MLP, the shared experts and
    routers, the head; of the routed experts those that got a row; of the
    token table a row a sequence), and the K and V of the rows' live
    positions (:func:`attn_need`). 2 operations a weight and row, plus
    the experts' and attention's."""
    s, p = sizes(model), param_parts(model)
    rows = kv["active"]
    wb = _BYTES[model.get("param_dtype", "float32")]
    fixed = (s["layers"] * p["attn"] + s["dense"] * p["dense"]
             + s["moe"] * p["moe_fixed"] + p["head"])
    routed, rings = experts_need(model, load), attn_need(model, kv)
    return {"flops": 2.0 * fixed * rows + routed["flops"] + rings["flops"],
            "bytes": float(fixed * wb + rows * s["E"] * wb + routed["bytes"]
                           + rings["bytes"])}

"""The sizes of a `deepseek_v2` configuration's ``model`` block and the need
functions of its roofline metrics (``layer_metrics/
dsv2_mla_attend_roofline.py``, ``dsv2_moe_experts_roofline.py``,
``dsv2_decode_step_roofline.py``), which count the JOB and not the
implementation: what a decode step has to read and multiply whatever the
program does (the live-latent read, ``mla_latent_decode_fwd``, fetches a
row's live blocks of 512 positions and is held against the live latents
alone: what the rounding up to blocks costs shows). Every size comes from
the ``model`` block; the defaults where a key is left out are the program's
(``config.py:ModelConfig``). ``lib/cost.py`` counts `control` and `diff`,
``lib/jamba_sizes.py`` `jamba`, ``lib/kimi_linear_sizes.py`` `kimi_linear`
(its ``expert_load`` reads the engine's spans only and serves this family
too), ``lib/afmoe_sizes.py`` `afmoe`.

Also what the engine's ``decode`` spans say of the latent rings
(:func:`latent_load`, the span's ``latent_live``) and of the router's
groups (:func:`group_load`, ``moe.rows_in_held_group``).
"""

from __future__ import annotations

from typing import Optional

_BYTES = {"float32": 4, "bfloat16": 2}


def sizes(model: dict) -> dict:
    if model["model"] != "deepseek_v2":
        raise ValueError(f"benchmark/lib/deepseek_v2_sizes.py counts the "
                         f"`deepseek_v2` family, not {model['model']!r}")
    E, L = model["n_embd"], model["n_layer"]
    N = model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    dense = min(model.get("first_dense_layers", 1), L) if N else L
    return {"E": E, "H": model["n_head"], "V": model["vocab_size"],
            "qr": model["q_lora_rank"],
            "rank": model.get("kv_lora_rank", 512),
            "nope": model.get("qk_nope_head_dim", 128),
            "rope": model.get("qk_rope_head_dim", 64),
            "vd": model.get("v_head_dim", 128),
            "F": model.get("ffn_hidden") or 4 * E,
            "Fm": model.get("moe_hidden", 1024), "N": N,
            "groups": model.get("n_group", 1),
            "shared": model.get("n_shared_experts", 1),
            "held": (hi or N) - lo, "layers": L,
            "dense": dense, "moe": L - dense}


def param_parts(model: dict) -> dict:
    """Parameters of each part of the tree of ``models/deepseek_v2.py``:
    one layer's MLA (the low-rank query's two projections and norm, the
    latent's projection and norm, the widening, the output projection;
    the block's two norm scales counted with it), one dense MLP, one
    expert layer without its routed experts (the shared experts as one
    MLP, the router), one routed expert, the head with the final norm, the
    token table."""
    s = sizes(model)
    E, H = s["E"], s["H"]
    return {
        "mla": (E * s["qr"] + s["qr"] + s["qr"] * H * (s["nope"] + s["rope"])
                + E * (s["rank"] + s["rope"]) + s["rank"]
                + s["rank"] * H * (s["nope"] + s["vd"]) + H * s["vd"] * E
                + 2 * E),
        "dense": 3 * E * s["F"],
        "moe_fixed": 3 * E * s["shared"] * s["Fm"] + E * s["N"],
        "expert": 3 * E * s["Fm"],
        "head": E * s["V"] + E,
        "embed": s["V"] * E,
    }


def param_count(model: dict) -> int:
    """Every parameter this share holds."""
    s, p = sizes(model), param_parts(model)
    return (s["layers"] * p["mla"] + s["dense"] * p["dense"]
            + s["moe"] * (p["moe_fixed"] + s["held"] * p["expert"])
            + p["head"] + p["embed"])


def position_bytes(model: dict) -> int:
    """The latents of one position over all layers, as the cache stores
    them (``rank + rope`` values a layer in the compute dtype)."""
    s = sizes(model)
    return (s["layers"] * (s["rank"] + s["rope"])
            * _BYTES[model.get("compute_dtype", "bfloat16")])


def _decode_spans(run, key) -> list:
    if run.spans is None:
        return []
    t0, t1 = run.values["measured_window"]
    return [a for n, _, b, a in list(run.spans.spans)
            if n == "decode" and t0 <= b < t1 and key(a or {})]


def latent_load(run) -> Optional[dict]:
    """Means a decode step of the measured window, from the ``decode``
    spans' ``latent_live`` (the latents the active rows hold live in one
    layer's rings, pos + 1 a row) and ``active`` rows. None where no span
    carries the argument (a program from before it, or another family)."""
    mine = _decode_spans(run, lambda a: "latent_live" in a)
    if not mine:
        return None
    mean = lambda f: sum(f(a) for a in mine) / len(mine)  # noqa: E731
    return {"live": mean(lambda a: a["latent_live"]),
            "active": mean(lambda a: a["active"]), "steps": len(mine)}


def group_load(run) -> Optional[dict]:
    """Sums over the measured window's decode steps, from the spans'
    ``moe`` argument of a router limited to groups: ``reached``, the (row,
    expert layer) pairs that kept a held group; ``held`` assignments on
    held experts; ``rows``, the active rows. None where no span carries
    ``moe.rows_in_held_group``."""
    mine = _decode_spans(
        run, lambda a: "rows_in_held_group" in (a.get("moe") or {}))
    if not mine:
        return None
    return {"reached": sum(a["moe"]["rows_in_held_group"] for a in mine),
            "held": sum(a["moe"]["held"] for a in mine),
            "rows": sum(a["active"] for a in mine), "steps": len(mine)}


def attend_need(model: dict, lat: dict) -> dict:
    """The latent reads of one decode step, all layers: the live latents
    of the active rows once (``rank + rope`` values a position and layer,
    one for all heads), and the absorbed form's two products a head and
    live position: a score over ``rank + rope``, a weighted latent over
    ``rank``."""
    s = sizes(model)
    live = lat["live"] * s["layers"]
    return {"flops": live * s["H"] * 2.0 * (2 * s["rank"] + s["rope"]),
            "bytes": float(lat["live"] * position_bytes(model))}


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


def decode_need(model: dict, load: dict, lat: dict) -> dict:
    """One decode step that advances ``lat["active"]`` sequences by a
    token: every weight the step must read, once, in its stored dtype
    (every layer's MLA, the dense MLP, the shared experts and routers, the
    head; of the routed experts those that got a row; of the token table a
    row a sequence), and the live latents (:func:`attend_need`). 2
    operations a weight and row, plus the experts' and attention's."""
    s, p = sizes(model), param_parts(model)
    rows = lat["active"]
    wb = _BYTES[model.get("param_dtype", "float32")]
    fixed = (s["layers"] * p["mla"] + s["dense"] * p["dense"]
             + s["moe"] * p["moe_fixed"] + p["head"])
    routed, rings = experts_need(model, load), attend_need(model, lat)
    return {"flops": 2.0 * fixed * rows + routed["flops"] + rings["flops"],
            "bytes": float(fixed * wb + rows * s["E"] * wb + routed["bytes"]
                           + rings["bytes"])}

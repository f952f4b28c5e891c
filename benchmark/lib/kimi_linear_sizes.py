"""The sizes of a `kimi_linear` configuration's ``model`` block and the
need functions of its roofline metrics (``layer_metrics/
moe_experts_roofline.py``, ``kda_state_update_roofline.py``,
``kimi_linear_decode_step_roofline.py``), which count the JOB and not the
implementation: what a decode step has to read, write and multiply
whatever the kernels do. The defaults where a key is left out are the
program's (``config.py:ModelConfig``). ``lib/cost.py`` counts `control`
and `diff`, ``lib/jamba_sizes.py`` `jamba`.

Also what the engine's ``decode`` spans say of the expert layers
(:func:`expert_load`): the span's ``moe`` argument, filled by the engine
from the decode program's own counters.
"""

from __future__ import annotations

from typing import Optional

_BYTES = {"float32": 4, "bfloat16": 2}


def sizes(model: dict) -> dict:
    if model["model"] != "kimi_linear":
        raise ValueError(f"benchmark/lib/kimi_linear_sizes.py counts the "
                         f"`kimi_linear` family, not {model['model']!r}")
    E, L = model["n_embd"], model["n_layer"]
    N = model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    kda = sum(1 for l in range(1, L + 1) if l in model["kda_layers"])
    dense = min(model.get("first_dense_layers", 1), L)
    return {"E": E, "H": model["n_head"], "V": model["vocab_size"],
            "d": model.get("kda_head_dim", 128), "K": model.get("kda_conv", 4),
            "rank": model.get("kv_lora_rank", 512),
            "nope": model.get("qk_nope_head_dim", 128),
            "rope": model.get("qk_rope_head_dim", 64),
            "vd": model.get("v_head_dim", 128),
            "F": model.get("ffn_hidden") or 4 * E,
            "Fm": model.get("moe_hidden", 1024), "N": N,
            "top": model.get("experts_per_token", 8),
            "held": (hi or N) - lo,
            "kda": kda, "mla": L - kda, "dense": dense, "moe": L - dense}


def param_parts(model: dict) -> dict:
    """Parameters of each part of the tree of ``models/kimi_linear.py``:
    one KDA mixer, one MLA mixer, one dense MLP, one expert layer without
    its routed experts (shared expert, router with its bias), one routed
    expert, the head with the final norm, the token table; a block's two
    norm scales are counted with its mixer."""
    s = sizes(model)
    E, H, d, K = s["E"], s["H"], s["d"], s["K"]
    Hd = H * d
    return {
        "kda": (E * 3 * Hd + K * 3 * Hd + 2 * (E * d + d * Hd) + Hd + H
                + E * H + d + Hd * E + 2 * E),
        "mla": (E * H * (s["nope"] + s["rope"]) + E * (s["rank"] + s["rope"])
                + s["rank"] + s["rank"] * H * (s["nope"] + s["vd"])
                + H * s["vd"] * E + 2 * E),
        "dense": 3 * E * s["F"],
        "moe_fixed": 3 * E * s["Fm"] + E * s["N"] + s["N"],
        "expert": 3 * E * s["Fm"],
        "head": E * s["V"] + E,
        "embed": s["V"] * E,
    }


def param_count(model: dict) -> int:
    """Every parameter this share holds."""
    s, p = sizes(model), param_parts(model)
    return (s["kda"] * p["kda"] + s["mla"] * p["mla"] + s["dense"] * p["dense"]
            + s["moe"] * (p["moe_fixed"] + s["held"] * p["expert"])
            + p["head"] + p["embed"])


def expert_load(run) -> Optional[dict]:
    """Means a decode step of the measured window, from the ``decode``
    spans' ``moe`` argument: ``held`` assignments on held experts,
    ``max_expert`` the largest count on one expert, ``experts_hit`` the
    held experts that got a row (each summed over the expert layers), and
    ``active`` rows. None where no span carries the argument (a program
    from before it, or a family without experts)."""
    if run.spans is None:
        return None
    t0, t1 = run.values["measured_window"]
    mine = [a for n, _, b, a in list(run.spans.spans)
            if n == "decode" and t0 <= b < t1 and (a or {}).get("moe")]
    if not mine:
        return None
    mean = lambda f: sum(f(a) for a in mine) / len(mine)  # noqa: E731
    return {"held": mean(lambda a: a["moe"]["held"]),
            "max_expert": mean(lambda a: a["moe"]["max_expert"]),
            "experts_hit": mean(lambda a: a["moe"]["experts_hit"]),
            "active": mean(lambda a: a["active"]), "steps": len(mine)}


def experts_need(model: dict, load: dict) -> dict:
    """The routed experts of one decode step, all expert layers: the
    weights of the experts that got a row read once in their stored dtype
    (``experts_hit`` of them: an expert nobody chose need not be read),
    a row of E values in and out an assignment in the compute dtype, and
    2 operations a weight and assignment."""
    s, p = sizes(model), param_parts(model)
    wb = _BYTES[model.get("param_dtype", "float32")]
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    return {"flops": 2.0 * p["expert"] * load["held"],
            "bytes": float(load["experts_hit"] * p["expert"] * wb
                           + load["held"] * 2 * s["E"] * cb)}


def kda_update_need(model: dict, rows: float) -> dict:
    """One token of ``rows`` active slots through every KDA layer: the
    slot's state (H x d x d, float32) read and written, its q, k, g, v
    (float32) and beta read and o written. A head and row: the decay, the
    read ``S^T k``, the rank-one write and the read ``S^T q`` are 7
    operations a state value. A slot that is not active needs nothing."""
    s = sizes(model)
    H, d = s["H"], s["d"]
    per_row = 2 * H * d * d * 4 + H * (5 * d + 1) * 4
    return {"flops": s["kda"] * rows * H * d * d * 7.0,
            "bytes": float(s["kda"] * rows * per_row)}


def decode_need(model: dict, v: dict, load: dict) -> dict:
    """One decode step that advances ``v["decode_rows"]`` sequences by a
    token: every weight the step must read, once, in its stored dtype (the
    mixers, the dense MLP, the shared experts and routers, the head; of
    the routed experts those that got a row, :func:`experts_need`; of the
    token table a row a sequence); a row and KDA layer, the recurrent
    state read and written (float32) with the convolution's window
    (compute dtype); the latents (compute dtype) of the
    ``v["decode_live_positions"]`` cached positions of those sequences
    read once, an MLA layer. 2 operations a weight and row, plus attention
    over the live latents in the absorbed form and the recurrence."""
    s, p = sizes(model), param_parts(model)
    rows, live = v["decode_rows"], v["decode_live_positions"]
    wb = _BYTES[model.get("param_dtype", "float32")]
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    fixed = (s["kda"] * p["kda"] + s["mla"] * p["mla"]
             + s["dense"] * p["dense"] + s["moe"] * p["moe_fixed"]
             + p["head"])
    routed = experts_need(model, load)
    update = kda_update_need(model, rows)
    window = s["kda"] * rows * 2 * (s["K"] - 1) * 3 * s["H"] * s["d"] * cb
    latent = s["rank"] + s["rope"]
    flops = (2.0 * fixed * rows + routed["flops"] + update["flops"]
             + live * s["mla"] * s["H"] * 2.0 * (latent + s["rank"]))
    return {"flops": flops,
            "bytes": float(fixed * wb + rows * s["E"] * wb + routed["bytes"]
                           + update["bytes"] + window
                           + live * s["mla"] * latent * cb)}

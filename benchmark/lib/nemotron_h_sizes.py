"""The sizes of a `nemotron_h` configuration's ``model`` block and the need
functions of its roofline metrics (``layer_metrics/
ssd_state_update_roofline.py``, ``ssd_chunk_roofline.py``,
``nemotron_h_moe_experts_roofline.py``,
``nemotron_h_decode_step_roofline.py``), which count the JOB and not the
implementation: what a decode step or a prompt chunk has to read and
multiply whatever the program does. Every size comes from the ``model``
block; the defaults where a key is left out are the program's
(``config.py:ModelConfig``). ``lib/cost.py`` counts `control` and `diff`,
``lib/jamba_sizes.py`` `jamba`, ``lib/kimi_linear_sizes.py`` `kimi_linear`
(its ``expert_load`` reads the engine's spans only and serves this family
too), ``lib/afmoe_sizes.py`` `afmoe`, ``lib/deepseek_v2_sizes.py``
`deepseek_v2`.

Also what the engine's ``decode`` spans say of the recurrent states
(:func:`state_load`, the span's ``live_state_bytes``) and which prefill
calls a trace holds (:func:`traced_prefill_calls`).
"""

from __future__ import annotations

from typing import Optional

from .deepseek_v2_sizes import _decode_spans  # the window's decode spans

_BYTES = {"float32": 4, "bfloat16": 2}


def sizes(model: dict) -> dict:
    if model["model"] != "nemotron_h":
        raise ValueError(f"benchmark/lib/nemotron_h_sizes.py counts the "
                         f"`nemotron_h` family, not {model['model']!r}")
    E, H = model["n_embd"], model["n_head"]
    N = model.get("num_experts", 0)
    lo, hi = model.get("held_experts") or (0, 0)
    pattern = model["hybrid_override_pattern"]
    Hm, P = model["mamba_num_heads"], model.get("mamba_head_dim", 64)
    G, Ns = model.get("n_groups", 1), model.get("ssm_state_size", 128)
    return {"E": E, "H": H, "KV": model.get("kv_heads") or H, "d": E // H,
            "V": model["vocab_size"], "Hm": Hm, "P": P, "G": G, "Ns": Ns,
            "Di": Hm * P, "Dc": Hm * P + 2 * G * Ns,
            "K": model.get("mamba_d_conv", 4),
            "Q": model.get("chunk_size", 128),
            "N": N, "top": model.get("experts_per_token", 8),
            "Fm": model.get("moe_hidden", 1024),
            "Fs": model.get("moe_shared_hidden", 0),
            "Lz": model.get("moe_latent_size", 0) or E,
            "latent": bool(model.get("moe_latent_size", 0)),
            "held": (hi or N) - lo, "layers": len(pattern),
            "mamba2": pattern.count("M"), "attn": pattern.count("*"),
            "moe": pattern.count("E")}


def param_parts(model: dict) -> dict:
    """Parameters of each part of the tree of ``models/nemotron_h.py``, the
    layer's one norm scale counted with it: a Mamba-2 layer (the input
    projection to z, x, B, C and dt, the convolution and its bias, dt_bias,
    A_log and D a head, the gated norm, the output projection), an
    attention layer, an expert layer without its routed experts (the
    router and its bias, the two latent projections, the shared expert),
    one routed expert (in the latent, ungated: two matrices), the head with
    the final norm, the token table."""
    s = sizes(model)
    E, Di, Dc, Hm = s["E"], s["Di"], s["Dc"], s["Hm"]
    return {
        "mamba2": (E * (Di + Dc + Hm) + s["K"] * Dc + Dc + 3 * Hm + Di
                   + Di * E + E),
        "attn": 2 * E * s["H"] * s["d"] + 2 * E * s["KV"] * s["d"] + E,
        "moe_fixed": (E * s["N"] + s["N"]
                      + (2 * E * s["Lz"] if s["latent"] else 0)
                      + 2 * E * s["Fs"] + E),
        "expert": 2 * s["Lz"] * s["Fm"],
        "head": E * s["V"] + E,
        "embed": s["V"] * E,
    }


def param_count(model: dict) -> int:
    """Every parameter this share holds."""
    s, p = sizes(model), param_parts(model)
    return (s["mamba2"] * p["mamba2"] + s["attn"] * p["attn"]
            + s["moe"] * (p["moe_fixed"] + s["held"] * p["expert"])
            + p["head"] + p["embed"])


def state_bytes(model: dict) -> int:
    """A slot's recurrent state over all Mamba-2 layers, as the pool stores
    it: ``(N, heads x P)`` float32 a layer (the convolution's window, 60 KB
    a layer, is no state leaf the engine counts)."""
    s = sizes(model)
    return s["mamba2"] * s["Ns"] * s["Di"] * 4


def slot_bytes(model: dict) -> int:
    """Everything a slot of the pool holds: the states, the convolution
    windows and the attention layers' K/V rings in the compute dtype."""
    s = sizes(model)
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    return (state_bytes(model) + s["mamba2"] * (s["K"] - 1) * s["Dc"] * cb
            + s["attn"] * 2 * s["KV"] * model["block_size"] * s["d"] * cb)


def state_load(run) -> Optional[dict]:
    """Means a decode step of the measured window, from the ``decode``
    spans' ``live_state_bytes`` (the active rows times a slot's state
    leaves) and ``active`` rows. None where no span carries the argument (a
    program from before it, or a family without a recurrent state)."""
    mine = _decode_spans(run, lambda a: "live_state_bytes" in a)
    if not mine:
        return None
    mean = lambda f: sum(f(a) for a in mine) / len(mine)  # noqa: E731
    return {"bytes": mean(lambda a: a["live_state_bytes"]),
            "active": mean(lambda a: a["active"]), "steps": len(mine)}


def traced_prefill_calls(run) -> list:
    """The token counts of the ``prefill_call`` spans inside the traced part
    of the window (its last ``trace_seconds``, at most half of it:
    lib/open_loop_cell.py): what each call really held, not the ladder's
    shape it was padded to."""
    if run.spans is None:
        return []
    t0, t1 = run.values["measured_window"]
    p0 = t1 - min(run.cell.traffic["trace_seconds"], (t1 - t0) / 2)
    return [args["size"] for n, a, b, args in list(run.spans.spans)
            if n == "prefill_call" and p0 <= a and b <= t1 and args]


def update_need(model: dict, rows: float) -> dict:
    """One token of ``rows`` active slots through every Mamba-2 layer: the
    slot's state (N x Di, float32) read and written; its decay and dt x a
    channel (float32) and B, C a group (float32) read, y (float32) written.
    Operations a channel and state: the decay's product, the input's
    product and sum, the read-out's product and sum (5). A slot that is
    not active needs nothing."""
    s = sizes(model)
    Di, Ns, layers = s["Di"], s["Ns"], s["mamba2"]
    per_row = 2 * Ns * Di * 4 + 3 * Di * 4 + 2 * s["G"] * Ns * 4
    return {"flops": layers * rows * Di * (5.0 * Ns + 1.0),
            "bytes": float(layers * rows * per_row)}


def chunk_need(model: dict, sizes_of_calls: list) -> dict:
    """The chunked (state-space dual) form over the given prefill calls,
    every Mamba-2 layer, for the tokens each call really held. A token in a
    sub-chunk of Q tokens (``chunk_size``, or the call if it is shorter):
    its row of ``C B^T`` (2 Q N a group), its row of ``((C B^T) . L)(dt .
    X)`` (2 Q P a head), its part of the sub-chunk's state (2 P N a head)
    and its read of the incoming state (2 P N a head). Bytes, each array
    once: a token reads x, B and C in the compute dtype and dt (float32)
    and writes y (float32); a call reads and writes the state (float32).
    The decay maps, the padding to the ladder's shape and to whole
    sub-chunks are the program's choice and do not count."""
    s = sizes(model)
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    Hm, P, G, Ns, Di = s["Hm"], s["P"], s["G"], s["Ns"], s["Di"]
    flops = 0.0
    for n in sizes_of_calls:
        q = min(s["Q"], n)
        flops += n * (2.0 * q * Ns * G + 2.0 * q * P * Hm
                      + 4.0 * P * Ns * Hm)
    tokens, calls = sum(sizes_of_calls), len(sizes_of_calls)
    per_token = Di * cb + 2 * G * Ns * cb + Hm * 4 + Di * 4
    per_call = 2 * Ns * Di * 4
    return {"flops": s["mamba2"] * flops,
            "bytes": float(s["mamba2"] * (tokens * per_token
                                          + calls * per_call))}


def experts_need(model: dict, load: dict) -> dict:
    """The routed experts of one decode step, all expert layers, as
    ``lib/kimi_linear_sizes.py:experts_need`` counts: the weights of the
    experts that got a row read once in their stored dtype, a row of the
    latent's width in and out an assignment in the compute dtype, 2
    operations a weight and assignment."""
    s, p = sizes(model), param_parts(model)
    wb = _BYTES[model.get("param_dtype", "float32")]
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    return {"flops": 2.0 * p["expert"] * load["held"],
            "bytes": float(load["experts_hit"] * p["expert"] * wb
                           + load["held"] * 2 * s["Lz"] * cb)}


def decode_need(model: dict, load: dict, state: dict) -> dict:
    """One decode step that advances ``state["active"]`` sequences by a
    token: every weight the step must read, once, in its stored dtype
    (every Mamba-2 and attention layer, the routers, latent projections
    and shared experts, the head; of the routed experts those that got a
    row; of the token table a row a sequence), and the live rows' states
    there and back (:func:`update_need`). 2 operations a weight and row,
    plus the experts' and the update's. The attention layers' live K/V
    (one layer in eleven, 1 KB a position) is left out: the share errs
    low."""
    s, p = sizes(model), param_parts(model)
    rows = state["active"]
    wb = _BYTES[model.get("param_dtype", "float32")]
    fixed = (s["mamba2"] * p["mamba2"] + s["attn"] * p["attn"]
             + s["moe"] * p["moe_fixed"] + p["head"])
    routed, states = experts_need(model, load), update_need(model, rows)
    return {"flops": 2.0 * fixed * rows + routed["flops"] + states["flops"],
            "bytes": float(fixed * wb + rows * s["E"] * wb + routed["bytes"]
                           + states["bytes"])}

"""The operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that a PR which changes a kernel cannot change
what the kernel is held against. Every function returns
``{"flops": .., "bytes": ..}`` for the work named in its docstring;
:func:`least_seconds` turns that into the least time the chip could take
and says which of the two peaks bounds it.
"""

from __future__ import annotations


def least_seconds(cost: dict, peaks: dict) -> tuple:
    """``(seconds, "compute" | "memory")``: the larger of operations over
    the bf16 peak and bytes over the memory bandwidth."""
    t_c = cost["flops"] / peaks["bf16_flops_per_s"]
    t_m = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def _attn_sizes(model: dict) -> tuple:
    """``(streams, heads, q/k width, value width)`` of the two families
    counted here, `control` and `diff`. Another family's attention has
    other shapes: its roofline metrics are files of their own with their
    own need functions (as ``layer_metrics/flash_attention_roofline.py``
    has), and a share printed from the wrong shape would be worse than
    none."""
    E, H = model["n_embd"], model["n_head"]
    if model["model"] == "control":
        H *= model.get("control_head_multiplier", 1)
        return 1, H, E // H, E // H
    if model["model"] == "diff":
        d = E // (2 * H)
        return 2, H, d, 2 * d
    raise ValueError(
        f"benchmark/lib/cost.py counts the `control` and `diff` families, "
        f"not {model['model']!r}")


def non_embedding_params(model: dict) -> int:
    """Parameters in the 6*N*D numerator of a `control` or a `diff` model:
    everything but the token table and, for `diff`, the learned position
    table (the definition of the program's
    ``obs/xprof.py:embedding_param_count``, copied)."""
    E, V, L = model["n_embd"], model["vocab_size"], model["n_layer"]
    S, H, d, dv = _attn_sizes(model)
    attn = 2 * S * E * H * d + E * H * dv + (H * dv * E + E)
    if model["model"] == "diff":
        attn += 2 * 2 * H * d + 2 * H * dv  # lambda vectors, group norm
    ffn = 2 * (E * 4 * E + 4 * E) + (4 * E * E + E)
    block = attn + ffn + 4 * E  # two layer norms
    return L * block + 2 * E + (E * V + V)


def train_step_6nd(model: dict, v: dict) -> dict:
    """One optimizer step of a `control` or a `diff` model on one chip
    (``v["rows_per_chip"]`` sequences of ``v["seq_len"]`` tokens) by the
    6*N*D rule: forward 2, backward 4, per parameter and token;
    attention's T*T products and recomputation are not counted."""
    tokens = v["rows_per_chip"] * v["seq_len"]
    return {"flops": 6.0 * non_embedding_params(model) * tokens, "bytes": 0.0}


def decode_step(model: dict, v: dict) -> dict:
    """One decode step of a `control` or a `diff` model that advances
    ``v["decode_rows"]`` sequences by a token (means over the traced
    steps): the non-embedding weights read once in bf16 and the K/V of the
    ``v["decode_live_positions"]`` cached positions of those sequences
    read once (all layers); 2 operations a weight and row plus attention
    over the live positions. Not what a path reads that streams whole
    rings or float32 weights."""
    S, H, d, dv = _attn_sizes(model)
    L = model["n_layer"]
    n = non_embedding_params(model)
    live_positions, rows = v["decode_live_positions"], v["decode_rows"]
    kv_bytes = live_positions * L * H * (S * d + dv) * 2
    flops = 2.0 * n * rows + live_positions * L * H * (2 * S * d + 2 * dv)
    return {"flops": flops, "bytes": 2.0 * n + kv_bytes}

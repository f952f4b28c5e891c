"""The sizes of a `jamba` configuration's ``model`` block, for the need
functions of its roofline metrics (``layer_metrics/ssm_scan_roofline.py``,
``ssm_state_update_roofline.py``, ``jamba_decode_step_roofline.py``). The
defaults where a key is left out are the program's
(``config.py:ModelConfig``). ``lib/cost.py`` counts `control` and `diff`.
"""

from __future__ import annotations


def sizes(model: dict) -> dict:
    if model["model"] != "jamba":
        raise ValueError(f"benchmark/lib/jamba_sizes.py counts the `jamba` "
                         f"family, not {model['model']!r}")
    E, H = model["n_embd"], model["n_head"]
    period = model.get("attn_layer_period", 8)
    offset = model.get("attn_layer_offset", 4)
    attn = sum(1 for i in range(model["n_layer"]) if i % period == offset)
    return {"E": E, "H": H, "KV": model.get("kv_heads") or H, "d": E // H,
            "F": model.get("ffn_hidden") or 4 * E, "V": model["vocab_size"],
            "Di": model.get("mamba_expand", 2) * E,
            "N": model.get("mamba_d_state", 16),
            "K": model.get("mamba_d_conv", 4),
            "R": model.get("mamba_dt_rank") or -(-E // 16),
            "attn": attn, "mamba": model["n_layer"] - attn,
            "tied": bool(model.get("tie_embeddings", False))}

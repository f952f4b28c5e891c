"""A `jamba` decode step's share of its roofline: the least time for what
the step NEEDS (:func:`decode_need`, from the traced steps' mean active
rows and live K/V positions) over the decode program's device time, read
as ``decode_step_device_ms`` reads it (the same ``module_needles``).
``lib/cost.py`` counts the `control` and `diff` families only."""

from lib import cost, harness, jamba_sizes, xplane

_BYTES = {"float32": 4, "bfloat16": 2}


def param_count(model: dict) -> int:
    """Every parameter of the model (``models/jamba.py``'s tree), the
    token table once where the head is tied to it."""
    s = jamba_sizes.sizes(model)
    E, F, Di, N, K, R = s["E"], s["F"], s["Di"], s["N"], s["K"], s["R"]
    mlp = 3 * E * F + 2 * E  # and the block's two norm scales
    attn = E * s["H"] * s["d"] + 2 * E * s["KV"] * s["d"] + s["H"] * s["d"] * E
    mamba = (E * 2 * Di + K * Di + Di + Di * (R + 2 * N) + R + 2 * N
             + R * Di + Di + Di * N + Di + Di * E)
    head = 0 if s["tied"] else E * s["V"]
    return (s["attn"] * (attn + mlp) + s["mamba"] * (mamba + mlp)
            + s["V"] * E + E + head)


def decode_need(model: dict, v: dict) -> dict:
    """One decode step that advances ``v["decode_rows"]`` sequences by a
    token: every weight read once in its stored dtype (the token table is
    the head's matrix; a row's own embedding is in it); a row and Mamba
    layer, the recurrent state read and written (N x Di in
    ``ssm_state_dtype``, K-1 inputs of the convolution in the compute
    dtype); the K/V (compute dtype) of the ``v["decode_live_positions"]``
    cached positions of those sequences read once, an attention layer.
    2 operations a weight and row, plus attention over the live positions
    and the recurrence."""
    s = jamba_sizes.sizes(model)
    rows, live = v["decode_rows"], v["decode_live_positions"]
    n = param_count(model)
    wb = _BYTES[model.get("param_dtype", "float32")]
    cb = _BYTES[model.get("compute_dtype", "bfloat16")]
    sb = _BYTES[model.get("ssm_state_dtype", "float32")]
    state = s["mamba"] * rows * 2 * (s["N"] * s["Di"] * sb
                                     + (s["K"] - 1) * s["Di"] * cb)
    kv = live * s["attn"] * s["KV"] * 2 * s["d"] * cb
    flops = (2.0 * n * rows + live * s["attn"] * s["H"] * 4 * s["d"]
             + s["mamba"] * rows * s["Di"] * (7.0 * s["N"] + 3.0))
    return {"flops": flops, "bytes": float(n * wb + state + kv)}


def read(run):
    v = run.values
    if (run.planes is None or run.env.peaks is None
            or v.get("decode_rows") is None):
        return None
    needles = harness.load_json(
        "layer_metrics", "decode_step_device_ms.json")["source"]["module_needles"]
    total, count = xplane.needle_seconds(run.planes, needles,
                                         xplane.MODULES_LINE)
    if not count:
        return None
    secs = total / count
    need = decode_need(run.cell.config["model"], v)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline jamba decode_step: {v['decode_rows']:.1f} rows, "
                f"{v['decode_live_positions']:.0f} live positions; "
                f"{need['flops']:.4g} operations, {need['bytes']:.4g} bytes; "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{secs * 1e3:.4f} ms measured")
    return 100.0 * least / secs

"""The decode step's state update's share of its roofline: the least time
for what the update NEEDS a decode step (:func:`update_need`, from the
traced steps' mean active rows) over the device time of the
``ssm_state_update`` kernels a step (one kernel a Mamba layer)."""

from lib import cost, harness, jamba_sizes, xplane


def update_need(model: dict, rows: float) -> dict:
    """One token of ``rows`` active slots through every Mamba layer: the
    slot's state (N x Di, float32) read and written, its u (bf16), delta,
    B and C (float32) read and y (float32) written, A and D read once a
    layer. Operations as ``ssm_scan_roofline.py:scan_need`` counts a
    token. A slot that is not active needs nothing."""
    s = jamba_sizes.sizes(model)
    Di, N, layers = s["Di"], s["N"], s["mamba"]
    per_row = 2 * Di * N * 4 + Di * (2 + 4 + 4) + 2 * N * 4
    return {"flops": layers * rows * Di * (7.0 * N + 3.0),
            "bytes": float(layers * (rows * per_row + (Di * N + Di) * 4))}


def read(run):
    v = run.values
    if (run.planes is None or run.env.peaks is None
            or v.get("decode_rows") is None):
        return None
    needles = harness.load_json(
        "layer_metrics", "ssm_state_update_roofline.json")["source"]["needles"]
    secs, count = xplane.needle_seconds(run.planes, needles)
    if not count:
        return None
    model = run.cell.config["model"]
    steps = count / jamba_sizes.sizes(model)["mamba"]
    need = update_need(model, v["decode_rows"])
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline ssm_state_update: {v['decode_rows']:.1f} active "
                f"rows a step, {steps:.0f} steps traced; {need['flops']:.4g} "
                f"operations, {need['bytes']:.4g} bytes a step; {bound}-bound, "
                f"least {least * 1e3:.4f} ms against "
                f"{secs / steps * 1e3:.4f} ms measured")
    return 100.0 * least * steps / secs

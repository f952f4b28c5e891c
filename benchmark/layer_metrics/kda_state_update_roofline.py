"""The decode step's KDA state update's share of its roofline: the least
time for what the update NEEDS a decode step
(``lib/kimi_linear_sizes.py:kda_update_need``, from the traced steps' mean
active rows) over the device time of the ``kda_state_update`` kernels a
step (one kernel a KDA layer)."""

from lib import cost, harness, kimi_linear_sizes, xplane


def read(run):
    v = run.values
    if (run.planes is None or run.env.peaks is None
            or v.get("decode_rows") is None):
        return None
    needles = harness.load_json(
        "layer_metrics", "kda_state_update_roofline.json")["source"]["needles"]
    secs, count = xplane.needle_seconds(run.planes, needles)
    if not count:
        return None
    model = run.cell.config["model"]
    steps = count / kimi_linear_sizes.sizes(model)["kda"]
    need = kimi_linear_sizes.kda_update_need(model, v["decode_rows"])
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline kda_state_update: {v['decode_rows']:.1f} active "
                f"rows a step, {steps:.0f} steps traced; {need['flops']:.4g} "
                f"operations, {need['bytes']:.4g} bytes a step; {bound}-bound, "
                f"least {least * 1e3:.4f} ms against "
                f"{secs / steps * 1e3:.4f} ms measured")
    return 100.0 * least * steps / secs

"""A `kimi_linear` decode step's share of its roofline: the least time for
what the step NEEDS (``lib/kimi_linear_sizes.py:decode_need``, from the
traced steps' mean active rows and live positions and the decode spans'
expert counts) over the decode program's device time, read as
``decode_step_device_ms`` reads it (the same ``module_needles``)."""

from lib import cost, harness, kimi_linear_sizes, xplane


def read(run):
    v = run.values
    if (run.planes is None or run.env.peaks is None
            or v.get("decode_rows") is None):
        return None
    load = kimi_linear_sizes.expert_load(run)
    if load is None:
        return None
    needles = harness.load_json(
        "layer_metrics", "decode_step_device_ms.json")["source"]["module_needles"]
    total, count = xplane.needle_seconds(run.planes, needles,
                                         xplane.MODULES_LINE)
    if not count:
        return None
    secs = total / count
    need = kimi_linear_sizes.decode_need(run.cell.config["model"], v, load)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline kimi_linear decode_step: {v['decode_rows']:.1f} "
                f"rows, {v['decode_live_positions']:.0f} live positions, "
                f"{load['experts_hit']:.1f} experts read; "
                f"{need['flops']:.4g} operations, {need['bytes']:.4g} bytes; "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{secs * 1e3:.4f} ms measured")
    return 100.0 * least / secs

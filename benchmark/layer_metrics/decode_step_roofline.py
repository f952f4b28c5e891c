"""The decode step's share of its roofline: the least time for what a
decode step NEEDS (lib/cost.py:decode_step, from the traced steps' mean
active rows and live K/V positions) over the decode program's device time,
read as ``decode_step_device_ms`` reads it (the same ``module_needles``)."""

from lib import cost, harness, xplane


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
    need = cost.decode_step(run.cell.config["model"], v)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline decode_step: {v['decode_rows']:.1f} rows, "
                f"{v['decode_live_positions']:.0f} live positions; "
                f"{need['flops']:.4g} operations, {need['bytes']:.4g} bytes; "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{secs * 1e3:.4f} ms measured")
    return 100.0 * least / secs

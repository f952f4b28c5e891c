"""An `afmoe` decode step's share of its roofline: the least time for what
the step NEEDS (``lib/afmoe_sizes.py:decode_need``, from the decode spans'
expert counts and live ring positions) over the decode program's device
time, read as ``decode_step_device_ms`` reads it (the same
``module_needles``)."""

from lib import afmoe_sizes, cost, harness, kimi_linear_sizes, xplane


def read(run):
    if run.planes is None or run.env.peaks is None:
        return None
    load = kimi_linear_sizes.expert_load(run)
    kv = afmoe_sizes.kv_load(run)
    if load is None or kv is None:
        return None
    needles = harness.load_json(
        "layer_metrics", "decode_step_device_ms.json")["source"]["module_needles"]
    total, count = xplane.needle_seconds(run.planes, needles,
                                         xplane.MODULES_LINE)
    if not count:
        return None
    secs = total / count
    need = afmoe_sizes.decode_need(run.cell.config["model"], load, kv)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline afmoe decode_step: {kv['active']:.1f} rows, "
                f"{afmoe_sizes.live_positions(run.cell.config['model'], kv):.0f}"
                f" live positions over the layers, {load['experts_hit']:.1f} "
                f"experts read; {need['flops']:.4g} operations, "
                f"{need['bytes']:.4g} bytes; {bound}-bound, least "
                f"{least * 1e3:.4f} ms against {secs * 1e3:.4f} ms measured")
    return 100.0 * least / secs

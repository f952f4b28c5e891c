"""A `deepseek_v2` decode step's share of its roofline: the least time for
what the step NEEDS (``lib/deepseek_v2_sizes.py:decode_need``, from the
decode spans' expert counts and live latents) over the decode program's
device time, read as ``decode_step_device_ms`` reads it (the same
``module_needles``)."""

from lib import cost, deepseek_v2_sizes, harness, kimi_linear_sizes, xplane


def read(run):
    if run.planes is None or run.env.peaks is None:
        return None
    load = kimi_linear_sizes.expert_load(run)
    lat = deepseek_v2_sizes.latent_load(run)
    if load is None or lat is None:
        return None
    needles = harness.load_json(
        "layer_metrics", "decode_step_device_ms.json")["source"]["module_needles"]
    total, count = xplane.needle_seconds(run.planes, needles,
                                         xplane.MODULES_LINE)
    if not count:
        return None
    secs = total / count
    need = deepseek_v2_sizes.decode_need(run.cell.config["model"], load, lat)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline dsv2 decode_step: {lat['active']:.1f} rows, "
                f"{lat['live']:.0f} live latents a layer, "
                f"{load['experts_hit']:.1f} experts read; "
                f"{need['flops']:.4g} operations, {need['bytes']:.4g} bytes; "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{secs * 1e3:.4f} ms measured")
    return 100.0 * least / secs

"""A `nemotron_h` decode step's share of its roofline: the least time for
what the step NEEDS (``lib/nemotron_h_sizes.py:decode_need``, from the
decode spans' expert counts and live state bytes) over the decode program's
device time, read as ``decode_step_device_ms`` reads it (the same
``module_needles``)."""

from lib import cost, harness, kimi_linear_sizes, nemotron_h_sizes, xplane


def read(run):
    if run.planes is None or run.env.peaks is None:
        return None
    load = kimi_linear_sizes.expert_load(run)
    state = nemotron_h_sizes.state_load(run)
    if load is None or state is None:
        return None
    needles = harness.load_json(
        "layer_metrics", "decode_step_device_ms.json")["source"]["module_needles"]
    total, count = xplane.needle_seconds(run.planes, needles,
                                         xplane.MODULES_LINE)
    if not count:
        return None
    secs = total / count
    need = nemotron_h_sizes.decode_need(run.cell.config["model"], load, state)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline nemotron_h decode_step: {state['active']:.1f} "
                f"rows, {state['bytes'] / 1e6:.1f} MB of live state, "
                f"{load['experts_hit']:.1f} experts read; "
                f"{need['flops']:.4g} operations, {need['bytes']:.4g} bytes; "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{secs * 1e3:.4f} ms measured")
    return 100.0 * least / secs

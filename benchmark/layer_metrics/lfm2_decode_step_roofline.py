"""An `lfm2` decode step's share of its roofline: the least time for what
the step NEEDS (``lib/lfm2_sizes.py:decode_need``, from the decode spans'
expert counts and live rows and the traced steps' live positions) over the
decode program's device time, read as ``decode_step_device_ms`` reads it
(the same ``module_needles``)."""

from lib import (
    cost,
    harness,
    kimi_linear_sizes,
    lfm2_sizes,
    nemotron_h_sizes,
    xplane,
)


def read(run):
    live = run.values.get("decode_live_positions")
    if run.planes is None or run.env.peaks is None or live is None:
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
    need = lfm2_sizes.decode_need(run.cell.config["model"], load,
                                  state["active"], live)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline lfm2 decode_step: {state['active']:.1f} rows, "
                f"{live:.0f} live positions, {load['experts_hit']:.1f} "
                f"experts read; {need['flops']:.4g} operations, "
                f"{need['bytes']:.4g} bytes; {bound}-bound, least "
                f"{least * 1e3:.4f} ms against {secs * 1e3:.4f} ms measured")
    return 100.0 * least / secs

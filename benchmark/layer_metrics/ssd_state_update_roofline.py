"""The decode step's Mamba-2 state update's share of its roofline: the
least time for what the update NEEDS a decode step
(``lib/nemotron_h_sizes.py:update_need``, from the traced steps' mean
active rows) over the device time under the scope ``ssm_state`` an
execution of the decode program (the ``ssm_ssd_state_update`` kernels, one
a Mamba-2 layer, and the XLA ops around them)."""

from lib import cost, harness, nemotron_h_sizes, scopes


def read(run):
    v = run.values
    if (run.planes is None or run.env.peaks is None
            or v.get("decode_rows") is None):
        return None
    src = harness.load_json(
        "layer_metrics", "ssd_state_update_roofline.json")["source"]
    ms = scopes.scope_ms(run, src["scope"], src["module"])
    if not ms or nemotron_h_sizes.state_load(run) is None:
        return None
    need = nemotron_h_sizes.update_need(run.cell.config["model"],
                                        v["decode_rows"])
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline ssd_state_update: {v['decode_rows']:.1f} active "
                f"rows a step; {need['flops']:.4g} operations, "
                f"{need['bytes']:.4g} bytes a step; {bound}-bound, least "
                f"{least * 1e3:.4f} ms against {ms:.4f} ms measured")
    return 100.0 * least * 1e3 / ms

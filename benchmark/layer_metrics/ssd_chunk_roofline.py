"""The chunked Mamba-2 scan's share of its roofline in the traced part of a
serve window: the least time for what the scans of the traced prefill
calls NEED (``lib/nemotron_h_sizes.py:chunk_need``, the tokens each call
really held), mean a call, over the device time under the scope
``ssm_scan`` an execution of the prefill program."""

from lib import cost, harness, nemotron_h_sizes, scopes


def read(run):
    if run.planes is None or run.env.peaks is None or run.spans is None:
        return None
    src = harness.load_json(
        "layer_metrics", "ssd_chunk_roofline.json")["source"]
    ms = scopes.scope_ms(run, src["scope"], src["module"])
    calls = nemotron_h_sizes.traced_prefill_calls(run)
    if not ms or not calls:
        return None
    need = nemotron_h_sizes.chunk_need(run.cell.config["model"], calls)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline ssd_chunk: {len(calls)} prefill calls of "
                f"{sum(calls)} tokens traced; {need['flops']:.4g} "
                f"operations, {need['bytes']:.4g} bytes; {bound}-bound, "
                f"least {least / len(calls) * 1e3:.4f} ms a call against "
                f"{ms:.4f} ms measured")
    return 100.0 * least / len(calls) * 1e3 / ms

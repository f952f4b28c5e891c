"""The ring read's share of its roofline in a decode step of the lfm2
family: the least time for what it NEEDS (``lib/lfm2_sizes.py:attn_need``,
from the traced steps' live positions) over the device time under the
scope ``attn_full`` an execution of the decode program."""

from lib import cost, harness, lfm2_sizes, scopes


def read(run):
    live = run.values.get("decode_live_positions")
    if run.planes is None or run.env.peaks is None or live is None:
        return None
    ms = scopes.scope_ms(run, "attn_full", "jit__decode")
    if not ms:
        return None
    need = lfm2_sizes.attn_need(run.cell.config["model"], live)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline lfm2 decode attention: {live:.0f} live positions "
                f"a step; {need['flops']:.4g} operations, "
                f"{need['bytes']:.4g} bytes; {bound}-bound, least "
                f"{least * 1e3:.4f} ms against {ms:.4f} ms measured")
    return 100.0 * least * 1e3 / ms

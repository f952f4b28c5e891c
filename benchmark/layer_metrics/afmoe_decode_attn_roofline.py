"""The ring reads' share of their roofline in a decode step of the afmoe
family: the least time for what they NEED (``lib/afmoe_sizes.py:attn_need``,
from the decode spans' ``kv``) over the device time under the scopes
``attn_window`` and ``attn_full`` an execution of the decode program."""

from lib import afmoe_sizes, cost, harness, scopes


def read(run):
    if run.planes is None or run.env.peaks is None:
        return None
    kv = afmoe_sizes.kv_load(run)
    parts = [scopes.scope_ms(run, scope, "jit__decode")
             for scope in ("attn_window", "attn_full")]
    ms = sum(p for p in parts if p)
    if kv is None or not ms:
        return None
    need = afmoe_sizes.attn_need(run.cell.config["model"], kv)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline afmoe decode attention: {kv['active']:.1f} rows, "
                f"{kv['live_window']:.0f} + {kv['live_full']:.0f} live "
                f"positions a sliding + a full layer ({kv['steps']} steps); "
                f"{need['flops']:.4g} operations, {need['bytes']:.4g} bytes; "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{ms:.4f} ms measured")
    return 100.0 * least * 1e3 / ms

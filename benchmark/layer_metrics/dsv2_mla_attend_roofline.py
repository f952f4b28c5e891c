"""The latent reads' share of their roofline in a decode step of the
deepseek_v2 family: the least time for what they NEED
(``lib/deepseek_v2_sizes.py:attend_need``, from the decode spans'
``latent_live``) over the device time under the scope ``mla_attend`` an
execution of the decode program."""

from lib import cost, deepseek_v2_sizes, harness, scopes


def read(run):
    if run.planes is None or run.env.peaks is None:
        return None
    lat = deepseek_v2_sizes.latent_load(run)
    ms = scopes.scope_ms(run, "mla_attend", "jit__decode")
    if lat is None or not ms:
        return None
    need = deepseek_v2_sizes.attend_need(run.cell.config["model"], lat)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline dsv2 mla_attend: {lat['active']:.1f} rows, "
                f"{lat['live']:.0f} live latents a layer ({lat['steps']} "
                f"steps); {need['flops']:.4g} operations, "
                f"{need['bytes']:.4g} bytes; {bound}-bound, least "
                f"{least * 1e3:.4f} ms against {ms:.4f} ms measured")
    return 100.0 * least * 1e3 / ms

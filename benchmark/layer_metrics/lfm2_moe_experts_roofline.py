"""The routed experts' share of their roofline in a decode step of the lfm2
family: the least time for what they NEED
(``lib/lfm2_sizes.py:experts_need``, from the decode spans' own counts)
over the device time under the scope ``moe_experts`` an execution of the
decode program."""

from lib import cost, harness, kimi_linear_sizes, lfm2_sizes, scopes


def read(run):
    if run.planes is None or run.env.peaks is None:
        return None
    load = kimi_linear_sizes.expert_load(run)
    ms = scopes.scope_ms(run, "moe_experts", "jit__decode")
    if load is None or not ms:
        return None
    need = lfm2_sizes.experts_need(run.cell.config["model"], load)
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline lfm2 moe_experts: {load['held']:.1f} "
                f"assignments on {load['experts_hit']:.1f} experts a step "
                f"({load['steps']} steps); {need['flops']:.4g} operations, "
                f"{need['bytes']:.4g} bytes; {bound}-bound, least "
                f"{least * 1e3:.4f} ms against {ms:.4f} ms measured")
    return 100.0 * least * 1e3 / ms

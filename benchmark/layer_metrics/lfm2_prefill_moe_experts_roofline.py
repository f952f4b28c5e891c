"""The routed experts' share of their roofline in the traced prefill calls
of the lfm2 family: the least time for what each call NEEDS
(``lib/lfm2_sizes.py:prefill_experts_need``, the tokens it really held),
mean a call, over the device time under the scope ``moe_experts`` an
execution of the prefill programs."""

from lib import cost, harness, lfm2_sizes, nemotron_h_sizes, scopes


def read(run):
    if run.planes is None or run.env.peaks is None or run.spans is None:
        return None
    ms = scopes.scope_ms(run, "moe_experts", "jit__prefill")
    calls = nemotron_h_sizes.traced_prefill_calls(run)
    if not ms or not calls:
        return None
    model = run.cell.config["model"]
    # a call is an execution of its own: its least time is its own
    parts = [cost.least_seconds(lfm2_sizes.prefill_experts_need(model, n),
                                run.env.peaks) for n in calls]
    least = sum(t for t, _ in parts) / len(calls)
    by_memory = sum(1 for _, bound in parts if bound == "memory")
    harness.say(f"roofline lfm2 prefill moe_experts: {len(calls)} prefill "
                f"calls of {sum(calls)} tokens traced, {by_memory} of them "
                f"memory-bound; least {least * 1e3:.4f} ms a call against "
                f"{ms:.4f} ms measured")
    return 100.0 * least * 1e3 / ms

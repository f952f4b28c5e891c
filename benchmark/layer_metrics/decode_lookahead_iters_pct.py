"""The share of the measured window's decode steps that were dispatched
BEFORE the host had read the tokens of the step before (the engine's late
read): beside `decode_host_idle_ms_per_iter` it says whether the idle
that is left lies under steps that had to read first, and by their
`drain` argument why."""


def read(run):
    if run.spans is None:
        return None
    t0, t1 = run.values["measured_window"]
    ahead = [args["lookahead"] for name, _, end, args in list(run.spans.spans)
             if name == "decode" and t0 <= end < t1
             and "lookahead" in (args or {})]
    if not ahead:
        return None
    return 100.0 * sum(ahead) / len(ahead)

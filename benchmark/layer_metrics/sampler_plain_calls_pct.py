"""The share of the sampler's calls that took its plain arm, over the
measured window: `sampler_device_ms_per_iter` falls with it, and beside
`sampler_rows_asking_pct` it says how few asking rows hold how many
calls on the full arm."""


def read(run):
    if run.spans is None:
        return None
    t0, t1 = run.values["measured_window"]
    plain = [args["plain"] for name, _, end, args in list(run.spans.spans)
             if name == "sample_operands" and t0 <= end < t1
             and "plain" in (args or {})]
    if not plain:
        return None
    return 100.0 * sum(plain) / len(plain)

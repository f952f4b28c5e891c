"""Prompt tokens a prefill program takes: the mean `size` of the
`prefill_call` spans that ended in the measured window (one span a
dispatched prefill program). With `prefill_calls_per_iter` it says whether
an iteration's prefill is many small programs or few large ones."""


def read(run):
    if run.spans is None:
        return None
    t0, t1 = run.values["measured_window"]
    sizes = [args["size"] for n, _, b, args in list(run.spans.spans)
             if n == "prefill_call" and t0 <= b < t1 and "size" in (args or {})]
    return sum(sizes) / len(sizes) if sizes else None

"""Median wall time of one engine iteration in the measured window: from
the start of the iteration's `schedule` span to the end of its last span
(`emit`, or `prefill` where nothing decodes). The engine stamps every span
with its iteration number."""

from lib.stats import percentile


def read(run):
    if run.spans is None:
        return None
    t0, t1 = run.values["measured_window"]
    first, last = {}, {}
    for name, a, b, args in list(run.spans.spans):
        it = (args or {}).get("iteration")
        if it is None or not (t0 <= b < t1):
            continue
        first[it] = min(a, first.get(it, a))
        last[it] = max(b, last.get(it, b))
    if not first:
        return None
    return percentile([(last[i] - first[i]) * 1e3 for i in first], 50)

"""Rows of the pool the decode attention read, a decode step of the
measured window: the mean of the engine's ``decode`` spans'
``attend_rows`` (a step is a ``decode`` span; an iteration without one,
which ``lib/engine_spans.py:per_iteration`` would count, read nothing).
None for a program whose spans carry no such argument (a program from
before it, or one that attends the pool another way)."""


def read(run):
    if run.spans is None:
        return None
    t0, t1 = run.values["measured_window"]
    rows = [args["attend_rows"] for name, _, end, args in list(run.spans.spans)
            if name == "decode" and t0 <= end < t1
            and "attend_rows" in (args or {})]
    return sum(rows) / len(rows) if rows else None

"""The engine's spans an iteration: what the readers of the serve cell's
host-side metrics share.

An iteration is counted as ``layer_metrics/engine_iter_p50_ms.py`` counts
it: the engine stamps the spans between an iteration's ``schedule`` start
and its ``emit`` end with ``iteration``, and an iteration belongs to the
measured window if one of its stamped spans ended there. Spans outside
that stretch (``step_tail``, ``intake``, ``deliver``) carry no stamp and
are taken by where they end.
"""

from __future__ import annotations

from typing import Optional, Sequence


def window_iterations(run) -> int:
    """Distinct ``iteration`` stamps among the spans that ended inside
    the measured window."""
    t0, t1 = run.values["measured_window"]
    return len({(args or {}).get("iteration")
                for _, _, b, args in list(run.spans.spans)
                if t0 <= b < t1} - {None})


def per_iteration(run, names: Sequence[str],
                  arg: Optional[str] = None) -> Optional[float]:
    """Over the spans called one of ``names`` that ended inside the
    measured window: the sum of their durations in ms, or with ``arg`` of
    that span argument, divided by the window's iterations. None where
    the program recorded no such span (a program from before the span
    was added), never 0 for "nothing there"."""
    if run.spans is None:
        return None
    t0, t1 = run.values["measured_window"]
    mine = [(a, b, args) for n, a, b, args in list(run.spans.spans)
            if n in names and t0 <= b < t1]
    if arg is not None:
        mine = [m for m in mine if arg in (m[2] or {})]
    iterations = window_iterations(run)
    if not mine or not iterations:
        return None
    if arg is not None:
        return sum(args[arg] for _, _, args in mine) / iterations
    return sum(b - a for a, b, _ in mine) * 1e3 / iterations

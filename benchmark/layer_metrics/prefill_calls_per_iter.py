"""Prefill programs dispatched an iteration of the measured window: the
sum of the `prefill` spans' `chunks` (one program a request and chunk)
over the window's iterations. A count made by the program: it repeats for
a seed as far as the arrivals fall into the same iterations."""

from lib import engine_spans


def read(run):
    return engine_spans.per_iteration(run, ("prefill",), arg="chunks")

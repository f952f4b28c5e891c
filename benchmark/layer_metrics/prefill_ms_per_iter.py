"""The host's time inside the engine's `prefill` spans an iteration of the
measured window (an iteration with nothing to prefill counts as one with
0 ms): the part of an iteration that the requests admitted in it add to
the gap of every request already decoding."""

from lib import engine_spans


def read(run):
    return engine_spans.per_iteration(run, ("prefill",))

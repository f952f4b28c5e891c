"""The host's time, an iteration of the measured window, between one
iteration's `emit` and the next one's `schedule`: the engine's `step_tail`
(histogram, gauges) and the runner's `deliver` (settling finished
requests, the progress snapshot) and `intake` (every `engine.submit` of
the arrivals). The wait for work, where there is none, is in no span and
not counted."""

from lib import engine_spans


def read(run):
    return engine_spans.per_iteration(run, ("step_tail", "deliver", "intake"))

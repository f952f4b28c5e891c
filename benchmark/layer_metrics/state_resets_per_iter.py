"""Recurrent-state resets an iteration of the measured window: the sum of
the engine's `state_reset` spans' `slots` (one a slot zeroed on admission)
over the window's iterations. None for a program that records no such
span (a family of K/V rings, or a program from before the span)."""

from lib import engine_spans


def read(run):
    return engine_spans.per_iteration(run, ("state_reset",), arg="slots")

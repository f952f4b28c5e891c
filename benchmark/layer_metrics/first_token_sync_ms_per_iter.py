"""The host's time inside the `first_token` spans an iteration of the
measured window: on the chunk that completes a prompt the engine samples
the first token and READS it, which blocks on the device before the next
request's prefill can be dispatched. Beside `prefill_ms_per_iter` it says
whether the device idles under dispatch cost or under that read."""

from lib import engine_spans


def read(run):
    return engine_spans.per_iteration(run, ("first_token",))

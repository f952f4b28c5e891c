"""The host's time inside the decode path's `token_read` spans an
iteration of the measured window: the one blocking read of an iteration,
wait + copy back + wake-up. `token_read_idle_ms_per_iter` is the part of
it during which the device has nothing left to do."""

from lib import host_share


def read(run):
    return host_share.span_ms_per_iter(run, "token_read", "decode")

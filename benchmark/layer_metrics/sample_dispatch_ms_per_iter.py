"""The host's time inside the decode path's `sample_dispatch` spans an
iteration of the measured window: one transfer and the sampler's call."""

from lib import host_share


def read(run):
    return host_share.span_ms_per_iter(run, "sample_dispatch", "decode")

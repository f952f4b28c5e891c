"""The host's time inside the decode path's `sample_operands` spans an
iteration of the measured window: the Python loop over the active slots
that packs the sampler's rows. What a leaner sampler call, or operands
kept between iterations, would save."""

from lib import host_share


def read(run):
    return host_share.span_ms_per_iter(run, "sample_operands", "decode")

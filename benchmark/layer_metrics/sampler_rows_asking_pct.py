"""The share of the sampler's rows that asked for more than an argmax,
over the measured window: beside `sampler_device_ms_per_iter` it says
what the sampler costs every row for what few rows wanted."""

from lib import host_share


def read(run):
    sums = host_share.arg_sums(run, "sample_operands", ("asking", "rows"))
    if sums is None or not sums[1]:
        return None
    return 100.0 * sums[0] / sums[1]

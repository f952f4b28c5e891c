"""Device time of the sampler program an iteration of the traced stretch:
it runs over ALL `num_slots` rows after every decode step, whatever the
rows asked for."""

from lib import host_share


def read(run):
    return host_share.sampler_ms_per_iter(run)

"""The sampler program's time under `logit_pipeline` an iteration of the
traced stretch: penalties and the constraint mask, applied to every row."""

from lib import host_share


def read(run):
    return host_share.sampler_ms_per_iter(run, "logit_pipeline")

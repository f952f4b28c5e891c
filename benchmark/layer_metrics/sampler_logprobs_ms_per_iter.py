"""The sampler program's time under `sampler_logprobs` an iteration of the
traced stretch: what every row pays for the log probabilities a row with
`logprobs > 0` asked for (`sampler_rows_asking_pct` says how many did)."""

from lib import host_share


def read(run):
    return host_share.sampler_ms_per_iter(run, "sampler_logprobs")

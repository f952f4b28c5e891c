"""The host's time inside the `decode_h2d` spans an iteration of the
measured window: the transfers of the decode step's operands, one
`jnp.asarray` each, before the step's call. What ONE transfer of the
operands would save is a share of this."""

from lib import host_share


def read(run):
    return host_share.span_ms_per_iter(run, "decode_h2d")

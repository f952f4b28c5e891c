"""The host's time inside the `decode_dispatch` spans an iteration of the
measured window: the call of the decode program alone (the pytree's
flattening, the runtime's enqueue), its operands already on the device.
What a cheaper dispatch would save."""

from lib import host_share


def read(run):
    return host_share.span_ms_per_iter(run, "decode_dispatch")

"""The part of the decode path's `token_read` spans that no device op
covers, from the trace (every span is a `TraceAnnotation` there): after
the sampler's last op the device waits for the copy back, the thread's
wake-up and the next iteration's host work to begin."""

from lib import host_share


def read(run):
    return host_share.idle_ms_per_iter(run, host_share.decode_token_read,
                                       "token_read")

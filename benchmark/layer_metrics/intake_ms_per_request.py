"""The runner's `intake` time a request it handed `engine.submit`, over
the measured window: `between_iterations_ms` holds the same spans an
iteration, which says nothing of an iteration that admits nobody."""

from lib import host_share


def read(run):
    return host_share.intake_ms_per_request(run)

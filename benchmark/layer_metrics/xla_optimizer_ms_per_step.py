"""XLA's time under the scopes grad_norm_clip and optimizer, read by
``lib/op_phases.py`` from the trace's metadata; the declaration beside
this file names the scopes."""

from lib import op_phases


def read(run):
    return op_phases.read_declared(run, "xla_optimizer_ms_per_step")

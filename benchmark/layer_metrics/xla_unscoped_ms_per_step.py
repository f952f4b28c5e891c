"""XLA's time under no phase scope, read by ``lib/op_phases.py`` from the
trace's metadata; the declaration beside this file names the scopes."""

from lib import op_phases


def read(run):
    return op_phases.read_declared(run, "xla_unscoped_ms_per_step")

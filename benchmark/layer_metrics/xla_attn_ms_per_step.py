"""XLA's time under the scopes attn and attn_norm, read by
``lib/op_phases.py`` from the trace's metadata; the declaration beside
this file names the scopes."""

from lib import op_phases


def read(run):
    return op_phases.read_declared(run, "xla_attn_ms_per_step")

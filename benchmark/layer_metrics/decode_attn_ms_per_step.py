"""The decode program's time under attn (outside kv_write) and attn_norm,
read by ``lib/op_phases.py`` from the trace's metadata; the declaration
beside this file names the scopes."""

from lib import op_phases


def read(run):
    return op_phases.read_declared(run, "decode_attn_ms_per_step")

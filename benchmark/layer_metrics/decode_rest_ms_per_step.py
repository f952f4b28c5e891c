"""The decode program's time outside attn and ffn, read by
``lib/op_phases.py`` from the trace's metadata; the declaration beside
this file names the scopes."""

from lib import op_phases


def read(run):
    return op_phases.read_declared(run, "decode_rest_ms_per_step")

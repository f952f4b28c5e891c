"""The decode program's time under kv_write, read by ``lib/op_phases.py``
from the trace's metadata; the declaration beside this file names the
scopes."""

from lib import op_phases


def read(run):
    return op_phases.read_declared(run, "decode_kv_write_ms_per_step")

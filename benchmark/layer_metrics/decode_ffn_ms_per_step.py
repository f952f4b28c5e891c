"""The decode program's time under ffn and ffn_norm. Device time an execution
of the decode program (jit__decode) of its ops, Pallas kernels included,
each instant given to the innermost running op, by the innermost
jax.named_scope in the op's op_name (the tf_op stat of the trace event's
metadata)., read by ``lib/op_phases.py`` from the trace's metadata; the
declaration beside this file names the scopes."""

from lib import op_phases


def read(run):
    return op_phases.read_declared(run, "decode_ffn_ms_per_step")

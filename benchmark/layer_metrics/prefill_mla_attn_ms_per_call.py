"""Device time under the scope ``mla_attend`` an execution of the prefill
program, read by ``lib/scopes.py`` from the trace's metadata; the
declaration beside this file names the scope and the program."""

from lib import scopes


def read(run):
    return scopes.read_declared(run, "prefill_mla_attn_ms_per_call")

"""Device time under one of the afmoe family's scopes, read by
``lib/scopes.py`` from the trace's metadata; the declaration beside this
file names the scope and the program."""

from lib import scopes


def read(run):
    return scopes.read_declared(run, "decode_attn_window_ms_per_step")

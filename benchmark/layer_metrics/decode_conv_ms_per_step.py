"""Device time under the short convolution's scope, read by
``lib/scopes.py`` from the trace's metadata; the declaration beside this
file names the scope and the program."""

from lib import scopes


def read(run):
    return scopes.read_declared(run, "decode_conv_ms_per_step")

"""Device time under the expert layers' ``moe_latent`` scope, read by
``lib/scopes.py`` from the trace's metadata; the declaration beside this
file names the scope and the program."""

from lib import scopes


def read(run):
    return scopes.read_declared(run, "decode_moe_latent_ms_per_step")

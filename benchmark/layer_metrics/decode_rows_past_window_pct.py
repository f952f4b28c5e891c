"""Rows past the sliding window over active rows, a decode step of the
measured window (``lib/afmoe_sizes.py:kv_load``: the engine's ``decode``
spans' ``kv`` argument). None for a program whose spans carry none."""

from lib import afmoe_sizes


def read(run):
    kv = afmoe_sizes.kv_load(run)
    if kv is None or not kv["active"]:
        return None
    return 100.0 * kv["rolled"] / kv["active"]

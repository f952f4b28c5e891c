"""Held experts a decode step of the nemotron_h family reads, summed over
the expert layers, from the engine's ``decode`` spans
(``lib/kimi_linear_sizes.py:expert_load``). None for a program whose spans
carry no ``moe`` argument or no ``live_state_bytes`` (another family)."""

from lib import kimi_linear_sizes, nemotron_h_sizes


def read(run):
    load = kimi_linear_sizes.expert_load(run)
    if load is None or nemotron_h_sizes.state_load(run) is None:
        return None
    return load["experts_hit"]

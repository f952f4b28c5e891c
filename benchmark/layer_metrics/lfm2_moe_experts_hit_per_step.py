"""Experts a decode step of the lfm2 family reads, summed over the expert
layers, from the engine's ``decode`` spans
(``lib/kimi_linear_sizes.py:expert_load``). None for a program whose spans
carry no ``moe`` argument."""

from lib import kimi_linear_sizes


def read(run):
    load = kimi_linear_sizes.expert_load(run)
    return None if load is None else load["experts_hit"]

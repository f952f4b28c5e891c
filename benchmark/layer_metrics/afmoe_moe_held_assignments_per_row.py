"""Held (row, expert) assignments a live row and expert layer of the afmoe
family, from the engine's ``decode`` spans
(``lib/kimi_linear_sizes.py:expert_load``). None for a program whose spans
carry no ``moe`` argument."""

from lib import afmoe_sizes, kimi_linear_sizes


def read(run):
    load = kimi_linear_sizes.expert_load(run)
    if load is None or not load["active"]:
        return None
    layers = afmoe_sizes.sizes(run.cell.config["model"])["moe"]
    return load["held"] / load["active"] / layers

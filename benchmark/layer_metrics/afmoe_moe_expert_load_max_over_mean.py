"""The fullest held expert's rows over the mean expert's in the afmoe
family's decode steps, from the engine's ``decode`` spans
(``lib/kimi_linear_sizes.py:expert_load``). None for a program whose spans
carry no ``moe`` argument."""

from lib import afmoe_sizes, kimi_linear_sizes


def read(run):
    load = kimi_linear_sizes.expert_load(run)
    if load is None or not load["held"]:
        return None
    held = afmoe_sizes.sizes(run.cell.config["model"])["held"]
    return load["max_expert"] * held / load["held"]

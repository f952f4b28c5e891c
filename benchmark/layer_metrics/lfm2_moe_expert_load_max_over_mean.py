"""The fullest expert's rows over the mean expert's in the lfm2 family's
decode steps, from the engine's ``decode`` spans
(``lib/kimi_linear_sizes.py:expert_load``). None for a program whose spans
carry no ``moe`` argument."""

from lib import kimi_linear_sizes, lfm2_sizes


def read(run):
    load = kimi_linear_sizes.expert_load(run)
    if load is None or not load["held"]:
        return None
    held = lfm2_sizes.sizes(run.cell.config["model"])["held"]
    return load["max_expert"] * held / load["held"]

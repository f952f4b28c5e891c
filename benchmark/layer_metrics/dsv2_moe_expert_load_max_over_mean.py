"""The fullest held expert's rows over the mean expert's in the
deepseek_v2 family's decode steps, from the engine's ``decode`` spans
(``lib/kimi_linear_sizes.py:expert_load``). None for a program whose spans
carry no ``moe`` argument, or none of this family's."""

from lib import deepseek_v2_sizes, kimi_linear_sizes


def read(run):
    load = kimi_linear_sizes.expert_load(run)
    if (load is None or not load["held"]
            or deepseek_v2_sizes.group_load(run) is None):
        return None
    held = deepseek_v2_sizes.sizes(run.cell.config["model"])["held"]
    return load["max_expert"] * held / load["held"]

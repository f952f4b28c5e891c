"""The fullest held expert's rows over the mean expert's in the nemotron_h
family's decode steps, from the engine's ``decode`` spans
(``lib/kimi_linear_sizes.py:expert_load``). None for a program whose spans
carry no ``moe`` argument or no ``live_state_bytes`` (another family)."""

from lib import kimi_linear_sizes, nemotron_h_sizes


def read(run):
    load = kimi_linear_sizes.expert_load(run)
    if (load is None or not load["held"]
            or nemotron_h_sizes.state_load(run) is None):
        return None
    held = nemotron_h_sizes.sizes(run.cell.config["model"])["held"]
    return load["max_expert"] * held / load["held"]

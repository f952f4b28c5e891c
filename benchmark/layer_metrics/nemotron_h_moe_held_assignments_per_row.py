"""Held (row, expert) assignments a live row and expert layer of the
nemotron_h family, from the engine's ``decode`` spans
(``lib/kimi_linear_sizes.py:expert_load``). None for a program whose spans
carry no ``moe`` argument or no ``live_state_bytes`` (another family)."""

from lib import kimi_linear_sizes, nemotron_h_sizes


def read(run):
    load = kimi_linear_sizes.expert_load(run)
    if (load is None or not load["active"]
            or nemotron_h_sizes.state_load(run) is None):
        return None
    layers = nemotron_h_sizes.sizes(run.cell.config["model"])["moe"]
    return load["held"] / (load["active"] * layers)

"""Share of the (live row, expert layer) pairs whose router kept a held
routing group, from the engine's ``decode`` spans
(``lib/deepseek_v2_sizes.py:group_load``). None for a program whose spans
carry no ``moe.rows_in_held_group``."""

from lib import deepseek_v2_sizes


def read(run):
    load = deepseek_v2_sizes.group_load(run)
    if load is None or not load["rows"]:
        return None
    layers = deepseek_v2_sizes.sizes(run.cell.config["model"])["moe"]
    return 100.0 * load["reached"] / (load["rows"] * layers)

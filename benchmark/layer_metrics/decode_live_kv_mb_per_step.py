"""Live K/V of a decode step's rows over all layers, MB
(``lib/afmoe_sizes.py:kv_load``, ``live_positions``, ``position_bytes``).
None for a program whose spans carry no ``kv`` argument."""

from lib import afmoe_sizes


def read(run):
    kv = afmoe_sizes.kv_load(run)
    if kv is None:
        return None
    model = run.cell.config["model"]
    return (afmoe_sizes.live_positions(model, kv)
            * afmoe_sizes.position_bytes(model) / 1e6)

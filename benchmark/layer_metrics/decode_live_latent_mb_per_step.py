"""Live latents of a decode step's rows over all layers, MB
(``lib/deepseek_v2_sizes.py:latent_load``, ``position_bytes``). None for a
program whose spans carry no ``latent_live`` argument."""

from lib import deepseek_v2_sizes


def read(run):
    lat = deepseek_v2_sizes.latent_load(run)
    if lat is None:
        return None
    return (lat["live"]
            * deepseek_v2_sizes.position_bytes(run.cell.config["model"]) / 1e6)

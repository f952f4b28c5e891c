"""Live recurrent state of a decode step's rows over all Mamba-2 layers,
MB (``lib/nemotron_h_sizes.py:state_load``). None for a program whose
spans carry no ``live_state_bytes`` argument."""

from lib import nemotron_h_sizes


def read(run):
    state = nemotron_h_sizes.state_load(run)
    return None if state is None else state["bytes"] / 1e6

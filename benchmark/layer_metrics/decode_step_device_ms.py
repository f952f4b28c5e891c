"""Device time of one decode program: the mean duration of the decode
program's executions on the trace's "XLA Modules" line (one event a launch
of a compiled program). ``module_needles`` in the declaration beside this
file names the program."""

from lib import harness, xplane


def read(run):
    if run.planes is None:
        return None
    needles = harness.load_json(
        "layer_metrics", "decode_step_device_ms.json")["source"]["module_needles"]
    secs, count = xplane.needle_seconds(run.planes, needles,
                                        xplane.MODULES_LINE)
    return secs * 1e3 / count if count else None

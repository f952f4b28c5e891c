"""What every cell shares: finding the cell's files by name, the look for
the chip, the compile count, the profiler window, the per-layer readers'
dispatch and the result line.

Nothing here knows a workload, a configuration or a metric by name: a cell
is ``BENCHMARK.json``'s entry plus ``configs/<config>.json`` and
``traffic/<traffic>.json``; a per-layer metric is
``layer_metrics/<name>.json`` (and ``<name>.py`` where a declaration is not
enough). Adding one of either is adding files and entries.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from . import program

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_start() -> float:
    """``time.time()`` of this process's start (from /proc, to a clock
    tick), so that ``setup_s`` holds the interpreter's start and every
    import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_reference(config: dict):
    """The configuration's plain reference: ``benchmark/<module>.py``,
    named by the configuration's file (``reference.module``)."""
    name = config["reference"]["module"]
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + name, os.path.join(BENCH_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    """The one general driver of a traffic ``kind``: the module
    ``lib/<kind>_cell.py``, with ``run`` (a cell for a window) and
    ``calibrate`` (the readings `correct`'s limits are set from). A new
    kind of traffic is a new module of that name, no edit here."""
    try:
        return importlib.import_module(f".{kind}_cell", __package__)
    except ModuleNotFoundError as e:
        if e.name != f"{__package__}.{kind}_cell":
            raise
        raise SystemExit(f"benchmark: no driver for traffic kind {kind!r} "
                         f"(benchmark/lib/{kind}_cell.py)")


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[str]  # the end-to-end metrics this cell reports
    per_layer: List[dict]  # BENCHMARK.json entries of its per-layer metrics


def find_cell(bench: dict, name: str) -> Cell:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have "
                         f"{[w['name'] for w in bench['workloads']]})")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    config["file"] = cfg_entry["file"]  # for the messages that name it
    program.check_config(config)
    traffic = load_json("traffic", entry["traffic"] + ".json")
    e2e = [m["name"] for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name]) and m["moves"] in e2e]
    return Cell(name, entry["chips"], config, traffic, e2e, layer)


def need(config: dict, block: str, who: str):
    """``config[a][b]`` for the block ``"a.b"``. A configuration states
    the paths it has: one that can only be served has no ``train`` and no
    ``correct.train``; asked for a path it lacks, the run ends with a
    sentence that names the block."""
    node = config
    for key in block.split("."):
        if not isinstance(node, dict) or key not in node:
            raise SystemExit(
                f"benchmark: {program.config_file(config)} has no "
                f"`{block}` block, which {who} needs: the configuration "
                "does not state that path")
        node = node[key]
    return node


@dataclass
class Env:
    """The machine as the run found it. ``rehearsal`` is set only by
    ``selftest.py``: no chip, no peaks, and no result line that could be
    read as a device's."""
    devices: list
    peaks: Optional[dict]
    rehearsal: bool = False

    @property
    def summary(self) -> dict:
        d = self.devices[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(self.devices)}


def find_chips(chips: int) -> Env:
    """Exit, printing no result, unless JAX's default backend is a TPU with
    at least ``chips`` chips of a kind the table of peaks holds."""
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"benchmark: JAX found no TPU (default backend "
            f"{jax.default_backend()!r}); a number from the CPU is not a "
            "device measurement")
    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX "
                         f"found {len(devices)}")
    peaks = load_json("lib", "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"benchmark: no published peaks for device kind "
                         f"{kind!r} in benchmark/lib/peaks.json")
    return Env(devices[:chips], peaks[kind])


class CompileCount:
    """Backend compilations seen by this process; the window's share of
    them has to be 0."""

    def __init__(self) -> None:
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip: the allocator's ``peak_bytes_in_use``
    (live arrays) plus its ``peak_bytes_reserved``, the region it sets
    aside for the running program's temporaries, which the first does not
    count (a train step of 8.3 GB of temporaries read 1.3 GB without it;
    my chip run, PR 23). 0 where the backend keeps no statistics (the
    CPU)."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(st.get("peak_bytes_in_use", 0)
                     + st.get("peak_bytes_reserved", 0))
    return int(max(peaks))


class Laps:
    """Where set-up went, for the line printed above the result."""

    def __init__(self, t_start: float) -> None:
        self.last, self.laps = t_start, []

    def lap(self, name: str) -> None:
        now = time.time()
        self.laps.append(f"{name} {now - self.last:.1f}")
        self.last = now

    def __str__(self) -> str:
        return "set-up (s): " + ", ".join(self.laps)


class Profile:
    """A ``jax.profiler`` trace of a part of the window, written under
    ``benchmark/out/trace/<tag>`` (replaced every run). The Python tracer
    is off: its events are most of a trace's bytes and none is read."""

    def __init__(self, tag: str, spans) -> None:
        self.dir = os.path.join(OUT_DIR, "trace", tag)
        self.spans = spans

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.annotate = True

    def stop(self) -> None:
        import jax

        self.spans.annotate = False
        jax.profiler.stop_trace()

    def load(self):
        from . import xplane

        return xplane.load_trace(self.dir)


@dataclass
class Run:
    """What the per-layer readers may read: the cell, the machine, the
    trace's planes, the host spans, and the series and counts the driver
    of the cell put down (``values``)."""
    cell: Cell
    env: Env
    planes: Optional[list] = None
    spans: Any = None
    values: Dict[str, Any] = field(default_factory=dict)


def _reader_for(name: str) -> Callable[[Run], Optional[float]]:
    """``layer_metrics/<name>.py:read`` where that file exists, else the
    declared source of ``layer_metrics/<name>.json`` through
    ``lib/readers.py``."""
    from . import readers

    py = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    if os.path.exists(py):
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + name.replace(".", "_").replace("-", "_"), py)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
    decl = load_json("layer_metrics", name + ".json")
    return lambda run: readers.read_declared(decl["source"], run)


def layer_metrics(run: Run) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader found something to
    read; one that returns None is left out of the line."""
    out: Dict[str, dict] = {}
    for entry in run.cell.per_layer:
        value = _reader_for(entry["name"])(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def end_to_end(cell: Cell, value_of: Callable[[str], float]) -> Dict[str, dict]:
    """The cell's end-to-end metrics with BENCHMARK.json's units."""
    units = {m["name"]: m["unit"] for m in load_benchmark()["end_to_end"]}
    return {k: {"value": float(value_of(k)), "unit": units[k]}
            for k in cell.end_to_end}


def result_line(env: Env, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], memory_peak: int,
                trace: Optional[dict] = None,
                extra: Optional[dict] = None) -> str:
    device = dict(env.summary, memory_peak_bytes=memory_peak)
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(attempted),
                            "failed": int(failed), "metrics": metrics,
                            "device": device}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    if extra:
        line.update(extra)
    return json.dumps(line)


def say(msg: str) -> None:
    """Progress and the numbers compared go to stdout above the result
    line; the result is the last line."""
    print(msg, flush=True)
    sys.stdout.flush()

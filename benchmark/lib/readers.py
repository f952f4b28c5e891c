"""The declared sources of ``layer_metrics/<name>.json``, one reader each.

A declaration is ``{"kind": ..., ...}``. A reader that finds nothing to
read (no trace in this run, no such span, no peaks in a rehearsal) returns
None and the metric is left out of the result line; it never returns 0 for
"nothing there".

  trace_bucket  device time of one of xplane.KERNEL_BUCKETS (``bucket``) or
                of the events of ``line`` holding one of ``needles``; ``per``
                "step" (ms a traced step) or "event" (ms an event)
  trace_idle    100 * (1 - union of op intervals / traced window)
  trace_exposed ms a traced step of the ops holding ``needles`` that no
                other op of that chip covers
  span          ``stat`` over the durations (ms) of the host spans ``name``
                that ended inside the measured window; with ``per_step``
                the sum over the window's steps
  value         ``stat`` over the series, or the scalar, the cell's driver
                put under ``key`` (times ``scale``)
  utilization   100 * operations of ``cost`` a step * ``values[rate_key]``
                steps a second / the chip's bf16 peak (host clock)
  memory        ``values["memory_peak_bytes"]`` in GB
"""

from __future__ import annotations

from typing import Optional

from . import cost as cost_lib
from . import xplane
from .stats import STATS


def _trace_seconds(src: dict, run) -> Optional[tuple]:
    if run.planes is None:
        return None
    if "bucket" in src:
        secs, count = xplane.bucket_seconds(run.planes, src["bucket"])
    else:
        secs, count = xplane.needle_seconds(
            run.planes, src["needles"], src.get("line", xplane.OPS_LINE))
    return (secs, count) if count else None


def read_declared(src: dict, run) -> Optional[float]:
    kind = src["kind"]
    v = run.values
    if kind == "trace_bucket":
        got = _trace_seconds(src, run)
        if got is None:
            return None
        secs, count = got
        per = count if src.get("per", "step") == "event" else v["trace_steps"]
        return secs * 1e3 / per
    if kind == "trace_idle":
        if run.planes is None:
            return None
        busy, window = xplane.busy_and_window(run.planes)
        return 100.0 * (1.0 - busy / window)
    if kind == "trace_exposed":
        if run.planes is None:
            return None
        _, count = xplane.needle_seconds(run.planes, src["needles"])
        if not count:
            return None
        return (xplane.exposed_seconds(run.planes, src["needles"]) * 1e3
                / v["trace_steps"])
    if kind == "span":
        durs = run.spans.durations_ms(src["name"], *v["measured_window"])
        if not durs:
            return None
        if src.get("per_step"):
            return sum(durs) / v["measured_steps"]
        return STATS[src["stat"]](durs)
    if kind == "value":
        got = v.get(src["key"])
        if got is None or (isinstance(got, list) and not got):
            return None
        if isinstance(got, list):
            got = STATS[src["stat"]](got)
        return got * src.get("scale", 1.0)
    if kind == "utilization":
        if run.env.peaks is None or v.get(src["rate_key"]) is None:
            return None
        need = getattr(cost_lib, src["cost"])(run.cell.config["model"], v)
        return (100.0 * need["flops"] * v[src["rate_key"]]
                / run.env.peaks["bf16_flops_per_s"])
    if kind == "memory":
        got = v.get("memory_peak_bytes")
        return got / 1e9 if got else None
    raise ValueError(f"unknown per-layer source kind {kind!r}")

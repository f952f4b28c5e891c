"""From a ``jax.profiler`` trace to seconds: the reduction every PR shares.

The protobuf reader, ``KERNEL_BUCKETS`` and the plane/line choice are
copied from the program's ``obs/xprof.py`` (which stays the program's own;
PERF.md lists it for a later PR to point here). What that file lacks is
added: intervals instead of summed durations, so that the busy time is the
*union* of the device's op intervals, the idle share has a window under it,
a collective's exposed time is what no other op covers, and an idle gap can
be named by the host span that covers it. Standard library only.

Field numbers (tensorflow/tsl/profiler/protobuf/xplane.proto):
  XSpace: planes=1   XPlane: name=2 lines=3 event_metadata=4 (map: key=1,
  value=2 XEventMetadata{id=1 name=2})   XLine: name=2 timestamp_ns=3
  events=4   XEvent: metadata_id=1 offset_ps=2 duration_ps=3
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # seconds on the trace's clock

# An event of the "XLA Ops" line is named by its whole HLO instruction,
# operands included: ``%all-reduce.3 = f32[..] all-reduce(%fusion.7), ...``.
# Only the instruction's own name (left of " = ") says what ran; a needle
# tried against the whole text would count every op that READS a
# collective's result as a collective.
#
# Buckets by instruction name, first match wins (the decode and fused-FFN
# kernels end in the flash needle ``_fwd_kernel`` and must be tried before
# it). A Pallas kernel carries a name only where the program gives
# ``pallas_call`` one that reaches the HLO. Since PR 24 the program names
# every kernel (``kernel_names.py``: ``%flash_bwd_tm_packed.7``, under vmap
# ``%vmap_fused_ffn_fwd_.1``), and the needles below catch those names; a
# kernel left without one is ``%jvp__.N`` / ``%transpose_jvp___.N`` with
# ``custom_call_target="tpu_custom_call"`` (every kernel until PR 24; my
# chip run, PR 23) and falls into ``pallas``, as does a named kernel no
# needle matches (``kv_row_write``, PR 25).
KERNEL_BUCKETS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("decode_attention", ("_dattn_",)),
    ("fused_ffn", ("_ffn_fwd", "_ffn_bwd", "_addnorm_", "fused_ffn",
                   "fused_norm", "fused_add_norm", "_swiglu2", "_norm2",
                   "_add_norm2")),
    ("flash_attention", ("_fwd_kernel", "_bwd_dq", "_bwd_dkv", "flash",
                         "_tm_", "tm_packed")),
    ("collectives", ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute",
                     "collective-broadcast")),
)
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_name(event_name: str) -> str:
    """The instruction's own name: ``all-reduce.3`` of the text above."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_group(event_name: str) -> str:
    """The name without its number: ``all-reduce``, ``transpose_jvp___``."""
    name = op_name(event_name)
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def bucket_for(event_name: str) -> Optional[str]:
    name = op_name(event_name)
    for bucket, needles in KERNEL_BUCKETS:
        if any(n in name for n in needles):
            return bucket
    return "pallas" if PALLAS_TARGET in event_name else None


def in_bucket(event_name: str, bucket: str) -> bool:
    """``pallas`` holds every Pallas kernel, named or not."""
    if bucket == "pallas":
        return PALLAS_TARGET in event_name
    return bucket_for(event_name) == bucket


# -- protobuf wire reader ---------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7
        if shift > 70:
            raise ValueError("varint longer than 10 bytes")


def _fields(buf):
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wt = tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        if i > n:
            raise ValueError("truncated protobuf field")
        yield tag >> 3, wt, v


class Line:
    """One line of a plane: ``events`` are (start_s, end_s, name), sorted."""

    __slots__ = ("name", "events")

    def __init__(self, name: str, events: List[Tuple[float, float, str]]):
        self.name, self.events = name, events


class Plane:
    __slots__ = ("name", "lines", "_by_name")

    def __init__(self, name: str, lines: List[Line]):
        self.name, self.lines, self._by_name = name, lines, {}

    def line_events(self, line_name: str) -> List[Tuple[float, float, str]]:
        """The events of every line of that name, sorted (kept: every
        reduction asks again)."""
        if line_name not in self._by_name:
            out: List[Tuple[float, float, str]] = []
            for ln in self.lines:
                if ln.name == line_name:
                    out.extend(ln.events)
            out.sort()
            self._by_name[line_name] = out
        return self._by_name[line_name]


def _parse_plane(buf) -> Plane:
    name, raw_lines, names = "", [], {}
    for fno, wt, v in _fields(buf):
        if fno == 2 and wt == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif fno == 3 and wt == 2:
            raw_lines.append(v)
        elif fno == 4 and wt == 2:
            key, ename = 0, ""
            for f1, w1, v1 in _fields(v):
                if f1 == 1 and w1 == 0:
                    key = v1
                elif f1 == 2 and w1 == 2:
                    for f2, w2, v2 in _fields(v1):
                        if f2 == 2 and w2 == 2:
                            ename = bytes(v2).decode("utf-8", "replace")
            names[key] = ename
    lines = []
    for raw in raw_lines:
        lname, t0_ns, events = "", 0, []
        for fno, wt, v in _fields(raw):
            if fno == 2 and wt == 2:
                lname = bytes(v).decode("utf-8", "replace")
            elif fno == 3 and wt == 0:
                t0_ns = v
            elif fno == 4 and wt == 2:
                mid = off = dur = 0
                for f1, w1, v1 in _fields(v):
                    if w1 != 0:
                        continue
                    if f1 == 1:
                        mid = v1
                    elif f1 == 2:
                        off = v1
                    elif f1 == 3:
                        dur = v1
                events.append((off, dur, mid))
        base = t0_ns * 1e-9
        evs = [(base + off * 1e-12, base + (off + dur) * 1e-12,
                names.get(mid, f"<meta:{mid}>")) for off, dur, mid in events]
        evs.sort()
        lines.append(Line(lname, evs))
    return Plane(name, lines)


def parse_xspace(data: bytes) -> List[Plane]:
    return [_parse_plane(v) for fno, wt, v in _fields(memoryview(data))
            if fno == 1 and wt == 2]


def load_trace(trace_dir: str) -> List[Plane]:
    """The planes of the newest ``*.xplane.pb`` under a trace directory."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        return parse_xspace(f.read())


def device_planes(planes: Sequence[Plane]) -> List[Plane]:
    """The TPU planes that ran ops, one a chip."""
    return [p for p in planes if p.name.startswith("/device:TPU")
            and p.line_events(OPS_LINE)]


def host_planes(planes: Sequence[Plane]) -> List[Plane]:
    return [p for p in planes if p.name.startswith("/host:")]


# -- interval arithmetic ----------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of intervals as sorted, disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the merged intervals ``a`` that the merged ``b`` does
    not cover."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -- the reductions ---------------------------------------------------------


def busy_and_window(planes: Sequence[Plane]) -> Tuple[float, float]:
    """``(busy_s, window_s)``: the union of the op intervals of each chip,
    averaged over the chips, and the traced window, taken from the first
    op's start to the last op's end over all chips (the profiler's own
    start-up and shut-down lie outside it)."""
    devs = device_planes(planes)
    if not devs:
        raise ValueError("no TPU plane with ops in the trace")
    unions = [merge((a, b) for a, b, _ in p.line_events(OPS_LINE))
              for p in devs]
    lo = min(u[0][0] for u in unions)
    hi = max(u[-1][1] for u in unions)
    return sum(total(u) for u in unions) / len(unions), hi - lo


def _matching_seconds(planes: Sequence[Plane], line: str,
                      match) -> Tuple[float, float]:
    """Summed duration and number of the events of ``line`` that ``match``
    takes, both averaged over the chips (so their ratio is the mean
    event's duration whichever chips ran it)."""
    devs = device_planes(planes)
    secs, count = 0.0, 0
    for p in devs:
        for a, b, name in p.line_events(line):
            if match(name):
                secs += b - a
                count += 1
    n = max(1, len(devs))
    return secs / n, count / n


def needle_seconds(planes: Sequence[Plane], needles: Sequence[str],
                   line: str = OPS_LINE) -> Tuple[float, float]:
    """Events whose own name (the instruction's, or a module's) holds one
    of ``needles``."""
    return _matching_seconds(
        planes, line, lambda name: any(n in op_name(name) for n in needles))


def bucket_seconds(planes: Sequence[Plane], bucket: str) -> Tuple[float, float]:
    """Events of one of ``KERNEL_BUCKETS``, with its first-match-wins order
    (a needle list alone would count the decode kernel under flash);
    ``pallas`` is every Pallas kernel."""
    return _matching_seconds(planes, OPS_LINE,
                             lambda name: in_bucket(name, bucket))


def exposed_seconds(planes: Sequence[Plane],
                    needles: Sequence[str]) -> float:
    """Time of the ops matching ``needles`` during which no other op runs
    on that chip, averaged over the chips."""
    devs = device_planes(planes)
    exposed = 0.0
    for p in devs:
        evs = p.line_events(OPS_LINE)
        mine = merge((a, b) for a, b, n in evs
                     if any(x in op_name(n) for x in needles))
        rest = merge((a, b) for a, b, n in evs
                     if not any(x in op_name(n) for x in needles))
        exposed += total(subtract(mine, rest))
    return exposed / max(1, len(devs))


def top_device_ops(planes: Sequence[Plane], limit: int = 10
                   ) -> List[List]:
    """``[[name, seconds], ...]``: the first chip's ops by summed time,
    grouped by kernel bucket where one matches (unnamed Pallas kernels as
    ``pallas:<instruction group>``) and by instruction group otherwise
    (``fusion``, ``cond``, ``all-reduce``)."""
    devs = device_planes(planes)
    if not devs:
        return []
    sums: Dict[str, float] = {}
    for a, b, name in devs[0].line_events(OPS_LINE):
        key = bucket_for(name)
        if key is None:
            key = op_group(name)
        elif key == "pallas":
            key = "pallas:" + op_group(name)
        sums[key] = sums.get(key, 0.0) + (b - a)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v] for k, v in ranked]


#: idle stretches shorter than this are the seams between back-to-back ops;
#: they are summed under one name instead of being matched to host spans
SEAM_S = 20e-6


def idle_gaps_by_host_span(planes: Sequence[Plane], prefix: str,
                           limit: int = 10) -> List[List]:
    """``[[name, seconds], ...]``: the first chip's idle time inside the
    window, split by the host span (a trace annotation whose name starts
    with ``prefix``) that covers each part. Where several spans overlap a
    gap, the innermost (shortest) takes its part; what no span covers is
    ``no-host-span``."""
    devs = device_planes(planes)
    if not devs:
        return []
    busy = merge((a, b) for a, b, _ in devs[0].line_events(OPS_LINE))
    gaps = subtract([(busy[0][0], busy[-1][1])], busy)
    spans: List[Tuple[float, float, str]] = []
    for hp in host_planes(planes):
        for ln in hp.lines:
            spans.extend((a, b, n[len(prefix):]) for a, b, n in ln.events
                         if n.startswith(prefix))
    spans.sort()
    starts = [s[0] for s in spans]
    longest = max((b - a for a, b, _ in spans), default=0.0)
    sums: Dict[str, float] = {}

    def add(name: str, secs: float) -> None:
        if secs > 0:
            sums[name] = sums.get(name, 0.0) + secs

    for gap in gaps:
        if gap[1] - gap[0] < SEAM_S:
            add("seams-under-20us", gap[1] - gap[0])
            continue
        lo = bisect.bisect_left(starts, gap[0] - longest)
        hi = bisect.bisect_left(starts, gap[1])
        inner_first = sorted(
            (s for s in spans[lo:hi] if s[1] > gap[0]),
            key=lambda s: s[1] - s[0])
        left = [gap]
        for a, b, name in inner_first:
            if not left:
                break
            rest = subtract(left, [(a, b)])
            add(name, total(left) - total(rest))
            left = rest
        add("no-host-span", total(left))
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v] for k, v in ranked]


def summary(planes: Sequence[Plane], span_prefix: str) -> dict:
    """What a traced run's result line carries of its trace."""
    busy, window = busy_and_window(planes)
    return {"busy_s": busy, "window_s": window, "breakdown": {
        "device_ops": top_device_ops(planes),
        "idle_gaps": idle_gaps_by_host_span(planes, span_prefix)}}

"""Xplane-proto parsing: the device-side profile as a library.

``jax.profiler.trace`` writes its capture as an **xplane** protobuf
(``plugins/profile/<run>/<host>.xplane.pb``) — the XLA op timeline,
per-kernel durations, HBM events. ``tools/profile_step.py`` used to
parse it inline with tensorflow's bundled proto; this module is that
logic extracted so it can run CONTINUOUSLY (obs/device_profile.py
samples production loops) and in tier-1 (a committed synthetic fixture,
tests/test_device_profile.py) — which forces two properties:

- **stdlib only.** The wire format is decoded by a ~60-line protobuf
  reader (:func:`parse_xspace`) covering exactly the fields the
  summaries read (field numbers pinned against tensorflow's
  ``xplane.proto``; cross-checked by test when tf is importable). No
  tensorflow import, no jax import — the parse can run on the
  device_profile worker thread of a jax process or in a bare CI job.
- **graceful degradation.** Every entry point that can fail on absent
  data (no trace written, no recognizable plane) returns an error
  STRING instead of raising, and callers surface it as ``{"error":
  ...}`` — a missing TPU must never crash the loop being profiled.

Plane selection: real telemetry comes from a ``/device:TPU`` plane's
"XLA Ops" line (one flat, non-overlapping event per executed op). GPU
planes are handled the same way. On CPU there is no device plane at
all — ``pick_plane`` falls back to the ``/host:CPU`` plane and
summarizes its busiest thread line; those numbers are plumbing-grade
(events nest, so sums overcount) but keep the capture->parse->publish
pipeline testable without hardware.

Bucket attribution: an "XLA Ops" event is named by its whole HLO
instruction (``%flash_fwd_tm.3 = bf16[..] custom-call(%fusion.7), ...``)
and only the instruction's OWN name, left of `` = ``, says what ran. A
Pallas kernel's own name is the ``name=`` its ``pallas_call`` was given
(``kernel_names.py``, the one table; without one the instruction is
``%jvp__.N`` and says nothing), so the kernel buckets are built from
that table and a kernel matches the bucket whose family holds its name.
Collectives are matched on their HLO op names.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple, Union

from differential_transformer_replication_tpu import kernel_names as _NAMES


# Buckets of the grouped breakdown, by the instruction's own name. The
# kernel buckets hold the names ``kernel_names.py`` gives the
# ``pallas_call`` sites (the norm/residual kernels are booked with the
# FFN they feed). "collectives" covers the HLO communication ops (DP
# all-reduce, tensor-parallel all-gather, ring ppermute) so a sharded
# step's exposed-communication share is its own line in the
# decomposition; everything unmatched is "rest".
KERNEL_BUCKETS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("decode_attention", _NAMES.DECODE),
    ("kv_write", _NAMES.KV_WRITE),
    ("ssm", _NAMES.SSM),
    ("kda", _NAMES.KDA),
    ("moe", _NAMES.MOE),
    ("ring_attention", _NAMES.RING),
    ("mla", _NAMES.MLA),
    ("fused_ffn", _NAMES.FUSED_FFN + _NAMES.FUSED_NORM),
    ("flash_attention", _NAMES.FLASH),
    ("collectives", ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute",
                     "collective-broadcast")),
)

# Published per-chip peaks, keyed by the ``device_kind`` string JAX
# reports — the one table bench.py's MFU and the continuous ``device_mfu``
# gauge divide by. Source: Google Cloud documentation, "TPU v5e" (system
# architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
# 819 GB/s per chip. A device that is not here is an error
# (:func:`device_peaks`), never a default.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind`` (``jax.devices()[0]
    .device_kind``). Raises ``KeyError`` naming the kind for a device
    the table does not hold: a utilization against another chip's peak
    is a wrong number, not an estimate."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"obs/xprof.py:DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); "
            "add the chip with its source before reporting a "
            "utilization on it"
        ) from None


# -- minimal protobuf wire reader -----------------------------------------
#
# Field numbers from tensorflow.tsl.profiler.protobuf.xplane:
#   XSpace:  planes=1 (msg)
#   XPlane:  name=2 (str), lines=3 (msg), event_metadata=4 (map entry:
#            key=1 varint, value=2 XEventMetadata{id=1, name=2})
#   XLine:   name=2 (str), timestamp_ns=3 (varint), events=4 (msg)
#   XEvent:  metadata_id=1, offset_ps=2, duration_ps=3 (varints)
# Everything else is skipped by wire type.


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
    """Yield ``(field_number, wire_type, value)`` triples; value is an
    int for varints and a memoryview for length-delimited fields."""
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


class XEvent:
    __slots__ = ("metadata_id", "offset_ps", "duration_ps")

    def __init__(self) -> None:
        self.metadata_id = 0
        self.offset_ps = 0
        self.duration_ps = 0


class XLine:
    __slots__ = ("name", "timestamp_ns", "events")

    def __init__(self) -> None:
        self.name = ""
        self.timestamp_ns = 0
        self.events: List[XEvent] = []


class XPlane:
    __slots__ = ("name", "lines", "event_names")

    def __init__(self) -> None:
        self.name = ""
        self.lines: List[XLine] = []
        self.event_names: Dict[int, str] = {}  # metadata_id -> op name

    def event_name(self, metadata_id: int) -> str:
        return self.event_names.get(metadata_id, f"<meta:{metadata_id}>")


def _parse_event(buf) -> XEvent:
    ev = XEvent()
    for fno, wt, v in _fields(buf):
        if wt != 0:
            continue
        if fno == 1:
            ev.metadata_id = v
        elif fno == 2:
            ev.offset_ps = v
        elif fno == 3:
            ev.duration_ps = v
    return ev


def _parse_line(buf) -> XLine:
    line = XLine()
    for fno, wt, v in _fields(buf):
        if fno == 2 and wt == 2:
            line.name = bytes(v).decode("utf-8", "replace")
        elif fno == 3 and wt == 0:
            line.timestamp_ns = v
        elif fno == 4 and wt == 2:
            line.events.append(_parse_event(v))
    return line


def _parse_event_metadata_entry(buf) -> Tuple[int, str]:
    """One ``event_metadata`` map entry -> (id, name)."""
    key, name = 0, ""
    for fno, wt, v in _fields(buf):
        if fno == 1 and wt == 0:
            key = v
        elif fno == 2 and wt == 2:
            for f2, w2, v2 in _fields(v):
                if f2 == 2 and w2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
    return key, name


def _parse_plane(buf) -> XPlane:
    plane = XPlane()
    for fno, wt, v in _fields(buf):
        if fno == 2 and wt == 2:
            plane.name = bytes(v).decode("utf-8", "replace")
        elif fno == 3 and wt == 2:
            plane.lines.append(_parse_line(v))
        elif fno == 4 and wt == 2:
            key, name = _parse_event_metadata_entry(v)
            plane.event_names[key] = name
    return plane


def parse_xspace(data: bytes) -> List[XPlane]:
    """Decode an ``XSpace`` protobuf into its planes. Raises
    ``ValueError`` on malformed bytes (callers that must not raise go
    through :func:`summarize_trace`, which degrades to an error
    string)."""
    planes = []
    for fno, wt, v in _fields(memoryview(data)):
        if fno == 1 and wt == 2:
            planes.append(_parse_plane(v))
    return planes


# -- plane selection + summaries ------------------------------------------


def find_xplane_pb(trace_dir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under a ``jax.profiler.trace`` output
    directory (the profiler nests it plugins/profile/<run>/)."""
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return sorted(paths)[-1] if paths else None


def pick_plane(
    planes: List[XPlane], host_fallback: bool = True
) -> Union[Tuple[XPlane, str], str]:
    """The most device-like plane: TPU, then GPU, then any
    ``/device:``, then — with ``host_fallback`` — the host-CPU plane
    (CI without an accelerator; see module docstring on the caveats).
    Returns ``(plane, kind)`` or an error string."""
    for prefix, kind in (("/device:TPU", "tpu"), ("/device:GPU", "gpu"),
                         ("/device:", "device")):
        for p in planes:
            if p.name.startswith(prefix) and p.lines:
                return p, kind
    if host_fallback:
        for p in planes:
            if p.name.startswith("/host:") and p.lines:
                return p, "host"
    names = [p.name for p in planes]
    return (
        f"no device plane in the trace (planes: {names})"
        if host_fallback else
        f"no TPU plane in the trace (planes: {names})"
    )


def _main_line(plane: XPlane, kind: str) -> Union[XLine, str]:
    """The line the summary reads. Device planes: the largest "XLA Ops"
    line (flat, one event per executed op). Host fallback: the busiest
    thread line by summed duration — events NEST there (a python call
    stack), so sums overcount; plumbing-grade only."""
    if kind in ("tpu", "gpu", "device"):
        line = max(
            (l for l in plane.lines if l.name == "XLA Ops"),
            key=lambda l: len(l.events),
            default=None,
        )
        if line is None:
            return f"no 'XLA Ops' line in the {plane.name} plane"
        return line
    line = max(
        plane.lines,
        key=lambda l: sum(e.duration_ps for e in l.events),
        default=None,
    )
    if line is None or not line.events:
        return f"no events in the {plane.name} plane"
    return line


def bucket_for(name: str) -> Optional[str]:
    """The :data:`KERNEL_BUCKETS` bucket of an event, by the
    instruction's own name (an op that READS a kernel's or a
    collective's result is neither); None -> "rest" in the
    decomposition."""
    own = name.split(" = ", 1)[0]
    for bucket, needles in KERNEL_BUCKETS:
        if any(n in own for n in needles):
            return bucket
    return None


def load_trace_plane(
    trace_dir: str, host_fallback: bool = True
) -> Union[str, Tuple[XPlane, str]]:
    """Parse a profiler trace directory and pick its device plane;
    ``(plane, kind)`` or an error string (never raises on bad input)."""
    path = find_xplane_pb(trace_dir)
    if path is None:
        return f"no xplane.pb under {trace_dir}"
    try:
        with open(path, "rb") as f:
            planes = parse_xspace(f.read())
    except (OSError, ValueError) as e:
        return f"cannot parse {path}: {e}"
    return pick_plane(planes, host_fallback=host_fallback)


def summarize_plane(
    plane: XPlane, kind: str, steps: int = 1
) -> Union[str, dict]:
    """The per-step breakdown of one plane's main line — or an error
    string when the plane has no summarizable line.

    Keys (all ms figures divided by ``steps``):
      ``groups``          op-family name -> ms/step (the ``%family``
                          prefix of each XLA op name),
      ``kernel_buckets``  :data:`KERNEL_BUCKETS` name -> ms/step,
      ``bucket_ms``       kernel_buckets plus ``rest`` — the full
                          step-time decomposition (sums to busy),
      ``totals``/``counts``  per-op-name total ms / event counts,
      ``busy_ms_per_step``   summed event time,
      ``plane``/``plane_kind``  which plane was summarized.
    """
    line = _main_line(plane, kind)
    if isinstance(line, str):
        return line

    steps = max(1, int(steps))
    totals: dict = defaultdict(float)
    counts: dict = defaultdict(int)
    groups: dict = defaultdict(float)
    buckets: dict = defaultdict(float)
    for ev in line.events:
        name = plane.event_name(ev.metadata_id)
        ms = ev.duration_ps / 1e9
        totals[name] += ms
        counts[name] += 1
        m = re.match(r"%([a-zA-Z_\.]+)", name)
        groups[m.group(1) if m else name[:24]] += ms
        b = bucket_for(name)
        if b is not None:
            buckets[b] += ms
    busy = sum(totals.values())
    decomp = {k: v / steps for k, v in buckets.items()}
    decomp["rest"] = max(0.0, busy - sum(buckets.values())) / steps
    return {
        "groups": {k: v / steps for k, v in groups.items()},
        "kernel_buckets": {k: v / steps for k, v in buckets.items()},
        "bucket_ms": decomp,
        "totals": dict(totals),
        "counts": dict(counts),
        "busy_ms_per_step": busy / steps,
        "plane": plane.name,
        "plane_kind": kind,
    }


def summarize_trace(
    trace_dir: str, steps: int = 1, host_fallback: bool = True
) -> Union[str, dict]:
    """:func:`load_trace_plane` + :func:`summarize_plane` in one call —
    what tools/profile_step.py reports from; error-string degradation
    on any missing/malformed input."""
    picked = load_trace_plane(trace_dir, host_fallback=host_fallback)
    if isinstance(picked, str):
        return picked
    return summarize_plane(picked[0], picked[1], steps=steps)


def derived_metrics(
    busy_ms_per_step: float,
    flops_per_step: Optional[float] = None,
    hbm_bytes_per_step: Optional[float] = None,
    peak_flops: Optional[float] = None,
) -> dict:
    """MFU / HBM-bandwidth estimates from the device-busy time.

    ``mfu`` divides the caller's model-FLOPs estimate (bench.py's
    6*N*D convention for training) by busy time and the device's peak
    (``device_peaks(kind)["bf16_flops_per_s"]``; omitted without one) —
    the same accounting as the bench JSON's ``mfu_6nd``, so continuous
    samples and bench rounds are directly comparable.
    ``hbm_gbps`` is the achieved bandwidth implied by the caller's
    bytes-moved estimate — roofline-order, not a measurement (real HBM
    counters need the memory-profiler plugin, not the op timeline).
    """
    out: dict = {}
    busy_s = busy_ms_per_step / 1e3
    if busy_s <= 0:
        return out
    if flops_per_step and peak_flops:
        out["mfu"] = flops_per_step / busy_s / peak_flops
    if hbm_bytes_per_step:
        out["hbm_gbps"] = hbm_bytes_per_step / busy_s / 1e9
    return out


def embedding_param_count(
    model: str, vocab_size: int, n_embd: int, block_size: int
) -> int:
    """Parameters EXCLUDED from the 6*N*D numerator: the token
    embedding (weight-tied with the lm head, counted once) plus — for
    the diff family only — its learned absolute position table
    (control/ndiff use RoPE, no positional params). One definition,
    shared by bench.py's ``mfu_6nd`` and the trainer's continuous
    ``device_mfu``, so the two can never subtract different N."""
    n = vocab_size * n_embd
    if model == "diff":
        n += block_size * n_embd
    return n


def train_flops_per_step(
    n_params: int, n_embed_params: int, tokens_per_step: int
) -> float:
    """The 6*N*D training-FLOPs estimate over non-embedding params —
    the numerator bench.py's ``mfu_6nd`` uses, shared here so the
    continuous ``device_mfu`` gauge agrees with bench rounds."""
    return 6.0 * max(0, n_params - n_embed_params) * tokens_per_step


def train_hbm_bytes_per_step(
    n_params: int, compute_bytes: int = 2, opt_state_bytes: int = 12
) -> float:
    """Rough HBM traffic of one optimizer step: params read twice in
    compute dtype (forward + backward) plus the fp32 optimizer update
    (grad read, m/v read+write, param read+write ~ 12 bytes/param for
    AdamW with fp32 master params). Activations are excluded — with
    flash + fused FFN they are the minority term at recipe scale
    (BASELINE.md round-5/6 decompositions)."""
    return float(n_params) * (2 * compute_bytes + opt_state_bytes)


# -- device lane (Chrome trace) -------------------------------------------


def plane_to_chrome_events(
    plane: XPlane,
    pid: int = 0,
    anchor_us: Optional[float] = None,
    capture: Optional[int] = None,
    max_events: int = 50_000,
) -> List[dict]:
    """Convert one xplane into Chrome-trace complete events — the
    DEVICE lane ``tools/trace_stitch.py`` merges under the host
    timeline.

    Device timestamps have an arbitrary epoch; ``anchor_us`` (a
    wall-clock microsecond timestamp, the same epoch obs/spans.py
    anchors host spans to) shifts the earliest event there, so the lane
    lands inside the host span that wrapped the captured step even
    before trace_stitch's capture-window alignment refines it. When
    ``capture`` is given, one enclosing ``capture_window`` event
    carries it as an arg — the join key the stitcher matches against
    the host ``device_capture`` span with the same ``capture`` arg.
    """
    raw: List[Tuple[float, float, int, str]] = []  # (ts_us, dur_us, tid, name)
    for tid, line in enumerate(plane.lines):
        base_us = line.timestamp_ns / 1e3
        for ev in line.events:
            raw.append((
                base_us + ev.offset_ps / 1e6,
                ev.duration_ps / 1e6,
                tid,
                plane.event_name(ev.metadata_id),
            ))
    if not raw:
        return []
    raw.sort(key=lambda r: r[0])
    if len(raw) > max_events:
        raw = raw[:max_events]
    shift = (anchor_us - raw[0][0]) if anchor_us is not None else 0.0
    events: List[dict] = [
        {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
         "args": {"name": f"device [{plane.name}]"}},
    ]
    for tid, line in enumerate(plane.lines):
        if line.events:
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": line.name or f"line-{tid}"},
            })
    lo = raw[0][0] + shift
    hi = max(ts + dur for ts, dur, _, _ in raw) + shift
    if capture is not None:
        events.append({
            "name": "capture_window", "ph": "X", "pid": pid, "tid": 0,
            "ts": lo, "dur": max(0.0, hi - lo),
            "args": {"capture": int(capture)},
        })
    for ts, dur, tid, name in raw:
        events.append({
            "name": name, "ph": "X", "pid": pid, "tid": tid,
            "ts": ts + shift, "dur": dur,
        })
    return events


def write_chrome_trace(path: str, events: List[dict]) -> None:
    """One valid Chrome-trace JSON array (what Perfetto and
    tools/trace_stitch.py load)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(events, f, separators=(",", ":"))
